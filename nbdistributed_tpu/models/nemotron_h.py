"""One mixer a layer, the order of kinds given as a string: the
Mamba-2 / attention / expert block family as NVIDIA-Nemotron-3-Nano
publishes it (``model_type: nemotron_h``), on the serving path.

Every layer is ``x + mixer(RMSNorm(x))`` and nothing else (no MLP
behind a mixer); ``hybrid_override_pattern`` names the mixer of each
layer with one letter (:data:`LETTERS`):

* ``M``, ``mamba2``: a state-space mixer with a *scalar* decay a head
  (:class:`Mamba2Mixer`).  ``[z | xBC | dt] = h W_in``; a causal
  depthwise convolution over ``x``, ``B`` and ``C`` together; ``B`` and
  ``C`` shared by the heads of a group; ``s_t = exp(dt_t a) s_{t-1} +
  dt_t x_t (x) B_t`` a head, ``y_t = s_t C_t + D x_t``; then ``y *
  silu(z)``, an RMS norm over each group's channels, and ``W_out``.  Its
  cache is the state ``(heads, head_dim, d_state)`` float32 and the
  last ``d_conv - 1`` inputs of the convolution, one of each a row.
* ``*``, ``attention``: grouped-query attention with no positional
  encoding (:class:`PlainAttnMixer`), ``head_dim`` a key of its own
  (``n_heads * head_dim != d_model``), over the ``full`` block pool.
* ``E``, ``experts``: sigmoid-routed squared-ReLU experts beside a
  shared one (:func:`~..parallel.expert.shared_routed_ffn`).  The layer
  is told which experts it holds (``experts_held = (first, count)``):
  it routes over all ``n_experts``, computes the choices that fall on
  its own, and drops the rest, which are another chip's.

**A decode step** updates the state of one token a row: one pass over
the state, read and written where it lies, the state's 128 on the
lanes.  The layers are unrolled, so each state-space layer's state is a
leaf of its own (``cache["ssm"]["state"]`` a tuple), read once and
written once a step: a stack updated by slice at the layer's index, as
:mod:`.hybrid`'s scan carries one, made XLA keep a second copy of all
1.6 GB in the chunk program (seen in the program compiled for v5e).

**A prefill chunk** runs the *block form* of the same recurrence
(:meth:`Mamba2Mixer._blocks`; the config's ``chunk_size`` is the block):
within a block of ``Q`` tokens the outputs are a masked ``(Q x Q)``
product of ``C B^T`` with the decays between its positions, between
blocks one state is handed on.  Token by token a 512-token chunk would
read and write the 2 MB state 512 times a layer.  A padded position
takes a step of size 0: it leaves the state, and the convolution's
tail, as the last real token left them.

**The layers past the last one that keeps a cache** (trailing expert
layers) feed nothing but the logits: a chunk that does not end its
prompt skips them and returns no logits, one that does runs them on
the prompt's last token only (``final``, as :mod:`.hybrid`).

Layers are a tuple of one tree a layer, unrolled: the kinds differ, and
an expert layer's grouped matmul takes its ``(E, D, F)`` operand whole
(:mod:`.mla` says what a slice of a stack costs).

Not here: a dense (unpaged) cache, a mesh (the ``ep`` exchange that
would bring the dropped choices' results back), and the training
forward: nothing in the tree takes a gradient through the block form
or through the share of experts yet.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..parallel.expert import shared_routed_ffn
from ..utils import fan_in_normal
from .hybrid import HybridCache, PagedAttention, PagePool, StatefulConfig
from .transformer import _preset, _rms_norm, qlinear

LETTERS = {"M": "mamba2", "*": "attention", "E": "experts"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(StatefulConfig):
    """``pattern``: one of :data:`LETTERS` a layer.  ``n_heads`` /
    ``n_kv_heads`` / ``attn_head_dim`` are the attention layers';
    ``n_experts`` is the router's width, ``experts_held`` the ``(first,
    count)`` of them this model's expert layers carry (all by
    default); ``d_ff`` is unused (no layer has a dense MLP)."""
    n_kv_heads: int = 2
    attn_head_dim: int = 128
    pattern: str = "M*E"
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    d_state: int = 128
    d_conv: int = 4
    ssm_block: int = 128
    n_experts: int = 128
    experts_held: tuple | None = None
    top_k: int = 6
    d_expert: int = 1856
    d_shared: int = 3712
    routed_scale: float = 2.5
    state_stacked = False       # layers are unrolled: a leaf a layer

    def __post_init__(self):
        object.__setattr__(self, "head_dim", self.attn_head_dim)

    @property
    def layer_kinds(self) -> tuple:
        return tuple(LETTERS[c] for c in self.pattern)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: ``x``, ``B`` and ``C``."""
        return self.d_inner + 2 * self.ssm_groups * self.d_state

    @property
    def d_expert_stored(self) -> int:
        """Columns of a routed expert's ``w_up`` as stored: ``d_expert``
        rounded up to whole 128-lane tiles
        (:func:`~..parallel.expert._relu2` says why)."""
        return -(-self.d_expert // 128) * 128

    @property
    def held(self) -> tuple[int, int]:
        return tuple(self.experts_held or (0, self.n_experts))

    @property
    def tail_from(self) -> int:
        """Index of the first layer past the last one that keeps a
        cache."""
        kinds = self.layer_kinds
        return 1 + max(i for i, k in enumerate(kinds) if k != "experts")

    def page_pools(self) -> dict[str, PagePool]:
        return {"full": PagePool(self.layer_kinds.count("attention"),
                                 self.n_kv_heads, self.attn_head_dim)}

    def state_leaves(self) -> tuple[int, dict]:
        return self.layer_kinds.count("mamba2"), {
            "state": ((self.ssm_heads, self.ssm_head_dim, self.d_state),
                      jnp.float32),
            "conv": ((self.d_conv - 1, self.conv_width), self.dtype)}

    def num_params(self) -> int:
        """Parameters held: an expert layer counts the experts it
        holds, ``w_up`` at its published width."""
        pad = (self.held[1] * self.d_model
               * (self.d_expert_stored - self.d_expert))
        per_kind = {k: sum(math.prod(s) for s in jax.tree_util.tree_leaves(
            layer_weight_dims(self, k), is_leaf=lambda x: isinstance(
                x, tuple))) for k in LETTERS.values()}
        return (2 * self.vocab_size * self.d_model + self.d_model
                + sum(per_kind[k] for k in self.layer_kinds)
                - pad * self.layer_kinds.count("experts"))


def check_pattern(cfg: NemotronHConfig) -> None:
    """What the forward cannot run is refused by name."""
    bad = set(cfg.pattern) - set(LETTERS)
    if bad or len(cfg.pattern) != cfg.n_layers:
        raise ValueError(
            f"pattern must be {cfg.n_layers} letters of "
            f"{''.join(LETTERS)}, got {cfg.pattern!r}")
    if "M" not in cfg.pattern or "*" not in cfg.pattern:
        raise ValueError("the caches are laid out for a pattern with at "
                         "least one 'M' and one '*' layer, got "
                         f"{cfg.pattern!r}")
    if cfg.ssm_heads % cfg.ssm_groups or cfg.d_inner % cfg.ssm_groups:
        raise ValueError("ssm_heads and d_inner must divide by "
                         f"ssm_groups={cfg.ssm_groups}")
    first, count = cfg.held
    if first < 0 or count < 1 or first + count > cfg.n_experts:
        raise ValueError(f"experts_held {cfg.held} is not a share of "
                         f"{cfg.n_experts} experts")


def nemotron3_nano_config(**kw) -> NemotronHConfig:
    """NVIDIA-Nemotron-3-Nano-30B-A3B as its ``config.json`` publishes
    it: 52 layers, 23 Mamba-2, 23 expert, 6 attention."""
    return _preset(
        kw, cls=NemotronHConfig, vocab_size=131072, d_model=2688,
        n_layers=52, n_heads=32, d_ff=0, max_seq_len=262144, norm_eps=1e-5,
        pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")


def tiny_nemotron_h_config(**kw) -> NemotronHConfig:
    return _preset(
        kw, cls=NemotronHConfig, vocab_size=512, d_model=64, n_layers=6,
        n_heads=4, n_kv_heads=2, attn_head_dim=32, d_ff=0, max_seq_len=512,
        norm_eps=1e-5, pattern="MEM*EE", ssm_heads=8, ssm_head_dim=8,
        ssm_groups=2, d_state=16, ssm_block=8, n_experts=8, top_k=2,
        d_expert=32, d_shared=64)


# ----------------------------------------------------------------------
# parameters

def layer_weight_dims(cfg: NemotronHConfig, kind: str) -> dict:
    """name -> shape of one layer of ``kind`` (``moe`` nested).  Column
    order: ``w_in`` is ``[z | x | B | C | dt]`` as published; ``wkv``
    all of K then all of V (:func:`~.hf.nemotron_h_config_from_hf` says
    what a checkpoint needs)."""
    D, C, H = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    if kind == "mamba2":
        return {"norm": (D,), "w_in": (D, C + cfg.conv_width + H),
                "conv_w": (cfg.d_conv, cfg.conv_width),
                "conv_b": (cfg.conv_width,), "dt_bias": (H,),
                "A_log": (H,), "D": (H,), "gate_norm": (C,),
                "w_out": (C, D)}
    if kind == "attention":
        return {"norm": (D,), "wq": (D, q), "wkv": (D, 2 * kv),
                "wo": (q, D)}
    if kind == "experts":
        E, F, Fs = cfg.held[1], cfg.d_expert, cfg.d_shared
        return {"norm": (D,),
                "moe": {"router": (D, cfg.n_experts),
                        "bias": (cfg.n_experts,),
                        "w_up": (E, D, cfg.d_expert_stored),
                        "w_down": (E, F, D),
                        "shared": {"w_up": (D, Fs), "w_down": (Fs, D)}}}
    raise ValueError(f"unknown layer kind {kind!r}")


def init_layer(key, cfg: NemotronHConfig, kind: str) -> dict:
    """One layer of ``kind``: matrices N(0, 1/fan_in) in ``cfg.dtype``
    (the router's float32), norm scales 1, biases 0, and the mixer's
    own start: ``A = 1..heads``, a step size of about 0.01, ``D = 1``."""
    dims = layer_weight_dims(cfg, kind)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        dims, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for (path, shape), k in zip(flat, jax.random.split(key, len(flat))):
        name = path[-1].key
        if name in ("norm", "gate_norm", "D"):
            w = jnp.ones(shape, jnp.float32)
        elif name == "A_log":
            w = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
        elif name == "dt_bias":
            w = jnp.full(shape, jnp.log(jnp.expm1(0.01)), jnp.float32)
        elif len(shape) == 1:
            w = jnp.zeros(shape, jnp.float32)
        else:
            w = fan_in_normal(k, shape, shape[-2],
                              jnp.float32 if name == "router" else cfg.dtype)
        out.append(w)
    return jax.tree_util.tree_unflatten(tree, out)


def init_nemotron_h_model(key, cfg: NemotronHConfig) -> dict:
    """``layers`` is a tuple of one tree a layer, in the pattern's
    order; the head is untied."""
    check_pattern(cfg)
    ks = jax.random.split(key, cfg.n_layers + 2)
    D = cfg.d_model
    return {"embed": fan_in_normal(ks[-2], (cfg.vocab_size, D), D,
                                   cfg.dtype),
            "layers": tuple(init_layer(k, cfg, kind)
                            for k, kind in zip(ks, cfg.layer_kinds)),
            "final_norm": jnp.ones((D,), jnp.float32),
            "lm_head": fan_in_normal(ks[-1], (D, cfg.vocab_size), D,
                                     cfg.dtype)}


# ----------------------------------------------------------------------
# the mixers

class Mamba2Mixer:
    """The Mamba-2 mixer, and a mixer that owns state: :meth:`mix`
    takes the rows' state and convolution tail and hands them back
    advanced over the ``valid`` tokens alone."""

    def __init__(self, cfg: NemotronHConfig):
        self.cfg = cfg

    def mix(self, h, layer, state, tail, valid):
        """h (B, S, D); state (B, heads, head_dim, d_state) float32;
        tail (B, d_conv - 1, conv_width); valid (B, S) bool, a prefix
        of each row -> (out (B, S, D), state, tail)."""
        cfg = self.cfg
        H, P, G, N, K = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                         cfg.d_state, cfg.d_conv)
        C, W = cfg.d_inner, cfg.conv_width
        B_, S = h.shape[:2]
        f32 = jnp.float32
        zxd = qlinear(h, layer["w_in"])
        z, xbc, dt = zxd[..., :C], zxd[..., C:C + W], zxd[..., C + W:]
        xcat = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
        conv = sum(xcat[:, k:k + S].astype(f32) * layer["conv_w"][k]
                   .astype(f32) for k in range(K)) + layer["conv_b"]
        xbc = jax.nn.silu(conv).astype(h.dtype)             # (B, S, W)
        n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
        tail = jax.vmap(lambda row, n: jax.lax.dynamic_slice(
            row, (n, 0), (K - 1, W)))(xcat, n_valid).astype(tail.dtype)
        # heads as (groups, heads a group): B and C are a group's
        R = H // G
        x = xbc[..., :C].reshape(B_, S, G, R, P)
        Bm = xbc[..., C:C + G * N].reshape(B_, S, G, N)
        Cm = xbc[..., C + G * N:].reshape(B_, S, G, N)
        delta = jax.nn.softplus(dt.astype(f32) + layer["dt_bias"])
        delta = jnp.where(valid[..., None], delta, 0.0)     # (B, S, H)
        delta = delta.reshape(B_, S, G, R)
        a = -jnp.exp(layer["A_log"].astype(f32)).reshape(G, R)
        state = state.reshape(B_, G, R, P, N)
        if S == 1:
            y, state = self._step(x[:, 0], Bm[:, 0], Cm[:, 0],
                                  delta[:, 0], a, state)
            y = y[:, None]
        else:
            y, state = self._blocks(x, Bm, Cm, delta, a, state)
        y = y + layer["D"].reshape(G, R, 1) * x.astype(f32)
        y = y.reshape(B_, S, C) * jax.nn.silu(z.astype(f32))
        # RMS norm over each group's channels, after the gate
        yg = y.reshape(B_, S, G, C // G)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                                + cfg.norm_eps)
        y = (yg.reshape(B_, S, C) * layer["gate_norm"]).astype(h.dtype)
        return (qlinear(y, layer["w_out"]), state.reshape(B_, H, P, N),
                tail)

    @staticmethod
    def _step(x, Bm, Cm, delta, a, state):
        """One token a row.  x (B, G, R, P); Bm, Cm (B, G, N); delta
        (B, G, R); state (B, G, R, P, N) -> (y (B, G, R, P) float32,
        state)."""
        f32 = jnp.float32
        decay = jnp.exp(delta * a)[..., None, None]
        dx = (delta[..., None] * x.astype(f32))[..., None]
        state = decay * state + dx * Bm.astype(f32)[:, :, None, None, :]
        y = jnp.sum(state * Cm.astype(f32)[:, :, None, None, :], axis=-1)
        return y, state

    def _blocks(self, x, Bm, Cm, delta, a, state):
        """The block form over blocks of ``ssm_block`` tokens.  x (B, S,
        G, R, P); Bm, Cm (B, S, G, N); delta (B, S, G, R) float32, 0
        at a padded position; state (B, G, R, P, N) -> (y (B, S, G, R,
        P) float32, state).

        With ``l_t`` the sum of ``delta a`` up to and including ``t``
        inside a block: ``y_t = exp(l_t) C_t s_0 + sum_{u <= t}
        exp(l_t - l_u) (C_t . B_u) delta_u x_u`` and ``s_Q = exp(l_Q)
        s_0 + sum_u exp(l_Q - l_u) delta_u x_u (x) B_u``.  Every
        exponent is <= 0.  The products run in ``cfg.dtype`` with
        float32 sums, as the projections do; decays, step sizes and the
        state are float32."""
        cfg = self.cfg
        Q = min(cfg.ssm_block, x.shape[1])
        B_, S = x.shape[:2]
        pad = -S % Q
        if pad:
            grow = lambda t: jnp.pad(t, ((0, 0), (0, pad))
                                     + ((0, 0),) * (t.ndim - 2))
            x, Bm, Cm, delta = grow(x), grow(Bm), grow(Cm), grow(delta)
        nb = (S + pad) // Q
        blocks = lambda t: jnp.moveaxis(
            t.reshape((B_, nb, Q) + t.shape[2:]), 1, 0)
        f32, dtype = jnp.float32, x.dtype
        causal = jnp.tril(jnp.ones((Q, Q), bool))

        def block(state, inp):
            x, Bm, Cm, delta = inp          # (B, Q, ...)
            lsum = jnp.cumsum(delta * a, axis=1)            # (B, Q, G, R)
            dxf = delta[..., None] * x.astype(f32)          # (B,Q,G,R,P)
            dx = dxf.astype(dtype)
            # within the block
            cb = jnp.einsum("bqgn,bkgn->bgqk", Cm, Bm,
                            preferred_element_type=f32)
            lt = jnp.moveaxis(lsum, 1, -1)                  # (B, G, R, Q)
            seg = lt[..., :, None] - lt[..., None, :]       # (.., Qt, Qu)
            decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
            m = (cb[:, :, None] * decay).astype(dtype)      # (B,G,R,Q,Q)
            y = jnp.einsum("bgrqk,bkgrp->bqgrp", m, dx,
                           preferred_element_type=f32)
            # from the state the block starts with
            y = y + (jnp.einsum("bqgn,bgrpn->bqgrp", Cm.astype(f32), state)
                     * jnp.exp(lsum)[..., None])
            # the state the block ends with
            last = lsum[:, -1]                              # (B, G, R)
            w = jnp.exp(last[:, None] - lsum)               # (B, Q, G, R)
            dxw = (w[..., None] * dxf).astype(dtype)
            state = (jnp.exp(last)[..., None, None] * state
                     + jnp.einsum("bqgrp,bqgn->bgrpn", dxw, Bm,
                                  preferred_element_type=f32))
            return state, y

        state, y = jax.lax.scan(
            block, state, (blocks(x), blocks(Bm), blocks(Cm),
                           blocks(delta)))
        y = jnp.moveaxis(y, 0, 1).reshape((B_, nb * Q) + y.shape[3:])
        return y[:, :S], state


class PlainAttnMixer(PagedAttention):
    """Grouped-query attention with no positional encoding and no
    biases: queries ``(B, S, H, Dh)``, keys and values one head a KV
    head in the pool's layout."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__(cfg.head_dim, None)
        self.cfg = cfg

    def project_q(self, h, layer):
        cfg = self.cfg
        return qlinear(h, layer["wq"]).reshape(
            *h.shape[:2], cfg.n_heads, cfg.head_dim)

    def project_kv(self, h, layer):
        cfg = self.cfg
        B, S = h.shape[:2]
        kv = qlinear(h, layer["wkv"]).reshape(B, S, 2, cfg.n_kv_heads,
                                              cfg.head_dim)
        kv = kv.transpose(2, 0, 3, 1, 4)            # (2, B, Hkv, S, Dh)
        return {"k": kv[0], "v": kv[1]}

    def out(self, o, layer):
        return qlinear(o, layer["wo"])


# ----------------------------------------------------------------------
# the forward over the caches

def nemotron_h_forward_with_cache(params: dict, tokens, cache: dict,
                                  cache_len, cfg: NemotronHConfig, *,
                                  block_table, row_mask=None,
                                  token_mask=None, last_index=None,
                                  slot=None, final: bool = True):
    """:func:`~.generate.forward_with_cache` for this family, over the
    paged caches only; the arguments are
    :func:`~.hybrid.hybrid_forward_with_cache`'s.  A chunk that does
    not end its prompt (``final`` false, static) runs nothing past the
    last layer that keeps a cache and returns None for logits.

    Returns (logits float32 (B, 1, V) or None, the updated cache, the
    expert layers' routing load as ``[experts touched (mean over the
    layers run), most rows on one expert, rows routed a layer]``, over
    the experts held)."""
    check_pattern(cfg)
    B, S = tokens.shape
    if slot is not None and B != 1:
        raise ValueError("a prefill chunk is one row's")
    if slot is None and S != 1:
        raise ValueError("several new tokens a row are a prefill chunk: "
                         "pass the row's slot")
    cache_len = jnp.asarray(cache_len, jnp.int32)
    offs = cache_len[:, None] if cache_len.ndim == 1 else cache_len
    positions = offs + jnp.broadcast_to(jnp.arange(S), (B, S))
    valid = jnp.ones((B, S), bool) if token_mask is None else token_mask
    if row_mask is not None:
        valid = valid & row_mask[:, None]
    length = jnp.sum(valid, axis=1).astype(jnp.int32)
    attn = PlainAttnMixer(cfg)
    kv = HybridCache(cache, cfg, block_table, slot=slot, active=row_mask,
                     length=length if slot is not None else None,
                     start=offs if slot is not None else None,
                     mixers={"full": attn})
    ssm = Mamba2Mixer(cfg)
    states, pool = cache["ssm"], cache["full"]
    x = params["embed"][tokens].astype(cfg.dtype)
    loads, n_ssm, n_attn = [], 0, 0
    for i, (kind, layer) in enumerate(zip(cfg.layer_kinds,
                                          params["layers"])):
        if i == cfg.tail_from and S > 1:
            # What follows feeds only the logits: the last real token's.
            if not final:
                break
            x, valid = _last_token(x, length, last_index), None
        h = _rms_norm(x, layer["norm"], cfg.norm_eps)
        if kind == "mamba2":
            state, tail = kv.state(states, n_ssm)
            with jax.named_scope("mamba2"):
                out, state, tail = ssm.mix(h, layer, state, tail, valid)
            states = kv.put_state(states, n_ssm, state, tail)
            n_ssm += 1
        elif kind == "attention":
            with jax.named_scope("attention"):
                o, pool = kv.full_layer(
                    pool, jnp.int32(n_attn), attn.project_q(h, layer),
                    attn.project_kv(h, layer), positions)
                out = attn.out(o, layer)
            n_attn += 1
        else:
            out, load = shared_routed_ffn(
                h, layer["moe"], top_k=cfg.top_k,
                routed_scale=cfg.routed_scale, token_mask=valid,
                held=cfg.held, expert="relu2")
            loads.append(load)
        x = x + out
    new_cache = {"full": pool, "ssm": states}
    each = jnp.stack(loads) if loads else jnp.zeros((1, 3), jnp.float32)
    load = jnp.stack([jnp.mean(each[:, 0]), jnp.max(each[:, 1]),
                      jnp.mean(each[:, 2])])
    if S > 1 and not final:
        return None, new_cache, load
    if x.shape[1] > 1:      # a pattern that ends in a layer with a cache
        x = _last_token(x, length, last_index)
    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = qlinear(x, params["lm_head"]).astype(jnp.float32)
    return logits, new_cache, load


def _last_token(x, length, last_index):
    """x (B, S, D) -> (B, 1, D) at ``last_index`` (B,), by default each
    row's last real token."""
    B = x.shape[0]
    at = (length - 1 if last_index is None
          else jnp.asarray(last_index, jnp.int32)).reshape(B, 1, 1)
    return jnp.take_along_axis(
        x, jnp.broadcast_to(at, (B, 1, x.shape[-1])), axis=1)
