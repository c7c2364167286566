"""State-space layers beside window and full attention, with one layer's
keys and values shared by the layers after it: the decoder-hybrid-decoder
block family as Phi-4-mini-flash-reasoning publishes it (``model_type:
phi4flash``), on the serving path.

Five kinds of layer (:data:`KINDS`), each ``x + mixer(LN(x))`` then
``x + SwiGLU(LN(x))`` with LayerNorm (scale and bias), no positional
encoding anywhere, and a head tied to the embedding:

* ``ssm``: a selective state-space mixer (Mamba-1).  Its cache is a
  recurrent state and the last ``d_conv - 1`` inputs of its causal
  convolution, one of each a row: counted in rows, not tokens
  (:class:`SSMMixer`).  The last one also hands its scan's output,
  before the gate, on as the token's *memory*.
* ``window`` / ``full``: differential attention (two softmaxes over one
  pair of values, :class:`DiffAttnMixer`) over the last
  ``sliding_window`` positions, or over all of them.  The one ``full``
  layer writes the *shared* pages.
* ``cross``: a query projection only; the same differential attention
  over the ``full`` layer's pages, which it reads and never writes.
* ``gmu``: a gated memory unit, ``W_out (m * silu(W_in h))`` with ``m``
  the memory of the same token.  No cache: ``m`` travels with the token.

The stack is ``P x (ssm, window)``, ``ssm``, ``full``, ``Q x (gmu,
cross)`` (:func:`layer_kinds_for` derives it from the published keys and
:func:`hybrid_stacks` refuses any other order); the pairs are stacked on
a leading axis and scanned, the two layers between them stand alone.

**Three kinds of cache** (:func:`make_hybrid_cache`, from what a
:class:`StatefulConfig` says it keeps: :mod:`.nemotron_h` is the second
family on the same :class:`HybridCache`, with no ``window`` kind and its
state a leaf a layer), all donated through the serving programs as one
tree:

* ``full``: today's block pool with one layer, a request's
  ``ceil((prompt + max_new) / block)`` blocks taken from the allocator;
* ``window``: a *ring of pages* a row a layer.  Row ``b`` owns physical
  pages ``[b R, (b + 1) R)`` and logical page ``j`` lies in ring slot
  ``j % R``, with ``R`` the pages of a window, a chunk and one more
  (what a chunk writes and attends at once), so a row holds at most
  ``R`` pages whatever ``max_len`` is and the table is arithmetic, not
  an allocation.  What a slot held a lap ago is masked by position, as
  a former owner's tokens are in the block pool;
* ``ssm``: ``(layers, rows, d_state, d_inner)`` float32 state and
  ``(layers, rows, d_conv - 1, d_inner)`` convolution tails.  Channels
  lie on the lanes: ``d_state`` minor would pad 16 to 128 lanes, eight
  times the memory and the traffic.  A prefill chunk at position 0
  starts from zeros (admission zeroes the row), and a padded position or
  an inactive row leaves both as the last real token left them (its
  step size is masked to 0 and the tail is taken at the real length).

**Differential attention through the kernels that are there.**  Query
heads pair up, KV heads pair up, and both softmaxes of a pair weigh the
same 128-wide ``[v1 | v2]``.  With ``K' = [k1 | k2]`` a pair, ``q1' =
[q1 | 0]`` and ``q2' = [0 | q2]``, that is grouped-query attention with
``n_kv_heads / 2`` KV heads of twice the width and four query heads
each: :func:`~..ops.decode.paged_decode_attention` and
:func:`~..ops.decode.paged_prefill_attention` run it unchanged, a page is
read once for both softmaxes, and a page's rows are whole 128-lane
tiles.  The price is the zeros in the score matmul, which a decode step
(bound by its reads) does not feel.

**Linear-time prefill.**  A chunk runs the layers up to the ``full``
one's K/V projection on every token, and the rest (that layer's own
attention included) on the prompt's last real token only, in the chunk
that holds it (``final``): the later layers' output at a position
depends only on that position's hidden state, its memory and the shared
pages, and only the last position's logits are served.

Not here: a dense (unpaged) cache, a mesh, an int8 pool, and the
training forward (the scan's backward): nothing in the tree trains this
family yet.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..serving_fast.paging import blocks_needed
from ..utils import fan_in_normal
from .transformer import TransformerConfig, _preset, qlinear

KINDS = ("ssm", "window", "full", "gmu", "cross")


class PagePool(NamedTuple):
    """One kind of paged K/V of a model that keeps several kinds of
    cache: ``layers`` layers write pages of ``heads`` rows ``width``
    wide a token; ``window`` (None: all a row holds) bounds what a query
    attends; ``readers`` layers read a page in a decode step for each
    layer that wrote it."""
    layers: int
    heads: int
    width: int
    window: int | None = None
    readers: int = 1


@dataclasses.dataclass(frozen=True)
class StatefulConfig(TransformerConfig):
    """A model whose layers keep per-row recurrent state beside (or in
    place of) pages: what :func:`make_hybrid_cache`,
    :class:`HybridCache` and :class:`~.serving.DecodeServer` ask of
    it.  Two families are: :class:`HybridConfig` here and
    :class:`~.nemotron_h.NemotronHConfig`."""

    def page_pools(self) -> dict[str, PagePool]:
        """kind -> :class:`PagePool`; ``"full"`` is the kind whose
        blocks the allocator hands out, a kind with a window is a ring
        of pages a row."""
        raise NotImplementedError

    def state_leaves(self) -> tuple[int, dict]:
        """(state-space layers, name -> (a row's shape, dtype)) of the
        ``ssm`` kind's leaves."""
        raise NotImplementedError

    # Whether the ``ssm`` leaves carry a leading layer axis (a stack
    # that a layer scan carries and updates by slice at the layer's
    # index) or are tuples of one array a layer (layers that are
    # unrolled: a layer's state is then a buffer of its own, read once
    # and written once where it lies, and no layer's update can make
    # XLA keep a second copy of another's).
    state_stacked = True


@dataclasses.dataclass(frozen=True)
class HybridConfig(StatefulConfig):
    """``layer_kinds``: one of :data:`KINDS` a layer, in order.
    ``sliding_window`` is the ``window`` layers' alone; ``d_ff`` every
    layer's SwiGLU."""
    n_kv_heads: int = 20
    sliding_window: int | None = 512
    layer_kinds: tuple = ()
    d_state: int = 16
    d_conv: int = 4
    ssm_expand: int = 2
    dt_rank: int = 160

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def kv_pairs(self) -> int:
        return self.n_kv_heads // 2

    @property
    def pair_dim(self) -> int:
        return 2 * self.head_dim

    def window_of(self, kind: str) -> int | None:
        """The window of a kind of attention layer."""
        return self.sliding_window if kind == "window" else None

    def page_pools(self) -> dict[str, PagePool]:
        p, q = hybrid_stacks(self)
        page = (self.kv_pairs, self.pair_dim)
        return {"full": PagePool(1, *page, readers=1 + q),
                "window": PagePool(p, *page, window=self.sliding_window)}

    def state_leaves(self) -> tuple[int, dict]:
        p, _q = hybrid_stacks(self)
        return p + 1, {
            "state": ((self.d_state, self.d_inner), jnp.float32),
            "conv": ((self.d_conv - 1, self.d_inner), self.dtype)}

    def num_params(self) -> int:
        per_kind = {k: sum(math.prod(s) for s in
                           layer_weight_dims(self, k).values())
                    for k in KINDS}
        return (self.vocab_size * self.d_model + 2 * self.d_model
                + sum(per_kind[k] for k in self.layer_kinds))


def layer_kinds_for(n_layers: int, period: int = 2) -> tuple:
    """The published rule (``num_hidden_layers``, ``mb_per_layer``):
    the first half and the layer after it alternate state-space and
    window attention, the next layer attends everything and keeps the
    shared K/V, the rest alternate memory units and cross-attention."""
    half = n_layers // 2
    kinds = []
    for i in range(n_layers):
        first = i % period == 0
        if i <= half:
            kinds.append("ssm" if first else "window")
        elif i == half + 1:
            kinds.append("full")
        else:
            kinds.append("gmu" if first else "cross")
    return tuple(kinds)


def hybrid_stacks(cfg: HybridConfig) -> tuple[int, int]:
    """(P, Q) of ``P x (ssm, window), ssm, full, Q x (gmu, cross)``;
    any other order of kinds is refused by name."""
    kinds = tuple(cfg.layer_kinds)
    if "full" not in kinds or kinds.index("full") % 2 == 0:
        raise ValueError(f"layer_kinds needs one 'full' layer at an odd "
                         f"index, got {kinds}")
    p = (kinds.index("full") - 1) // 2
    q = (len(kinds) - 2 * p - 2) // 2
    want = ("ssm", "window") * p + ("ssm", "full") + ("gmu", "cross") * q
    if kinds != want or len(kinds) != cfg.n_layers:
        raise ValueError(
            f"layer_kinds must be P x (ssm, window), ssm, full, Q x (gmu, "
            f"cross) over n_layers={cfg.n_layers}, got {kinds}")
    if cfg.n_heads != 2 * cfg.n_kv_heads or cfg.n_kv_heads % 2:
        raise ValueError("differential attention pairs two query heads "
                         "to a KV head and the KV heads two by two: "
                         f"n_heads={cfg.n_heads}, "
                         f"n_kv_heads={cfg.n_kv_heads}")
    return p, q


def phi4_mini_flash_config(**kw) -> HybridConfig:
    """Phi-4-mini-flash-reasoning (3.8B) as its ``config.json``
    publishes it."""
    return _preset(kw, cls=HybridConfig, vocab_size=200064, d_model=2560,
                   n_layers=32, n_heads=40, n_kv_heads=20, d_ff=10240,
                   max_seq_len=262144, norm_eps=1e-5, sliding_window=512,
                   layer_kinds=layer_kinds_for(32, 2), dt_rank=160)


def tiny_hybrid_config(**kw) -> HybridConfig:
    return _preset(kw, cls=HybridConfig, vocab_size=512, d_model=64,
                   n_layers=8, n_heads=8, n_kv_heads=4, d_ff=128,
                   max_seq_len=512, norm_eps=1e-5, sliding_window=32,
                   layer_kinds=layer_kinds_for(8, 2), d_state=8,
                   dt_rank=4)


# ----------------------------------------------------------------------
# parameters

def layer_weight_dims(cfg: HybridConfig, kind: str) -> dict:
    """name -> shape of one layer of ``kind``: its mixer, its two
    LayerNorms and its SwiGLU.  Column order: ``wq`` a KV pair's four
    query heads at a time, ``[q1 | q1' | q2 | q2']`` (the two query
    pairs that read the pair, first softmax then second); ``wkv`` all
    of K then all of V, a pair ``[k1 | k2]`` / ``[v1 | v2]`` at a time
    (:func:`~.hf.hybrid_config_from_hf` says what a checkpoint
    needs)."""
    D, C, N = cfg.d_model, cfg.d_inner, cfg.d_state
    Dh, H, Hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    dims = {"norm1_scale": (D,), "norm1_bias": (D,),
            "norm2_scale": (D,), "norm2_bias": (D,),
            "w_gate": (D, cfg.d_ff), "w_up": (D, cfg.d_ff),
            "w_down": (cfg.d_ff, D)}
    lambdas = {"lambda_q1": (Dh,), "lambda_k1": (Dh,),
               "lambda_q2": (Dh,), "lambda_k2": (Dh,),
               "subln": (2 * Dh,)}
    if kind == "ssm":
        dims.update(w_in=(D, 2 * C), conv_w=(cfg.d_conv, C), conv_b=(C,),
                    w_x=(C, cfg.dt_rank + 2 * N), w_dt=(cfg.dt_rank, C),
                    b_dt=(C,), A_log=(N, C), D=(C,), w_out=(C, D))
    elif kind in ("window", "full"):
        dims.update(wq=(D, H * Dh), bq=(H * Dh,),
                    wkv=(D, 2 * Hkv * Dh), bkv=(2 * Hkv * Dh,),
                    wo=(H * Dh, D), bo=(D,), **lambdas)
    elif kind == "cross":
        dims.update(wq=(D, H * Dh), bq=(H * Dh,), wo=(H * Dh, D),
                    bo=(D,), **lambdas)
    elif kind == "gmu":
        dims.update(w_in=(D, C), w_out=(C, D))
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return dims


def init_layer(key, cfg: HybridConfig, kind: str) -> dict:
    """One layer of ``kind``: matrices N(0, 1/fan_in) in ``cfg.dtype``,
    norm scales 1, biases 0, and the state-space mixer's own start
    (``A = -(1..d_state)``, a step size of about 0.01, ``D = 1``)."""
    out = {}
    dims = layer_weight_dims(cfg, kind)
    for (name, shape), k in zip(dims.items(),
                                jax.random.split(key, len(dims))):
        if name.endswith("_scale") or name in ("D", "subln"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name == "A_log":
            out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
        elif name == "b_dt":
            out[name] = jnp.full(shape, jnp.log(jnp.expm1(0.01)),
                                 jnp.float32)
        elif name.startswith("lambda_"):
            out[name] = 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif len(shape) == 1:
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = fan_in_normal(k, shape, shape[0], cfg.dtype)
    return out


def init_hybrid_model(key, cfg: HybridConfig) -> dict:
    """``self_pairs`` and ``cross_pairs`` carry a leading axis of their
    depth and hold a pair's two layers under their kinds; ``mid`` holds
    the two layers between them.  No ``lm_head``: the head is
    ``embed``."""
    p, q = hybrid_stacks(cfg)
    ks = jax.random.split(key, 4)

    def pairs(key, n, a, b):
        def one(k):
            ka, kb = jax.random.split(k)
            return {a: init_layer(ka, cfg, a), b: init_layer(kb, cfg, b)}
        return jax.vmap(one)(jax.random.split(key, n))

    k_ssm, k_full = jax.random.split(ks[2])
    D = cfg.d_model
    return {"embed": fan_in_normal(ks[3], (cfg.vocab_size, D), D,
                                   cfg.dtype),  # a head's scale: it is one
            "self_pairs": pairs(ks[0], p, "ssm", "window"),
            "mid": {"ssm": init_layer(k_ssm, cfg, "ssm"),
                    "full": init_layer(k_full, cfg, "full")},
            "cross_pairs": pairs(ks[1], q, "gmu", "cross"),
            "final_norm_scale": jnp.ones((D,), jnp.float32),
            "final_norm_bias": jnp.zeros((D,), jnp.float32)}


# ----------------------------------------------------------------------
# the caches

def ring_pages(cfg: StatefulConfig, block_tokens: int, max_len: int,
               chunk: int | None, window: int | None = None) -> int:
    """Pages a row's ring holds a window layer: a window's (``window``,
    by default ``cfg.sliding_window``), a chunk's (the longest run of
    tokens one program writes: ``chunk``, or the whole row where prompts
    are not chunked) and one more (a chunk that starts inside a page),
    never more than the row's pages."""
    pages = lambda t: blocks_needed(t, block_tokens)
    return min(pages(max_len), pages(window or cfg.sliding_window)
               + pages(chunk or max_len) + 1)


def make_hybrid_cache(cfg: StatefulConfig, n_blocks: int,
                      block_tokens: int, *, rows: int, max_len: int,
                      chunk: int | None = None) -> dict:
    """The kinds of cache ``cfg`` keeps, zeroed (the module docstring
    lays them out): one paged pool a :class:`PagePool`, each ending in
    a trash block, and the ``ssm`` leaves, a row a slot."""
    cache = {}
    for kind, pool in cfg.page_pools().items():
        blocks = int(n_blocks) if pool.window is None else rows * ring_pages(
            cfg, block_tokens, max_len, chunk, pool.window)
        cache[kind] = {
            name: jnp.zeros((pool.layers, blocks + 1, pool.heads,
                             int(block_tokens), pool.width), cfg.dtype)
            for name in ("k", "v")}
    layers, leaves = cfg.state_leaves()
    if cfg.state_stacked:
        cache["ssm"] = {name: jnp.zeros((layers, rows) + shape, dtype)
                        for name, (shape, dtype) in leaves.items()}
    else:
        cache["ssm"] = {
            name: tuple(jnp.zeros((rows,) + shape, dtype)
                        for _ in range(layers))
            for name, (shape, dtype) in leaves.items()}
    return cache


def cache_bytes_by_kind(cache: dict) -> dict:
    return {kind: sum(c.nbytes for c in jax.tree_util.tree_leaves(sub))
            for kind, sub in cache.items()}


# ----------------------------------------------------------------------
# the mixers

def _layer_norm(x, layer, name, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return (xc * jax.lax.rsqrt(var + eps) * layer[name + "_scale"]
            + layer[name + "_bias"]).astype(x.dtype)


def _mlp(x, layer, cfg):
    with jax.named_scope("mlp"):
        h = _layer_norm(x, layer, "norm2", cfg.norm_eps)
        gated = (jax.nn.silu(qlinear(h, layer["w_gate"]))
                 * qlinear(h, layer["w_up"]))
        return x + qlinear(gated, layer["w_down"])


class SSMMixer:
    """The selective state-space mixer, and a mixer that owns state:
    :meth:`mix` takes the row's state and convolution tail and hands
    them back advanced over the ``valid`` tokens alone."""

    def __init__(self, cfg: HybridConfig):
        self.cfg = cfg

    def mix(self, h, layer, state, tail, valid):
        """h (B, S, D); state (B, d_state, d_inner) float32; tail
        (B, d_conv - 1, d_inner); valid (B, S) bool, a prefix of each
        row -> (out (B, S, D), y (B, S, d_inner) the scan's output
        before the gate, state, tail)."""
        cfg = self.cfg
        C, N, R, K = cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv
        S = h.shape[1]
        f32 = jnp.float32
        xz = qlinear(h, layer["w_in"])
        x, z = xz[..., :C], xz[..., C:]
        xcat = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
        conv = sum(xcat[:, k:k + S].astype(f32) * layer["conv_w"][k]
                   .astype(f32) for k in range(K)) + layer["conv_b"]
        xc = jax.nn.silu(conv).astype(x.dtype)              # (B, S, C)
        n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
        tail = jax.vmap(lambda row, n: jax.lax.dynamic_slice(
            row, (n, 0), (K - 1, C)))(xcat, n_valid).astype(tail.dtype)
        dbc = qlinear(xc, layer["w_x"])                     # (B, S, R+2N)
        delta = jax.nn.softplus(
            qlinear(dbc[..., :R], layer["w_dt"]).astype(f32)
            + layer["b_dt"])
        delta = jnp.where(valid[..., None], delta, 0.0)     # (B, S, C)
        Bm = dbc[..., R:R + N].astype(f32)                  # (B, S, N)
        Cm = dbc[..., R + N:].astype(f32)
        A = -jnp.exp(layer["A_log"].astype(f32))            # (N, C)
        xf = xc.astype(f32)

        def token(s, inp):
            d, b, c, xt = inp           # (B, C), (B, N), (B, N), (B, C)
            s = (jnp.exp(d[:, None, :] * A[None]) * s
                 + (d * xt)[:, None, :] * b[:, :, None])
            return s, jnp.sum(s * c[:, :, None], axis=1)

        if S == 1:
            state, y = token(state, (delta[:, 0], Bm[:, 0], Cm[:, 0],
                                     xf[:, 0]))
            y = y[:, None]
        else:
            state, y = jax.lax.scan(
                token, state,
                tuple(a.swapaxes(0, 1) for a in (delta, Bm, Cm, xf)),
                unroll=8)
            y = y.swapaxes(0, 1)
        y = (y + layer["D"] * xf).astype(h.dtype)           # (B, S, C)
        out = qlinear(y * jax.nn.silu(z), layer["w_out"])
        return out, y, state, tail


class PagedAttention:
    """What every attention mixer of a :class:`StatefulConfig` shares:
    grouped-query attention with no positional encoding over K and V
    heads ``width`` wide, through a gathered view (:meth:`attend`) or
    over the paged pool where it lies (:meth:`attend_paged`).  A
    subclass projects queries, keys and values and the output."""

    def __init__(self, width: int, window: int | None):
        self.window = window
        self.scale = 1.0 / float(width) ** 0.5

    def attend(self, q, view, positions):
        from .generate import _cached_attention
        return _cached_attention(q, view["k"], view["v"], positions,
                                 self.scale, window=self.window)

    def attend_paged(self, q, pool, layer_idx, table, pos, active,
                     length=None):
        from ..ops.decode import (paged_decode_attention,
                                  paged_prefill_attention)
        if length is None:
            o = paged_decode_attention(
                q[:, 0], pool["k"], pool["v"], layer_idx, table, pos,
                active=active, scale=self.scale, window=self.window)
        else:
            o = paged_prefill_attention(
                q, pool["k"], pool["v"], layer_idx, table, pos, length,
                scale=self.scale, window=self.window)
        return o.reshape(*q.shape[:2], -1)


class DiffAttnMixer(PagedAttention):
    """Differential attention's half of the seam (the contract is
    :class:`~.generate.GQAMixer`'s, in two halves since a layer may
    project queries alone): queries come out as four heads a KV pair,
    zero-padded to the pair's width, keys and values as one head a pair
    (the module docstring says why), and :meth:`out` takes the second
    softmax's output from the first's."""

    def __init__(self, cfg: HybridConfig, window: int | None):
        super().__init__(cfg.head_dim, window)
        self.cfg = cfg

    def project_q(self, h, layer):
        cfg = self.cfg
        B, S = h.shape[:2]
        q = (qlinear(h, layer["wq"]) + layer["bq"].astype(h.dtype)) \
            .reshape(B, S, cfg.kv_pairs, 4, cfg.head_dim)
        zero = jnp.zeros_like(q[..., :2, :])
        q = jnp.concatenate(
            [jnp.concatenate([q[..., :2, :], zero], axis=-1),
             jnp.concatenate([zero, q[..., 2:, :]], axis=-1)], axis=-2)
        return q.reshape(B, S, cfg.n_heads, cfg.pair_dim)

    def project_kv(self, h, layer):
        cfg = self.cfg
        B, S = h.shape[:2]
        kv = (qlinear(h, layer["wkv"]) + layer["bkv"].astype(h.dtype)) \
            .reshape(B, S, 2, cfg.kv_pairs, cfg.pair_dim)
        kv = kv.transpose(2, 0, 3, 1, 4)            # (2, B, pairs, S, 2Dh)
        return {"k": kv[0], "v": kv[1]}

    def out(self, o, layer, depth):
        """o (B, S, n_heads * pair_dim) -> (B, S, D).  ``depth`` is the
        layer's index in the model (traced in a scanned stack)."""
        cfg = self.cfg
        B, S = o.shape[:2]
        f32, dtype = jnp.float32, o.dtype
        lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, f32))
        lam = (jnp.exp(jnp.sum(layer["lambda_q1"] * layer["lambda_k1"]))
               - jnp.exp(jnp.sum(layer["lambda_q2"] * layer["lambda_k2"]))
               + lam_init)
        o = o.astype(f32).reshape(B, S, cfg.kv_pairs, 4, cfg.pair_dim)
        a = o[..., :2, :] - lam * o[..., 2:, :]     # (B, S, pairs, 2, 2Dh)
        a = (a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True)
                               + cfg.norm_eps)
             * layer["subln"] * (1.0 - lam_init))
        a = a.reshape(B, S, -1).astype(dtype)
        return qlinear(a, layer["wo"]) + layer["bo"].astype(dtype)


class HybridCache:
    """The cache's side of the seam, every kind in one object: what a
    ``window`` layer does to its ring (:meth:`window_layer`), what a
    layer that keeps all its keys does to its pages
    (:meth:`full_layer`; or, where later layers read one layer's pages,
    :meth:`full_write` and :meth:`full_read` apart), and a state-space
    layer's state in and out of the tree (:meth:`state` /
    :meth:`put_state`).  ``mixers``: the :class:`PagedAttention` of
    each kind of pages the cache holds.

    ``slot`` (a traced scalar) makes the call a prefill chunk of that
    one row: its tokens go into pages through ``write_chunk``, its
    state is taken out of the row and put back.  Without it the call is
    a decode step over every row, one token each, ``active`` the rows
    that take part."""

    def __init__(self, cache: dict, cfg: StatefulConfig, block_table, *,
                 slot, active, length, start, mixers: dict):
        from .paged_kv import reads_in_place
        self.cfg = cfg
        self._table = block_table
        self._slot, self._active, self._length = slot, active, length
        self._start = start
        self._in_place = reads_in_place(cfg, None)
        self._bt = cache["full"]["k"].shape[3]
        self._stacked = cfg.state_stacked
        rows_total = jax.tree_util.tree_leaves(cache["ssm"])[0].shape[
            1 if self._stacked else 0]
        rows = (jnp.arange(rows_total, dtype=jnp.int32) if slot is None
                else jnp.asarray(slot, jnp.int32).reshape(1))
        self._rows = rows
        self.full = mixers["full"]
        self.window = mixers.get("window")
        if self.window is not None:
            self._ring = (cache["window"]["k"].shape[1] - 1) // rows_total
            pages = jnp.arange(block_table.shape[1], dtype=jnp.int32)
            self._ring_table = (rows[:, None] * self._ring
                                + (pages % self._ring)[None, :])

    # -- state ---------------------------------------------------------
    def state(self, ssm: dict, i) -> tuple:
        """State-space layer ``i``'s (state, tail) over the call's
        rows: every row's, or the one row's of a chunk, zeros where the
        chunk opens the prompt.  Taken from, and put back into
        (:meth:`put_state`), the tree the layers hand on, so the
        donated buffers are updated where they lie: a stack by slice at
        the layer's index (``i`` may be traced), a tuple of one leaf a
        layer by its element (``i`` a Python int)."""
        if not self._stacked:
            ssm = {k: c[i][None] for k, c in ssm.items()}
            i = 0
        if self._slot is None:
            take = lambda c: jax.lax.dynamic_index_in_dim(
                c, i, 0, keepdims=False)
            return take(ssm["state"]), take(ssm["conv"])
        keep = self._start.reshape(()) != 0
        take = lambda c: jax.lax.dynamic_slice(
            c, (i, self._slot) + (0,) * (c.ndim - 2),
            (1, 1) + c.shape[2:])[0]
        return (take(ssm["state"]) * keep.astype(jnp.float32),
                take(ssm["conv"]) * keep.astype(ssm["conv"].dtype))

    def put_state(self, ssm: dict, i, state, tail) -> dict:
        new = {"state": state, "conv": tail}
        if not self._stacked:
            if self._slot is None:
                one = lambda c, n: n.astype(c.dtype)
            else:
                one = lambda c, n: jax.lax.dynamic_update_slice(
                    c, n.astype(c.dtype),
                    (self._slot,) + (0,) * (c.ndim - 1))
            return {k: c[:i] + (one(c[i], new[k]),) + c[i + 1:]
                    for k, c in ssm.items()}
        row = 0 if self._slot is None else self._slot
        return {k: jax.lax.dynamic_update_slice(
            c, new[k].astype(c.dtype)[None],
            (i, row) + (0,) * (c.ndim - 2)) for k, c in ssm.items()}

    # -- pages ---------------------------------------------------------
    def _write(self, pool, layer_idx, new, table, pos):
        from .paged_kv import write_chunk, write_token
        if self._slot is not None:
            return write_chunk(pool, layer_idx, new, table, pos)
        return write_token(pool, layer_idx, new, table, pos, self._active)

    def _read_one(self, mixer, pool, layer_idx, q, table, pos):
        """One query a row at ``pos`` (B,), over the pages ``table``
        maps: the kernel in place, or a gathered view."""
        if self._in_place:
            return mixer.attend_paged(q, pool, layer_idx, table, pos,
                                      self._active)
        from .paged_kv import gather_layer
        return mixer.attend(q, gather_layer(pool, layer_idx, table),
                            pos[:, None])

    def window_layer(self, pool, layer_idx, q, new, positions):
        """Write the new tokens into the rows' rings, then attend."""
        pos = positions[:, 0]
        pool = self._write(pool, layer_idx, new, self._ring_table, pos)
        if self._slot is not None:
            return self.window.attend_paged(
                q, pool, layer_idx, self._ring_table, pos, None,
                length=self._length), pool
        if not self._in_place:
            return self._read_one(self.window, pool, layer_idx, q,
                                  self._ring_table, pos), pool
        # A step's window spans at most pages(window) + 1 pages: hand
        # the kernel a table of those alone, and the position counted
        # from the first of them (the window's mask is the same under
        # a shift), so the table it keeps in scalar memory is the
        # window's pages and not the row's.
        bt, ring = self._bt, self._ring
        span = -(-self.window.window // bt) + 1
        base = jnp.maximum(pos + 1 - self.window.window, 0) // bt
        table = (self._rows[:, None] * ring
                 + (base[:, None] + jnp.arange(span)[None, :]) % ring)
        return self.window.attend_paged(
            q, pool, layer_idx, table.astype(jnp.int32), pos - base * bt,
            self._active), pool

    def full_layer(self, pool, layer_idx, q, new, positions):
        """Write the new tokens into the rows' pages of layer
        ``layer_idx``, then attend: every token of a chunk, or a
        step's one a row."""
        pos = positions[:, 0]
        pool = self._write(pool, layer_idx, new, self._table, pos)
        if self._slot is not None:
            return self.full.attend_paged(
                q, pool, layer_idx, self._table, pos, None,
                length=self._length), pool
        return self._read_one(self.full, pool, layer_idx, q, self._table,
                              pos), pool

    def full_write(self, pool, new, positions):
        return self._write(pool, jnp.int32(0), new, self._table,
                           positions[:, 0])

    def full_read(self, pool, q, pos):
        return self._read_one(self.full, pool, jnp.int32(0), q,
                              self._table, pos)


# ----------------------------------------------------------------------
# the forward over the caches

def hybrid_forward_with_cache(params: dict, tokens, cache: dict, cache_len,
                              cfg: HybridConfig, *, block_table,
                              row_mask=None, token_mask=None,
                              last_index=None, slot=None,
                              final: bool = True):
    """:func:`~.generate.forward_with_cache` for this family, over the
    paged caches only.  A decode step: ``tokens`` (rows, 1),
    ``cache_len`` (rows,), ``row_mask`` the active rows.  A prefill
    chunk: ``tokens`` (1, S) right-padded, ``cache_len`` its first
    token's position, ``token_mask`` its real tokens, ``slot`` the row
    it belongs to, ``last_index`` its last real token, and ``final``
    (static) whether it ends the prompt: a chunk that does not returns
    no logits and runs nothing past the shared K/V's projection.

    Returns (logits float32 (B, 1, V) or None, the updated cache)."""
    p_pairs, _q = hybrid_stacks(cfg)
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
    kv = HybridCache(
        cache, cfg, block_table, slot=slot, active=row_mask,
        length=length if slot is not None else None,
        start=offs if slot is not None else None,
        mixers={"full": DiffAttnMixer(cfg, cfg.window_of("full")),
                "window": DiffAttnMixer(cfg, cfg.window_of("window"))})
    ssm = SSMMixer(cfg)
    eps = cfg.norm_eps
    x = params["embed"][tokens].astype(cfg.dtype)

    def ssm_layer(x, layer, states, i):
        state, tail = kv.state(states, i)
        with jax.named_scope("ssm"):
            out, y, state, tail = ssm.mix(
                _layer_norm(x, layer, "norm1", eps), layer, state, tail,
                valid)
        return (_mlp(x + out, layer, cfg), y,
                kv.put_state(states, i, state, tail))

    def self_pair(carry, inp):
        x, wpool, states = carry
        pair, i = inp
        x, _y, states = ssm_layer(x, pair["ssm"], states, i)
        layer = pair["window"]
        with jax.named_scope("attention"):
            h = _layer_norm(x, layer, "norm1", eps)
            o, wpool = kv.window_layer(
                wpool, i, kv.window.project_q(h, layer),
                kv.window.project_kv(h, layer), positions)
            x = x + kv.window.out(o, layer, 2 * i + 1)
        return (_mlp(x, layer, cfg), wpool, states), None

    (x, wpool, states), _ = jax.lax.scan(
        self_pair, (x, cache["window"], cache["ssm"]),
        (params["self_pairs"], jnp.arange(p_pairs, dtype=jnp.int32)))
    x, memory, states = ssm_layer(x, params["mid"]["ssm"], states,
                                  jnp.int32(p_pairs))
    new_cache = {"window": wpool, "ssm": states}

    # The layer that keeps the shared K/V: every token's go into its
    # pages; everything after that is the last real token's alone.
    layer = params["mid"]["full"]
    with jax.named_scope("attention"):
        h = _layer_norm(x, layer, "norm1", eps)
        new_cache["full"] = fpool = kv.full_write(
            cache["full"], kv.full.project_kv(h, layer), positions)
    if not final:
        return None, new_cache
    if S > 1:
        at = (length - 1 if last_index is None
              else jnp.asarray(last_index, jnp.int32)).reshape(B, 1, 1)
        tail_of = lambda a: jnp.take_along_axis(
            a, jnp.broadcast_to(at, (B, 1, a.shape[-1])), axis=1)
        x, h, memory = tail_of(x), tail_of(h), tail_of(memory)
        pos = jnp.take_along_axis(positions, at[:, :, 0], axis=1)[:, 0]
    else:
        pos = positions[:, 0]
    depth = 2 * p_pairs + 1
    with jax.named_scope("attention"):
        o = kv.full_read(fpool, kv.full.project_q(h, layer), pos)
        x = x + kv.full.out(o, layer, depth)
    x = _mlp(x, layer, cfg)

    def cross_pair(x, inp):
        pair, j = inp
        layer = pair["gmu"]
        with jax.named_scope("gmu"):
            h = _layer_norm(x, layer, "norm1", eps)
            x = x + qlinear(memory * jax.nn.silu(qlinear(h, layer["w_in"])),
                            layer["w_out"])
        x = _mlp(x, layer, cfg)
        layer = pair["cross"]
        with jax.named_scope("cross_attention"):
            h = _layer_norm(x, layer, "norm1", eps)
            o = kv.full_read(fpool, kv.full.project_q(h, layer), pos)
            x = x + kv.full.out(o, layer, depth + 2 + 2 * j)
        return _mlp(x, layer, cfg), None

    n_cross = jax.tree_util.tree_leaves(params["cross_pairs"])[0].shape[0]
    x, _ = jax.lax.scan(cross_pair, x,
                        (params["cross_pairs"],
                         jnp.arange(n_cross, dtype=jnp.int32)))
    x = _layer_norm(x, params, "final_norm", eps)
    # The head is the embedding: contracted over its minor axis where
    # it lies, no transposed copy of it.
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"],
                        preferred_element_type=jnp.float32)
    return logits.astype(jnp.float32), new_cache
