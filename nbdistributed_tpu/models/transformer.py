"""Llama-family decoder-only transformer, TPU-first.

The reference framework ships no models (users bring HF torch models in
cells — its demo runs SmolLM2-135M: 00_accelerate.ipynb cell 10); a
TPU-native framework needs a first-party model family for its
benchmarks and acceptance configs (BASELINE.json: tiny transformer DDP,
Llama-2-7B tensor-parallel forward).  Design:

* pure-JAX pytree params (no framework dependency on flax), bfloat16
  activations, fp32 RMSNorm accumulation — MXU-friendly;
* rotary embeddings, grouped-query attention (flash kernel from
  :mod:`nbdistributed_tpu.ops`), SwiGLU MLP — the Llama recipe;
* explicit ``PartitionSpec`` rules per parameter for dp/tp meshes
  (Megatron-style column/row splits expressed as shardings — XLA
  inserts the all-reduces the reference's users typed by hand,
  README.md:115-125);
* ``lax.scan`` over layers for O(1) compile scaling.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops import flash_attention
from ..parallel.overlap import hold_for_grad, sum_grads


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    use_flash: bool = True
    # Mistral-style sliding-window attention: each position attends at
    # most the previous `sliding_window` tokens.  None = full causal.
    sliding_window: int | None = None
    # Rematerialize each layer in the backward pass (jax.checkpoint):
    # activation memory drops from O(L·S·D) to O(S·D) + one extra
    # forward of compute — the standard long-context training trade on
    # HBM-bound TPUs.  Composes with sequence parallelism (ring/Ulysses
    # shard S; remat shrinks the per-layer residual footprint).
    remat: bool = False
    # Remat *policy*: what the checkpointed layer may keep.
    #   None      — save nothing (full recompute, minimum memory);
    #   "dots"    — jax.checkpoint_policies.checkpoint_dots: matmul
    #               outputs are saved, only cheap elementwise/norm ops
    #               recompute.  The backward skips re-running the MXU
    #               work, trading ~L·S·(3·d_ff + H·Dh + 2·Hkv·Dh + D)
    #               bytes of saved dots for most of remat's recompute
    #               FLOPs — the right default when the model fits.
    remat_policy: str | None = None
    # Chunked-vocab cross-entropy (ops/xent.py): the training loss
    # streams the lm_head in blocks of this many vocab columns and
    # never materializes the (B, S, V) logits — the buffer that caps
    # the train batch at LM scale (two+ fp32 copies of it live in the
    # naive loss).  None = standard full-logits path.  Engages on the
    # single-device / dp / sp paths (the scan body is row-wise math
    # GSPMD partitions over sharded tokens); under tp the head is
    # already vocab-sharded and the loss falls back to the standard
    # tail (loss_fn checks the sp mesh's tp axis; plain-tp callers
    # keep ce_chunk=None).  An int8-quantized lm_head also falls back
    # (quantized heads are the inference configuration; training
    # wants the dense head).
    ce_chunk: int | None = None
    # A head's width.  None (every preset): ``d_model // n_heads``,
    # filled in at construction; a model whose projections are wider or
    # narrower than the residual stream states its own.
    head_dim: int | None = None

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.n_heads)

    def num_params(self) -> int:
        emb = self.vocab_size * self.d_model
        attn = (self.d_model * self.n_heads * self.head_dim
                + 2 * self.d_model * self.n_kv_heads * self.head_dim
                + self.n_heads * self.head_dim * self.d_model)
        mlp = 3 * self.d_model * self.d_ff
        norms = 2 * self.d_model
        return emb * 2 + self.n_layers * (attn + mlp + norms) + self.d_model


# Preset configs.  llama2_7b matches the acceptance config in
# BASELINE.json ("8-rank Llama-2-7B forward"); tiny is the test/demo
# scale (SmolLM2-135M-like role in the reference's notebook).
# Caller kwargs OVERRIDE the preset's defaults (so e.g.
# smol_135m_config(max_seq_len=8192) works).
def _preset(kw: dict, cls=None, **defaults):
    """Build a preset config with caller kwargs overriding the
    defaults.  ``cls`` lets subclass factories (MoEConfig) share the
    same override contract."""
    return (cls or TransformerConfig)(**{**defaults, **kw})


def tiny_config(**kw) -> TransformerConfig:
    return _preset(kw, vocab_size=512, d_model=128, n_layers=2,
                   n_heads=4, n_kv_heads=2, d_ff=384, max_seq_len=256)


def smol_135m_config(**kw) -> TransformerConfig:
    return _preset(kw, vocab_size=49152, d_model=576, n_layers=30,
                   n_heads=9, n_kv_heads=3, d_ff=1536,
                   max_seq_len=2048)


def tinyllama_1b_config(**kw) -> TransformerConfig:
    """TinyLlama-1.1B dims (Zhang et al. 2024): the ~1B scale where
    d_model=2048 matmuls feed the MXU properly (a 135M model's d=576
    GEMMs cannot reach competitive MFU on a v5e)."""
    return _preset(kw, vocab_size=32000, d_model=2048, n_layers=22,
                   n_heads=32, n_kv_heads=4, d_ff=5632,
                   max_seq_len=2048)


def mistral_7b_config(**kw) -> TransformerConfig:
    """Mistral-7B-v0.1: the sliding-window release (4096-token window,
    rope theta 1e4, 32k positions).  v0.2/v0.3 dropped the window and
    raised theta to 1e6 — convert those via config_from_hf instead of
    this preset."""
    return _preset(kw, vocab_size=32000, d_model=4096, n_layers=32,
                   n_heads=32, n_kv_heads=8, d_ff=14336,
                   max_seq_len=32768, sliding_window=4096,
                   rope_theta=10000.0)


def llama2_7b_config(**kw) -> TransformerConfig:
    return _preset(kw, vocab_size=32000, d_model=4096, n_layers=32,
                   n_heads=32, n_kv_heads=32, d_ff=11008,
                   max_seq_len=4096)


# ----------------------------------------------------------------------
# parameters

def layer_weight_dims(cfg: TransformerConfig) -> dict:
    """(d_in, d_out) of every per-layer weight matrix — the single
    source of truth shared by :func:`init_params` and the LoRA adapter
    factory (lora.lora_init)."""
    D, H, Hkv, Dh, F = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                        cfg.head_dim, cfg.d_ff)
    return {"wq": (D, H * Dh), "wk": (D, Hkv * Dh), "wv": (D, Hkv * Dh),
            "wo": (H * Dh, D), "w_gate": (D, F), "w_up": (D, F),
            "w_down": (F, D)}


def init_params(key, cfg: TransformerConfig) -> dict:
    """Layer-stacked parameter pytree: per-layer arrays carry a leading
    (n_layers,) axis so the forward can ``lax.scan`` over them."""
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    D, L = cfg.d_model, cfg.n_layers
    dims = layer_weight_dims(cfg)

    def normal(key, shape, fan_in):
        from ..utils import fan_in_normal
        return fan_in_normal(key, shape, fan_in, cfg.dtype)

    names = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
    ks = dict(zip(names, jax.random.split(k_layers, len(names))))
    layers = {name: normal(ks[name], (L,) + dims[name], dims[name][0])
              for name in names}
    layers["attn_norm"] = jnp.ones((L, D), jnp.float32)
    layers["mlp_norm"] = jnp.ones((L, D), jnp.float32)
    return {
        "embed": normal(k_emb, (cfg.vocab_size, D), 1.0),
        "layers": layers,
        "final_norm": jnp.ones((D,), jnp.float32),
        "lm_head": normal(k_out, (D, cfg.vocab_size), D),
    }


def param_shardings(cfg: TransformerConfig) -> dict:
    """Megatron-style tensor-parallel sharding rules over mesh axis
    ``tp`` (columns of qkv/gate/up; rows of o/down — so each layer needs
    exactly one all-reduce per block, inserted by XLA)."""
    return {
        "embed": P(None, "tp"),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, "tp"),
            "wk": P(None, None, "tp"),
            "wv": P(None, None, "tp"),
            "wo": P(None, "tp", None),
            "mlp_norm": P(None, None),
            "w_gate": P(None, None, "tp"),
            "w_up": P(None, None, "tp"),
            "w_down": P(None, "tp", None),
        },
        "final_norm": P(None),
        "lm_head": P(None, "tp"),
    }


def fsdp_param_shardings(cfg: TransformerConfig,
                         dp_axis: str = "dp",
                         tp_axis: str | None = None) -> dict:
    """FSDP / ZeRO-3-style weight sharding expressed as GSPMD rules:
    every large weight is sharded over ``dp_axis`` (column-split
    weights on their contraction dim, row-split wo/w_down on their
    output dim — the opposite axis from Megatron's split, so the two
    never collide), and per-device parameter (and gradient, and — via
    the same rules on the optimizer init — optimizer-state) memory
    drops by the dp size.  XLA compiles the per-use all-gather /
    reduce-scatter schedule from the sharding lattice, exactly as
    torch FSDP does by hand; numerics are identical to replicated
    training (tested).

    With ``tp_axis`` the Megatron split applies on the other dim
    simultaneously (2-D weight sharding — the HSDP layout).  Norms
    stay replicated (tiny)."""
    row, col = dp_axis, tp_axis
    return {
        "embed": P(dp_axis, col),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, row, col),
            "wk": P(None, row, col),
            "wv": P(None, row, col),
            "wo": P(None, col, row),
            "mlp_norm": P(None, None),
            "w_gate": P(None, row, col),
            "w_up": P(None, row, col),
            "w_down": P(None, col, row),
        },
        "final_norm": P(None),
        "lm_head": P(dp_axis, col),
    }


# ----------------------------------------------------------------------
# forward

def is_quantized(leaf) -> bool:
    """True for an int8 weight-only quantized leaf ``{"q8", "s"}``
    (produced by models/quant.py; defined here so qlinear and quant.py
    share one predicate without an import cycle)."""
    return isinstance(leaf, dict) and "q8" in leaf and "s" in leaf


def is_quantized4(leaf) -> bool:
    """True for a nibble-packed int4 leaf ``{"q4", "s"}``
    (models/quant.py quantize_weight4)."""
    return isinstance(leaf, dict) and "q4" in leaf and "s" in leaf


# Nibble pack/unpack live HERE (beside the qlinear consumer) so the
# packing layout has exactly one definition; quant.py re-exports them
# — the same no-import-cycle arrangement as is_quantized above.

def _pack_nibbles(q):
    """(..., d_in, d_out) int values in [-7, 7] -> (..., d_in/2, d_out)
    uint8; row 2k rides the low nibble, row 2k+1 the high."""
    lo = (q[..., 0::2, :] & 0xF)
    hi = (q[..., 1::2, :] & 0xF)
    return (lo | (hi << 4)).astype(jnp.uint8)


def _unpack_nibbles(packed, dtype):
    """Inverse of :func:`_pack_nibbles` (sign-extended)."""
    p = packed.astype(jnp.int32)
    lo = (((p & 0xF) ^ 8) - 8)
    hi = ((((p >> 4) & 0xF) ^ 8) - 8)
    q = jnp.stack([lo, hi], axis=-2)          # (..., d_in/2, 2, d_out)
    return q.reshape(*packed.shape[:-2], packed.shape[-2] * 2,
                     packed.shape[-1]).astype(dtype)


def _qlinear4(x, w):
    """``x @ W`` for a nibble-packed int4 leaf with grouped scales.

    The packed uint8 array (d_in/2, d_out) is HALF the int8 bytes —
    what decode streams; the unpack (shift/mask/sign-extend) is
    elementwise arithmetic XLA fuses into the consumer.  Grouped
    scales don't commute with the whole matmul, so the contraction
    runs as G batched (group x d_out) einsums whose partials combine
    with the (G, d_out) scales — one extra small reduction on the
    activation side, nothing extra on the weight side."""
    q4, s = w["q4"], w["s"]
    if q4.ndim != 2:
        # quantize_weight4 supports stacked leaves (e.g. the
        # (n_layers, ...) scanned-layers tree), but this contraction
        # is written for one 2D weight — the reshape below would fold
        # the leading dims into G and fail with an opaque size
        # mismatch (or worse, silently contract wrong axes).
        raise ValueError(
            f"qlinear on a stacked int4 leaf (q4 shape "
            f"{tuple(q4.shape)}): expected a 2D (d_in/2, d_out) "
            f"weight — index or scan over the leading "
            f"{q4.ndim - 2} dim(s) and apply qlinear per slice")
    d_in, d_out = q4.shape[-2] * 2, q4.shape[-1]
    G = s.shape[-3]
    group = d_in // G
    qu = _unpack_nibbles(q4, x.dtype)
    qg = qu.reshape(G, group, d_out)
    xg = x.reshape(*x.shape[:-1], G, group)
    y = jnp.einsum("...gk,gko->...go", xg, qg).astype(jnp.float32)
    y = jnp.einsum("...go,go->...o", y,
                   s.reshape(G, d_out).astype(jnp.float32))
    return y.astype(x.dtype)


def qlinear(x, w):
    """``x @ w`` where ``w`` is a plain array or an int8 weight-only
    quantized leaf ``{"q8", "s"}`` (see models/quant.py).  Per-output-
    channel scales commute with the matmul, so the dot consumes the raw
    int8 array (half the HBM traffic — the convert to x.dtype fuses
    into the operand read; int8 magnitudes are exact in bf16) and the
    rescale is one fused per-column multiply in fp32."""
    if is_quantized(w):
        y = x @ w["q8"].astype(x.dtype)
        return (y.astype(jnp.float32) * w["s"]).astype(x.dtype)
    if is_quantized4(w):
        return _qlinear4(x, w)
    return x @ w


def _rms_norm(x, weight, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight).astype(x.dtype)


def _rope(x, positions, theta):
    """Rotary embedding.  x: (B, S, H, D); positions: (B, S)."""
    B, S, H, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # (B,S,half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class SeqParallel:
    """Route the model's attention through sequence parallelism.

    The rest of the network (embeddings, norms, MLP, lm_head) is
    position-wise, so GSPMD keeps it sequence-sharded for free once the
    batch's S axis is sharded over ``mesh[axis]``; attention is the one
    op that mixes positions, and this spec swaps it for the ring
    (``method="ring"``, any head count, K/V circulate at Hkv heads) or
    Ulysses (``method="ulysses"``, needs per-tp-shard head counts
    divisible by the axis size) implementation from the parallel
    library.  Zigzag-order ring training stays a library-level tool
    (it permutes the sequence axis, which would also permute the
    loss's next-token shift).

    ``dp_axis``/``tp_axis`` name the mesh axes the batch and head dims
    ride (they extend the attention shard_map specs, so dp/tp
    composition keeps attention local instead of all-gathering); each
    is used only if present in ``mesh`` — the defaults compose with
    the standard dp×sp×tp mesh with no ceremony.  ``use_flash=None``
    (default) follows ``cfg.use_flash``, so a CPU-oriented config
    doesn't silently pick the Pallas path.
    """
    mesh: Any
    axis: str = "sp"
    method: str = "ring"
    use_flash: bool | None = None
    dp_axis: str | None = "dp"
    tp_axis: str | None = "tp"

    def __post_init__(self):
        if self.method not in ("ring", "ulysses"):
            raise ValueError(f"unknown SeqParallel method "
                             f"{self.method!r}; use 'ring' or 'ulysses'")

    def _resolved_axes(self):
        """(batch_axis, head_axis), dropping names absent from mesh."""
        names = set(self.mesh.shape)
        return (self.dp_axis if self.dp_axis in names else None,
                self.tp_axis if self.tp_axis in names else None)


def _flash_on_mesh(q, k, v, window, segment_ids):
    """``flash_attention`` inside a program that may span devices.

    GSPMD cannot split a Mosaic kernel (jax refuses at lowering: "Mosaic
    kernels cannot be automatically partitioned"), so under an active
    mesh — the mesh step builders trace under theirs, a user's
    ``jax.set_mesh`` does the same — the kernel runs in a shard_map:
    batch over ``dp`` and heads over ``tp`` where the mesh has those
    axes and they divide (the naming :class:`SeqParallel` and
    ``generate._flash_decode_on_mesh`` use; whole GQA groups per shard,
    see ``ring_attention``), every other axis replicated.  Axes an
    enclosing shard_map already made manual are local as they are.
    """
    # block sizes None -> each kernel's tile from these shapes
    # (ops/attention.py::_block_sizes).
    def local(q, k, v, seg=None):
        return flash_attention(q, k, v, True, None, None, None, window,
                               seg)

    mesh = jax.sharding.get_abstract_mesh()
    free = frozenset(mesh.axis_names) - frozenset(mesh.manual_axes)
    if not free:
        return local(q, k, v, segment_ids)
    dp = "dp" if "dp" in free and q.shape[0] % mesh.shape["dp"] == 0 \
        else None
    tp = "tp" if "tp" in free and k.shape[2] % mesh.shape["tp"] == 0 \
        else None
    spec = P(dp, None, tp, None)
    args, in_specs = (q, k, v), (spec, spec, spec)
    if segment_ids is not None:
        args, in_specs = args + (segment_ids,), in_specs + (P(dp, None),)
    return jax.shard_map(local, in_specs=in_specs, out_specs=spec,
                         axis_names=free, check_vma=False)(*args)


def _attention_block(x, layer, cfg: TransformerConfig, positions,
                     sp: SeqParallel | None = None, segment_ids=None):
    B, S, D = x.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = _rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = qlinear(h, layer["wq"]).reshape(B, S, H, Dh)
    k = qlinear(h, layer["wk"]).reshape(B, S, Hkv, Dh)
    v = qlinear(h, layer["wv"]).reshape(B, S, Hkv, Dh)
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    if sp is not None:
        flash = cfg.use_flash if sp.use_flash is None else sp.use_flash
        batch_axis, head_axis = sp._resolved_axes()
        if sp.method == "ulysses":
            from ..parallel.ulysses import ulysses_attention
            o = ulysses_attention(q, k, v, sp.mesh, axis=sp.axis,
                                  causal=True, use_flash=flash,
                                  batch_axis=batch_axis,
                                  head_axis=head_axis,
                                  window=cfg.sliding_window,
                                  segment_ids=segment_ids)
        else:
            from ..parallel.ring import ring_attention
            o = ring_attention(q, k, v, sp.mesh, axis=sp.axis,
                               causal=True, use_flash=flash,
                               batch_axis=batch_axis,
                               head_axis=head_axis,
                               window=cfg.sliding_window,
                               segment_ids=segment_ids)
    elif cfg.use_flash:
        o = _flash_on_mesh(q, k, v, cfg.sliding_window, segment_ids)
    else:
        from ..ops import attention_reference
        o = attention_reference(q, k, v, causal=True,
                                window=cfg.sliding_window,
                                segment_ids=segment_ids)
    return x + qlinear(o.reshape(B, S, H * Dh), layer["wo"])


def _mlp_block(x, layer, cfg: TransformerConfig):
    h = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    # hold_for_grad: where a data-parallel step sums these gradients
    # inside the backward, each one's sends start at its own matmul
    gated = (jax.nn.silu(qlinear(*hold_for_grad(h, layer["w_gate"])))
             * qlinear(*hold_for_grad(h, layer["w_up"])))
    return x + qlinear(*hold_for_grad(gated, layer["w_down"]))


def make_layer_fn(cfg: TransformerConfig, positions,
                  sp: SeqParallel | None = None, segment_ids=None):
    """The per-layer recipe (attention block + MLP block, optionally
    rematerialized) — one definition shared by the plain forward and
    the pipelined stages (models/pp.py), so a change to the layer
    structure cannot silently diverge between them."""

    def one_layer(x, layer):
        x = _attention_block(x, layer, cfg, positions, sp, segment_ids)
        return _mlp_block(x, layer, cfg)

    # Validate the policy BEFORE the remat gate: a config carrying a
    # policy but remat=False (or an unknown policy string) must fail
    # loudly, not silently train with full activation memory.
    policy = getattr(cfg, "remat_policy", None)
    if policy not in (None, "dots", "attn_only", "mlp_only"):
        raise ValueError(f"unknown remat_policy {policy!r} "
                         f"(None, 'dots', 'attn_only' or 'mlp_only')")
    if policy is not None and not cfg.remat:
        raise ValueError("remat_policy is set but remat=False — the "
                         "policy would be silently ignored; set "
                         "remat=True (or drop the policy)")
    if not cfg.remat:
        return one_layer
    if policy == "dots":
        return jax.checkpoint(
            one_layer,
            policy=jax.checkpoint_policies.checkpoint_dots)
    if policy == "attn_only":
        # Recompute only the attention block (the O(S·D) internals the
        # flash kernel re-runs cheaply off its saved logsumexp); the
        # MLP's d_ff-wide activations — the per-layer memory bulk —
        # stay saved, so the backward skips 2/3 of the layer FLOPs a
        # full remat would re-run.
        attn = jax.checkpoint(lambda x, layer: _attention_block(
            x, layer, cfg, positions, sp, segment_ids))

        def one_layer_attn(x, layer):
            return _mlp_block(attn(x, layer), layer, cfg)

        return one_layer_attn
    if policy == "mlp_only":
        # Mirror image: recompute the MLP (plain GEMMs), keep the
        # attention internals saved — maximal memory saving among the
        # partial policies (the d_ff buffers dominate) at ~2/3-layer
        # recompute.
        mlp = jax.checkpoint(lambda x, layer: _mlp_block(
            x, layer, cfg))

        def one_layer_mlp(x, layer):
            return mlp(_attention_block(x, layer, cfg, positions, sp,
                                        segment_ids), layer)

        return one_layer_mlp
    return jax.checkpoint(one_layer)


def forward_hidden(params: dict, tokens, cfg: TransformerConfig,
                   positions=None, *, sp: SeqParallel | None = None,
                   segment_ids=None):
    """tokens: (B, S) int32 -> final-norm hidden states (B, S, D) in
    ``cfg.dtype`` — everything before the lm_head.  The chunked-vocab
    loss (ops/xent.py) consumes this directly so the (B, S, V) logits
    never exist."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = sum_grads(params["embed"])[tokens].astype(cfg.dtype)
    one_layer = make_layer_fn(cfg, positions, sp,
                              segment_ids=segment_ids)

    def layer_step(x, layer):
        # sum_grads: under a data-parallel step's grad_sums a layer's
        # gradients are summed over the shards inside the backward
        # scan, as each is made; anywhere else it is not in the trace.
        return one_layer(x, sum_grads(layer, of=params["layers"])), None

    x, _ = jax.lax.scan(layer_step, x, params["layers"])
    return _rms_norm(x, sum_grads(params["final_norm"]), cfg.norm_eps)


def forward(params: dict, tokens, cfg: TransformerConfig,
            positions=None, *, sp: SeqParallel | None = None,
            segment_ids=None):
    """tokens: (B, S) int32 -> logits (B, S, vocab) in fp32.

    With ``sp``, attention runs sequence-parallel (see
    :class:`SeqParallel`); shard the batch's S axis over
    ``sp.mesh[sp.axis]`` and jit as usual.  ``segment_ids`` (B, S):
    packed-document attention masking (see
    :func:`~nbdistributed_tpu.ops.attention.flash_attention`) —
    positions attend only within their own document."""
    x = forward_hidden(params, tokens, cfg, positions, sp=sp,
                       segment_ids=segment_ids)
    return qlinear(*hold_for_grad(x, sum_grads(params["lm_head"]))
                   ).astype(jnp.float32)


def shifted_xent(logits, tokens, segment_ids=None):
    """The logits-shift next-token cross-entropy tail: logits (B, S, V)
    from a full-S forward predict tokens[:, 1:] from positions 0..S-2.
    The single definition shared by the plain, SP, and pipelined
    losses — change it here and every path follows.

    ``segment_ids`` (B, S): packed-document batches exclude the
    boundary targets — position i must not be trained to predict the
    first token of the NEXT document (seg[i] != seg[i+1]); the mean
    runs over the surviving targets."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    if segment_ids is None:
        return jnp.mean(nll)
    keep = (segment_ids[:, :-1] == segment_ids[:, 1:])[..., None]
    return (jnp.sum(jnp.where(keep, nll, 0.0))
            / jnp.maximum(jnp.sum(keep), 1))


def packed_positions(segment_ids):
    """Within-document positions for a packed batch: position restarts
    at 0 at every document boundary, so RoPE sees each document as if
    it started the sequence — matching what the model will see at
    inference on unpacked prompts.  segment_ids (B, S) non-decreasing
    per row -> (B, S) int32."""
    seg = jnp.asarray(segment_ids)
    pos = jnp.arange(seg.shape[1], dtype=jnp.int32)[None]
    is_start = jnp.concatenate(
        [jnp.ones_like(seg[:, :1], bool),
         seg[:, 1:] != seg[:, :-1]], axis=1)
    seg_start = jax.lax.cummax(jnp.where(is_start, pos, 0), axis=1)
    return pos - seg_start


def _head_vocab_sharded(head) -> bool:
    """Best-effort: is this lm_head leaf sharded on its vocab (last)
    axis by a >1-way mesh axis?  Catches the plain-TP layout
    (``device_put`` with ``P(None, "tp")``, no SeqParallel object)
    whose sharding the ``sp``-based check below cannot see.  Only
    concrete arrays expose a committed ``NamedSharding``; under jit
    tracing or for quantized dict leaves detection is impossible and
    this returns False (the documented contract — don't set
    ``ce_chunk`` under plain tp — still applies there)."""
    try:
        spec = head.sharding.spec
        mesh_shape = dict(head.sharding.mesh.shape)
        ndim = head.ndim
    except Exception:
        return False
    if len(spec) < ndim:
        return False  # trailing (vocab) axis unmentioned = replicated
    entry = spec[ndim - 1]
    if entry is None:
        return False
    axes = entry if isinstance(entry, tuple) else (entry,)
    size = 1
    for a in axes:
        size *= mesh_shape.get(a, 1)
    return size > 1


def loss_fn(params, batch, cfg: TransformerConfig,
            sp: SeqParallel | None = None):
    """Next-token cross-entropy.  batch: {tokens (B,S)}; predicts
    tokens[:, 1:] from the logits at positions 0..S-2.

    The forward runs on the full S tokens and the *logits* are
    shifted, not the inputs: under causal attention position i's
    logits depend only on tokens <= i, so this is mathematically
    identical to forwarding tokens[:, :-1] — but it keeps the model's
    sequence length equal to the batch's (typically a power of two, so
    no kernel padding, and divisible by a sequence-parallel axis,
    which S-1 never is).

    ``batch["segments"]`` (optional, (B, S)): packed-document
    training — attention masks across documents, RoPE positions
    restart per document, and boundary targets drop from the loss."""
    tokens = batch["tokens"]
    seg = batch.get("segments")
    positions = packed_positions(seg) if seg is not None else None
    # A tp axis in the sp mesh means the lm_head is vocab-sharded
    # (param_shardings: P(None, "tp")) — slicing it chunk-wise would
    # make GSPMD re-gather the head every scan step, destroying the
    # memory win; fall back to the standard (already tp-sharded) tail.
    tp_sharded_head = (
        sp is not None and sp.tp_axis is not None
        and dict(getattr(sp.mesh, "shape", {})).get(sp.tp_axis, 1) > 1)
    if (not tp_sharded_head and cfg.ce_chunk is not None
            and _head_vocab_sharded(params["lm_head"])):
        # Plain-TP trap: a vocab-sharded head reached the
        # chunked path without an sp object — slicing it chunk-wise
        # would make GSPMD re-gather the whole head every scan step,
        # silently destroying the memory win.  Fall back loudly.
        import warnings
        warnings.warn(
            "ce_chunk ignored: lm_head is vocab-sharded (plain tensor "
            "parallelism) — the chunked tail would re-gather the head "
            "every scan step; using the standard tp-sharded tail "
            "instead", stacklevel=2)
        tp_sharded_head = True
    if (cfg.ce_chunk is not None and not tp_sharded_head
            and not is_quantized(params["lm_head"])
            and not is_quantized4(params["lm_head"])):
        # Chunked-vocab tail (ops/xent.py): the (B, S, V) logits never
        # materialize.  Same shift/boundary-mask contract as
        # shifted_xent — tests pin the two paths equal to fp32
        # reassociation.  Composes with sp: the scan body is plain
        # row-wise math over S-sharded hidden states and a replicated
        # head chunk, so GSPMD partitions it like the standard tail
        # (equality tested on the virtual sp mesh).
        from ..ops.xent import shifted_chunked_xent
        hidden = forward_hidden(params, tokens, cfg, positions, sp=sp,
                                segment_ids=seg)
        return shifted_chunked_xent(hidden, params["lm_head"], tokens,
                                    segment_ids=seg,
                                    chunk=cfg.ce_chunk)
    logits = forward(params, tokens, cfg, positions, sp=sp,
                     segment_ids=seg)
    return shifted_xent(logits, tokens, segment_ids=seg)


# ----------------------------------------------------------------------
# training step

def apply_optimizer_updates(params, updates):
    """Apply optax updates with fp32 accumulation, casting back to each
    leaf's storage dtype — the one mixed-precision update convention,
    shared by the full and LoRA train steps."""
    return jax.tree_util.tree_map(
        lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype),
        params, updates)


def make_train_step(cfg: TransformerConfig, optimizer,
                    sp: SeqParallel | None = None):
    """Returns ``step(params, opt_state, batch) -> (params, opt_state,
    loss)`` — shard params/batch and jit with shardings to scale it over
    any dp/tp mesh (XLA inserts gradient all-reduces for dp and
    activation collectives for tp).  ``sp`` additionally runs attention
    sequence-parallel for long-context batches (shard the batch's S
    axis over ``sp.mesh[sp.axis]``)."""

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg,
                                                  sp)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = apply_optimizer_updates(params, updates)
        return params, opt_state, loss

    return step


def num_tokens_per_step(batch_shape) -> int:
    return int(np.prod(batch_shape))
