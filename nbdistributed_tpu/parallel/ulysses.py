"""Ulysses-style all-to-all sequence parallelism.

The second long-context strategy beside ring attention (ring.py): instead
of streaming K/V chunks around the ring, two ``all_to_all`` collectives
re-shard the activations from sequence-sharded to *head*-sharded and
back, so every device runs ordinary full-sequence attention on its slice
of heads (DeepSpeed-Ulysses pattern; the reference has no sequence
parallelism at all, SURVEY §5.7).

Trade-off vs ring: communication is 2 all-to-alls of the activations
(O(B·S·H·D / n) per device, one shot each way, ideal on ICI's all-to-all
bandwidth) instead of n ppermute hops, and the inner attention is a
plain local kernel — so it composes directly with the Pallas flash
kernel (ops/attention.py).  The constraint is that the head counts
(H *and* Hkv) must be divisible by the mesh axis size, which ring does
not require.  GQA is native: K/V all-to-all at Hkv heads (H/Hkv× less
traffic than pre-expanding), and the local attention keeps the group
ratio.

Layouts inside ``shard_map`` (local views, mesh axis size n; K/V the
same with H -> Hkv):

    (B, S/n, H, D)  --all_to_all(split H, concat S)-->  (B, S, H/n, D)
        ... full-sequence GQA attention over H/n q heads ...
    (B, S, H/n, D)  --all_to_all(split S, concat H)-->  (B, S/n, H, D)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


@functools.lru_cache(maxsize=None)
def _ulysses_fn(mesh, axis: str, causal: bool, scale: float,
                use_flash: bool, batch_axis: str | None = None,
                head_axis: str | None = None,
                window: int | None = None,
                with_segments: bool = False):
    spec = P(batch_axis, axis, head_axis, None)
    inner = functools.partial(_ulysses_inner, axis=axis, causal=causal,
                              scale=scale, use_flash=use_flash,
                              window=window)
    in_specs = (spec, spec, spec)
    if with_segments:
        in_specs = in_specs + (P(batch_axis, axis),)
    return jax.jit(jax.shard_map(
        inner, mesh=mesh, in_specs=in_specs, out_specs=spec,
        check_vma=False))


def ulysses_attention(q, k, v, mesh, *, axis: str = "sp",
                      causal: bool = True, scale: float | None = None,
                      use_flash: bool = False,
                      batch_axis: str | None = None,
                      head_axis: str | None = None,
                      window: int | None = None,
                      segment_ids=None):
    """Exact attention with Q/K/V sequence-sharded over ``mesh[axis]``,
    computed head-parallel after an all-to-all re-shard.

    q: (B, S, H, D) and k/v: (B, S, Hkv, D) global arrays, S sharded
    over ``mesh[axis]``; returns output with the same sharding.
    Requires ``H % n == 0`` and ``Hkv % n == 0`` — K/V are NOT
    expanded: their all-to-alls move ``H/Hkv``× less data than
    pre-expanding would, and the local attention runs GQA natively
    (each device holds H/n query heads against Hkv/n KV heads, the
    same group ratio).  ``use_flash=True`` runs the Pallas flash
    kernel as the local attention (TPU path; forward and blockwise
    backward); default is the XLA reference.

    ``batch_axis``/``head_axis``: mesh axes the batch and head dims are
    sharded over (dp/tp composition).  With ``head_axis`` the per-shard
    head counts ``H/tp`` and ``Hkv/tp`` are what the sequence
    all-to-alls split, so both must still be divisible by the ``axis``
    size; omitting these when activations ARE dp/tp-sharded makes GSPMD
    all-gather and compute attention replicated.
    """
    n = mesh.shape[axis]
    H, Hkv = q.shape[2], k.shape[2]
    t = mesh.shape[head_axis] if head_axis is not None else 1
    if head_axis is not None and (H % t or Hkv % t):
        raise ValueError(
            f"head_axis {head_axis!r} (size {t}) must divide both "
            f"H={H} and Hkv={Hkv}")
    if (H // t) % n != 0 or (Hkv // t) % n != 0:
        raise ValueError(
            f"ulysses_attention needs both per-shard head counts "
            f"divisible by the {axis!r} axis: H/t={H // t}, "
            f"Hkv/t={Hkv // t}, n={n}. Use ring_attention for head "
            "counts that don't split.")
    if H % Hkv != 0:
        raise ValueError(
            f"n_heads {H} not divisible by n_kv_heads {Hkv}")
    if v.shape[2] != Hkv:
        raise ValueError(
            f"k/v head counts differ: {Hkv} vs {v.shape[2]}")
    from ..ops.attention import check_window
    check_window(window, causal)
    if segment_ids is not None:
        # Packed-document masking: each device's local segment chunk is
        # all-gathered to full length inside the shard_map (tiny int32
        # vs the activation all-to-alls) and the local full-sequence
        # attention applies the mask.
        if segment_ids.shape != q.shape[:2]:
            raise ValueError(
                f"segment_ids shape {segment_ids.shape} != (B, S) "
                f"{q.shape[:2]}")
        if q.shape[1] != k.shape[1]:
            raise ValueError("segment_ids requires Sq == Sk")
    D = q.shape[-1]
    scale = scale if scale is not None else float(1.0 / np.sqrt(D))
    fn = _ulysses_fn(mesh, axis, causal, scale, use_flash,
                     batch_axis, head_axis, window,
                     with_segments=segment_ids is not None)
    if segment_ids is None:
        return fn(q, k, v)
    return fn(q, k, v, jnp.asarray(segment_ids, jnp.int32))


def _ulysses_inner(q, k, v, seg=None, *, axis: str, causal: bool,
                   scale: float, use_flash: bool,
                   window: int | None = None):
    from ..ops import attention_reference, flash_attention

    # seq-sharded -> head-sharded: gather the full sequence, keep H/n.
    def seq_to_heads(x):
        return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    # After the all-to-all each device holds the FULL sequence on its
    # head slice, so the sliding window is just the local kernels'
    # ordinary window argument — and packed-document segments are the
    # full-length ids, all-gathered from the sequence shards.
    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    seg_full = (None if seg is None else
                jax.lax.all_gather(seg, axis, axis=1, tiled=True))
    if use_flash:
        out = flash_attention(qh, kh, vh, causal=causal, scale=scale,
                              window=window, segment_ids=seg_full)
    else:
        out = attention_reference(qh, kh, vh, causal=causal,
                                  scale=scale, window=window,
                                  segment_ids=seg_full)
    return heads_to_seq(out.astype(q.dtype))
