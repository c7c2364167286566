"""Fused attention for TPU: Pallas flash-attention forward + reference path.

The reference framework has no first-party kernels (its compute is
whatever users type into cells), but a TPU-native framework's hot op is
attention, so this module provides:

* :func:`flash_attention` — blockwise online-softmax attention as a
  Pallas TPU kernel, tiled for the MXU (128-lane blocks), with a
  blockwise Pallas backward (separate dQ and dK/dV kernels driven by
  the saved per-row logsumexp): no (B,H,S,S) score tensor is ever
  materialized in either direction, so training memory is O(S)
  end-to-end — the flash-attention trade in both passes.
* :func:`attention_reference` — pure-jnp attention, numerically exact,
  used for CPU execution and as the test oracle (including grad
  checks against the Pallas backward).

Supports causal masking and grouped-query attention (n_kv_heads <
n_heads).  Layout: (batch, seq, heads, head_dim) — the native layout for
sequence-sharded training.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ._common import NEG_INF as _NEG_INF
from ._common import use_interpret as _shared_use_interpret


# ----------------------------------------------------------------------
# Reference implementation (oracle + backward + CPU path)

def check_window(window, causal: bool) -> None:
    """The one window-argument validator, shared by every attention
    entry point (reference, flash, ring, Ulysses)."""
    if window is None:
        return
    if not causal:
        raise ValueError("sliding window implies causal attention")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")


def attention_reference(q, k, v, *, causal: bool = True,
                        scale: float | None = None,
                        window: int | None = None,
                        segment_ids=None, kv_segment_ids=None):
    """Exact attention.  q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D) with
    H % Hkv == 0 (grouped-query).  ``window``: sliding-window size —
    query row i attends keys in [i - window + 1, i] (Mistral-style;
    requires ``causal=True``).

    ``segment_ids`` (B, Sq) int: packed-document masking — a query
    attends only keys with the SAME segment id (``kv_segment_ids``
    defaults to ``segment_ids``, which requires Sq == Sk).  With
    ``causal=True`` the diagonal is always in-segment, so every row
    has at least one key; rows masked everywhere (possible only
    non-causally) are undefined — keep packed masking causal.
    """
    B, Sq, H, D = q.shape
    _, Sk, Hkv, _ = k.shape
    if H % Hkv:
        raise ValueError(f"n_heads {H} not divisible by n_kv_heads {Hkv}")
    check_window(window, causal)
    if segment_ids is not None and kv_segment_ids is None:
        if Sq != Sk:
            raise ValueError("segment_ids with Sq != Sk needs explicit "
                             "kv_segment_ids")
        kv_segment_ids = segment_ids
    group = H // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(D)

    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)

    # (B, H, Sq, Sk); the keep mask stays broadcast-shaped — (Sq, Sk)
    # for the batch-invariant causal band, batch-extended only when
    # segments actually vary per row.
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    keep = None
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        keep = ki <= qi
        if window is not None:
            keep = keep & (ki > qi - window)
        keep = keep[None, None]                      # (1, 1, Sq, Sk)
    if segment_ids is not None:
        seg = (jnp.asarray(segment_ids)[:, :, None]
               == jnp.asarray(kv_segment_ids)[:, None, :])  # (B, Sq, Sk)
        keep = seg[:, None] if keep is None else keep & seg[:, None]
    if keep is not None:
        logits = jnp.where(keep, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ----------------------------------------------------------------------
# Shared in-kernel masking / causal block-range helpers
#
# One definition for the offset-causal math used by the forward and both
# backward kernels — forward and backward must never disagree on which
# (qi, ki) pairs attend.

def _causal_k_iters(q_off, k_off, q_idx, block_q, block_k, num_k_blocks):
    """How many leading k-blocks a causal q-block can see: the largest
    key this block's last row may attend is q_off - k_off + last row."""
    qmax = q_off - k_off + (q_idx + 1) * block_q - 1
    return jnp.clip(jax.lax.div(qmax, block_k) + 1, 0, num_k_blocks)


def _causal_first_q_block(k_idx, q_off, k_off, block_q, block_k,
                          num_q_blocks):
    """First q-block whose rows can attend this k-block: rows before
    the block's first (offset) key never see it."""
    first_qi = jnp.maximum(k_idx * block_k + k_off - q_off, 0)
    return jnp.minimum(jax.lax.div(first_qi, block_q), num_q_blocks)


def _window_first_k_block(q_off, k_off, q_idx, block_q, block_k,
                          window, num_k_blocks):
    """With a sliding window, the earliest key this q block's first
    row can see is its position - window + 1."""
    lo = q_off - k_off + q_idx * block_q - window + 1
    return jnp.clip(jax.lax.div(lo, block_k), 0, num_k_blocks)


def _window_last_q_block(k_idx, q_off, k_off, block_q, block_k,
                         window, num_q_blocks):
    """With a sliding window, the last q row that can see this
    k-block's final key sits window - 1 rows after it."""
    hi_qi = (k_idx * block_k + block_k - 1) + k_off - q_off + window - 1
    return jnp.clip(jax.lax.div(hi_qi, block_q) + 1, 0, num_q_blocks)


def _keep_mask(q_idx, kb, *, block_q, block_k, q_off, k_off,
               seq_k_valid, causal, seq_q_valid=None, window=None,
               qseg=None, kseg=None, keys_first: bool = False):
    """(block_q, block_k) bool: which score entries are real — inside
    the valid key range, (optionally) inside the valid query range,
    at-or-below the offset causal diagonal, (optionally) within the
    sliding window, and (optionally) in the same packed-document
    segment (``qseg`` (block_q, 1) vs ``kseg`` (1, block_k)).

    ``keys_first``: the same mask for a transposed score tile,
    (block_k, block_q), with ``qseg`` (1, block_q) and ``kseg``
    (block_k, 1) — the dK/dV kernel's orientation."""
    shape = (block_k, block_q) if keys_first else (block_q, block_k)
    q_dim = 1 if keys_first else 0
    qi = q_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                    q_dim)
    ki = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                 1 - q_dim)
    keep = ki < seq_k_valid
    if seq_q_valid is not None:
        keep = keep & (qi < seq_q_valid)
    if causal:
        keep = keep & (ki + k_off <= qi + q_off)
        if window is not None:
            keep = keep & (ki + k_off > qi + q_off - window)
    if qseg is not None:
        keep = keep & (qseg == kseg)
    return keep


# ----------------------------------------------------------------------
# Pallas forward kernel

def _flash_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                  block_k: int, seq_k: int, seq_k_valid: int,
                  causal: bool, scale: float, block_q: int,
                  window: int | None = None,
                  qseg_ref=None, kseg_ref=None):
    """One (batch*kv-head, q-block) program: stream K/V blocks with the
    online-softmax recurrence (running max m, normalizer l, accumulator).

    GQA is native: the program's q block carries all ``group = H/Hkv``
    query heads sharing this KV head — K/V are staged once per group
    (never expanded to H heads).  The group is processed by a *static
    Python unroll* with rank-2 dots, NOT a batched rank-3 dot_general:
    rank-2 is the only dot shape Mosaic reliably lowers (JAX's own TPU
    flash kernel holds to the same rule) — do not reintroduce batched
    dots here.

    ``seq_k`` is the (block-padded) buffer length; ``seq_k_valid`` the
    real key count — keys at or beyond it are masked out, so inputs of
    any length are handled exactly (the wrapper pads to block multiples).
    ``offs_ref`` holds (q_offset, k_offset): global positions of this
    chunk's first query/key row, so causal masking works when the
    inputs are one chunk of a larger sequence (ring attention hops);
    both are 0 for ordinary whole-sequence calls.  Rows whose keys are
    entirely masked self-heal through the online recurrence (their
    garbage acc/l is wiped by corr = exp(-inf) at the first real block)
    and surface lse ~ -inf, which the ring hop-combine weights to zero.
    Besides the output block, writes the per-row logsumexp (m + log l)
    — the only residual the blockwise backward needs.
    """
    from jax.experimental import pallas as pl

    G, D = q_ref.shape[1], q_ref.shape[3]
    q_idx = pl.program_id(1)
    q_off, k_off = offs_ref[0], offs_ref[1]
    # Per-group state as tuples of 2D arrays and a static Python loop
    # over the (small, static) group: every matmul stays rank-2 —
    # the only dot shape Mosaic is guaranteed to lower (JAX's own TPU
    # flash kernel holds to the same rule).
    qs = tuple(q_ref[0, g].astype(jnp.float32) * scale
               for g in range(G))                     # G x (Bq, D)
    accs = tuple(jnp.zeros((block_q, D), jnp.float32) for _ in range(G))
    ms = tuple(jnp.full((block_q, 1), _NEG_INF, jnp.float32)
               for _ in range(G))
    ls = tuple(jnp.zeros((block_q, 1), jnp.float32) for _ in range(G))

    num_k_blocks = pl.cdiv(seq_k, block_k)
    first_iter = 0
    if causal:
        num_iters = _causal_k_iters(q_off, k_off, q_idx, block_q,
                                    block_k, num_k_blocks)
        if window is not None:
            first_iter = _window_first_k_block(q_off, k_off, q_idx,
                                               block_q, block_k,
                                               window, num_k_blocks)
    else:
        num_iters = num_k_blocks

    mask_keys = seq_k_valid < seq_k
    has_seg = qseg_ref is not None
    qseg_blk = qseg_ref[0] if has_seg else None       # (Bq, 1)
    need_mask = causal or mask_keys or has_seg

    def body(kb, carry):
        accs, ms, ls = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k)].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k)].astype(jnp.float32)
        if need_mask:
            kseg_blk = (kseg_ref[0, :, pl.ds(kb * block_k, block_k)]
                        if has_seg else None)          # (1, Bk)
            keep = _keep_mask(q_idx, kb, block_q=block_q,
                              block_k=block_k, q_off=q_off, k_off=k_off,
                              seq_k_valid=seq_k_valid, causal=causal,
                              window=window, qseg=qseg_blk,
                              kseg=kseg_blk)
        new_acc, new_m, new_l = [], [], []
        for g in range(G):
            s = jax.lax.dot_general(
                qs[g], k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # (Bq, Bk)
            if need_mask:
                s = jnp.where(keep, s, _NEG_INF)
            m_new = jnp.maximum(ms[g],
                                jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)                    # (Bq, Bk)
            corr = jnp.exp(ms[g] - m_new)
            new_l.append(ls[g] * corr
                         + jnp.sum(p, axis=-1, keepdims=True))
            new_acc.append(accs[g] * corr + jax.lax.dot_general(
                p, v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            new_m.append(m_new)
        return tuple(new_acc), tuple(new_m), tuple(new_l)

    accs, ms, ls = jax.lax.fori_loop(first_iter, num_iters, body,
                                     (accs, ms, ls))
    for g in range(G):
        l_safe = jnp.maximum(ls[g], 1e-30)
        o_ref[0, g] = (accs[g] / l_safe).astype(o_ref.dtype)
        lse_ref[0, g] = (ms[g] + jnp.log(l_safe))[:, 0]


def _round_up(n: int, unit: int) -> int:
    return -(-n // unit) * unit


def _fold_heads(x, S_pad):
    """(B, S, Hkv, D) → (B*Hkv, S_pad, D), zero-padding the seq axis.
    The per-(batch, kv-head) layout gives every kernel program
    contiguous (seq, head_dim) MXU tiles."""
    B, S, H, D = x.shape
    if S_pad != S:
        x = jnp.pad(x, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
    return x.transpose(0, 2, 1, 3).reshape(B * H, S_pad, D)


def _fold_q_gqa(x, Hkv: int, S_pad: int):
    """(B, S, H, D) → (B*Hkv, group, S_pad, D): query heads grouped
    under the KV head they attend (head h ↔ kv head h // group), so a
    kernel program over (batch, kv-head) sees its whole group as a
    leading batch dim."""
    B, S, H, D = x.shape
    group = H // Hkv
    if S_pad != S:
        x = jnp.pad(x, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
    return (x.reshape(B, S_pad, Hkv, group, D)
            .transpose(0, 2, 3, 1, 4)
            .reshape(B * Hkv, group, S_pad, D))


def _unfold_q_gqa(x, B, Hkv, S):
    """(B*Hkv, group, S_pad, D) → (B, S, H, D), dropping seq padding."""
    _, group, S_pad, D = x.shape
    return (x.reshape(B, Hkv, group, S_pad, D)
            .transpose(0, 3, 1, 2, 4)
            .reshape(B, S_pad, Hkv * group, D)[:, :S])


def _unfold_heads(x, B, H, S):
    """(B*H, S_pad, D) → (B, S, H, D), dropping seq padding."""
    x = x.reshape(B, H, x.shape[1], -1).transpose(0, 2, 1, 3)
    return x[:, :S]


def _offsets_array(offsets):
    if offsets is None:
        return jnp.zeros((2,), jnp.int32)
    q_off, k_off = offsets
    return jnp.stack([jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(k_off, jnp.int32)])


def _seg_planes(segment_ids, kv_segment_ids, Sq_pad, Sk_pad):
    """Stage packed-document segment ids for the kernels.

    Returns (qseg (B, Sq_pad, 1), kseg (B, 1, Sk_pad)) int32 — layouts
    whose last-two block dims satisfy Mosaic's (8-divisible | equal)
    rule for per-q-block and full-row staging respectively.

    The pad sentinels (-1 queries / -2 keys) are belt-and-braces, not
    load-bearing: padded KEYS are always excluded by _keep_mask's
    ``ki < seq_k_valid`` term regardless of segment values, and padded
    QUERY rows are sliced off by the wrappers — so user segment ids
    may be any integers (equality defines membership), including
    negatives that happen to collide with a sentinel."""
    qs = jnp.asarray(segment_ids, jnp.int32)
    ks = jnp.asarray(kv_segment_ids, jnp.int32)
    qs = jnp.pad(qs, ((0, 0), (0, Sq_pad - qs.shape[1])),
                 constant_values=-1)
    ks = jnp.pad(ks, ((0, 0), (0, Sk_pad - ks.shape[1])),
                 constant_values=-2)
    return qs[:, :, None], ks[:, None, :]


def _flash_forward(q, k, v, *, causal: bool, scale: float,
                   block_q: int, block_k: int, interpret: bool,
                   offsets=None, window: int | None = None,
                   segment_ids=None, kv_segment_ids=None):
    """Returns (out (B,Sq,H,D), lse (B*Hkv, group, Sq_pad) float32).

    K/V are staged at their native Hkv heads — the GQA group rides the
    q block as a batch dim, so no repeated-KV buffer ever exists.
    ``offsets`` — optional (q_offset, k_offset) traced scalars giving
    the global position of row 0 of q and of k/v, for chunk-of-a-
    larger-sequence calls (ring attention).  ``segment_ids`` — packed-
    document masking (see :func:`attention_reference`).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Sq, H, D = q.shape
    _, Sk, Hkv, _ = k.shape
    group = H // Hkv

    # Pad both sequence axes to block multiples; padded keys are masked
    # inside the kernel (dynamic-slice clamping would otherwise re-read
    # earlier rows), padded query rows are sliced off below.
    Sq_pad = _round_up(Sq, block_q)
    Sk_pad = _round_up(Sk, block_k)

    qt = _fold_q_gqa(q, Hkv, Sq_pad)      # (B*Hkv, G, Sq_pad, D)
    kt = _fold_heads(k, Sk_pad)           # (B*Hkv, Sk_pad, D)
    vt = _fold_heads(v, Sk_pad)

    grid = (B * Hkv, Sq_pad // block_q)
    has_seg = segment_ids is not None
    in_specs = [
        pl.BlockSpec((1, group, block_q, D),
                     lambda bh, qb, offs: (bh, 0, qb, 0)),
        pl.BlockSpec((1, Sk_pad, D),
                     lambda bh, qb, offs: (bh, 0, 0)),
        pl.BlockSpec((1, Sk_pad, D),
                     lambda bh, qb, offs: (bh, 0, 0)),
    ]
    args = [qt, kt, vt]
    if has_seg:
        qseg, kseg = _seg_planes(segment_ids, kv_segment_ids,
                                 Sq_pad, Sk_pad)
        # Segments are per (batch, position): the index map recovers
        # the batch row from the folded batch*kv-head program id.
        in_specs += [
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, qb, offs: (bh // Hkv, qb, 0)),
            pl.BlockSpec((1, 1, Sk_pad),
                         lambda bh, qb, offs: (bh // Hkv, 0, 0)),
        ]
        args += [qseg, kseg]

    base = functools.partial(
        _flash_kernel, block_k=block_k, seq_k=Sk_pad, seq_k_valid=Sk,
        causal=causal, scale=scale, block_q=block_q, window=window)

    def kernel(offs_ref, *refs):
        if has_seg:
            (q_r, k_r, v_r, qs_r, ks_r, o_r, l_r) = refs
            base(offs_ref, q_r, k_r, v_r, o_r, l_r,
                 qseg_ref=qs_r, kseg_ref=ks_r)
        else:
            (q_r, k_r, v_r, o_r, l_r) = refs
            base(offs_ref, q_r, k_r, v_r, o_r, l_r)

    out, lse = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, group, Sq_pad, D), q.dtype),
            jax.ShapeDtypeStruct((B * Hkv, group, Sq_pad), jnp.float32),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, group, block_q, D),
                             lambda bh, qb, offs: (bh, 0, qb, 0)),
                pl.BlockSpec((1, group, block_q),
                             lambda bh, qb, offs: (bh, 0, qb)),
            ],
        ),
        interpret=interpret,
        compiler_params=_mosaic_params("fwd", block_q, block_k, Sq_pad,
                                       Sk_pad, D, group,
                                       q.dtype.itemsize),
        name="nbd_flash_fwd",
    )(_offsets_array(offsets), *args)
    return _unfold_q_gqa(out, B, Hkv, Sq), lse


# ----------------------------------------------------------------------
# Pallas backward kernels (FlashAttention-2 style)
#
# With the saved logsumexp L_i the softmax row is reconstructible
# blockwise as p = exp(s - L), so the backward is two streaming passes
# that never materialize (Sq, Sk):
#   delta_i = sum_d dO_id * O_id                    (tiny, plain XLA)
#   dV_j    = sum_i p_ij dO_i
#   dS_ij   = p_ij (dO_i . V_j - delta_i)
#   dQ_i    = scale * sum_j dS_ij K_j
#   dK_j    = scale * sum_i dS_ij Q_i
# The dQ kernel grids over q-blocks streaming K/V; the dK/dV kernel
# grids over k-blocks streaming Q/dO (starting at the diagonal block
# when causal — earlier q rows cannot attend to this k block).

def _flash_bwd_dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         dta_ref, dq_ref, *, block_k: int, seq_k: int,
                         seq_k_valid: int, causal: bool, scale: float,
                         block_q: int, window: int | None = None,
                         qseg_ref=None, kseg_ref=None):
    from jax.experimental import pallas as pl

    G, D = q_ref.shape[1], q_ref.shape[3]
    q_idx = pl.program_id(1)
    q_off, k_off = offs_ref[0], offs_ref[1]
    # Static per-group unroll, rank-2 dots only (see _flash_kernel).
    qs = tuple(q_ref[0, g].astype(jnp.float32) * scale
               for g in range(G))
    dos = tuple(do_ref[0, g].astype(jnp.float32) for g in range(G))
    lses = tuple(lse_ref[0, g][:, None] for g in range(G))
    deltas = tuple(dta_ref[0, g][:, None] for g in range(G))

    num_k_blocks = pl.cdiv(seq_k, block_k)
    first_iter = 0
    if causal:
        num_iters = _causal_k_iters(q_off, k_off, q_idx, block_q,
                                    block_k, num_k_blocks)
        if window is not None:
            first_iter = _window_first_k_block(q_off, k_off, q_idx,
                                               block_q, block_k,
                                               window, num_k_blocks)
    else:
        num_iters = num_k_blocks

    has_seg = qseg_ref is not None
    qseg_blk = qseg_ref[0] if has_seg else None       # (Bq, 1)

    def body(kb, dq_accs):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k)].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k)].astype(jnp.float32)
        kseg_blk = (kseg_ref[0, :, pl.ds(kb * block_k, block_k)]
                    if has_seg else None)              # (1, Bk)
        keep = _keep_mask(q_idx, kb, block_q=block_q, block_k=block_k,
                          q_off=q_off, k_off=k_off,
                          seq_k_valid=seq_k_valid, causal=causal,
                          window=window, qseg=qseg_blk, kseg=kseg_blk)
        out = []
        for g in range(G):
            s = jax.lax.dot_general(
                qs[g], k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # (Bq, Bk)
            s = jnp.where(keep, s, _NEG_INF)
            p = jnp.exp(s - lses[g])                  # (Bq, Bk)
            dp = jax.lax.dot_general(
                dos[g], v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # (Bq, Bk)
            ds = p * (dp - deltas[g])
            out.append(dq_accs[g] + jax.lax.dot_general(
                ds, k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        return tuple(out)

    dqs = jax.lax.fori_loop(
        first_iter, num_iters, body,
        tuple(jnp.zeros((block_q, D), jnp.float32) for _ in range(G)))
    for g in range(G):
        dq_ref[0, g] = (dqs[g] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(offs_ref, k_ref, v_ref, q_ref, do_ref, lse_ref,
                          dta_ref, dk_ref, dv_ref, dk_s, dv_s, *,
                          block_q: int, seq_q: int, seq_q_valid: int,
                          seq_k_valid: int, causal: bool, scale: float,
                          block_k: int, group: int,
                          window: int | None = None,
                          qseg_ref=None, kseg_ref=None):
    """dK/dV for one k-block and one query head.  The GQA group rides
    the *grid* (innermost dim, sequential on-core): each step stages
    one head's (Sq_pad, D) q/dO planes — the same per-program VMEM
    footprint as an MHA kernel — and accumulates this k-block's dk/dv
    across the group in fp32 scratch, writing out on the last head.

    The score tile is computed transposed, keys on the sublanes and
    queries on the lanes: lse and delta are then rows ``(1, Bq)`` as
    the forward wrote them (a staged plane is Sq_pad floats; as a
    column it is Sq_pad 128-lane tiles, 2 MiB at 4096 rows, and was
    most of what a grid step moved), and all four products are plain
    or transposed-rhs matmuls, none with a transposed lhs."""
    from jax.experimental import pallas as pl

    k_blk = k_ref[0].astype(jnp.float32)              # (Bk, D)
    v_blk = v_ref[0].astype(jnp.float32)
    k_idx = pl.program_id(1)
    g = pl.program_id(2)
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(g == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    num_q_blocks = pl.cdiv(seq_q, block_q)
    last_block = num_q_blocks
    if causal:
        first_block = _causal_first_q_block(k_idx, q_off, k_off,
                                            block_q, block_k,
                                            num_q_blocks)
        if window is not None:
            last_block = _window_last_q_block(k_idx, q_off, k_off,
                                              block_q, block_k,
                                              window, num_q_blocks)
    else:
        first_block = 0

    has_seg = qseg_ref is not None
    kseg_blk = kseg_ref[0] if has_seg else None       # (Bk, 1)
    nt = (((1,), (1,)), ((), ()))                     # a @ b.T
    nn = (((1,), (0,)), ((), ()))

    def body(qb, carry):
        dk_acc, dv_acc = carry
        rows = pl.ds(pl.multiple_of(qb * block_q, block_q), block_q)
        q_blk = q_ref[0, 0, rows].astype(jnp.float32) * scale  # (Bq, D)
        do_blk = do_ref[0, 0, rows].astype(jnp.float32)
        lse = lse_ref[0, 0, :, rows]                  # (1, Bq)
        delta = dta_ref[0, 0, :, rows]
        qseg_blk = qseg_ref[0, :, rows] if has_seg else None  # (1, Bq)
        s = jax.lax.dot_general(
            k_blk, q_blk, nt,
            preferred_element_type=jnp.float32)       # (Bk, Bq)
        # seq_q_valid: padded q rows carry a meaningless lse — mask
        # them here so they contribute nothing to dk/dv.
        keep = _keep_mask(qb, k_idx, block_q=block_q, block_k=block_k,
                          q_off=q_off, k_off=k_off,
                          seq_k_valid=seq_k_valid, causal=causal,
                          seq_q_valid=seq_q_valid, window=window,
                          qseg=qseg_blk, kseg=kseg_blk, keys_first=True)
        s = jnp.where(keep, s, _NEG_INF)
        p = jnp.exp(s - lse)                          # (Bk, Bq)
        dv_new = dv_acc + jax.lax.dot_general(
            p, do_blk, nn,
            preferred_element_type=jnp.float32)       # (Bk, D)
        dp = jax.lax.dot_general(
            v_blk, do_blk, nt,
            preferred_element_type=jnp.float32)       # (Bk, Bq)
        ds = p * (dp - delta)
        dk_new = dk_acc + jax.lax.dot_general(
            ds, q_blk, nn,
            preferred_element_type=jnp.float32)       # (Bk, D)
        return dk_new, dv_new

    zero = jnp.zeros((block_k, k_blk.shape[-1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(first_block, last_block, body,
                               (zero, zero))
    dk_s[...] += dk
    dv_s[...] += dv

    @pl.when(g == group - 1)
    def _finalize():
        # q_blk was pre-scaled, so dk already carries the
        # d(s)/d(k) = scale * q chain term.
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _flash_bwd_prep(q, o, g, block_q: int, Hkv: int):
    """Fold the hop-invariant backward inputs once: q/dO in the grouped
    kernel layout plus delta_i = rowsum(dO * O) (one elementwise pass
    XLA fuses; padded rows give 0).  Split out so ring attention can
    hoist this out of its per-hop loop instead of redoing it n times."""
    Sq_pad = _round_up(q.shape[1], block_q)
    qt = _fold_q_gqa(q, Hkv, Sq_pad)      # (B*Hkv, G, Sq_pad, D)
    got = _fold_q_gqa(g, Hkv, Sq_pad)
    ot = _fold_q_gqa(o, Hkv, Sq_pad)
    delta = jnp.sum(got.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1)              # (B*Hkv, G, Sq_pad)
    return qt, got, delta


def _flash_backward(q, k, v, o, lse, g, *, causal: bool, scale: float,
                    block_q: int, block_k: int, interpret: bool,
                    offsets=None, window: int | None = None,
                    segment_ids=None, kv_segment_ids=None,
                    dkv_blocks=None):
    qt, got, delta = _flash_bwd_prep(q, o, g, block_q, k.shape[2])
    return _flash_backward_folded(
        qt, got, delta, lse, k, v, B=q.shape[0], Sq=q.shape[1],
        q_dtype=q.dtype, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
        offsets=offsets, window=window, segment_ids=segment_ids,
        kv_segment_ids=kv_segment_ids, dkv_blocks=dkv_blocks)


def _flash_backward_folded(qt, got, delta, lse, k, v, *, B: int, Sq: int,
                           q_dtype, causal: bool, scale: float,
                           block_q: int, block_k: int, interpret: bool,
                           offsets=None, window: int | None = None,
                           segment_ids=None, kv_segment_ids=None,
                           dkv_blocks=None):
    """The two backward pallas_calls over pre-folded q/dO/delta (see
    :func:`_flash_bwd_prep`); k/v arrive raw (B, Sk, Hkv, D) and stay
    at Hkv heads throughout — the dK/dV kernel's contractions sum the
    GQA group inside the matmul.  ``block_q``/``block_k`` are the dQ
    kernel's tile; ``dkv_blocks`` the dK/dV kernel's own pair (the
    same where none is given).  Its rows must pad the queries to the
    length the folded operands already have."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, Sk, Hkv, D = k.shape
    group = qt.shape[1]
    Sq_pad = qt.shape[2]
    Sk_pad = _round_up(Sk, block_k)
    kv_bq, kv_bk = dkv_blocks or (block_q, block_k)
    if (Sq_pad % kv_bq or Sq_pad % block_q or lse.shape[-1] != Sq_pad
            or _round_up(Sk, kv_bk) != Sk_pad):
        raise ValueError(
            f"backward tiles {(block_q, block_k)} and {(kv_bq, kv_bk)} do "
            f"not pad {Sq} queries and {Sk} keys to {Sq_pad} (the saved "
            f"logsumexp has {lse.shape[-1]}) and {Sk_pad}")
    itemsize = qt.dtype.itemsize

    kt = _fold_heads(k, Sk_pad)           # (B*Hkv, Sk_pad, D)
    vt = _fold_heads(v, Sk_pad)
    offs = _offsets_array(offsets)
    has_seg = segment_ids is not None
    if has_seg:
        qseg, kseg = _seg_planes(segment_ids, kv_segment_ids,
                                 Sq_pad, Sk_pad)

    dq_base = functools.partial(
        _flash_bwd_dq_kernel, block_k=block_k, seq_k=Sk_pad,
        seq_k_valid=Sk, causal=causal, scale=scale, block_q=block_q,
        window=window)

    def dq_kernel(offs_ref, *refs):
        if has_seg:
            (q_r, k_r, v_r, do_r, l_r, d_r, qs_r, ks_r, dq_r) = refs
            dq_base(offs_ref, q_r, k_r, v_r, do_r, l_r, d_r, dq_r,
                    qseg_ref=qs_r, kseg_ref=ks_r)
        else:
            (q_r, k_r, v_r, do_r, l_r, d_r, dq_r) = refs
            dq_base(offs_ref, q_r, k_r, v_r, do_r, l_r, d_r, dq_r)

    dq_in_specs = [
        pl.BlockSpec((1, group, block_q, D),
                     lambda bh, qb, offs: (bh, 0, qb, 0)),  # q
        pl.BlockSpec((1, Sk_pad, D),
                     lambda bh, qb, offs: (bh, 0, 0)),      # k
        pl.BlockSpec((1, Sk_pad, D),
                     lambda bh, qb, offs: (bh, 0, 0)),      # v
        pl.BlockSpec((1, group, block_q, D),
                     lambda bh, qb, offs: (bh, 0, qb, 0)),  # dO
        pl.BlockSpec((1, group, block_q),
                     lambda bh, qb, offs: (bh, 0, qb)),     # lse
        pl.BlockSpec((1, group, block_q),
                     lambda bh, qb, offs: (bh, 0, qb)),     # dta
    ]
    dq_args = [qt, kt, vt, got, lse, delta]
    if has_seg:
        dq_in_specs += [
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, qb, offs: (bh // Hkv, qb, 0)),
            pl.BlockSpec((1, 1, Sk_pad),
                         lambda bh, qb, offs: (bh // Hkv, 0, 0)),
        ]
        dq_args += [qseg, kseg]
    dq = pl.pallas_call(
        dq_kernel,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, group, Sq_pad, D),
                                       q_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * Hkv, Sq_pad // block_q),
            in_specs=dq_in_specs,
            out_specs=pl.BlockSpec((1, group, block_q, D),
                                   lambda bh, qb, offs: (bh, 0, qb, 0)),
        ),
        interpret=interpret,
        compiler_params=_mosaic_params("dq", block_q, block_k, Sq_pad,
                                       Sk_pad, D, group, itemsize),
        name="nbd_flash_bwd_dq",
    )(offs, *dq_args)

    dkv_base = functools.partial(
        _flash_bwd_dkv_kernel, block_q=kv_bq, seq_q=Sq_pad,
        seq_q_valid=Sq, seq_k_valid=Sk, causal=causal, scale=scale,
        block_k=kv_bk, group=group, window=window)

    def dkv_kernel(offs_ref, *refs):
        if has_seg:
            (k_r, v_r, q_r, do_r, l_r, d_r, qs_r, ks_r,
             dk_r, dv_r, dk_s, dv_s) = refs
            dkv_base(offs_ref, k_r, v_r, q_r, do_r, l_r, d_r,
                     dk_r, dv_r, dk_s, dv_s,
                     qseg_ref=qs_r, kseg_ref=ks_r)
        else:
            (k_r, v_r, q_r, do_r, l_r, d_r,
             dk_r, dv_r, dk_s, dv_s) = refs
            dkv_base(offs_ref, k_r, v_r, q_r, do_r, l_r, d_r,
                     dk_r, dv_r, dk_s, dv_s)

    dkv_in_specs = [
        pl.BlockSpec((1, kv_bk, D),
                     lambda bh, kb, g, offs: (bh, kb, 0)),   # k
        pl.BlockSpec((1, kv_bk, D),
                     lambda bh, kb, g, offs: (bh, kb, 0)),   # v
        pl.BlockSpec((1, 1, Sq_pad, D),
                     lambda bh, kb, g, offs: (bh, g, 0, 0)),  # q
        pl.BlockSpec((1, 1, Sq_pad, D),
                     lambda bh, kb, g, offs: (bh, g, 0, 0)),  # dO
        # lse/delta get a unit axis before the rows: the last two
        # block dims (1, Sq_pad) then equal the array's — Mosaic's
        # block-shape rule, which (1, 1, Sq_pad) over (.., group,
        # Sq_pad) fails unless group is 1 or a multiple of 8 — and
        # the rows lie on the lanes.
        pl.BlockSpec((1, 1, 1, Sq_pad),
                     lambda bh, kb, g, offs: (bh, g, 0, 0)),  # lse
        pl.BlockSpec((1, 1, 1, Sq_pad),
                     lambda bh, kb, g, offs: (bh, g, 0, 0)),  # dta
    ]
    dkv_args = [kt, vt, qt, got, lse[:, :, None], delta[:, :, None]]
    if has_seg:
        # transposed tile: query segments a row, key segments a column
        dkv_in_specs += [
            pl.BlockSpec((1, 1, Sq_pad),
                         lambda bh, kb, g, offs: (bh // Hkv, 0, 0)),
            pl.BlockSpec((1, kv_bk, 1),
                         lambda bh, kb, g, offs: (bh // Hkv, kb, 0)),
        ]
        dkv_args += [qseg.transpose(0, 2, 1), kseg.transpose(0, 2, 1)]
    dk, dv = pl.pallas_call(
        dkv_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, Sk_pad, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, Sk_pad, D), v.dtype),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # Group innermost: sequential on-core, so the fp32 scratch
            # accumulators carry this k-block's dk/dv across the
            # group's heads; q/dO stage one (Sq_pad, D) plane at a time.
            grid=(B * Hkv, Sk_pad // kv_bk, group),
            in_specs=dkv_in_specs,
            out_specs=[
                pl.BlockSpec((1, kv_bk, D),
                             lambda bh, kb, g, offs: (bh, kb, 0)),
                pl.BlockSpec((1, kv_bk, D),
                             lambda bh, kb, g, offs: (bh, kb, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((kv_bk, D), jnp.float32),   # dk
                pltpu.VMEM((kv_bk, D), jnp.float32),   # dv
            ],
        ),
        interpret=interpret,
        compiler_params=_mosaic_params("dkv", kv_bq, kv_bk, Sq_pad,
                                       Sk_pad, D, group, itemsize),
        name="nbd_flash_bwd_dkv",
    )(offs, *dkv_args)

    dq = _unfold_q_gqa(dq, B, Hkv, Sq)
    dk = _unfold_heads(dk, B, Hkv, Sk)
    dv = _unfold_heads(dv, B, Hkv, Sk)
    return dq, dk, dv


_use_interpret = _shared_use_interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True,
                    scale: float | None = None,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    window: int | None = None,
                    segment_ids=None):
    """Flash attention: fused, O(S) memory forward.

    q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D).  ``window``: sliding-window
    size (Mistral-style, causal only) — both passes prune k/q blocks
    outside the band, so compute is O(S * window) instead of O(S^2/2).
    ``segment_ids`` (B, S) int: packed-document masking — queries
    attend only keys in the same segment (requires Sq == Sk; compose
    with causal for the standard packed-pretraining mask).  Both
    backward kernels apply the identical mask.
    ``block_q``/``block_k`` default to tiles each of the three kernels
    derives from the call's shapes (:func:`_block_sizes`); given, they
    hold for all three.  On non-TPU backends the Pallas kernel runs in
    interpreter mode (slow but exact), so tests exercise the same code
    path everywhere.
    """
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                      window, segment_ids)[0]


def _resolved_scale(scale, D):
    return scale if scale is not None else 1.0 / np.sqrt(D)


# ----------------------------------------------------------------------
# Tiles from shapes
#
# Each of the three kernels takes the tile its call's shapes allow: the
# largest multiple of 128 rows and of 128 keys, dividing the padded
# lengths, whose VMEM by that kernel's :func:`_vmem_need` stays under
# :data:`_VMEM_BUDGET`, up to where the chip's sweep at the training
# shape stopped gaining (:data:`_TILE_CAP`).  No table, no file, no
# environment: as ``ops/decode.py::_pages_per_tile`` and
# ``ops/grouped.py::_column_tile`` choose theirs.

_TILE_UNIT = 128            # rows and keys of the smallest tile Mosaic takes
_VMEM_BUDGET = 48 << 20     # what a derived tile may need, by _vmem_need
_VMEM_DEFAULT = 16 << 20    # Mosaic's scoped VMEM when a call states none
_VMEM_MOST = 100 << 20      # the most a call asks for, of a v5e's 128 MiB
# (block_q, block_k) past which none of the three kernels gained at
# S = 4096, D = 128, group 4: each was fastest at 512 x 512 and read
# 0.5-5% slower at 1024 either way (the chip's sweep: PERF.md section
# 6, PR 45).  A causal kernel also wastes half of every tile on the
# diagonal, which grows with the tile.
_TILE_CAP = (512, 512)


def _mosaic_block(block: int) -> int:
    """The nearest block size Mosaic accepts: a multiple of 128.

    These kernels put block_q on the 128 lanes of the logsumexp output
    and slice K/V, segment ids and lse/delta planes by block inside the
    kernel, so on hardware every block is a whole number of 128-row
    tiles and a shorter sequence is padded up to one (the MXU works in
    128-wide tiles anyway; the wrappers mask padded keys and drop
    padded query rows).  Interpret mode checks none of this: a 64-row
    block over 256 rows, or an 89-row sequence as its own block, passes
    every CPU test and is refused by the compiler on the chip."""
    return _round_up(block, 128)


def _vmem_need(kernel, bq, bk, sq_pad, sk_pad, D, group, itemsize) -> int:
    """VMEM bytes a kernel's grid step needs at a (bq, bk) tile, from
    the shapes alone: the blocks its specs stage (twice each: Pallas
    double-buffers), the float32 score tiles and accumulators of one
    group, and minor dimensions padded to whole 128-lane tiles as VMEM
    holds them.  What the tile choice is held to and what the call's
    limit is stated from."""
    Dl = _round_up(D, 128)
    if kernel == "dkv":
        staged = (4 * bk * Dl * itemsize          # k, v in; dk, dv out
                  + 2 * sq_pad * Dl * itemsize    # one head's q, dO planes
                  + 2 * 8 * sq_pad * 4)     # lse, delta rows, 8 sublanes
        live = (6 * bk * Dl * 4     # k, v in float32; scratch; accumulators
                + 2 * bq * Dl * 4   # one q and dO tile in float32
                + 4 * bq * bk * 4)  # s/p, dp/ds and the mask's iotas
        return 2 * staged + live
    grads = 2 if kernel == "dq" else 1            # dq stages dO beside q
    staged = ((grads + 1) * group * bq * Dl * itemsize   # q (dO), out
              + 2 * sk_pad * Dl * itemsize        # the K and V planes
              + 2 * 8 * bq * 4)                   # lse (and delta) rows
    live = ((grads + 1) * group * bq * Dl * 4     # float32 q (dO), acc
            + 2 * group * bq * 128 * 4      # m, l (lse, delta) columns
            + 2 * bk * Dl * 4                     # one K and V tile
            + (2 * group + 2) * bq * bk * 4)      # s, p a head; the mask
    return 2 * staged + live


def _mosaic_params(kernel, bq, bk, sq_pad, sk_pad, D, group, itemsize):
    """The call's compiler parameters: its VMEM limit stated from the
    tile's own arithmetic, twice the need (Mosaic's internal scratch
    and spills are not in it), at least the default and at most
    :data:`_VMEM_MOST`."""
    from jax.experimental.pallas import tpu as pltpu
    need = _vmem_need(kernel, bq, bk, sq_pad, sk_pad, D, group, itemsize)
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(max(2 * need, _VMEM_DEFAULT), _VMEM_MOST))


def _block_sizes(block_q, block_k, Sq, Sk, D=128, group=1, *,
                 interpret: bool, kernel: str = "fwd", itemsize: int = 2):
    """The (block_q, block_k) one kernel (``"fwd"``, ``"dq"`` or
    ``"dkv"``) runs at.  Explicit sizes win: compiled kernels get the
    nearest Mosaic accepts (:func:`_mosaic_block`), interpret mode
    takes them as given, clamped to the array, so CPU tests can drive
    the multi-block logic with small blocks.  ``None`` is derived from
    the shapes: the largest tile of whole :data:`_TILE_UNIT`s that
    divides the length as the unit pads it (so a sequence is never
    padded further than a 128-row tile pads it, the three kernels pad
    alike whatever their tiles, and the forward's saved logsumexp fits
    both backward kernels), needs at most :data:`_VMEM_BUDGET` by
    :func:`_vmem_need`, and is no larger than :data:`_TILE_CAP`."""
    if interpret:
        unit_q, unit_k = min(_TILE_UNIT, Sq), min(_TILE_UNIT, Sk)
        given = min
    else:
        unit_q = unit_k = _TILE_UNIT
        given = lambda block, _S: _mosaic_block(block)
    cap_q, cap_k = _TILE_CAP

    def choices(block, S, unit, cap):
        if block is not None:
            return [given(block, S)]
        padded = _round_up(S, unit)
        return [t for t in range(unit, max(cap, unit) + 1, unit)
                if padded % t == 0]

    qs = choices(block_q, Sq, unit_q, cap_q)
    ks = choices(block_k, Sk, unit_k, cap_k)
    fits = [(bq, bk) for bq in qs for bk in ks
            if _vmem_need(kernel, bq, bk, _round_up(Sq, bq),
                          _round_up(Sk, bk), D, group,
                          itemsize) <= _VMEM_BUDGET]
    # the largest score tile, the wider in keys of two alike
    return max(fits, key=lambda t: (t[0] * t[1], t[1]),
               default=(qs[0], ks[0]))


def _tile_of(q, k, block_q, block_k, interpret):
    """:func:`_block_sizes` bound to one call's shapes: ``kernel`` in,
    that kernel's (block_q, block_k) out."""
    return functools.partial(
        _block_sizes, block_q, block_k, q.shape[1], k.shape[1],
        q.shape[-1], q.shape[2] // k.shape[2], interpret=interpret,
        itemsize=q.dtype.itemsize)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, window=None,
               segment_ids=None):
    check_window(window, causal)
    if segment_ids is not None and q.shape[1] != k.shape[1]:
        raise ValueError("segment_ids requires Sq == Sk (packed "
                         "self-attention)")
    D = q.shape[-1]
    interpret = _use_interpret()
    bq, bk = _tile_of(q, k, block_q, block_k, interpret)(kernel="fwd")
    out, lse = _flash_forward(q, k, v, causal=causal,
                              scale=_resolved_scale(scale, D),
                              block_q=bq, block_k=bk,
                              interpret=interpret,
                              window=window, segment_ids=segment_ids,
                              kv_segment_ids=segment_ids)
    return out, (q, k, v, out, lse, segment_ids)


def _flash_bwd(causal, scale, block_q, block_k, window, residuals, g):
    """Blockwise Pallas backward: reconstructs each score block from
    the saved logsumexp, so no O(S^2) tensor exists in the backward
    either."""
    q, k, v, out, lse, segment_ids = residuals
    interpret = _use_interpret()
    tile = _tile_of(q, k, block_q, block_k, interpret)
    bq, bk = tile(kernel="dq")
    dq, dk, dv = _flash_backward(
        q, k, v, out, lse, g, causal=causal,
        scale=_resolved_scale(scale, q.shape[-1]),
        block_q=bq, block_k=bk, dkv_blocks=tile(kernel="dkv"),
        interpret=interpret, window=window,
        segment_ids=segment_ids, kv_segment_ids=segment_ids)
    if segment_ids is None:
        return dq, dk, dv, None
    # Integer primal: its cotangent is the symbolic-zero float0.
    dseg = np.zeros(segment_ids.shape, jax.dtypes.float0)
    return dq, dk, dv, dseg


flash_attention.defvjp(_flash_fwd, _flash_bwd)
