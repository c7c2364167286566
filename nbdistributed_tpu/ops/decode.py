"""Pallas flash-decode: single-token attention against the KV cache.

The decode step's hot op is bandwidth-bound: every generated token
reads the whole (B, Hkv, T, D) heads-major cache once.  This kernel
fuses the masked online-softmax into that single streaming pass — no
(B, H, T) score tensor ever hits HBM — with one program per (batch,
kv-head) whose query block is the GQA *group* (all H/Hkv query heads
sharing that KV head), so the per-block matmuls are
(group, D) @ (D, block_k): the same shape decode GQA is compute-bound
on.  The heads-major layout keeps (T, D) as each block's minor dims,
which Mosaic's block-shape rules require (a seq-major (B, T, Hkv, D)
cache puts the tiny Hkv in the sublane slot and fails to lower on
real TPU hardware).

Same recurrence as the prefill flash kernel (attention.py), lifted to
the cache layout + per-batch valid-length masking (cache slots
t <= pos[b] attend; later slots are unwritten).  On non-TPU backends
the kernel runs in interpreter mode, so tests exercise the identical
code path everywhere.

Over a **paged** pool (``models/paged_kv.py``) the same recurrence runs
where the pool lies, the block table inside the kernel
(:func:`paged_decode_attention`, :func:`paged_latent_decode_attention`;
``nbd_flash_decode_paged`` / ``nbd_mla_decode_paged`` in a profile).
The grid is the rows; the pool stays in HBM and a row's grid step
walks its own live pages, from the window's first to the page of
``pos``: a loop whose trip count is data, each trip copying a tile of
pages into one of two VMEM buffers while the tile before it is
computed, the next row's first tile started before this row ends.  So
the traffic, the work and the steps all go with the tokens held, not
with ``max_len`` or the table's width.  How many pages a tile takes
comes from the operands' shapes (:func:`_pages_per_tile`).  A prefill
chunk (many queries a row, causal among themselves) runs it in
``jax.numpy`` over the row's live pages
(:func:`paged_prefill_attention`), under the same bound.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ._common import NEG_INF as _NEG_INF
from ._common import use_interpret as _use_interpret


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_s, m_s, l_s, *, block_k: int, seq_k: int,
                   scale: float, num_kb: int,
                   window: int | None = None,
                   ks_ref=None, vs_ref=None, lse_ref=None):
    """One grid step = one (batch, kv-head, k-block).  The k axis rides
    the grid (sequential on-core), so only a (block_k, D) window of the
    cache is ever staged in VMEM — context length is bounded by HBM,
    not VMEM — with the online-softmax state carried in scratch.

    With ``ks_ref``/``vs_ref`` (per-token scale blocks, (Bk, 1)), the
    cache arrives int8 and the scales commute through both matmuls:
    ``q . (q8_k * s_k)`` rescales the score columns, and
    ``p @ (q8_v * s_v)`` folds ``s_v`` into ``p`` — the cache streams
    from HBM at half width, the math is exact given the quantization.
    """
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    kb = pl.program_id(2)
    valid = pos_ref[b] + 1                              # keys [0, valid)
    # The sp-sharded caller passes LOCAL positions that can exceed
    # this shard's cache length (a later global position means "every
    # local key attends") — clamp the upper bound to seq_k so the
    # padded tail of a partial final block never enters the softmax.
    # The window's lower bound stays on the UNCLAMPED position: it is
    # offset-invariant in local coordinates only as valid - window.
    valid_k = jnp.minimum(valid, seq_k)
    # Sliding window: only keys in [valid - window, valid) attend;
    # blocks entirely below the window are skipped like blocks past
    # the valid length.
    lo = valid - window if window is not None else 0

    @pl.when(kb == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    # lo < valid_k: an sp-sharded caller's overshooting position can
    # put the whole window past this shard's slice (lo >= valid_k) —
    # without this clause such a block runs with an empty mask and its
    # all -NEG_INF scores make p == 1 everywhere (m_new == NEG_INF),
    # averaging garbage rows into acc; today the cross-shard combine
    # happens to flush it (exp(lse−m) underflows to 0 because NEG_INF
    # is finite), but correctness must not hang on an underflow.
    @pl.when((kb * block_k < valid_k)
             & ((kb + 1) * block_k > lo)
             & (lo < valid_k))
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale     # (group, D)
        k_blk = k_ref[0, 0].astype(jnp.float32)         # (Bk, D)
        v_blk = v_ref[0, 0].astype(jnp.float32)
        # A final block that extends past seq_k is padded by Pallas
        # with undefined data (NaN in interpret mode, garbage memory on
        # hardware).  The score mask below already discards those
        # columns of s, but the p @ v matmul would still compute
        # 0 * NaN = NaN through the padded v rows — so zero the
        # out-of-bounds rows explicitly before they enter any matmul.
        kpad = (kb * block_k
                + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0))
        in_bounds = kpad < seq_k                        # (Bk, 1)
        v_blk = jnp.where(in_bounds, v_blk, 0.0)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (group, Bk)
        if ks_ref is not None:
            # Per-token K scales rescale the score columns.
            s = s * ks_ref[0, 0, :, 0][None, :]
        ki = (kb * block_k
              + jax.lax.broadcasted_iota(jnp.int32,
                                         (q.shape[0], block_k), 1))
        # < valid_k also masks the padded tail of a non-multiple T
        # (valid_k <= seq_k by construction, even for the sp-sharded
        # caller's overshooting positions) — including any NaN columns
        # of s from padded k rows (jnp.where does not propagate the
        # unselected branch).
        s = jnp.where((ki < valid_k) & (ki >= lo), s, _NEG_INF)
        m_prev, l_prev = m_s[...], l_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        # The softmax normalizer sums the UNSCALED probabilities; only
        # the V contraction takes the per-token V scale.
        l_s[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        if vs_ref is not None:
            # Zero out-of-bounds scale rows for the same reason as
            # v_blk above: p is 0 there, but 0 * NaN/garbage = NaN.
            vs = jnp.where(in_bounds[:, 0],
                           vs_ref[0, 0, :, 0], 0.0)[None, :]
            pv = p * vs
        else:
            pv = p
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            pv, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(kb == num_kb - 1)
    def _finalize():
        o_ref[0, 0] = (acc_s[...]
                       / jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)
        if lse_ref is not None:
            # log-sum-exp of the masked scores; an all-masked shard
            # (this query attends to nothing here — the sp-sharded
            # cache case) reports NEG_INF so the cross-shard combine
            # weighs it zero.
            lse_ref[0, 0] = jnp.where(
                l_s[...] > 0.0, m_s[...] + jnp.log(
                    jnp.maximum(l_s[...], 1e-30)), _NEG_INF)


@functools.partial(jax.jit,
                   static_argnames=("block_k", "scale", "interpret",
                                    "window", "return_lse"))
def _decode_call(q, kc, vc, pos, *, block_k: int, scale: float,
                 interpret: bool, window: int | None = None,
                 k_s=None, v_s=None, return_lse: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hkv, group, D = q.shape
    T = kc.shape[2]
    num_kb = -(-T // block_k)
    quantized = k_s is not None

    def _kernel(pos_ref, *refs):
        lse_ref = None
        if return_lse:
            *refs, a, m, l = refs
            *refs, o_ref, lse_ref = refs
        else:
            *refs, o_ref, a, m, l = refs
        if quantized:
            q_ref, k_ref, v_ref, ks_ref, vs_ref = refs
        else:
            (q_ref, k_ref, v_ref), ks_ref, vs_ref = refs, None, None
        _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, a, m, l,
                       block_k=block_k, seq_k=T, scale=scale,
                       num_kb=num_kb, window=window, ks_ref=ks_ref,
                       vs_ref=vs_ref, lse_ref=lse_ref)

    in_specs = [
        pl.BlockSpec((1, 1, group, D),
                     lambda b, h, kb, pos: (b, h, 0, 0)),  # q
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, h, kb, pos: (b, h, kb, 0)),  # k
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, h, kb, pos: (b, h, kb, 0)),  # v
    ]
    args = [pos, q, kc, vc]
    if quantized:
        # Scales live as (B, Hkv, T, 1), same heads-major layout as
        # K/V: every block's last two dims are (token-block, minor) and
        # satisfy Mosaic's (8-divisible | equal) rule.
        in_specs += [
            pl.BlockSpec((1, 1, block_k, 1),
                         lambda b, h, kb, pos: (b, h, kb, 0)),  # k_s
            pl.BlockSpec((1, 1, block_k, 1),
                         lambda b, h, kb, pos: (b, h, kb, 0)),  # v_s
        ]
        args += [k_s, v_s]

    out_specs = pl.BlockSpec((1, 1, group, D),
                             lambda b, h, kb, pos: (b, h, 0, 0))
    out_shape = jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype)
    if return_lse:
        # The lse plane keeps a trailing unit dim so its block's last
        # two dims equal the array's — Mosaic's block-shape rule (the
        # same pattern as the int8 scale planes).
        out_specs = [out_specs,
                     pl.BlockSpec((1, 1, group, 1),
                                  lambda b, h, kb, pos: (b, h, 0, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B, Hkv, group, 1),
                                          jnp.float32)]

    # pos rides as a prefetched scalar array (SMEM on real TPU) —
    # the kernel indexes it by the batch program id.  The k axis is the
    # innermost grid dim: sequential on-core, scratch carries state.
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, num_kb),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((group, D), jnp.float32),   # acc
                pltpu.VMEM((group, 1), jnp.float32),   # running max
                pltpu.VMEM((group, 1), jnp.float32),   # normalizer
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
        name="nbd_flash_decode",
    )(*args)


# VMEM a paged call gives its page tiles, both buffers of every pool
# operand together: what sets the pages a trip of the kernel's loop
# takes (:func:`_pages_per_tile`).
_TILE_VMEM_BYTES = 4 << 20


def _pages_per_tile(pools, width: int) -> int:
    """Pages a trip of :func:`_paged_decode_kernel`'s loop fetches and
    computes: as many as ``_TILE_VMEM_BYTES`` holds twice over (a page
    of every operand, its minor axis padded to whole 128-lane tiles as
    VMEM keeps it), and no more than the table has."""
    page = sum(int(np.prod(c.shape[2:-1])) * -(-c.shape[-1] // 128) * 128
               * c.dtype.itemsize for c in pools)
    return max(1, min(width, _TILE_VMEM_BYTES // (2 * page)))


def _paged_decode_kernel(layer_ref, table_ref, pos_ref, q_ref, *refs,
                         pages: int, scale: float, window: int | None,
                         latent: bool, quantized: bool):
    """One grid step = one row, which walks its own live pages: a loop
    from the row's first live page (the window's) to the page of
    ``pos``, ``pages`` of them a trip.  The pools stay in HBM; a trip's
    pages are copied (``make_async_copy``) side by side into one of two
    VMEM tiles ``(Hkv, pages * bt, W)`` an operand, the next trip's
    started before this one's are waited for, and the next row's first
    tile before this row's last is computed, so only a row after an
    idle one (or the first) waits for a copy it has just started.  So a
    call costs the pages its rows hold, whatever the table's width.

    The scores of a tile are one dot batched over ``Hkv``; same
    recurrence, masks and float32 state as :func:`_decode_kernel`.  A
    tile's slots past the row's last page are not fetched, and a last
    page's tail no token has written: what lies there (an earlier
    row's page, whatever the pool held) is kept out of both products by
    position, values zeroed, since a probability of zero does not clean
    a NaN.  A row whose ``pos`` is negative copies nothing and writes
    zeros.

    ``latent``: the values are a prefix of the keys (a latent pool's
    page holds ``[c_kv | k_rope]``: the scores take all of it, the
    weighted sum its first ``o_ref.shape[-1]`` columns), so a page is
    fetched once for both products."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = 1 if latent else 4 if quantized else 2
    pools, o_ref, bufs = refs[:n], refs[n], refs[n + 1:2 * n + 1]
    sem, slot_s, acc_s, m_s, l_s = refs[2 * n + 1:]
    bt = pools[0].shape[3]
    keys = pages * bt
    width = table_ref.shape[1]
    b, rows = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]

    def span(r):
        """(takes part, first live page, last) of row ``r``."""
        p = pos_ref[r]
        last = jnp.minimum(jnp.maximum(p, 0) // bt, width - 1)
        first = (jnp.maximum(p + 1 - window, 0) // bt
                 if window is not None else 0)
        return p >= 0, first, last

    def tile(r, j0, last, slot, wait=False):
        """Start (or wait for) the copies of row ``r``'s pages
        ``[j0, j0 + pages)`` that are live into buffer ``slot``."""
        for i in range(pages):
            @pl.when(j0 + i <= last)
            def _copy():
                # a wait needs the copy's shape alone
                phys = 0 if wait else table_ref[r, j0 + i]
                for c, (pool, buf) in enumerate(zip(pools, bufs)):
                    cp = pltpu.make_async_copy(
                        pool.at[layer, phys],
                        buf.at[slot, :, pl.ds(i * bt, bt), :],
                        sem.at[slot, c])
                    cp.wait() if wait else cp.start()

    live, first, last = span(b)
    nb = jnp.minimum(b + 1, rows - 1)
    next_live, next_first, next_last = span(nb)
    next_live &= b + 1 < rows
    prev_live = (b > 0) & (pos_ref[jnp.maximum(b - 1, 0)] >= 0)

    @pl.when(b == 0)
    def _first_row():
        slot_s[0] = 0

    @pl.when(live & ~prev_live)
    def _cold():                        # nobody started this row's first
        tile(b, first, last, slot_s[0])

    @pl.when(~live)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _row():
        valid = pos_ref[b] + 1                          # keys [0, valid)
        lo = jnp.maximum(valid - window, 0) if window is not None else 0
        trips = (last - first) // pages + 1
        slot0 = slot_s[0]
        q = q_ref[0].astype(jnp.float32) * scale        # (Hkv, group, D)
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

        def trip(t, _):
            slot = (slot0 + t) % 2
            j0 = first + t * pages
            more = t + 1 < trips

            @pl.when(more | next_live)
            def _prefetch():            # the next tile, or the next row's
                tile(jnp.where(more, b, nb),
                     jnp.where(more, j0 + pages, next_first),
                     jnp.where(more, last, next_last), 1 - slot)

            tile(b, j0, last, slot, wait=True)
            k_tl = bufs[0][slot].astype(jnp.float32)    # (Hkv, keys, D)
            v_tl = (k_tl[..., :o_ref.shape[-1]] if latent
                    else bufs[1][slot].astype(jnp.float32))
            s = jax.lax.dot_general(
                q, k_tl, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)     # (Hkv, group, keys)
            if quantized:
                s = s * bufs[2][slot][:, :, 0][:, None, :]
            ki = j0 * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            keep = (ki < valid) & (ki >= lo)
            s = jnp.where(keep, s, _NEG_INF)
            kv = j0 * bt + jax.lax.broadcasted_iota(
                jnp.int32, (1, keys, 1), 1)         # keys down the rows
            v_tl = jnp.where((kv < valid) & (kv >= lo), v_tl, 0.0)
            m_prev, l_prev = m_s[...], l_s[...]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_s[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                vs = bufs[3][slot][:, :, 0][:, None, :]
                p = p * jnp.where(keep, vs, 0.0)
            acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
                p, v_tl, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)     # (Hkv, group, Dv)
            m_s[...] = m_new

        jax.lax.fori_loop(0, trips, trip, None)
        slot_s[0] = (slot0 + trips) % 2
        o_ref[0] = (acc_s[...]
                    / jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "window",
                                    "v_width"))
def _paged_decode_call(q, k_pool, v_pool, layer, table, pos, *,
                       scale: float, interpret: bool,
                       window: int | None = None, k_s=None, v_s=None,
                       v_width: int | None = None):
    """``v_pool`` None makes the pool a latent one, whose values are
    the first ``v_width`` columns of its keys (one operand, one copy of
    a page for both products; the call carries its own name in a
    profile).

    The pool stays where it lies (``memory_space=ANY``): ``layer``,
    ``table`` and ``pos`` are scalar-prefetch operands, the grid is the
    rows, and the kernel copies physical block ``table[b, j]`` of layer
    ``layer`` itself, for the logical pages ``j`` of the row's live
    range alone (:func:`_paged_decode_kernel`).  How many a trip comes
    from the operands' shapes (:func:`_pages_per_tile`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, Hkv, group, D = q.shape
    bt = k_pool.shape[3]
    latent, quantized = v_pool is None, k_s is not None
    Dv = v_width if latent else D
    pools = [c for c in (k_pool, v_pool, k_s, v_s) if c is not None]
    pages = _pages_per_tile(pools, table.shape[1])

    def row(b, layer, table, pos):
        return (b, 0, 0, 0)

    kernel = functools.partial(
        _paged_decode_kernel, pages=pages, scale=scale, window=window,
        latent=latent, quantized=quantized)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, Hkv, group, D), row)]      # q
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec((1, Hkv, group, Dv), row),
            scratch_shapes=[
                pltpu.VMEM((2, Hkv, pages * bt, c.shape[-1]), c.dtype)
                for c in pools] + [
                pltpu.SemaphoreType.DMA((2, len(pools))),
                pltpu.SMEM((1,), jnp.int32),    # the next tile's buffer
                pltpu.VMEM((Hkv, group, Dv), jnp.float32),  # acc
                pltpu.VMEM((Hkv, group, 1), jnp.float32),   # running max
                pltpu.VMEM((Hkv, group, 1), jnp.float32),   # normalizer
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, Hkv, group, Dv), q.dtype),
        interpret=interpret,
        name="nbd_mla_decode_paged" if latent
        else "nbd_flash_decode_paged",
    )(layer.reshape(1), table, pos, q, *pools)


# Keys a grid step when the caller passes no block_k.  Decode streams K
# and V from HBM, and 128 keys a block is what every run of this round
# used.
_DEFAULT_BLOCK_K = 128


def flash_decode_attention(q, kc, vc, pos, *, scale: float | None = None,
                           block_k: int | None = None,
                           window: int | None = None,
                           k_s=None, v_s=None,
                           return_lse: bool = False):
    """Fused decode attention: one new token per sequence against the
    cache.

    q: (B, H, D) — this step's queries (S = 1 squeezed);
    kc/vc: (B, Hkv, T, D) heads-major cache buffers (slots beyond
    ``pos`` unwritten);
    pos: (B,) int32 — the global position of the new token per
    sequence (cache slots ``t <= pos[b]`` attend); ``window`` further
    restricts to the last ``window`` positions (sliding-window
    models) with out-of-band blocks skipped, not just masked.
    Returns (B, H, D).  Any cache length works at full block width —
    a non-multiple tail is handled by an overlapping, masked final
    block read inside the kernel.

    ``k_s``/``v_s`` (both or neither, (B, Hkv, T, 1) fp32): per-token
    per-kv-head scales for an **int8 cache** — kc/vc arrive int8 and
    stream from HBM at half width; the scales commute through the two
    matmuls inside the kernel (see models/quant.py for the cache
    quantizer).

    ``return_lse=True`` additionally returns the per-query-head
    log-sum-exp of the masked scores, (B, H) fp32 (``NEG_INF`` for a
    query that attends to nothing) — the combiner a sequence-sharded
    cache needs: shards compute locally and merge as
    ``o = Σ exp(lse_i − m)·o_i / Σ exp(lse_i − m)`` (see
    ``models/generate._flash_decode_on_mesh``).
    """
    B, H, D = q.shape
    Hkv, T = kc.shape[1], kc.shape[2]
    if H % Hkv:
        raise ValueError(f"n_heads {H} not divisible by n_kv_heads {Hkv}")
    if (k_s is None) != (v_s is None):
        raise ValueError("pass both k_s and v_s, or neither")
    group = H // Hkv
    scale = scale if scale is not None else float(1.0 / np.sqrt(D))
    block_k = min(_DEFAULT_BLOCK_K if block_k is None else block_k, T)
    qg = q.reshape(B, Hkv, group, D)
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    out = _decode_call(qg, kc, vc, jnp.asarray(pos, jnp.int32),
                       block_k=block_k, scale=float(scale),
                       interpret=_use_interpret(), window=window,
                       k_s=k_s, v_s=v_s, return_lse=return_lse)
    if return_lse:
        o, lse = out
        return o.reshape(B, H, D), lse.reshape(B, H)
    return out.reshape(B, H, D)


def paged_decode_attention(q, k_pool, v_pool, layer, table, pos, *,
                           active=None, scale: float | None = None,
                           window: int | None = None,
                           k_s=None, v_s=None):
    """Decode attention that reads the paged KV pool in place: the
    same mathematics as :func:`flash_decode_attention`, over keys that
    lie in table-selected physical blocks instead of a dense row.

    q: (S, H, D) — this step's queries, one per slot — or (S, L, H, D):
    a block of ``L`` queries a slot that share the one bound ``pos``
    (a block-causal model's step: ``pos`` is the block's last position,
    and every query of the block attends keys ``<= pos``, its own block
    in both directions).  They fold into the kernel's query-group axis,
    ``L`` times the queries a KV head, so a slot's pages are read once
    a slot and not once a query;
    k_pool/v_pool: (L, NB+1, Hkv, bt, D) — the whole physical pool
    (:func:`~..models.paged_kv.make_paged_pool`), of which only layer
    ``layer`` (a traced int32 scalar) is read;
    table: (S, MB) int32 physical block ids per slot;
    pos: (S,) int32 — the position of the new token per slot: keys
    ``[max(0, pos + 1 - window), pos]`` attend, so its own K/V must
    already be in the pool;
    active: (S,) bool — slots that take part; the others fetch and
    compute nothing and come back as zeros;
    ``k_s``/``v_s``: (L, NB+1, Hkv, bt, 1) fp32 scales of an int8 pool
    (they ride the kernel's copies in interpret mode; on the chip Mosaic
    refuses the copy of a page of them, one lane wide, so a served int8
    pool gathers: :func:`~..models.paged_kv.reads_in_place`).
    Returns (S, H, D), or (S, L, H, D).  Only the pages of a slot's
    live range are copied out of the pool: the step's traffic goes with
    the tokens held, not with ``max_len``."""
    if q.ndim == 4:
        S, L, H, D = q.shape
        Hkv = k_pool.shape[2]
        if H % Hkv:
            raise ValueError(f"n_heads {H} not divisible by n_kv_heads "
                             f"{Hkv}")
        g = H // Hkv
        # (S, L, Hkv, g, D) -> (S, Hkv*L*g, D): a KV head's L*g queries
        folded = (q.reshape(S, L, Hkv, g, D).transpose(0, 2, 1, 3, 4)
                  .reshape(S, Hkv * L * g, D))
        out = paged_decode_attention(
            folded, k_pool, v_pool, layer, table, pos, active=active,
            scale=scale, window=window, k_s=k_s, v_s=v_s)
        return (out.reshape(S, Hkv, L, g, D).transpose(0, 2, 1, 3, 4)
                .reshape(S, L, H, D))
    S, H, D = q.shape
    Hkv = k_pool.shape[2]
    if H % Hkv:
        raise ValueError(f"n_heads {H} not divisible by n_kv_heads {Hkv}")
    if (k_s is None) != (v_s is None):
        raise ValueError("pass both k_s and v_s, or neither")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    scale = scale if scale is not None else float(1.0 / np.sqrt(D))
    pos = jnp.asarray(pos, jnp.int32)
    if active is not None:
        pos = jnp.where(active, pos, -1)
    out = _paged_decode_call(
        q.reshape(S, Hkv, H // Hkv, D), k_pool, v_pool,
        jnp.asarray(layer, jnp.int32), jnp.asarray(table, jnp.int32),
        pos, scale=float(scale), interpret=_use_interpret(),
        window=window, k_s=k_s, v_s=v_s)
    return out.reshape(S, H, D)


def paged_latent_decode_attention(q, pool, layer, table, pos, *,
                                  v_width: int, scale: float,
                                  active=None):
    """Absorbed latent (MLA) decode attention over a paged latent pool,
    read in place like :func:`paged_decode_attention`: one KV head whose
    keys are a whole page row ``[c_kv | k_rope]`` and whose values are
    its first ``v_width`` columns.

    q: (S, H, W) — per head ``[q_nope W_uk^T | q_rope]``, W the pool's
    width; pool: (L, NB+1, 1, bt, W); ``layer``, ``table``, ``pos``,
    ``active`` as there.  Returns (S, H, v_width): the probability-
    weighted sum of ``c_kv``, still to be taken through ``W_uv``."""
    S, H, W = q.shape
    if pool.shape[2] != 1 or pool.shape[-1] != W:
        raise ValueError(f"a latent pool holds one head of the "
                         f"queries' width {W}, got {pool.shape}")
    pos = jnp.asarray(pos, jnp.int32)
    if active is not None:
        pos = jnp.where(active, pos, -1)
    out = _paged_decode_call(
        q.reshape(S, 1, H, W), pool, None,
        jnp.asarray(layer, jnp.int32), jnp.asarray(table, jnp.int32),
        pos, scale=float(scale), interpret=_use_interpret(),
        v_width=int(v_width))
    return out.reshape(S, H, v_width)


# Keys a trip of :func:`paged_prefill_attention`'s loop takes, in whole
# pages: enough to fill the lanes of the scores and to spread the
# rescaling of the accumulator over many keys.
_PREFILL_TILE_KEYS = 512


def paged_prefill_attention(q, k_pool, v_pool, layer, table, start,
                            length=None, *, scale: float,
                            window: int | None = None,
                            v_width: int | None = None,
                            k_s=None, v_s=None, block: int = 1):
    """Attention of a chunk of new tokens over the paged pool:
    :func:`paged_decode_attention` with ``S`` queries a row, causal
    among themselves.  The chunk's own keys must already be in the
    pool.  ``block`` > 1 makes the mask block-causal: the query at
    position ``p`` attends every key up to the last position of its
    block, ``(p // block + 1) * block - 1`` (1: the causal mask).

    q: (B, S, H, D) — token ``i`` of row ``b`` sits at position
    ``start[b] + i``; ``length`` (B,) the real tokens of each row's
    chunk (the rest is its padded tail; default all): a key attends if
    a real token wrote it, at or before the query (and inside
    ``window``);
    k_pool/v_pool, ``layer``, ``table``, ``k_s``/``v_s`` as there;
    ``v_pool`` None makes the pool a latent one
    (:func:`paged_latent_decode_attention`), the values its keys' first
    ``v_width`` columns.
    Returns (B, S, H, Dv) in the queries' dtype.

    No Pallas: the decode kernels' online softmax (float32 scores,
    state and accumulator, the finite mask value) in ``jax.numpy``,
    over key tiles of whole pages taken out of the pool through the
    table, in a loop that runs from the tile of the chunk's first
    window to the tile of its last real token.  So a chunk costs the
    keys its row holds, whatever ``S`` and the table's width, and
    ``start`` and ``length`` are data: one compiled program a chunk
    shape.  A Pallas form of it tied this one on the chip (PERF.md,
    PR 28) and was not kept; this one also serves a mesh and an int8
    pool.  No real query needs a key at or past ``start + length``, and
    what lies there (the chunk's padded tail, a former owner's tokens)
    is kept out of both products, values zeroed: a probability of zero
    does not clean a NaN."""
    B, S, H, D = q.shape
    Hkv = k_pool.shape[2]
    if H % Hkv:
        raise ValueError(f"n_heads {H} not divisible by n_kv_heads {Hkv}")
    if (k_s is None) != (v_s is None):
        raise ValueError("pass both k_s and v_s, or neither")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if (v_pool is None) == (v_width is None):
        raise ValueError("a latent pool (v_pool None) takes v_width, "
                         "and no other does")
    group, R = H // Hkv, S * (H // Hkv)
    Dv = v_pool.shape[-1] if v_width is None else int(v_width)
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
    length = jnp.broadcast_to(
        jnp.asarray(S if length is None else length, jnp.int32), (B,))
    layer = jnp.asarray(layer, jnp.int32)
    bt, trash = k_pool.shape[3], k_pool.shape[1] - 1
    pages = max(1, min(table.shape[1], _PREFILL_TILE_KEYS // bt))
    keys = pages * bt
    table = jnp.asarray(table, jnp.int32)
    table = jnp.pad(table, ((0, 0), (0, -table.shape[1] % pages)),
                    constant_values=trash)
    f32 = jnp.float32
    # A KV head's queries folded into the matmul's rows, token-major.
    qf = (q.reshape(B, S, Hkv, group, D).transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, R, D))
    tok = jnp.arange(R) // group                        # row -> token
    t_idx = jnp.arange(keys)

    def row(b):
        end = start[b] + length[b]
        qpos = (start[b] + tok)[:, None]                # (R, 1)
        # the last key a query attends: itself, or its block's last
        bound = qpos if block == 1 else (qpos // block + 1) * block - 1
        last = jnp.maximum(jnp.minimum(start[b] + S, end) - 1, 0)
        first = (jnp.maximum(start[b] + 1 - window, 0)
                 if window is not None else 0)
        qs = qf[b].astype(f32) * scale

        def tile(c, ids):       # (L, NB+1, Hkv, bt, W) -> (Hkv, keys, W)
            g = c[layer, ids].transpose(1, 0, 2, 3)
            return g.reshape(Hkv, keys, -1).astype(f32)

        def body(t, carry):
            acc, m, l = carry
            ids = jax.lax.dynamic_slice(table[b], (t * pages,), (pages,))
            k = tile(k_pool, ids)
            v = k[..., :Dv] if v_pool is None else tile(v_pool, ids)
            if k_s is not None:
                k, v = k * tile(k_s, ids), v * tile(v_s, ids)
            ki = t * keys + t_idx                       # (keys,)
            keep = (ki[None] <= bound) & (ki[None] < end)
            if window is not None:
                keep &= ki[None] > qpos - window
            v = jnp.where((ki < end)[None, :, None], v, 0.0)
            s = jnp.einsum("hrd,htd->hrt", qs, k,
                           preferred_element_type=f32)
            s = jnp.where(keep[None], s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + jnp.einsum("hrt,htd->hrd", p, v,
                                          preferred_element_type=f32)
            return acc, m_new, l

        acc, _m, l = jax.lax.fori_loop(
            first // keys, last // keys + 1, body,
            (jnp.zeros((Hkv, R, Dv), f32),
             jnp.full((Hkv, R, 1), _NEG_INF, f32),
             jnp.zeros((Hkv, R, 1), f32)))
        return acc / jnp.maximum(l, 1e-30)

    out = jnp.stack([row(b) for b in range(B)])         # (B, Hkv, R, Dv)
    return (out.reshape(B, Hkv, S, group, Dv).transpose(0, 2, 1, 3, 4)
            .reshape(B, S, H, Dv).astype(q.dtype))
