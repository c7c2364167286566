"""Ring attention: exact attention over sequence-sharded inputs.

Long-context training shards the *sequence* axis across devices (the
reference has no sequence-parallel story at all: SURVEY §5.7).  Ring
attention keeps the O(S^2) score matrix virtual: each device holds one
sequence chunk of Q locally and streams K/V chunks around the ring via
``jax.lax.ppermute`` (ICI neighbor exchange), folding each visiting
chunk into an online-softmax accumulator — so communication overlaps
compute blockwise and peak memory stays sub-quadratic per step.

GQA is native end-to-end: K/V ride the ring at ``n_kv_heads`` (hop
traffic ``H/Hkv``× smaller than pre-expanding) AND stay at Hkv inside
the local attention — the einsum path groups the query heads in the
einsums, and the Pallas kernels grid over (batch, kv-head) with the
group as a batch dim of the q block, so no expanded K/V buffer exists
anywhere, on the wire or in HBM.

Two inner paths:

* ``use_flash=False`` (default, any backend): grouped-einsum online
  softmax — differentiable through plain autodiff.
* ``use_flash=True`` (the TPU path): every hop runs the Pallas flash
  kernel (ops/attention.py) with chunk offsets for cross-chunk causal
  masking; hop results are folded by their logsumexp.  The custom VJP
  re-rings K/V through the blockwise Pallas backward — a ring hop is
  just a k-block at scale, and k-blocks are independent given the
  global (lse, delta) — so no (Sq, Sk) tensor exists in either
  direction, per hop or globally.

This is the shard_map/ppermute formulation the scaling-book recipe
prescribes; the same math as the flash kernel's inner loop, lifted
from k-blocks to ring hops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


@functools.lru_cache(maxsize=None)
def _ring_fn(mesh, axis: str, causal: bool, scale: float,
             use_flash: bool, schedule: str,
             batch_axis: str | None = None,
             head_axis: str | None = None,
             window: int | None = None,
             with_segments: bool = False):
    """Jitted ring kernel, cached per (mesh, axis, causal, scale, path)
    so repeated training-loop calls hit the jit cache instead of
    retracing.  ``batch_axis``/``head_axis`` put the embarrassingly
    parallel batch and head dims on their mesh axes (dp/tp) — the ring
    math never mixes them, so the inner is unchanged; without them the
    shard_map would declare B and H replicated and GSPMD would
    all-gather dp/tp-sharded activations at every call."""
    n = mesh.shape[axis]
    spec = P(batch_axis, axis, head_axis, None)
    if schedule == "zigzag":
        inner = _make_ring_flash_zigzag(axis, n, scale, window=window,
                                        with_segments=with_segments)
    elif use_flash:
        inner = _make_ring_flash(axis, n, causal, scale, window=window,
                                 with_segments=with_segments)
    else:
        inner = functools.partial(_ring_inner, axis=axis, n=n,
                                  causal=causal, scale=scale,
                                  window=window)
    in_specs = (spec, spec, spec)
    if with_segments:
        # Segment ids are per (batch, position): sequence-sharded like
        # q, replicated over heads.
        in_specs = in_specs + (P(batch_axis, axis),)
    return jax.jit(jax.shard_map(
        inner, mesh=mesh, in_specs=in_specs, out_specs=spec,
        check_vma=False))


def ring_attention(q, k, v, mesh, *, axis: str = "sp",
                   causal: bool = True, scale: float | None = None,
                   use_flash: bool = False, schedule: str = "plain",
                   batch_axis: str | None = None,
                   head_axis: str | None = None,
                   window: int | None = None,
                   segment_ids=None):
    """Exact (causal) attention with Q/K/V sharded on ``axis`` along the
    sequence dimension.

    q: (B, S, H, D) and k/v: (B, S, Hkv, D) global arrays whose S
    dimension is sharded over ``mesh[axis]``; returns attention output
    with the same sharding.  ``H % Hkv == 0`` (grouped-query) — K/V are
    NOT expanded: they circulate the ring at Hkv heads.
    ``use_flash=True`` runs the Pallas flash kernel per hop (forward
    and backward); the default grouped-einsum path works on any
    backend.

    ``schedule="zigzag"`` is the load-balanced causal schedule: inputs
    must be in zigzag order (:func:`zigzag_shard` — device d holds
    global chunks d and 2n-1-d), and the output comes back in the same
    order (:func:`zigzag_unshard` restores it).  With plain chunking,
    causality idles device 0 on every hop but the first while device
    n-1 computes on all of them — the ring's wall-clock is the
    *unmasked* cost.  Zigzag gives every device ~2 half-chunk blocks
    of real work per hop, halving causal ring step time at scale.
    Requires ``causal=True`` and ``use_flash=True`` (only the Pallas
    path actually *skips* masked blocks; a masked einsum computes them
    anyway), and S divisible by 2n.

    ``batch_axis``/``head_axis``: mesh axes the batch and head dims are
    sharded over (dp/tp composition) — batch and heads are
    embarrassingly parallel through the ring, so these just extend the
    shard_map specs; omitting them when activations ARE dp/tp-sharded
    makes GSPMD all-gather and compute attention replicated.
    ``head_axis`` needs ``Hkv`` divisible by that axis (each shard then
    keeps whole GQA groups: q heads [t·H/tp, (t+1)·H/tp) attend exactly
    kv heads [t·Hkv/tp, (t+1)·Hkv/tp)).
    """
    H, D = q.shape[2], q.shape[-1]
    Hkv = k.shape[2]
    if H % Hkv:
        raise ValueError(f"n_heads {H} not divisible by n_kv_heads {Hkv}")
    if head_axis is not None and Hkv % mesh.shape[head_axis]:
        raise ValueError(
            f"head_axis {head_axis!r} (size {mesh.shape[head_axis]}) "
            f"must divide n_kv_heads {Hkv} so each shard keeps whole "
            f"GQA groups")
    if v.shape[2] != Hkv:
        raise ValueError(f"k/v head counts differ: {Hkv} vs {v.shape[2]}")
    if schedule not in ("plain", "zigzag"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if schedule == "zigzag":
        n = mesh.shape[axis]
        if not causal:
            raise ValueError("zigzag is a causal-balance schedule; "
                             "use schedule='plain' for non-causal")
        if not use_flash:
            raise ValueError(
                "zigzag requires use_flash=True: only the Pallas path "
                "skips masked blocks (a masked einsum computes them "
                "anyway, so zigzag would buy nothing)")
        if q.shape[1] % (2 * n):
            raise ValueError(f"zigzag needs S divisible by 2n="
                             f"{2 * n}, got S={q.shape[1]}")
    from ..ops.attention import check_window
    check_window(window, causal)
    if segment_ids is not None:
        # Packed-document masking: each device's q-chunk segments stay
        # local; the K-chunk segments ride the ring with K/V (a tiny
        # int32 extra rider).  Hops whose chunks share no segment
        # self-heal through the lse fold (weight 0).
        # Zigzag composes too: the segment array must be in zigzag
        # order like q/k/v (zigzag_shard it with them) — the fold
        # slices its half-chunks exactly as it slices K/V.
        if segment_ids.shape != q.shape[:2]:
            raise ValueError(
                f"segment_ids shape {segment_ids.shape} != (B, S) "
                f"{q.shape[:2]}")
        if q.shape[1] != k.shape[1]:
            raise ValueError("segment_ids requires Sq == Sk")
    scale = scale if scale is not None else float(1.0 / np.sqrt(D))
    fn = _ring_fn(mesh, axis, causal, scale, use_flash, schedule,
                  batch_axis, head_axis, window,
                  with_segments=segment_ids is not None)
    if segment_ids is None:
        return fn(q, k, v)
    return fn(q, k, v, jnp.asarray(segment_ids, jnp.int32))


def zigzag_order(S: int, n: int):
    """Permutation putting a (B, S, ...) sequence into zigzag layout:
    position p of the reordered sequence holds original index
    ``order[p]``.  Sharding the result contiguously over n devices
    gives device d the original chunks d and 2n-1-d."""
    if S % (2 * n):
        raise ValueError(f"S={S} not divisible by 2n={2 * n}")
    C = S // (2 * n)
    idx = []
    for d in range(n):
        idx.extend(range(d * C, (d + 1) * C))
        idx.extend(range((2 * n - 1 - d) * C, (2 * n - d) * C))
    return np.asarray(idx)


def zigzag_shard(x, n: int, axis: int = 1):
    """Reorder a global array's sequence axis into zigzag layout (do
    this once on the data, before sequence-sharding it)."""
    return jnp.take(x, jnp.asarray(zigzag_order(x.shape[axis], n)),
                    axis=axis)


def zigzag_unshard(x, n: int, axis: int = 1):
    """Inverse of :func:`zigzag_shard`."""
    order = zigzag_order(x.shape[axis], n)
    inv = np.empty_like(order)
    inv[order] = np.arange(len(order))
    return jnp.take(x, jnp.asarray(inv), axis=axis)


def _intervals_touch(q_ivals, k_ivals, window: int) -> bool:
    """Whether any (query position, key position) pair drawn from the
    given half-open global-index intervals is visible under the causal
    + sliding-window mask (``ki <= qi`` and ``ki > qi - window``).
    Only called with a real window — hop_plan early-returns the full
    ring otherwise."""
    for q0, q1 in q_ivals:
        for k0, k1 in k_ivals:
            if k0 <= q1 - 1 and k1 - 1 >= q0 - window + 1:
                return True
    return False


def hop_plan(n: int, s_local: int, window: int | None,
             schedule: str = "plain", *, sk_local: int | None = None):
    """The static set of ring steps that can contribute under a sliding
    window: step ``s`` gives device ``my`` the K/V chunk of device
    ``(my - s) % n``; a step is in the plan iff ANY device has a
    mask-visible (q-interval, k-interval) pair there (the plan must be
    device-uniform — every device executes the same SPMD program).

    Without a window every causal step contributes somewhere (device
    n-1 sees all of history), so the plan is ``range(n)``.  With a
    window of w tokens over chunks of C tokens, the plain schedule's
    plan collapses to a prefix of ``1 + ceil((w-1)/C)`` steps and the
    zigzag schedule's to a short prefix + suffix (zigzag pairs chunk d
    with chunk 2n-1-d, whose window neighbors arrive at ring distance
    n-1, n-2, ...) — O(window/C) hops instead of n, and K/V jump
    straight across skipped steps in one ``ppermute``.

    ``s_local`` is the per-device Q length; ``sk_local`` the per-device
    K length when they differ (cross-length attention in the plain
    schedule; zigzag requires them equal).
    """
    if window is None:
        return tuple(range(n))
    sk_local = s_local if sk_local is None else sk_local
    steps = []
    for s in range(n):
        for my in range(n):
            src = (my - s) % n
            if schedule == "zigzag":
                C = s_local // 2
                q_iv = [(my * C, (my + 1) * C),
                        ((2 * n - 1 - my) * C, (2 * n - my) * C)]
                k_iv = [(src * C, (src + 1) * C),
                        ((2 * n - 1 - src) * C, (2 * n - src) * C)]
            else:
                q_iv = [(my * s_local, (my + 1) * s_local)]
                k_iv = [(src * sk_local, (src + 1) * sk_local)]
            if _intervals_touch(q_iv, k_iv, window):
                steps.append(s)
                break
    return tuple(steps)


def _jump(arrs, axis: str, n: int, d: int):
    """Move every device's chunk ``d`` ring positions forward in ONE
    ppermute per array (a skipped-hop jump is a single collective, not
    d neighbor exchanges)."""
    if d % n == 0:
        return list(arrs)
    perm = [(j, (j + d) % n) for j in range(n)]
    return [jax.lax.ppermute(a, axis, perm) for a in arrs]


def _run_hops(plan, n: int, axis: str, my, fold, carry, riders,
              home: int = 0):
    """Shared hop-loop driver for every ring path (einsum/flash fwd,
    flash/zigzag bwd): run ``carry, riders = fold(carry, riders, src)``
    at each plan step with the K/V (and any gradient-accumulator)
    ``riders`` rotated between steps.

    Full plan -> the classic fori_loop of neighbor ppermutes (one
    compiled body, n trips).  Pruned plan (sliding window) -> unrolled,
    with a single ppermute jumping each gap.  ``home``: how many
    trailing riders (dk/dv accumulators) must end on their owning
    device — the fori path returns them home by construction (n
    rotations), the plan path jumps them back by ``-plan[-1]``.
    """
    riders = tuple(riders)
    if len(plan) == n:
        perm = [(j, (j + 1) % n) for j in range(n)]

        def body(step, state):
            c, r = state
            c, r = fold(c, r, (my - step) % n)
            return c, tuple(jax.lax.ppermute(x, axis, perm) for x in r)

        return jax.lax.fori_loop(0, n, body, (carry, riders))
    prev = 0
    for s in plan:
        riders = tuple(_jump(riders, axis, n, s - prev))
        prev = s
        carry, riders = fold(carry, riders, (my - s) % n)
    if home:
        riders = riders[:-home] + tuple(
            _jump(riders[-home:], axis, n, -plan[-1]))
    return carry, riders


def _ring_inner(q, k, v, seg=None, *, axis: str, n: int, causal: bool,
                scale: float, window: int | None = None):
    """Grouped-einsum online-softmax ring (local view inside shard_map).

    q: (B, Sq, H, D) local chunk; k/v: (B, Sk, Hkv, D) rotating chunks;
    ``seg``: optional (B, Sq) local segment ids (the K-side copy rides
    the ring as an extra rider — packed-document masking).
    """
    B, Sq, H, Dh = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    my = jax.lax.axis_index(axis)
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, g, Dh)
    acc = jnp.zeros((B, Sq, Hkv, g, Dh), jnp.float32)
    m = jnp.full((B, Hkv, g, Sq, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, Hkv, g, Sq, 1), jnp.float32)

    def fold(carry, riders, src):
        acc, m, l = carry
        if seg is None:
            k_cur, v_cur = riders
            kseg_cur = None
        else:
            k_cur, v_cur, kseg_cur = riders
        Sk = k_cur.shape[1]
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf,
                       k_cur.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        if causal or seg is not None:
            keep = jnp.ones((1, Sq, Sk), bool)
            if causal:
                qi = (my * Sq + jax.lax.broadcasted_iota(
                    jnp.int32, (Sq, Sk), 0))
                ki = (src * Sk + jax.lax.broadcasted_iota(
                    jnp.int32, (Sq, Sk), 1))
                ck = ki <= qi
                if window is not None:
                    ck = ck & (ki > qi - window)
                keep = keep & ck[None]
            if seg is not None:
                keep = keep & (seg[:, :, None] == kseg_cur[:, None, :])
            s = jnp.where(keep[:, None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                       # (B,Hkv,g,Sq,Sk)
        corr = jnp.exp(m - m_new)                    # (B,Hkv,g,Sq,1)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bkgqs,bskd->bqkgd", p,
                        v_cur.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        return ((acc * corr.transpose(0, 3, 1, 2, 4) + pv, m_new,
                 l_new), riders)

    plan = hop_plan(n, Sq, window if causal else None,
                    sk_local=k.shape[1])
    riders = (k, v) if seg is None else (k, v, seg)
    (acc, m, l), _ = _run_hops(plan, n, axis, my, fold, (acc, m, l),
                               riders)
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


# ----------------------------------------------------------------------
# Flash (Pallas) inner path

def _wrap_vjp(rf_fwd, rf_bwd, with_segments: bool):
    """The custom_vjp trailer shared by the plain and zigzag flash
    builders: custom_vjp needs a FIXED arity, so build the exact-arity
    wrapper per variant around the shared fwd/bwd bodies (rf_bwd always
    returns a 4-tuple whose last entry is the segment cotangent —
    float0 for int ids, None when absent — truncated to 3 for the
    segment-free variant)."""
    if with_segments:
        @jax.custom_vjp
        def rf(q, k, v, seg):
            return rf_fwd(q, k, v, seg)[0]

        rf.defvjp(lambda q, k, v, seg: rf_fwd(q, k, v, seg), rf_bwd)
        return rf

    @jax.custom_vjp
    def rf(q, k, v):
        return rf_fwd(q, k, v)[0]

    rf.defvjp(lambda q, k, v: rf_fwd(q, k, v),
              lambda res, g: rf_bwd(res, g)[:3])
    return rf


def _fold_hop(O, L, o_j, lse_j, B, Sq):
    """One online-softmax fold of a hop contribution (o_j, lse_j) into
    the running (O, L) — the numerically delicate core shared by the
    plain and zigzag schedules."""
    L_new = jnp.logaddexp(L, lse_j)
    w_old = _hop_weights(jnp.exp(L - L_new), B, Sq)
    w_j = _hop_weights(jnp.exp(lse_j - L_new), B, Sq)
    return O * w_old + o_j.astype(jnp.float32) * w_j, L_new


def _hop_weights(w, B, Sq):
    """(B*Hkv, group, Sq_pad) fold-layout weights -> (B, Sq, H, 1)
    (head h = kv_head * group + g, matching _fold_q_gqa)."""
    BHkv, group, Sq_pad = w.shape
    Hkv = BHkv // B
    return (w.reshape(B, Hkv, group, Sq_pad)
            .transpose(0, 3, 1, 2)
            .reshape(B, Sq_pad, Hkv * group)[:, :Sq, :, None])


def _make_ring_flash(axis: str, n: int, causal: bool, scale: float,
                     block_q: int | None = None,
                     block_k: int | None = None,
                     window: int | None = None,
                     with_segments: bool = False):
    """Builds the shard_map inner for the Pallas ring with exact
    gradients: forward folds per-hop (out, lse) pairs; backward re-rings
    K/V through the blockwise dq/dkv kernels using the saved global
    logsumexp (hops are independent given (lse, delta), exactly like
    k-blocks inside one kernel call).  ``with_segments``: the inner
    takes a fourth (B, Sq) segment-id chunk; its K-side copy rides the
    ring with K/V and each hop's kernel call applies the packed-
    document mask in both passes (a hop sharing no segment self-heals
    to weight 0 through the lse fold)."""
    from ..ops.attention import (_block_sizes, _flash_backward_folded,
                                 _flash_bwd_prep, _flash_forward,
                                 _use_interpret)


    def _rf_fwd(q, k, v, seg=None):
        B, Sq, H, D = q.shape
        Sk, Hkv = k.shape[1], k.shape[2]
        interp = _use_interpret()
        bq, bk = _block_sizes(block_q, block_k, Sq, Sk, D, H // Hkv,
                              interpret=interp)
        my = jax.lax.axis_index(axis)
        Sq_pad = -(-Sq // bq) * bq
        O = jnp.zeros((B, Sq, H, D), jnp.float32)
        L = jnp.full((B * Hkv, H // Hkv, Sq_pad), _NEG_INF, jnp.float32)

        def fold(carry, riders, src):
            # step 0 is always the diagonal chunk (src == my), so L is
            # real from the first fold and fully-masked later hops
            # (lse ~ -inf) get weight exp(-inf - L) = 0.
            O, L = carry
            if seg is None:
                k_cur, v_cur = riders
                kseg_cur = None
            else:
                k_cur, v_cur, kseg_cur = riders
            o_j, lse_j = _flash_forward(
                q, k_cur, v_cur, causal=causal, scale=scale,
                block_q=bq, block_k=bk, interpret=interp,
                offsets=(my * Sq, src * Sk), window=window,
                segment_ids=seg, kv_segment_ids=kseg_cur)
            return _fold_hop(O, L, o_j, lse_j, B, Sq), riders

        plan = hop_plan(n, Sq, window if causal else None,
                        sk_local=Sk)
        riders = (k, v) if seg is None else (k, v, seg)
        (O, L), _ = _run_hops(plan, n, axis, my, fold, (O, L), riders)
        out = O.astype(q.dtype)
        return out, (q, k, v, out, L, seg)

    def _rf_bwd(res, g):
        q, k, v, out, L, seg = res
        B, Sq, H, D = q.shape
        Sk, Hkv = k.shape[1], k.shape[2]
        interp = _use_interpret()
        bq, bk = _block_sizes(block_q, block_k, Sq, Sk, D, H // Hkv,
                              interpret=interp, kernel="dq")
        dkv_blocks = _block_sizes(block_q, block_k, Sq, Sk, D, H // Hkv,
                                  interpret=interp, kernel="dkv")
        my = jax.lax.axis_index(axis)
        # Hop-invariant work — the q/dO folds and the delta reduction —
        # happens once, not n times (only k/v change per hop).
        qt, got, delta = _flash_bwd_prep(q, out, g, bq, k.shape[2])
        dq0 = jnp.zeros((B, Sq, H, D), jnp.float32)
        dk0 = jnp.zeros(k.shape, jnp.float32)
        dv0 = jnp.zeros(v.shape, jnp.float32)

        def fold(dq, riders, src):
            # dk/dv accumulators ride WITH their chunk (trailing
            # riders): each chunk collects its gradient contributions
            # as it visits every device, then lands home.
            if seg is None:
                k_cur, v_cur, dk_cur, dv_cur = riders
                kseg_cur = None
            else:
                k_cur, v_cur, kseg_cur, dk_cur, dv_cur = riders
            dq_j, dk_j, dv_j = _flash_backward_folded(
                qt, got, delta, L, k_cur, v_cur, B=B, Sq=Sq,
                q_dtype=q.dtype, causal=causal, scale=scale,
                block_q=bq, block_k=bk, dkv_blocks=dkv_blocks,
                interpret=interp,
                offsets=(my * Sq, src * Sk), window=window,
                segment_ids=seg, kv_segment_ids=kseg_cur)
            rest = (dk_cur + dk_j.astype(dk_cur.dtype),
                    dv_cur + dv_j.astype(dv_cur.dtype))
            head = ((k_cur, v_cur) if seg is None
                    else (k_cur, v_cur, kseg_cur))
            return dq + dq_j.astype(jnp.float32), head + rest

        plan = hop_plan(n, Sq, window if causal else None,
                        sk_local=Sk)
        riders = ((k, v, dk0, dv0) if seg is None
                  else (k, v, seg, dk0, dv0))
        dq, out_riders = _run_hops(plan, n, axis, my, fold, dq0,
                                   riders, home=2)
        dk, dv = out_riders[-2], out_riders[-1]
        grads = (dq.astype(q.dtype), dk.astype(k.dtype),
                 dv.astype(v.dtype))
        if seg is None:
            return grads + (None,)
        return grads + (np.zeros(seg.shape, jax.dtypes.float0),)

    return _wrap_vjp(_rf_fwd, _rf_bwd, with_segments)


def _make_ring_flash_zigzag(axis: str, n: int, scale: float,
                            block_q: int | None = None,
                            block_k: int | None = None,
                            window: int | None = None,
                            with_segments: bool = False):
    """Zigzag causal ring (local view: the two half-chunks d and
    2n-1-d, concatenated).  Every hop runs four half-pair Pallas calls
    with exact global offsets; causal block-skip inside the kernel
    makes the never-attending pairs near-free, so per-hop work is ~2
    half-blocks on EVERY device — the load-balanced schedule.  Exact
    gradients via the same per-pair blockwise backward, with dk/dv
    half-accumulators riding the ring home."""
    from ..ops.attention import (_block_sizes, _flash_backward_folded,
                                 _flash_bwd_prep, _flash_forward,
                                 _use_interpret)


    def _offs(idx, C):
        """Global offsets of owner ``idx``'s two half-chunks."""
        return (idx * C, (2 * n - 1 - idx) * C)

    def _rf_fwd(q, k, v, seg=None):
        B, Sq, H, D = q.shape
        Hkv = k.shape[2]
        C = Sq // 2
        G = H // Hkv
        interp = _use_interpret()
        bq, bk = _block_sizes(block_q, block_k, C, C, D, H // Hkv,
                              interpret=interp)
        my = jax.lax.axis_index(axis)
        C_pad = -(-C // bq) * bq
        q_offs = _offs(my, C)
        qh = (q[:, :C], q[:, C:])
        qsegh = (None, None) if seg is None else (seg[:, :C], seg[:, C:])
        O = [jnp.zeros((B, C, H, D), jnp.float32) for _ in range(2)]
        L = [jnp.full((B * Hkv, G, C_pad), _NEG_INF, jnp.float32)
             for _ in range(2)]

        def fold(carry, riders, src):
            Oa, La, Ob, Lb = carry
            if seg is None:
                k_cur, v_cur = riders
                kseg_cur = None
            else:
                k_cur, v_cur, kseg_cur = riders
            k_offs = _offs(src, C)
            Os, Ls = [Oa, Ob], [La, Lb]
            # Step 0 folds real data first for both q halves: (qa, ka)
            # is qa's diagonal and (qb, ka) is fully unmasked, so each
            # L[qi] is finite from its first fold (fully-masked pairs
            # surface lse ~ -inf and weight to zero, as in the plain
            # schedule).
            for qi in range(2):
                for ki in range(2):
                    o_j, lse_j = _flash_forward(
                        qh[qi], k_cur[:, ki * C:(ki + 1) * C],
                        v_cur[:, ki * C:(ki + 1) * C],
                        causal=True, scale=scale, block_q=bq,
                        block_k=bk, interpret=interp,
                        offsets=(q_offs[qi], k_offs[ki]),
                        window=window,
                        segment_ids=qsegh[qi],
                        kv_segment_ids=(
                            None if kseg_cur is None else
                            kseg_cur[:, ki * C:(ki + 1) * C]))
                    Os[qi], Ls[qi] = _fold_hop(Os[qi], Ls[qi], o_j,
                                               lse_j, B, C)
            return (Os[0], Ls[0], Os[1], Ls[1]), riders

        # Windowed zigzag plans are a short prefix + suffix (chunk d's
        # pair 2n-1-d meets its window neighbors at ring distance n-1,
        # n-2, ...); K/V jump across the gap in one ppermute.
        plan = hop_plan(n, Sq, window, "zigzag")
        riders = (k, v) if seg is None else (k, v, seg)
        (Oa, La, Ob, Lb), _ = _run_hops(
            plan, n, axis, my, fold, (O[0], L[0], O[1], L[1]), riders)
        out = jnp.concatenate([Oa, Ob], axis=1).astype(q.dtype)
        return out, (q, k, v, out, La, Lb, seg)

    def _rf_bwd(res, g):
        q, k, v, out, La, Lb, seg = res
        B, Sq, H, D = q.shape
        Hkv = k.shape[2]
        C = Sq // 2
        interp = _use_interpret()
        bq, bk = _block_sizes(block_q, block_k, C, C, D, H // Hkv,
                              interpret=interp, kernel="dq")
        dkv_blocks = _block_sizes(block_q, block_k, C, C, D, H // Hkv,
                                  interpret=interp, kernel="dkv")
        my = jax.lax.axis_index(axis)
        q_offs = _offs(my, C)
        Ls = (La, Lb)
        qsegh = (None, None) if seg is None else (seg[:, :C], seg[:, C:])
        # Hoisted per-half backward prep (hop-invariant).
        prep = [_flash_bwd_prep(q[:, h * C:(h + 1) * C],
                                out[:, h * C:(h + 1) * C],
                                g[:, h * C:(h + 1) * C], bq, Hkv)
                for h in range(2)]
        dq0 = [jnp.zeros((B, C, H, D), jnp.float32) for _ in range(2)]
        dk0 = jnp.zeros(k.shape, jnp.float32)
        dv0 = jnp.zeros(v.shape, jnp.float32)

        def fold(carry, riders, src):
            dqa, dqb = carry
            if seg is None:
                k_cur, v_cur, dk_cur, dv_cur = riders
                kseg_cur = None
            else:
                k_cur, v_cur, kseg_cur, dk_cur, dv_cur = riders
            k_offs = _offs(src, C)
            dqs = [dqa, dqb]
            for qi in range(2):
                qt, got, delta = prep[qi]
                for ki in range(2):
                    dq_j, dk_j, dv_j = _flash_backward_folded(
                        qt, got, delta, Ls[qi],
                        k_cur[:, ki * C:(ki + 1) * C],
                        v_cur[:, ki * C:(ki + 1) * C],
                        B=B, Sq=C, q_dtype=q.dtype, causal=True,
                        scale=scale, block_q=bq, block_k=bk,
                        dkv_blocks=dkv_blocks, interpret=interp,
                        offsets=(q_offs[qi], k_offs[ki]),
                        window=window,
                        segment_ids=qsegh[qi],
                        kv_segment_ids=(
                            None if kseg_cur is None else
                            kseg_cur[:, ki * C:(ki + 1) * C]))
                    dqs[qi] = dqs[qi] + dq_j.astype(jnp.float32)
                    sl = slice(ki * C, (ki + 1) * C)
                    dk_cur = dk_cur.at[:, sl].add(
                        dk_j.astype(jnp.float32))
                    dv_cur = dv_cur.at[:, sl].add(
                        dv_j.astype(jnp.float32))
            head = ((k_cur, v_cur) if seg is None
                    else (k_cur, v_cur, kseg_cur))
            return (dqs[0], dqs[1]), head + (dk_cur, dv_cur)

        plan = hop_plan(n, Sq, window, "zigzag")
        riders = ((k, v, dk0, dv0) if seg is None
                  else (k, v, seg, dk0, dv0))
        (dqa, dqb), out_riders = _run_hops(
            plan, n, axis, my, fold, (dq0[0], dq0[1]), riders, home=2)
        dk, dv = out_riders[-2], out_riders[-1]
        dq = jnp.concatenate([dqa, dqb], axis=1)
        grads = (dq.astype(q.dtype), dk.astype(k.dtype),
                 dv.astype(v.dtype))
        if seg is None:
            return grads + (None,)
        return grads + (np.zeros(seg.shape, jax.dtypes.float0),)

    return _wrap_vjp(_rf_fwd, _rf_bwd, with_segments)
