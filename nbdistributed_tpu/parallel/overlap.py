"""Collective-matmul overlap: ring-decomposed ``all_gather -> matmul``
and ``matmul -> reduce_scatter`` for the Megatron sequence-parallel
tensor-parallel block.

Why this exists (TPU-first rationale): in the sequence-parallel TP
layout, activations enter the MLP/attention block sharded on the
sequence axis and must be all-gathered before the column-parallel
matmul; the row-parallel output is reduce-scattered back.  Issued as
monolithic collectives, the ICI transfer and the MXU GEMM serialize:
``t_total = t_comm + t_matmul``.  Decomposing both collectives into a
ring of ``ppermute`` hops interleaved with per-chunk GEMMs lets XLA's
async collective machinery run hop ``i+1`` while chunk ``i`` is on the
MXU, hiding up to all of ``t_comm`` behind compute (the "collective
matmul" of the scaling-book / Wang et al., ASPLOS'23).  XLA can fuse
this itself in some cases (``--xla_tpu_enable_async_collective_fusion``
pass); the explicit ring makes the overlap structural — guaranteed by
dataflow, not by a scheduler heuristic — and works under ``shard_map``
where the user owns the SPMD program.

Reference parity note: the reference has no tensor parallelism at all —
its TP story is users typing broadcasts by hand
(reference: README.md:115-125).  This module is beyond-parity TPU
machinery, composing with
:func:`~nbdistributed_tpu.parallel.tensor_parallel.make_tp_train_step`
(GSPMD path) as the hand-scheduled alternative for the hot block.

All functions run **inside shard_map** over the given axis and are
fully differentiable (the transpose of ``ppermute`` is ``ppermute``,
of ``dynamic_slice`` is ``dynamic_update_slice`` — the backward is a
ring program of the same shape).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax



def allgather_matmul(x, w, axis_name: str):
    """``all_gather(x, axis) @ w``, ring-decomposed.

    Inside ``shard_map``: ``x (m, K)`` is this shard's slice of the
    row-sharded (e.g. sequence-sharded) left operand; ``w (K, n)`` is
    this shard's column slice of the weight.  Returns ``(t*m, n)`` —
    the full-length rows times the local columns, i.e. the
    column-parallel Megatron matmul with sequence-parallel input.

    Chunk ``i`` hops the ring while chunk ``i-1`` multiplies: the
    ``ppermute`` and the GEMM at each step share no dataflow edge, so
    XLA schedules them concurrently (DMA vs MXU).
    """
    t = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    m = x.shape[0]
    fwd = [(i, (i + 1) % t) for i in range(t)]
    part0 = x @ w
    y = jnp.zeros((t * m, part0.shape[1]), part0.dtype)
    buf = x
    for i in range(t):
        # buf arrived over i hops of the +1 ring: it is shard
        # (me - i)'s chunk, and lands at that row offset.
        src = (me - i) % t
        part = part0 if i == 0 else buf @ w
        y = lax.dynamic_update_slice(y, part, (src * m, 0))
        if i < t - 1:
            buf = lax.ppermute(buf, axis_name, fwd)
    return y


def matmul_reducescatter(x, w, axis_name: str):
    """``reduce_scatter(x @ w, axis)``, ring-decomposed.

    Inside ``shard_map``: ``x (M, k)`` is this shard's slice of the
    column-sharded left operand (``k = K/t``), ``w (k, N)`` the
    matching row slice of the weight — the row-parallel Megatron
    matmul, whose partial products are summed over shards and row-
    scattered: returns ``(M/t, N)``, this shard's row chunk of the
    reduced result (sequence-parallel output layout).

    The accumulator for destination shard ``d`` starts at shard
    ``d+1``, visits every shard once (each adds its local partial for
    rows ``[d*M/t, (d+1)*M/t)``), and terminates at ``d`` — so each
    hop's transfer overlaps the next chunk's GEMM.
    """
    t = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    M = x.shape[0]
    if M % t:
        raise ValueError(f"leading dim {M} not divisible by axis size {t}")
    m = M // t
    fwd = [(i, (i + 1) % t) for i in range(t)]
    acc = None
    for i in range(t):
        j = (me - 1 - i) % t
        part = lax.dynamic_slice(x, (j * m, 0), (m, x.shape[1])) @ w
        acc = part if acc is None else acc + part
        if i < t - 1:
            acc = lax.ppermute(acc, axis_name, fwd)
    return acc


def megatron_sp_block(x, w_up, w_down, axis_name: str, act=jax.nn.gelu):
    """The canonical sequence-parallel TP MLP with both collectives
    ring-overlapped: ``reduce_scatter(act(all_gather(x) @ w_up) @
    w_down)``.

    Inside ``shard_map``: ``x (S/t, D)`` sequence-sharded activations,
    ``w_up (D, F/t)`` column-parallel, ``w_down (F/t, D)``
    row-parallel.  Returns ``(S/t, D)`` — same layout as the input, so
    blocks chain without extra collectives.
    """
    h = act(allgather_matmul(x, w_up, axis_name))
    return matmul_reducescatter(h, w_down, axis_name)
