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

import contextlib
import contextvars
import functools

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


# ----------------------------------------------------------------------
# the data-parallel gradient sum as asynchronous sends (ISSUE 36)

# Leaves under this many elements take a plain ``psum``.  Measured on
# four v5e chips (PERF.md §6, PR 36): for Mistral-7B's attention
# matrices (16.8 M and 4.2 M elements) the blocking all-reduce costs
# less than the sends' fixed costs; for the MLP's (58.7 M) and the
# embeddings (131 M) the sends win: a step of 225.9 ms here, 229.0
# at 2^24 (``wq`` and ``wo`` sent too), 230.2 with every leaf sent.
EXCHANGE_MIN_SIZE = 1 << 25


class _Exchange:
    """One leaf's sum over ``axis_name``.  Begun (``__init__``): send
    every other shard this shard's values for that shard's chunk,
    ``n − 1`` asynchronous ``ppermute``s.  Finished (:meth:`finish`):
    sum my chunk in float32 from the shards' own values, round it
    once, and ``all_gather`` the chunks."""

    def __init__(self, g, axis_name: str):
        n, me = lax.axis_size(axis_name), lax.axis_index(axis_name)
        self.g, self.axis_name, self.out = g, axis_name, None
        self.trace = jax.core.get_opaque_trace_state()
        chunks = g.reshape(n, g.shape[0] // n, *g.shape[1:])
        self.mine = lax.dynamic_index_in_dim(chunks, me, 0, keepdims=False)
        self.flying = [lax.ppermute(
            lax.dynamic_index_in_dim(chunks, (me + k) % n, 0,
                                     keepdims=False),
            axis_name, [(i, (i + k) % n) for i in range(n)])
            for k in range(1, n)]

    def finish(self):
        if self.out is None:
            mine = sum((c.astype(jnp.float32) for c in self.flying),
                       self.mine.astype(jnp.float32)).astype(self.g.dtype)
            self.out = lax.all_gather(mine, self.axis_name, axis=0,
                                      tiled=True)
        return self.out

    def live(self) -> bool:
        # a rule traced in a scan's body must not take up what the
        # program around the scan began, nor the other way round
        return self.trace == jax.core.get_opaque_trace_state()


def _exchanges(g, axis_name: str) -> bool:
    n = lax.axis_size(axis_name)
    return (n > 1 and g.ndim > 0 and g.shape[0] % n == 0
            and g.size >= EXCHANGE_MIN_SIZE)


def exchange_sum(g, axis_name: str):
    """``psum(g, axis_name)`` with its reduce-scatter half as ``n − 1``
    asynchronous ``ppermute`` sends of ``1/n`` of ``g`` (split along
    its leading dimension): every shard sends each other shard its
    values for that shard's chunk, sums its own chunk, and the chunks
    are ``all_gather``ed.

    Why: on the TPU ``psum`` compiles to one *blocking* ``all-reduce``
    that stops the TensorCore for as long as the links need, whatever
    stands beside it, and so do ``psum_scatter`` and ``all_gather``; a
    ``ppermute`` compiles to a ``collective-permute-start`` / ``-done``
    pair with the operations it does not depend on scheduled between
    the two.  The sends cost the TensorCore copies (a chunk is sliced
    out to be sent) where a blocking collective costs it the links'
    time: for the reduce half that is 3.4 ms a gigabyte against 8.6;
    for the gather half (the received chunks are copied into place) it
    is no less than the blocking ``all_gather``'s, which therefore
    stays (PERF.md §6, PR 36).

    A chunk is summed in float32 from the shards' own values and
    rounded to ``g``'s dtype once: the same bits on every shard, and no
    less exact than any all-reduce in ``g``'s dtype.  A leaf whose
    leading dimension ``n`` does not divide, or smaller than
    :data:`EXCHANGE_MIN_SIZE`, takes a plain ``psum``.
    """
    if not _exchanges(g, axis_name):
        return lax.psum(g, axis_name)
    return _Exchange(g, axis_name).finish()


class _GradSums:
    """What a step builder's trace tells the model it differentiates,
    and what the backward rules below tell one another while that
    trace lasts."""

    def __init__(self, axis_name: str):
        self.axis_name = axis_name      # manual; the weights replicated
        self.owned = []                 # the leaves being differentiated
        self.claimed = set()            # ids of those whose gradients
                                        # the loss sums itself
        self.marked = {}                # id(weight) -> weight, as marked
        self.begun = {}                 # id(gradient) -> its _Exchange
        self.flying = []                # the exchanges not yet whole
        self.points = {}                # id(activation) -> (its number,
                                        # the activation: kept, so that
                                        # no other takes its id)
        self.at = None                  # the number the clock last
                                        # ticked at

    def own(self, params):
        """The builder, inside the function it differentiates: these
        are the leaves whose gradients must come out summed."""
        self.owned = jax.tree_util.tree_leaves(params)
        return params

    def owns(self, weights) -> bool:
        mine = {id(w) for w in self.owned}
        return all(id(w) in mine
                   for w in jax.tree_util.tree_leaves(weights))

    def sum_rest(self, grads):
        """The builder, after the backward: sum every gradient that
        the loss did not (:func:`sum_grads`), so each is summed once
        whatever the loss marked."""
        leaves, tree = jax.tree_util.tree_flatten(grads)
        return jax.tree_util.tree_unflatten(tree, [
            g if id(w) in self.claimed
            else exchange_sum(g, self.axis_name)
            for w, g in zip(self.owned, leaves)])


_grad_sums = contextvars.ContextVar("nbd_grad_sums", default=None)


@contextlib.contextmanager
def grad_sums(axis_name: str):
    """Opened by a step builder around the ``value_and_grad`` it traces
    inside a ``shard_map`` over ``axis_name`` with replicated weights.
    Yields the scope: the builder tells it the leaves it differentiates
    (``own``) and has it sum what the loss left unsummed
    (``sum_rest``)."""
    sums = _GradSums(axis_name)
    token = _grad_sums.set(sums)
    try:
        yield sums
    finally:
        _grad_sums.reset(token)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _grads_summed(ws, axis_name):
    return ws


def _grads_summed_bwd(axis_name, _, cts):
    sums = _grad_sums.get()
    out = []
    for g in cts:
        ex = sums.begun.pop(id(g), None) if sums is not None else None
        if ex is None or ex.g is not g or not ex.live():
            out.append(exchange_sum(g, axis_name))
        else:                           # the backward's last matmul
            out.append(ex.finish())
    return (tuple(out),)


_grads_summed.defvjp(lambda ws, axis_name: (ws, None), _grads_summed_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _held(x, w, at):
    return x, w


def _held_bwd(at, _, cts):
    """``x``'s cotangent waits for ``w``'s: left alone, the compiler
    runs the whole chain of activation gradients first and every
    weight gradient after it.  Then ``w``'s sends start here, and if
    this is a new point of the backward (``at``: matmuls that share
    their activation, as a gated MLP's two up-projections do, are one
    point) the exchanges begun before are finished here: their sends
    must be in before ``x``'s cotangent goes on.  That barrier gives
    the sends the backward between two points to run under: a
    ``-done`` that only a collective waits for the compiler parks at
    the end of the loop's body, and the ``-start`` just before it."""
    ct_x, g = lax.optimization_barrier(cts)
    sums = _grad_sums.get()
    if sums is None:
        return ct_x, g
    flying = [ex for ex in sums.flying if ex.out is None and ex.live()]
    if at != sums.at:
        sums.at = at
        ct_x, landed = lax.optimization_barrier(
            (ct_x, [ex.flying for ex in flying]))
        for ex, sends in zip(flying, landed):
            ex.flying = sends
            ex.finish()
        flying = []
    new = sums.begun[id(g)] = _Exchange(g, sums.axis_name)
    sums.flying = flying + [new]
    return ct_x, g


_held.defvjp(lambda x, w, at: ((x, w), None), _held_bwd)


def sum_grads(weights, of=None):
    """Identity on a tree of weights, called by a model where it first
    uses them.  Under a :func:`grad_sums` each floating leaf's
    cotangent is summed over its axis by :func:`exchange_sum`
    *at this point of the backward pass*: inside a layer scan that is
    the layer's own iteration of the backward loop, with the rest of
    the backward still to run beside the sends.  Anywhere else it
    returns ``weights`` and traces to nothing.

    ``of``: the parameters that ``weights`` are a scan's slice of
    (default: ``weights`` themselves).  They are how the step builder
    learns which gradients arrive summed: where they are not, leaf for
    leaf, what it differentiates (a loss that casts or transforms its
    parameters first), nothing is marked and the builder sums those
    gradients itself after the backward.  A loss that marks a
    parameter uses the marked copy wherever it uses the parameter."""
    sums = _grad_sums.get()
    if sums is None or not sums.owns(weights if of is None else of):
        return weights
    sums.claimed.update(id(w) for w in jax.tree_util.tree_leaves(
        weights if of is None else of))
    leaves, tree = jax.tree_util.tree_flatten(weights)
    floats = [i for i, w in enumerate(leaves)
              if jnp.issubdtype(w.dtype, jnp.inexact)]
    summed = _grads_summed(tuple(leaves[i] for i in floats),
                           sums.axis_name)
    for i, w in zip(floats, summed):
        leaves[i] = sums.marked[id(w)] = w
    return jax.tree_util.tree_unflatten(tree, leaves)


def hold_for_grad(x, w):
    """Identity on an activation and the weight it is about to meet in
    a matmul.  Under a :func:`grad_sums`, for a weight that
    :func:`sum_grads` marked and that is large enough to be sent, the
    matmul's place in the backward pass is where its gradient's sends
    start and the earlier ones' must be in (see ``_held_bwd``).
    Scheduling only: it changes no value, and a model may apply it to
    some matmuls and not others."""
    sums = _grad_sums.get()
    if (sums is None or sums.marked.get(id(w)) is not w
            or not _exchanges(w, sums.axis_name)):
        return x, w
    at, _ = sums.points.setdefault(id(x), (len(sums.points), x))
    return _held(x, w, at)
