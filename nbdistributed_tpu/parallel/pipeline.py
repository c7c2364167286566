"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

The reference has no pipeline parallelism (SURVEY §2.3: "Absent"; its
users could only hand-roll stages with ``%%rank`` groups and point-to-
point sends).  This module is the TPU-idiomatic version: stages are a
*mesh axis*, not processes — stage parameters live sharded over the
``pp`` axis, the whole schedule is one XLA program under ``shard_map``,
and activations hop stage-to-stage with ``lax.ppermute`` over ICI.  The
schedule is a ``lax.scan`` (compiler-friendly control flow: one trace,
no Python loop over steps), so compile time is O(1) in the number of
microbatches.

Semantics: ``stage_fn`` is applied ``n_stages`` times in sequence, so

    pipeline_forward(f, params, x, ...) ==  f(p[S-1], ... f(p[0], x))

(the unit tests assert equality with the sequential loop to float
tolerance — reduction order differs, so bitwise identity is not
guaranteed).  The usual GPipe bubble applies: utilisation is
``n_micro / (n_micro + n_stages - 1)`` — raise ``n_microbatches`` to
amortise it.  Differentiable end-to-end: ``ppermute``'s transpose is the
reverse permute, so ``jax.grad`` through a pipelined loss just works.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P



def shard_stage_params(stage_params, mesh, axis: str = "pp"):
    """Place stage-stacked parameters (every leaf carries a leading
    ``n_stages`` axis) so each pipeline stage holds only its own slice."""
    sharding = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, sharding), stage_params)


def pipeline_forward(stage_fn, stage_params, x, mesh, *, axis: str = "pp",
                     n_microbatches: int | None = None):
    """Run ``x`` through ``n_stages`` sequential applications of
    ``stage_fn``, pipelined over the ``axis`` mesh axis.

    Args:
      stage_fn: ``(params_one_stage, activation) -> activation`` with the
        activation shape preserved (homogeneous stages, e.g. transformer
        blocks).
      stage_params: pytree whose leaves have leading dim ``n_stages``,
        sharded over ``axis`` (see :func:`shard_stage_params`).
      x: the global batch, leading dim divisible by ``n_microbatches``.
      n_microbatches: defaults to ``n_stages``.  More microbatches →
        smaller pipeline bubble.

    Returns the output batch, replicated over ``axis``.
    """
    n_stages = mesh.shape[axis]
    n_micro = n_microbatches if n_microbatches is not None else n_stages
    batch = x.shape[0]
    if batch % n_micro:
        raise ValueError(
            f"batch {batch} not divisible by {n_micro} microbatches")
    xs = x.reshape(n_micro, batch // n_micro, *x.shape[1:])
    n_steps = n_micro + n_stages - 1
    multi_stage = n_stages > 1

    def spmd(params, xs):
        stage = jax.lax.axis_index(axis)
        # shard_map leaves a length-1 stage axis on local shards.
        local = jax.tree_util.tree_map(lambda a: a[0], params)

        def step(recv, t):
            # Stage 0 consumes the next microbatch while it exists (the
            # clamp only feeds don't-care work into drain steps whose
            # outputs are never collected); other stages consume what
            # the previous stage sent last step.
            x_in = jnp.where(stage == 0,
                             xs[jnp.minimum(t, n_micro - 1)], recv)
            y = stage_fn(local, x_in)
            out = jnp.where(stage == n_stages - 1, y, jnp.zeros_like(y))
            if multi_stage:
                recv = jax.lax.ppermute(
                    y, axis,
                    [(i, i + 1) for i in range(n_stages - 1)])
            return recv, out

        _, outs = jax.lax.scan(step, jnp.zeros_like(xs[0]),
                               jnp.arange(n_steps))
        # Only the last stage produced real outputs; sum-replicate them
        # so every stage returns the full result.
        return jax.lax.psum(outs, axis)

    outs = jax.shard_map(
        spmd, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False)(stage_params, xs)
    # Microbatch m exits the last stage at step m + n_stages - 1.
    return outs[n_stages - 1:].reshape(batch, *x.shape[1:])


def make_pipeline_1f1b(stage_fn, loss_tail, mesh, *, axis: str = "pp",
                       n_microbatches: int | None = None,
                       batch_axis: str | None = None):
    """One-forward-one-backward (1F1B / PipeDream-flush) training
    schedule: a jitted ``(stage_params, x, batch) -> (loss, grads)``.

    GPipe via autodiff (``jax.grad`` of :func:`pipeline_forward`) runs
    all M forward microbatches, then replays all M backwards — every
    stage must hold M microbatches of residuals, so activation memory
    grows with the microbatch count that was supposed to shrink the
    bubble.  1F1B interleaves: each scan tick does one forward sub-step
    (activations ``ppermute`` up) and one backward sub-step (cotangents
    ``ppermute`` down), with stage ``s`` forwarding microbatch
    ``t - s`` and backwarding microbatch ``t - 2(S-1) + s``.  A saved
    input lives exactly ``2(S-1-s)`` ticks, so the in-flight buffer is
    ``2S - 1`` microbatch inputs regardless of M — **activation memory
    O(S) instead of O(M)**, which is the schedule's point.  The bubble
    fraction itself matches GPipe's flush (``(S-1)`` idle ticks at each
    end: ``2(S-1) / (M + 2(S-1))`` of the combined fwd+bwd timeline) —
    non-interleaved 1F1B trades no compute, only memory.

    Backward sub-steps recompute the stage forward from the saved
    *input* (`jax.vjp` at use-time) rather than storing VJP residuals —
    per-stage activation checkpointing, the standard pairing with 1F1B.

    Honest accounting for THIS (dense-SPMD scan) realization: every
    tick computes both sub-steps on every device — masked warmup/drain
    work is not free the way it is in a sparse per-device runtime — so
    the scan runs ``M + 2(S-1)`` full-work ticks where
    autodiff-GPipe-with-remat replays ``~M + S - 1``: 1F1B here costs
    ``O(S)`` extra chunk-units in exchange for the O(S)-vs-O(M)
    activation memory, the right trade exactly when M >> S (the regime
    where microbatching pays at all).  The same arithmetic is why the
    *interleaved* (virtual-chunk) 1F1B variant is deliberately absent:
    its bubble win exists only when idle ticks cost nothing, but an
    SPMD scan must execute every (device, tick) slot — with V virtual
    chunks the dense schedule runs ``M + 2(VS-1)`` ticks of unreduced
    per-tick work, strictly worse.  A sparse interleaved schedule
    needs per-device program divergence that shard_map's single traced
    program cannot express.

    Contract: ``loss_tail(y_micro, batch_micro) -> scalar`` must be a
    per-microbatch loss whose full-batch value is the mean over
    microbatches (true for mean-reduced losses over equal microbatch
    sizes); ``batch`` is any pytree with leading batch dim.  Gradients
    match ``jax.grad`` of the sequential/GPipe loss to float tolerance.
    """
    full = make_pipeline_1f1b_full(
        stage_fn, lambda tp, y, b: loss_tail(y, b), mesh, axis=axis,
        n_microbatches=n_microbatches, batch_axis=batch_axis)

    def plain_loss_and_grads(stage_params, x, batch):
        # `full` is already jit-wrapped; a second jax.jit here would
        # only add a trace layer and a duplicate cache entry.
        loss, stage_grads, _tail, _dx = full({}, stage_params, x,
                                             batch)
        return loss, stage_grads

    return plain_loss_and_grads


def make_pipeline_1f1b_full(stage_fn, tail_fn, mesh, *,
                            axis: str = "pp",
                            n_microbatches: int | None = None,
                            dx_sink=None, dx_init=None,
                            batch_axis: str | None = None):
    """The general 1F1B machinery: gradients for the loss tail's own
    parameters and for the pipeline *input*, on top of the stage
    gradients — what a full model (embedding below the pipelined
    region, norm + head + loss above it) needs to train end-to-end
    under the schedule.

    ``tail_fn(tail_params, y_micro, batch_micro) -> scalar`` is the
    per-microbatch loss head; its parameter gradients accumulate on
    the last stage and are psum-replicated.  ``dx_sink(acc, dx_micro,
    batch_micro) -> acc`` (with ``dx_init()`` building the initial
    accumulator) folds each microbatch's input-cotangent as it exits
    stage 0's backward — e.g. an embedding scatter-add — so no O(M)
    dx buffer ever exists; omit both to skip input gradients.

    Returns a jitted ``(tail_params, stage_params, x, batch) ->
    (loss, stage_grads, tail_grads, dx_acc)`` (``dx_acc`` is None
    without a sink).  Schedule, memory bound, and cost accounting: see
    :func:`make_pipeline_1f1b`, which is this with an empty tail.

    ``batch_axis``: a ``dp`` mesh axis the microbatch *rows* are
    sharded over (DP × PP): each dp group pipelines its own batch
    shard, and loss/stage/tail/dx gradients are mean-reduced across
    the groups — the per-shard-mean of a mean-reduced loss equals the
    global mean at equal shard sizes, exactly the DDP convention.
    """
    n_stages = mesh.shape[axis]
    n_micro_default = n_microbatches
    if (dx_sink is None) != (dx_init is None):
        raise ValueError("pass both dx_sink and dx_init, or neither")

    @jax.jit
    def loss_and_grads(tail_params, stage_params, x, batch):
        S = n_stages
        M = n_micro_default if n_micro_default is not None else S
        B = x.shape[0]
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} "
                             f"microbatches")
        if batch_axis is not None:
            d = mesh.shape[batch_axis]
            if (B // M) % d:
                raise ValueError(
                    f"per-microbatch rows {B // M} (batch {B} / "
                    f"{M} microbatches) not divisible by "
                    f"{batch_axis}={d} — shard_map would fail with an "
                    f"opaque sharding error")
        xs = x.reshape(M, B // M, *x.shape[1:])
        bt = jax.tree_util.tree_map(
            lambda a: a.reshape(M, B // M, *a.shape[1:]), batch)
        T = M + 2 * (S - 1)
        A = 2 * S - 1  # in-flight saved inputs: O(S), NOT O(M)
        multi = S > 1

        def spmd(tp, params, xs, bt):
            stage = jax.lax.axis_index(axis)
            local = jax.tree_util.tree_map(lambda a: a[0], params)
            g0 = jax.tree_util.tree_map(jnp.zeros_like, local)
            tg0 = jax.tree_util.tree_map(jnp.zeros_like, tp)
            dx0 = dx_init() if dx_init is not None else jnp.float32(0.0)

            def tick(carry, t):
                f_recv, b_recv, buf, grads, tg, dxa, loss_acc = carry
                # ---- forward sub-step: stage s runs microbatch t-s.
                m_f = t - stage
                act_f = (m_f >= 0) & (m_f < M)
                x_in = jnp.where(stage == 0,
                                 xs[jnp.clip(m_f, 0, M - 1)], f_recv)
                y = stage_fn(local, x_in)
                if multi:
                    f_recv = jax.lax.ppermute(
                        y, axis,
                        [(i, i + 1) for i in range(S - 1)])
                # Save this tick's input for its backward, 2(S-1-s)
                # ticks later; slot reuse is safe because lifetimes
                # never exceed A ticks.
                buf = buf.at[t % A].set(
                    jnp.where(act_f, x_in, buf[t % A]))

                # ---- backward sub-step: stage s re-derives microbatch
                # t - 2(S-1) + s from its saved input (recompute VJP).
                m_b = t - 2 * (S - 1) + stage
                act_b = (m_b >= 0) & (m_b < M)
                slot = (t - 2 * (S - 1) + 2 * stage) % A
                x_sav = buf[slot]
                y_b, vjp = jax.vjp(stage_fn, local, x_sav)
                # Last stage seeds the cotangent from the loss head on
                # its recomputed output; earlier stages use what the
                # next stage sent down.
                mb_idx = jnp.clip(m_b, 0, M - 1)
                bt_m = jax.tree_util.tree_map(lambda a: a[mb_idx], bt)
                loss_m, lt_vjp = jax.vjp(
                    lambda tp_, y_: tail_fn(tp_, y_, bt_m), tp, y_b)
                dtp, cot_seed = lt_vjp(jnp.float32(1.0) / M)
                last_b = act_b & (stage == S - 1)
                tg = jax.tree_util.tree_map(
                    lambda g, d: g + jnp.where(last_b, d, 0), tg, dtp)
                cot = jnp.where(stage == S - 1, cot_seed, b_recv)
                dp, dx = vjp(cot.astype(y_b.dtype))
                grads = jax.tree_util.tree_map(
                    lambda g, d: g + jnp.where(act_b, d, 0), grads, dp)
                if dx_sink is not None:
                    # Fold stage 0's input-cotangent immediately (other
                    # stages / inactive ticks fold zeros — a no-op), so
                    # the input gradient never needs an O(M) buffer.
                    dxa = dx_sink(
                        dxa, jnp.where(act_b & (stage == 0), dx, 0),
                        bt_m)
                loss_acc = loss_acc + jnp.where(last_b, loss_m / M, 0.0)
                if multi:
                    b_recv = jax.lax.ppermute(
                        dx, axis,
                        [(i, i - 1) for i in range(1, S)])
                return (f_recv, b_recv, buf, grads, tg, dxa,
                        loss_acc), None

            buf0 = jnp.zeros((A,) + xs.shape[1:], xs.dtype)
            (_, _, _, grads, tg, dxa, loss_acc), _ = jax.lax.scan(
                tick, (jnp.zeros_like(xs[0]), jnp.zeros_like(xs[0]),
                       buf0, g0, tg0, dx0, jnp.float32(0.0)),
                jnp.arange(T))
            # Loss and tail grads live on the last stage, the dx
            # accumulator on stage 0; psum replicates each (all other
            # stages contributed zeros).  Stage grads are each stage's
            # own slice (restacked via the pp out_spec).
            loss = jax.lax.psum(loss_acc, axis)
            tg = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, axis), tg)
            dxa = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, axis), dxa)
            if batch_axis is not None:
                # DP x PP: every dp group pipelined its own batch
                # shard; mean-reduce everything across the groups
                # (equal shard sizes -> the global-batch mean).
                loss = jax.lax.pmean(loss, batch_axis)
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, batch_axis), grads)
                tg = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, batch_axis), tg)
                dxa = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, batch_axis), dxa)
            grads = jax.tree_util.tree_map(lambda g: g[None], grads)
            return loss, grads, tg, dxa

        # Microbatch ROWS (axis 1 of the (M, mb, ...) reshape) carry
        # the dp sharding when batch_axis is set.
        data_spec = (P(None, batch_axis) if batch_axis is not None
                     else P())
        loss, stage_grads, tail_grads, dxa = jax.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), P(axis), data_spec, data_spec),
            out_specs=(P(), P(axis), P(), P()), check_vma=False)(
            tail_params, stage_params, xs, bt)
        return (loss, stage_grads, tail_grads,
                dxa if dx_sink is not None else None)

    return loss_and_grads



def make_pipeline_loss(stage_fn, loss_tail, mesh, *, axis: str = "pp",
                       n_microbatches: int | None = None,
                       remat: bool = False):
    """Compose a pipelined forward with a loss head.

    ``loss_tail(final_activation, batch) -> scalar``.  The returned
    ``loss(stage_params, x, batch)`` differentiates end-to-end (the
    backward pass pipelines in reverse through the transposed
    ppermutes).  ``remat=True`` checkpoints each stage application, so
    the GPipe backward stores M microbatch *inputs* per stage instead
    of M sets of stage-internal residuals — the intermediate memory
    point between plain GPipe (O(M·residuals)) and
    :func:`make_pipeline_1f1b` (O(S·inputs)).
    """
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    @jax.jit
    def loss(stage_params, x, batch):
        y = pipeline_forward(fn, stage_params, x, mesh, axis=axis,
                             n_microbatches=n_microbatches)
        return loss_tail(y, batch)

    return loss
