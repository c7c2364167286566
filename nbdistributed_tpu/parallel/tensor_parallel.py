"""Tensor parallelism: sharding-rule application and a combined-mesh
train-step builder.

The reference's TP story is "users broadcast params and type the
all_reduce themselves" (README.md:115-125).  Here TP is declarative:
parameter pytrees carry ``PartitionSpec`` rules (e.g.
``models.transformer.param_shardings``), this module places them on the
mesh, and XLA compiles the Megatron pattern (column-parallel matmul →
row-parallel matmul → one all-reduce) from the sharding lattice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import overlap


def apply_shardings(tree, mesh, rules):
    """Place ``tree`` on ``mesh`` according to a matching pytree of
    ``PartitionSpec`` rules."""
    return jax.tree_util.tree_map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        tree, rules,
        is_leaf=lambda x: isinstance(x, P))


def sharding_tree(mesh, rules):
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), rules,
        is_leaf=lambda x: isinstance(x, P))


def make_tp_train_step(loss_fn, optimizer, mesh, param_rules, *,
                       dp_axis: str = "dp", donate: bool = True,
                       opt_state_sh=None, accum_steps: int = 1,
                       accum_rules=None, guard: bool = False):
    """Combined dp×tp train step: params sharded by ``param_rules``
    (tp axes; ``None`` = fully replicated, i.e. pure DDP), batch sharded
    on ``dp_axis``.

    Optimizer-state sharding: with ``opt_state_sh=None`` the state
    passes through (optax states are zeros_like the params, so
    initializing from already-sharded params gives param-sharded state
    for free); passing an explicit ``NamedSharding`` pytree pins it —
    :mod:`~nbdistributed_tpu.parallel.zero` uses this to add the ZeRO-1
    dp axis, with this one step definition serving both.

    ``accum_steps > 1`` splits the batch's leading axis into that many
    microbatches inside the compiled step (``lax.scan``, fp32 gradient
    accumulator) — same numerics as the full batch for mean losses,
    activation memory divided by ``accum_steps``.

    What "mean losses" asks: the step's loss is the mean over equal
    pieces of the batch, each piece's loss computed alone — the
    microbatches here; the ``dp`` shards in the pure DDP step over
    several of them (``exchanged_grads_of`` below).  That is the
    global batch's loss for an equal-weight mean over rows, and not
    for a loss that weighs rows unequally (packed rows), sums, or
    takes statistics over the batch.

    ``accum_rules``: optional pytree of ``PartitionSpec`` for the fp32
    accumulator (ZeRO-2; see :mod:`~nbdistributed_tpu.parallel.zero`).
    Without accumulation, gradients are transient inside the fused
    step and XLA already consumes them reduce-scattered when the
    optimizer state is ZeRO-sharded — the accumulator is the one
    place a *persistent* full-size gradient buffer exists, so it is
    the one place ZeRO-2 sharding buys memory (4 bytes/param/replica
    → /dp).

    ``guard=True`` (ISSUE 19) fuses a device-side integrity check into
    the step: the fp32 global grad-norm² (one extra reduction riding
    the same compiled program — no extra host sync) gates the update,
    so a non-finite gradient *skips* it and params/opt state come back
    bitwise unchanged.  The step then returns a 4-tuple
    ``(params, opt_state, loss, aux)`` with replicated device scalars
    ``aux = {"v": float32[3]}`` — the ``v`` lane packs ``[ok, loss,
    gnorm]`` for a single-transfer host resolve — that the host-side
    :class:`~nbdistributed_tpu.resilience.trainguard.TrainGuard`
    resolves one step late — the skip decision itself never leaves
    the device."""
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    repl = NamedSharding(mesh, P())
    param_sh = sharding_tree(mesh, param_rules) if param_rules is not None \
        else repl
    batch_sh = NamedSharding(mesh, P(dp_axis))

    d = mesh.shape[dp_axis]

    def exchanged_grads_of(params, batch):
        """Pure DDP (ISSUE 36): each shard differentiates its own rows
        with ``dp_axis`` manual, and the gradients are summed by
        ``overlap.exchange_sum`` (the reduce half of a large leaf as
        asynchronous sends; GSPMD's all-reduce stops the chip for as
        long as the links need): those the loss marks with
        ``overlap.sum_grads`` as the backward makes each one, the rest
        here, after the backward.  The loss is the mean of the shards'
        losses, each over its own rows: the global batch's loss where
        that is an equal-weight mean over rows, not otherwise (packed
        rows divide by the targets a shard keeps)."""
        def local(params, batch):
            with overlap.grad_sums(dp_axis) as sums:
                loss, grads = jax.value_and_grad(
                    lambda p: loss_fn(sums.own(p), batch) / d)(params)
                grads = sums.sum_rest(grads)
            return jax.lax.psum(loss, dp_axis), grads

        return jax.shard_map(
            local, mesh=mesh, in_specs=(P(), P(dp_axis)),
            out_specs=(P(), P()), axis_names={dp_axis},
            check_vma=False)(params, batch)

    def grads_of(params, batch):
        if (accum_steps == 1 and param_rules is None
                and opt_state_sh is None and d > 1):
            return exchanged_grads_of(params, batch)
        if accum_steps == 1:
            return jax.value_and_grad(loss_fn)(params, batch)

        def split(x):
            B = x.shape[0]
            if B % (d * accum_steps):
                raise ValueError(
                    f"batch leading dim {B} not divisible by "
                    f"dp({d}) * accum_steps({accum_steps})")
            # Microbatch i = the i-th contiguous chunk of every
            # device's local shard, so the split is a device-local
            # reshape (a naive (accum, B/accum) reshape would need an
            # all-to-all to re-lay the dp shards every step).  Mean
            # losses are permutation-invariant, so numerics match the
            # full batch.
            mb = (x.reshape(d, accum_steps, B // (d * accum_steps),
                            *x.shape[1:])
                  .swapaxes(0, 1)
                  .reshape(accum_steps, B // accum_steps, *x.shape[1:]))
            return jax.lax.with_sharding_constraint(
                mb, NamedSharding(
                    mesh, P(None, dp_axis, *[None] * (x.ndim - 1))))

        micro = jax.tree_util.tree_map(split, batch)

        def pin_accum(t):
            if accum_rules is None:
                return t
            return jax.tree_util.tree_map(
                lambda a, r: jax.lax.with_sharding_constraint(
                    a, NamedSharding(mesh, r)),
                t, accum_rules, is_leaf=lambda x: isinstance(x, P))

        def body(carry, mb):
            gsum, lsum = carry
            l, g = jax.value_and_grad(loss_fn)(params, mb)
            gsum = pin_accum(jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), gsum, g))
            return (gsum, lsum + l), None

        zeros = pin_accum(jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params))
        (gsum, lsum), _ = jax.lax.scan(body, (zeros, jnp.float32(0.0)),
                                       micro)
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / accum_steps).astype(p.dtype), gsum, params)
        return lsum / accum_steps, grads

    def step(params, opt_state, batch):
        loss, grads = grads_of(params, batch)
        if not guard:
            updates, new_state = optimizer.update(grads, opt_state,
                                                  params)
            return optax.apply_updates(params, updates), new_state, loss
        # Fused finite check: the fp32 sum of squares over every grad
        # leaf is non-finite iff any leaf holds a NaN/inf (NaN
        # propagates through the sum; inf² = inf), and doubles as the
        # global grad-norm² — one reduction, computed inside the same
        # program, where the dp all-reduce already paid for the
        # gradients.  The optimizer update runs inside a scalar-pred
        # ``lax.cond``: the skip branch passes the OLD buffers through
        # bitwise intact, and the healthy branch pays no extra select
        # pass over params/opt state (a per-leaf ``where`` gate costs
        # ~20% of a CPU step in pure memory traffic).
        gn_sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree_util.tree_leaves(grads))
        ok = jnp.isfinite(gn_sq) & jnp.isfinite(loss)

        def do_update(_):
            updates, new_state = optimizer.update(grads, opt_state,
                                                  params)
            return optax.apply_updates(params, updates), new_state

        def skip_update(_):
            return params, opt_state

        out_params, out_state = jax.lax.cond(ok, do_update, skip_update,
                                             None)
        # Packed verdict [ok, loss, gnorm] as the ONLY aux output:
        # the host resolves a whole step with one 12-byte transfer,
        # and the jit call materializes one extra array per step
        # instead of three.
        aux = {"v": jnp.stack([ok.astype(jnp.float32),
                               loss.astype(jnp.float32),
                               jnp.sqrt(gn_sq)])}
        return out_params, out_state, loss, aux

    def step_on_mesh(params, opt_state, batch):
        # Traced with the mesh active: code GSPMD cannot partition (a
        # Mosaic kernel — models/transformer._flash_on_mesh) reads it
        # there and wraps itself in a shard_map.
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return step(params, opt_state, batch)

    out_sh = ((param_sh, opt_state_sh, repl, repl) if guard
              else (param_sh, opt_state_sh, repl))
    return jax.jit(
        step_on_mesh,
        in_shardings=(param_sh, opt_state_sh, batch_sh),
        out_shardings=out_sh,
        donate_argnums=(0, 1) if donate else ())
