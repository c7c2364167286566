"""Ring-overlapped collective matmuls: exact vs the monolithic
collective + matmul, differentiable, and structurally a ring (the
jaxpr carries exactly t-1 ppermutes per decomposed collective)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from nbdistributed_tpu.parallel import mesh as mesh_mod
from nbdistributed_tpu.parallel.overlap import (allgather_matmul,
                                                matmul_reducescatter,
                                                megatron_sp_block)

T = 4


@pytest.fixture(scope="module")
def mesh():
    return mesh_mod.make_mesh({"tp": T}, devices=jax.devices()[:T])


def test_allgather_matmul_exact(mesh):
    S, D, F = 16, 12, 24
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (S, D), jnp.float32)
    w = jax.random.normal(ks[1], (D, F), jnp.float32)

    got = jax.jit(jax.shard_map(
        lambda xs, ws: allgather_matmul(xs, ws, "tp"),
        mesh=mesh, in_specs=(P("tp", None), P(None, "tp")),
        out_specs=P(None, "tp")))(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               atol=1e-5, rtol=1e-5)


def test_matmul_reducescatter_exact(mesh):
    S, F, D = 16, 24, 12
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    h = jax.random.normal(ks[0], (S, F), jnp.float32)
    w = jax.random.normal(ks[1], (F, D), jnp.float32)

    got = jax.jit(jax.shard_map(
        lambda hs, ws: matmul_reducescatter(hs, ws, "tp"),
        mesh=mesh, in_specs=(P(None, "tp"), P("tp", None)),
        out_specs=P("tp", None)))(h, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(h @ w),
                               atol=1e-4, rtol=1e-4)


def test_megatron_sp_block_exact_and_grads(mesh):
    """Full SP->TP->SP MLP: forward exact vs the replicated block, and
    grads of a scalar loss match for every operand."""
    S, D, F = 16, 8, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (S, D), jnp.float32)
    wu = jax.random.normal(ks[1], (D, F), jnp.float32) / np.sqrt(D)
    wd = jax.random.normal(ks[2], (F, D), jnp.float32) / np.sqrt(F)

    def sharded(x, wu, wd):
        return jax.shard_map(
            lambda a, b, c: megatron_sp_block(a, b, c, "tp"),
            mesh=mesh,
            in_specs=(P("tp", None), P(None, "tp"), P("tp", None)),
            out_specs=P("tp", None))(x, wu, wd)

    ref = jax.nn.gelu(x @ wu) @ wd
    np.testing.assert_allclose(np.asarray(jax.jit(sharded)(x, wu, wd)),
                               np.asarray(ref), atol=1e-4, rtol=1e-4)

    loss_s = lambda *a: jnp.sum(sharded(*a) ** 2)
    loss_r = lambda x, wu, wd: jnp.sum((jax.nn.gelu(x @ wu) @ wd) ** 2)
    gs = jax.jit(jax.grad(loss_s, argnums=(0, 1, 2)))(x, wu, wd)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(x, wu, wd)
    for a, b, name in zip(gs, gr, ("x", "w_up", "w_down")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3, err_msg=name)


def test_ring_structure(mesh):
    """The decomposition is structural: each collective lowers to
    exactly t-1 ppermutes (not one all_gather / psum_scatter), which is
    what makes the overlap guaranteed dataflow rather than a scheduler
    choice."""
    S, D, F = 8, 4, 8
    x = jnp.ones((S, D))
    w = jnp.ones((D, F))
    jaxpr = str(jax.make_jaxpr(jax.shard_map(
        lambda xs, ws: allgather_matmul(xs, ws, "tp"),
        mesh=mesh, in_specs=(P("tp", None), P(None, "tp")),
        out_specs=P(None, "tp")))(x, w))
    assert jaxpr.count("ppermute") == T - 1, jaxpr
    assert "all_gather" not in jaxpr

    h = jnp.ones((S, F))
    wd = jnp.ones((F, D))
    jaxpr = str(jax.make_jaxpr(jax.shard_map(
        lambda hs, ws: matmul_reducescatter(hs, ws, "tp"),
        mesh=mesh, in_specs=(P(None, "tp"), P("tp", None)),
        out_specs=P("tp", None)))(h, wd))
    assert jaxpr.count("ppermute") == T - 1, jaxpr
    assert "psum_scatter" not in jaxpr


def test_reducescatter_rejects_indivisible(mesh):
    with pytest.raises(ValueError, match="not divisible"):
        jax.shard_map(
            lambda hs, ws: matmul_reducescatter(hs, ws, "tp"),
            mesh=mesh, in_specs=(P(None, "tp"), P("tp", None)),
            out_specs=P("tp", None))(jnp.ones((6, 8)), jnp.ones((8, 4)))
