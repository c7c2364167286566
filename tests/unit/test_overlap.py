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


# ----------------------------------------------------------------------
# the data-parallel gradient sum as asynchronous sends (ISSUE 36)

from nbdistributed_tpu.parallel import overlap  # noqa: E402


@pytest.fixture
def small_leaves_exchange(monkeypatch):
    """The size from which a leaf is sent is set for Mistral-7B's
    matrices; these tests' leaves are a few hundred kilobytes."""
    monkeypatch.setattr(overlap, "EXCHANGE_MIN_SIZE", 1 << 16)


def _sum_over(n, x):
    """``exchange_sum`` and float32 ``psum`` of ``x[i]`` over n shards."""
    m = mesh_mod.make_mesh({"dp": n}, devices=jax.devices()[:n])
    got, ref = jax.jit(jax.shard_map(
        lambda a: (overlap.exchange_sum(a[0], "dp")[None],
                   jax.lax.psum(a[0].astype(jnp.float32), "dp")[None]),
        mesh=m, in_specs=P("dp"), out_specs=P("dp"), check_vma=False))(x)
    return np.asarray(got.astype(jnp.float32)), np.asarray(ref)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("rows", ["divisible", "indivisible", "small"])
def test_exchange_sum_is_psum(n, dtype, rows, small_leaves_exchange):
    """The float32 sum of the shards' values, rounded once, the same
    bits on every shard; a leading dimension n does not divide and a
    leaf of a few kilobytes fall back to ``psum``."""
    shape = {"divisible": (8 * n, 3, 700), "indivisible": (8 * n + 1, 2100),
             "small": (8 * n, 16)}[rows]
    x = jax.random.normal(jax.random.PRNGKey(n), (n,) + shape,
                          jnp.float32).astype(dtype)
    got, ref = _sum_over(n, x)
    assert (got == got[0]).all()
    want = np.asarray(jnp.asarray(ref).astype(dtype).astype(jnp.float32))
    if dtype == jnp.bfloat16 and rows == "divisible":
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=2e-2 if dtype ==
                                   jnp.bfloat16 else 1e-6, atol=1e-5)


def test_exchange_sum_sends_only_what_it_must(small_leaves_exchange):
    """n−1 sends of 1/n of the leaf and one gather of the sums, none
    for a leaf that falls back."""
    n = 4
    m = mesh_mod.make_mesh({"dp": n}, devices=jax.devices()[:n])

    def ops(shape):
        f = jax.shard_map(lambda a: overlap.exchange_sum(a, "dp"), mesh=m,
                          in_specs=P(), out_specs=P(), check_vma=False)
        text = str(jax.make_jaxpr(f)(jnp.zeros(shape)))
        return (text.count("ppermute"), text.count("all_gather["),
                text.count("psum"))

    assert ops((8 * n, 4096)) == (n - 1, 1, 0)
    assert ops((8 * n + 1, 4096)) == (0, 0, 1)
    assert ops((64,)) == (0, 0, 1)


def _mlp_loss(p, b):
    h = jnp.tanh(b["x"] @ p["w1"])
    return jnp.mean((h @ p["w2"] - b["y"]) ** 2)


def _marked_mlp_loss(p, b):
    p = overlap.sum_grads(p)
    x, w1 = overlap.hold_for_grad(b["x"], p["w1"])
    h, w2 = overlap.hold_for_grad(jnp.tanh(x @ w1), p["w2"])
    return jnp.mean((h @ w2 - b["y"]) ** 2)


def _partly_marked_mlp_loss(p, b):
    w1 = overlap.sum_grads(p["w1"])
    x, w1 = overlap.hold_for_grad(b["x"], w1)
    return jnp.mean((jnp.tanh(x @ w1) @ p["w2"] - b["y"]) ** 2)


def _marked_copies_mlp_loss(p, b):
    # not the leaves the step differentiates: nothing is marked
    return _marked_mlp_loss(jax.tree.map(lambda w: w * 1.0, p), b)


@pytest.mark.parametrize("loss", [_mlp_loss, _marked_mlp_loss,
                                  _partly_marked_mlp_loss,
                                  _marked_copies_mlp_loss],
                         ids=["unmarked", "marked", "partly_marked",
                              "marked_copies"])
def test_ddp_step_sums_any_loss(loss, small_leaves_exchange):
    """Every gradient is summed once, whatever the loss marks: the
    weights it marks inside its backward, the rest (all of them where
    it marks none, or marks copies of its parameters) by the step
    after it.  Each is the single-device step on the global batch."""
    import optax
    from nbdistributed_tpu.parallel import data_parallel
    n = 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {"w1": jax.random.normal(ks[0], (256, 512)) / 16,
              "w2": jax.random.normal(ks[1], (512, 256)) / 22}
    batch = {"x": jax.random.normal(ks[2], (8, 256)),
             "y": jax.random.normal(ks[3], (8, 256))}
    opt = optax.adamw(1e-2)
    l1, g = jax.value_and_grad(_mlp_loss)(params, batch)
    u, s1 = opt.update(g, opt.init(params), params)
    p1 = optax.apply_updates(params, u)

    m = mesh_mod.make_mesh({"dp": n}, devices=jax.devices()[:n])
    step = data_parallel.make_ddp_step(loss, opt, m, donate=False)
    pr, sr = data_parallel.ddp_init(params, opt.init(params), m)
    br = mesh_mod.shard_batch(batch, m)
    p2, s2, l2 = step(pr, sr, br)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-6)
    # (AdamW's first update is g / |g|: float32 reassociation shows)
    for a, b in zip(jax.tree.leaves((p1, s1)), jax.tree.leaves((p2, s2))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=1e-5)
    counts = data_parallel.collectives_of(step.lower(pr, sr, br).compile())
    assert counts["async_sends"] == 2 * (n - 1)
    assert counts["blocking_all_reduce_bytes"] <= 64
    assert counts["blocking_all_gather_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(params))


def test_marks_outside_a_step_trace_to_nothing(small_leaves_exchange):
    """``sum_grads`` / ``hold_for_grad`` engage only under a step
    builder's ``grad_sums``: a user's own ``shard_map`` over ``dp``
    that sums its gradients itself is not summed twice."""
    n = 4
    m = mesh_mod.make_mesh({"dp": n}, devices=jax.devices()[:n])
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    params = {"w1": jax.random.normal(ks[0], (256, 512)) / 16,
              "w2": jax.random.normal(ks[1], (512, 256)) / 22}
    batch = {"x": jax.random.normal(ks[2], (8, 256)),
             "y": jax.random.normal(ks[3], (8, 256))}
    plain = jax.make_jaxpr(jax.grad(_mlp_loss))(params, batch)
    marked = jax.make_jaxpr(jax.grad(_marked_mlp_loss))(params, batch)
    assert str(marked) == str(plain)

    def by_hand(p, b):
        g = jax.grad(_marked_mlp_loss)(p, b)
        return jax.tree.map(lambda x: jax.lax.pmean(x, "dp"), g)

    got = jax.jit(jax.shard_map(by_hand, mesh=m, in_specs=(P(), P("dp")),
                                out_specs=P(), check_vma=False))(
        params, batch)
    want = jax.grad(_mlp_loss)(params, batch)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-5)


def test_ddp_loss_is_the_mean_of_the_shards_losses():
    """What ``make_ddp_step`` computes over several shards: the mean of
    the shards' losses, each over its own rows, and that mean's
    gradient.  For a loss that is an equal-weight mean over rows that
    is the loss of the global batch; for one that is not it is another
    number: packed rows, whose loss divides by the targets a shard
    keeps, not by those the whole batch keeps."""
    import optax
    from nbdistributed_tpu.models import init_params, loss_fn, tiny_config
    from nbdistributed_tpu.parallel import data_parallel
    n, S = 4, 32
    cfg = tiny_config(dtype=jnp.float32, use_flash=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (n, S), 0,
                                cfg.vocab_size)
    # documents of 32, 8, 4 and 2 tokens: 31, 28, 24 and 16 targets kept
    seg = jnp.stack([jnp.arange(S) // k for k in (32, 8, 4, 2)])
    batch = {"tokens": tokens, "segments": seg.astype(jnp.int32)}
    loss = lambda p, b: loss_fn(p, b, cfg)
    rows = [jax.tree.map(lambda x: x[i:i + 1], batch) for i in range(n)]

    def mean_of_shards(p):
        return sum(loss(p, r) for r in rows) / n

    opt = optax.sgd(1e-1)
    l1, g = jax.value_and_grad(mean_of_shards)(params)
    p1 = optax.apply_updates(params, opt.update(g, opt.init(params))[0])
    assert abs(float(loss(params, batch)) - float(l1)) > 1e-4

    m = mesh_mod.make_mesh({"dp": n}, devices=jax.devices()[:n])
    step = data_parallel.make_ddp_step(loss, opt, m, donate=False)
    pr, sr = data_parallel.ddp_init(params, opt.init(params), m)
    p2, _, l2 = step(pr, sr, mesh_mod.shard_batch(batch, m))
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-5)
