"""The flash kernel inside a multi-device program.

GSPMD cannot partition a Mosaic kernel — on the chip a raw
``pallas_call`` in a multi-device jit is refused at lowering — so
``models/transformer._flash_on_mesh`` wraps the kernel in a shard_map
over the active mesh and the mesh step builders trace under theirs.
Interpret mode (this CPU run) would partition the kernel happily, so
these tests pin the structure (a manual computation around the kernel)
and the numbers, not the refusal itself."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from nbdistributed_tpu.models import (init_params, loss_fn, make_train_step,
                                      tiny_config)
from nbdistributed_tpu.models.transformer import _flash_on_mesh
from nbdistributed_tpu.ops import flash_attention
from nbdistributed_tpu.parallel import data_parallel, mesh as mesh_mod

pytestmark = pytest.mark.unit


def _qkv(B=4, S=64, H=4, Hkv=2, D=16):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(ks[0], (B, S, H, D)),
            jax.random.normal(ks[1], (B, S, Hkv, D)),
            jax.random.normal(ks[2], (B, S, Hkv, D)))


def _manual_regions(fn, *args) -> int:
    return jax.jit(fn).lower(*args).as_text().count("manual_computation")


@pytest.mark.parametrize("axes", [{"dp": 4}, {"dp": 2, "tp": 2},
                                  {"x": 2}, {"dp": 8}])
def test_wraps_under_an_active_mesh_and_matches(axes):
    """dp carries the batch and tp whole GQA groups where they divide
    (dp=8 does not divide B=4; "x" is no known axis): every choice is
    the same attention."""
    q, k, v = _qkv()
    seg = jnp.repeat(jnp.arange(4), 16)[None].repeat(4, 0)
    want = flash_attention(q, k, v, True, None, None, None, 32, seg)
    n = int(np.prod(list(axes.values())))
    mesh = mesh_mod.make_mesh(axes, devices=jax.devices()[:n])

    def fn(q, k, v, seg):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return _flash_on_mesh(q, k, v, 32, seg)

    sh = NamedSharding(mesh, P())
    args = jax.device_put((q, k, v, seg), sh)
    assert _manual_regions(fn, *args) == 1
    np.testing.assert_allclose(np.asarray(jax.jit(fn)(*args)),
                               np.asarray(want), atol=1e-5, rtol=1e-5)


def test_plain_call_without_a_mesh_or_inside_a_manual_one():
    q, k, v = _qkv()
    assert _manual_regions(
        lambda q, k, v: _flash_on_mesh(q, k, v, None, None), q, k, v) == 0
    # Inside a shard_map over every axis the shards are already local:
    # one manual region (the enclosing one), no nested wrap.
    mesh = mesh_mod.make_mesh({"dp": 4}, devices=jax.devices()[:4])
    outer = jax.shard_map(
        lambda q, k, v: _flash_on_mesh(q, k, v, None, None), mesh=mesh,
        in_specs=P("dp"), out_specs=P("dp"), check_vma=False)
    assert _manual_regions(outer, q, k, v) == 1
    np.testing.assert_allclose(
        np.asarray(jax.jit(outer)(q, k, v)),
        np.asarray(flash_attention(q, k, v, True, None, None, None)),
        atol=1e-5, rtol=1e-5)


def test_ddp_step_with_flash_is_the_single_device_step():
    """The README's DDP recipe with ``use_flash=True``: the plain
    ``loss_fn(p, b, cfg)`` through ``make_ddp_step``: loss, updated
    weights and optimizer state."""
    cfg = tiny_config(dtype=jnp.float32, use_flash=True, n_layers=1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-3)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                cfg.vocab_size)
    p1, s1, l1 = jax.jit(make_train_step(cfg, opt))(
        params, opt.init(params), {"tokens": tokens})

    mesh = mesh_mod.make_mesh({"dp": 4}, devices=jax.devices()[:4])
    pr, _ = data_parallel.ddp_init(params, (), mesh)
    st = opt.init(pr)
    batch = mesh_mod.shard_batch({"tokens": tokens}, mesh)
    step = data_parallel.make_ddp_step(
        lambda p, b: loss_fn(p, b, cfg), opt, mesh, donate=False)
    # Since ISSUE 36 the step differentiates inside ONE shard_map over
    # dp (the gradients' sends need the axis manual), and the forward,
    # dq and dk/dv kernels run local in it, as _flash_on_mesh documents
    # for an axis an enclosing shard_map made manual; before, each rode
    # a shard_map of its own (three manual computations).
    text = step.lower(pr, st, batch).as_text()
    assert text.count("manual_computation") == 1
    p2, s2, l2 = step(pr, st, batch)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree.leaves((p1, s1)), jax.tree.leaves((p2, s2))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-4)


def test_ddp_step_with_flash_sends_inside_the_backward_scan(monkeypatch):
    """At widths whose matrices are sent (all but ``wk`` and ``wv``,
    which stay under the size a send is worth): the kernels still run
    local in the step's one manual region, beside the sends, and the
    step is the single-device step."""
    from nbdistributed_tpu.parallel import overlap
    monkeypatch.setattr(overlap, "EXCHANGE_MIN_SIZE", 1 << 16)
    cfg = tiny_config(dtype=jnp.float32, use_flash=True, n_layers=2,
                      d_model=256, d_ff=512, n_heads=4, n_kv_heads=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.sgd(1e-2, momentum=0.9)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                cfg.vocab_size)
    p1, s1, l1 = jax.jit(make_train_step(cfg, opt))(
        params, opt.init(params), {"tokens": tokens})
    mesh = mesh_mod.make_mesh({"dp": 4}, devices=jax.devices()[:4])
    pr, _ = data_parallel.ddp_init(params, (), mesh)
    st = opt.init(pr)
    batch = mesh_mod.shard_batch({"tokens": tokens}, mesh)
    step = data_parallel.make_ddp_step(
        lambda p, b: loss_fn(p, b, cfg), opt, mesh, donate=False)
    text = step.lower(pr, st, batch).as_text()
    assert text.count("manual_computation") == 1
    assert text.count("stablehlo.collective_permute") == 3 * (2 + 5)
    p2, s2, l2 = step(pr, st, batch)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree.leaves((p1, s1)), jax.tree.leaves((p2, s2))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)
