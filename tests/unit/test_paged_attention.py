"""The decode step reads the paged KV pool in place (ISSUE 26).

The paged attention call against an einsum oracle over the gathered
view; a paged :class:`DecodeServer` whose step attends inside the kernel
against one whose step takes the einsum fallback; what one step may
touch in the pool; and the structure of the step's program: no
intermediate the size of the pool or of its dense view.  Interpret
mode, tiny shapes: kept out of the ``slow`` tier, so it counts where
the driver counts."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nbdistributed_tpu.models import init_params, tiny_config
from nbdistributed_tpu.models.serving import DecodeServer
from nbdistributed_tpu.ops.decode import paged_decode_attention

pytestmark = [pytest.mark.unit, pytest.mark.serve]

L, S, BT, MB, D, GROUP = 2, 3, 8, 4, 16, 2
NB = S * MB                 # physical blocks; block NB is the trash
T = MB * BT


def make_pool(hkv, seed=0, quantized=False):
    rng = np.random.default_rng(seed)
    shape = (L, NB + 1, hkv, BT, D)
    if quantized:
        pool = {n: jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                for n in ("k", "v")}
        for n in ("k_s", "v_s"):
            pool[n] = jnp.asarray(
                rng.uniform(0.005, 0.02, shape[:-1] + (1,)), jnp.float32)
        return pool
    return {n: jnp.asarray(rng.normal(size=shape), jnp.float32)
            for n in ("k", "v")}


def make_table(pos, seed=1):
    """A permuted table: each row owns the pages its ``pos`` needs, at
    scattered physical ids; the tail entries map to the trash block."""
    ids = np.random.default_rng(seed).permutation(NB).reshape(S, MB)
    need = np.asarray(pos)[:, None] // BT + 1
    return jnp.asarray(np.where(np.arange(MB)[None, :] < need, ids, NB),
                       jnp.int32)


def oracle(q, pool, layer, table, pos, active, window=None):
    """Plain einsum attention over the gathered dense view."""
    hkv = pool["k"].shape[2]

    def view(c):
        g = jnp.take(c[layer], table, axis=0)   # (S, MB, Hkv, bt, D)
        g = g.transpose(0, 2, 1, 3, 4)
        return g.reshape(S, hkv, T, -1).astype(jnp.float32)
    k, v = view(pool["k"]), view(pool["v"])
    if "k_s" in pool:
        k, v = k * view(pool["k_s"]), v * view(pool["v_s"])
    t = jnp.arange(T)[None, :]
    keep = t <= pos[:, None]
    if window is not None:
        keep &= t > pos[:, None] - window
    # keys that do not attend are not read at all (the tests plant NaN
    # where the kernel must not look)
    k = jnp.where(keep[:, None, :, None], k, 0.0)
    v = jnp.where(keep[:, None, :, None], v, 0.0)
    qg = q.reshape(S, hkv, -1, D).astype(jnp.float32) / np.sqrt(D)
    s = jnp.einsum("bkgd,bktd->bkgt", qg, k)
    p = jax.nn.softmax(jnp.where(keep[:, None, None], s, -1e30), -1)
    o = jnp.einsum("bkgt,bktd->bkgd", p, v).reshape(S, -1, D)
    return jnp.where(active[:, None, None], o, 0.0)


def check(pool, pos, *, active=(True,) * S, window=None, layer=1,
          atol=2e-6):
    hkv = pool["k"].shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    active = jnp.asarray(active)
    table = make_table(pos)
    q = jax.random.normal(jax.random.PRNGKey(2), (S, hkv * GROUP, D))
    got = paged_decode_attention(
        q, pool["k"], pool["v"], layer, table, pos, active=active,
        window=window, k_s=pool.get("k_s"), v_s=pool.get("v_s"))
    want = oracle(q, pool, layer, table, pos, active, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=atol, rtol=1e-5)


# position 0, one short of a page edge, on it, a full row
@pytest.mark.parametrize("pos", [0, BT - 2, BT - 1, BT, T - 1])
@pytest.mark.parametrize("hkv", [1, 2])
def test_paged_call_matches_the_einsum_oracle(pos, hkv):
    # the other rows sit elsewhere, so every row clamps differently
    check(make_pool(hkv), [pos, (pos + 11) % T, T - 1 - pos])


@pytest.mark.parametrize("layer", [0, 1])
def test_paged_call_reads_the_layer_it_is_given(layer):
    check(make_pool(2), [5, 17, 30], layer=layer)


def test_an_inactive_row_takes_no_part_and_comes_back_as_zeros():
    pool = make_pool(2)
    # NaN in the trash block and in the idle row's own pages: neither
    # may reach any row's output
    idle_pages = make_table([20, 20, 20])[1]
    for n in pool:
        pool[n] = pool[n].at[:, NB].set(jnp.nan)
        pool[n] = pool[n].at[:, idle_pages].set(jnp.nan)
    check(pool, [20, 20, 9], active=(True, False, True))


@pytest.mark.parametrize("window", [1, 5, BT, BT + 3])
def test_a_window_shorter_than_the_row_skips_the_pages_below_it(window):
    pool = make_pool(2)
    pos = [T - 1, 2 * BT, 3]
    # NaN in every page wholly below a row's window: skipped, not
    # merely masked (a masked NaN would still poison p @ v)
    table = np.asarray(make_table(pos))
    for b, p in enumerate(pos):
        for j in range(max(0, p + 1 - window) // BT):
            for n in pool:
                pool[n] = pool[n].at[:, table[b, j]].set(jnp.nan)
    check(pool, pos, window=window)


@pytest.mark.parametrize("hkv", [1, 2])
def test_the_int8_pool_goes_through_the_same_index_maps(hkv):
    check(make_pool(hkv, quantized=True), [0, BT, T - 1], atol=2e-5)
    check(make_pool(hkv, quantized=True), [T - 1, 3, BT - 1],
          active=(True, True, False), window=BT + 1, atol=2e-5)


def test_paged_call_validates_its_operands():
    pool = make_pool(2)
    q = jnp.zeros((S, 3, D))
    table, pos = make_table([0, 0, 0]), jnp.zeros((S,), jnp.int32)
    with pytest.raises(ValueError, match="divisible"):
        paged_decode_attention(q, pool["k"], pool["v"], 0, table, pos)
    q = jnp.zeros((S, 4, D))
    with pytest.raises(ValueError, match="both k_s and v_s"):
        paged_decode_attention(q, pool["k"], pool["v"], 0, table, pos,
                               k_s=pool["k"][..., :1])
    with pytest.raises(ValueError, match="window"):
        paged_decode_attention(q, pool["k"], pool["v"], 0, table, pos,
                               window=0)


# ----------------------------------------------------------------------
# the server


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config(dtype=jnp.float32, use_flash=False)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def serve(model, use_flash, **kw):
    cfg, params = model
    cfg = dataclasses.replace(cfg, use_flash=use_flash)
    return DecodeServer(params, cfg, max_batch=2, pad_to=4,
                        kv_block_tokens=8, **kw)


def drive(srv):
    """Staggered admissions over two slots, a request cancelled in
    flight, and a pool so small that the fourth request waits for the
    blocks the first three give back."""
    out = {}
    out["a"] = srv.submit([5, 9, 2], 7)
    srv.step()
    out["b"] = srv.submit([7, 1, 3, 11, 4, 8, 6], 12)   # crosses a page
    srv.step()
    out["c"] = srv.submit([2, 2], 6)                    # waits for a slot
    out["d"] = srv.submit(list(range(1, 20)), 9)        # 4 recycled blocks
    for _ in range(3):
        srv.step()
    assert srv.cancel(out["b"])
    srv.run_until_done(max_steps=100)
    assert srv.kv_snapshot()["used"] == 0
    return {k: list(srv.outputs[r]) for k, r in out.items()}


@pytest.mark.parametrize("kv_quantized", [False, True])
def test_server_on_the_kernel_emits_the_einsum_servers_tokens(
        model, kv_quantized):
    kw = dict(max_len=32, kv_blocks=5, kv_quantized=kv_quantized)
    kernel, einsum = serve(model, True, **kw), serve(model, False, **kw)
    assert kernel.kv_view_bytes == 0 < einsum.kv_view_bytes
    got, want = drive(kernel), drive(einsum)
    assert got == want
    assert [len(want[k]) for k in "acd"] == [7, 6, 9]
    assert 1 <= len(want["b"]) < 12             # cancelled in flight
    assert kernel.kv_read_bytes_total == einsum.kv_read_bytes_total > 0


@pytest.mark.parametrize("use_flash", [True, False])
def test_a_step_leaves_every_block_no_active_slot_owns_bit_identical(
        model, use_flash):
    srv = serve(model, use_flash, max_len=32, prefill_chunk=8,
                interleave_prefill=True)
    srv.submit([5, 9, 2, 7, 1, 3], 4)           # active after admission
    srv.submit(list(range(1, 20)), 4)           # mid-prefill: inactive
    srv.step()                                  # one chunk of the second
    assert srv._prefilling and list(srv._slot_req) == [0]
    owned = set(srv._paged.allocator._tables["0"])
    before = jax.tree_util.tree_map(np.asarray, srv._cache)
    lens = np.asarray(srv._lens)
    srv._dispatch_step()                        # the decode step alone
    after = jax.tree_util.tree_map(np.asarray, srv._cache)
    trash = srv._paged.trash
    others = [b for b in range(trash) if b not in owned]
    for name in before:
        np.testing.assert_array_equal(after[name][:, others],
                                      before[name][:, others])
        # of the active slot's blocks, exactly one token changed
        diff = np.argwhere((after[name] != before[name])[:, :trash]
                           .any(axis=(2, 4)))   # (layer, block, offset)
        blk = srv._paged.allocator._tables["0"][lens[0] // 8]
        assert {tuple(d[1:]) for d in diff} == {(blk, lens[0] % 8)}


# ----------------------------------------------------------------------
# the step's program

def _sizes(jaxpr, out):
    """(primitive, elements) of every intermediate, sub-jaxprs too."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v.aval, "shape"):
                out.append((eqn.primitive.name,
                            int(np.prod(v.aval.shape, dtype=np.int64))))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _sizes(sub, out)
    return out


# what hands the pool on without making another: the slice update of
# the one-token write, and the control flow the pool is carried through
PASSES_THE_POOL_ON = {"dynamic_update_slice", "scan", "while", "pjit",
                      "jit", "closed_call", "core_call", "custom_jvp_call"}


def test_no_intermediate_of_the_step_is_as_large_as_the_pool_or_its_view():
    """At the benchmark's rehearsal geometry, with the kernel: what
    keeps a later refactor from bringing the dense view back."""
    from benchmarks.drivers.train_worker import program_config
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    cfg = json.load(open(os.path.join(
        root, "benchmarks/configs/mistral7b-serve.json")))
    cfg = {**cfg, **cfg["rehearse"]}
    geo, pc = cfg["assumed"], program_config(cfg)
    assert pc.use_flash
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), pc))
    srv = DecodeServer(params, pc, max_batch=geo["max_batch"],
                       max_len=geo["max_len"], pad_to=geo["pad_to"],
                       kv_block_tokens=geo["kv_block_tokens"],
                       prefill_chunk=geo["prefill_chunk"],
                       interleave_prefill=True)
    leaf = srv._cache["k"]
    n_layers, n_phys, hkv, bt, d = leaf.shape
    pool_leaf = leaf.size
    view = (n_layers * geo["max_batch"] * hkv * srv._paged.max_blocks
            * bt * d)
    jaxpr = jax.make_jaxpr(srv._step_fn)(
        params, srv._cache, srv._paged.device_table(), srv._lens,
        srv._last, srv._active, srv._key)
    sizes = _sizes(jaxpr.jaxpr, [])
    assert any(p == "pallas_call" for p, _ in sizes)
    # a layer's view alone is the fallback's mark
    layer_view = view // n_layers
    big = [(p, n) for p, n in sizes
           if n >= min(pool_leaf, view) and p not in PASSES_THE_POOL_ON]
    assert not big, big
    assert not [(p, n) for p, n in sizes if n == layer_view], \
        "a per-layer view is gathered on the kernel path"
    # and the same walk does see the fallback's per-layer view
    srv_e = DecodeServer(params, dataclasses.replace(pc, use_flash=False),
                         max_batch=geo["max_batch"], max_len=geo["max_len"],
                         pad_to=geo["pad_to"],
                         kv_block_tokens=geo["kv_block_tokens"])
    sizes_e = _sizes(jax.make_jaxpr(srv_e._step_fn)(
        params, srv_e._cache, srv_e._paged.device_table(), srv_e._lens,
        srv_e._last, srv_e._active, srv_e._key).jaxpr, [])
    assert [(p, n) for p, n in sizes_e if n == layer_view]
    assert not [(p, n) for p, n in sizes_e
                if n >= min(pool_leaf, view) and p not in PASSES_THE_POOL_ON]
