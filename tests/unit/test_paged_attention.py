"""The decode step (ISSUE 26) and the prefill chunk (ISSUE 28) read the
paged KV pool in place.

The paged attention calls against an einsum oracle over the gathered
view (a step's one query a row; a chunk's many, for both mixers); a
paged :class:`DecodeServer` whose step attends inside the kernel
against one that takes the fallback, and against the dense server; what no real token wrote,
poisoned, reaching no served token; what one step may touch in the
pool; and the structure of both programs: no intermediate the size of
the pool, of its dense view or of a row of ``max_len`` keys.  Interpret
mode, tiny shapes: kept out of the ``slow`` tier, so it counts where
the driver counts."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nbdistributed_tpu.models import (init_latent_moe_model, init_params,
                                      tiny_config, tiny_latent_moe_config)
from nbdistributed_tpu.models.generate import _cached_attention
from nbdistributed_tpu.models.mla import MLAMixer
from nbdistributed_tpu.models.serving import DecodeServer
from nbdistributed_tpu.ops.decode import (paged_decode_attention,
                                          paged_prefill_attention)

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
# the kernel walks a row's live pages itself (ISSUE 32): what a grid
# over every page of the table never told apart

@pytest.fixture
def tile_pages(monkeypatch):
    """Make a trip of the kernel's loop take ``n`` pages (the table's
    width at most), whatever the operands' shapes would give."""
    from nbdistributed_tpu.ops import decode

    def set_to(n):
        monkeypatch.setattr(decode, "_pages_per_tile",
                            lambda pools, width: min(width, n))
        decode._paged_decode_call.clear_cache()    # the count is traced
    yield set_to
    decode._paged_decode_call.clear_cache()


def walk_case(pos, *, mb, bt=8, hkv=2, group=2, d=16, w=None, v_width=None,
              window=None, seed=0, poisoned=True, layers=2):
    """One call against the einsum oracle at any geometry: rows of
    ``mb`` pages of ``bt`` tokens scattered over the pool, ``pos`` < 0
    a row that takes no part.  ``w`` makes the pool a latent one (one
    head of width ``w``, values its first ``v_width`` columns).  NaN in
    the trash block, in an idle row's pages and in every page a live
    row's ``pos`` (and window) leaves out: nothing may look there."""
    from nbdistributed_tpu.ops.decode import paged_latent_decode_attention
    latent = w is not None
    if latent:
        hkv, d = 1, w
    rows, t_max = len(pos), mb * bt
    nb = rows * mb
    rng = np.random.default_rng(seed)
    pos = np.asarray(pos)
    ids = rng.permutation(nb).reshape(rows, mb)
    last = np.maximum(pos, 0) // bt
    first = (np.maximum(pos + 1 - window, 0) // bt if window is not None
             else np.zeros_like(pos))
    j = np.arange(mb)[None]
    owned = (pos[:, None] >= 0) & (j >= first[:, None]) & (j <= last[:, None])
    leaves = {}
    for name in ("k",) if latent else ("k", "v"):
        c = rng.normal(size=(layers, nb + 1, hkv, bt, d)).astype(np.float32)
        if poisoned:
            c[:, nb] = np.nan
            c[:, ids[~owned]] = np.nan
        leaves[name] = jnp.asarray(c)
    table = jnp.asarray(ids, jnp.int32)
    q = jnp.asarray(rng.normal(size=(rows, hkv * group, d)), jnp.float32)
    scale, layer = 1.0 / np.sqrt(d), layers - 1
    posj = jnp.asarray(pos, jnp.int32)
    if latent:
        got = paged_latent_decode_attention(
            q, leaves["k"], layer, table, jnp.maximum(posj, 0),
            v_width=v_width, scale=scale, active=posj >= 0)
    else:
        got = paged_decode_attention(
            q, leaves["k"], leaves["v"], layer, table, posj, scale=scale,
            window=window)      # a negative position is an idle row

    t = np.arange(t_max)[None]
    keep = (t <= pos[:, None]) & (pos[:, None] >= 0)
    if window is not None:
        keep &= t > pos[:, None] - window

    def view(c):            # (rows, hkv, T, W), zeros where nothing attends
        g = np.asarray(c)[layer][ids].transpose(0, 2, 1, 3, 4)
        return np.where(keep[:, None, :, None],
                        g.reshape(rows, hkv, t_max, -1), 0.0)
    k = view(leaves["k"])
    v = k[..., :v_width] if latent else view(leaves["v"])
    qg = np.asarray(q).reshape(rows, hkv, group, d) * scale
    sc = np.where(keep[:, None, None], np.einsum("bkgd,bktd->bkgt", qg, k),
                  -np.inf)
    with np.errstate(invalid="ignore"):
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr = np.nan_to_num(pr / pr.sum(-1, keepdims=True))  # an idle row
    want = np.einsum("bkgt,bktd->bkgd", pr, v).reshape(rows, hkv * group, -1)
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-6, rtol=2e-5)
    return got


# a tile of n pages of 8 tokens, a table of 8 pages: the last key of
# the first tile, the first of the second, one page, the whole table
@pytest.mark.parametrize("edge", ["tile-1", "tile", "page", "table"])
@pytest.mark.parametrize("n", [2, 3])       # 3 does not divide 8
def test_positions_at_a_tiles_edges(tile_pages, n, edge):
    tile_pages(n)
    p = {"tile-1": n * 8 - 1, "tile": n * 8, "page": 7, "table": 63}[edge]
    walk_case([p, 63 - p, (p + 8 * n) % 64], mb=8)


# the window layers' cut table: nine pages, which no tile above one
# divides, eight or nine of them live
@pytest.mark.parametrize("n", [2, 4, 6, 9])
def test_a_table_of_nine_pages_under_tiles_that_do_not_divide_it(
        tile_pages, n):
    tile_pages(n)
    walk_case([9 * 8 - 1, 8 * 8 - 1, 8 * 8 + 3, 5], mb=9, window=8 * 8)


# a long row before a short one (what the tiles held must not be read
# again), an idle row between live ones, a first and a last row idle
@pytest.mark.parametrize("pos", [
    [63, 2, -1, 31, 0], [-1, 63, -1, -1, 9], [5, -1, 63, 17, -1],
    [-1, -1, -1, -1, -1]], ids=["long-short", "idle-first", "idle-last",
                                 "all-idle"])
@pytest.mark.parametrize("n", [1, 2, 4])
def test_rows_of_very_different_lengths_in_one_call(tile_pages, n, pos):
    tile_pages(n)
    walk_case(pos, mb=8)


# a window that opens inside the table: the walk starts at its first
# page, several tiles on, and that page's keys below the window are
# held (no NaN there) and masked
@pytest.mark.parametrize("window", [8 + 3, 3 * 8, 5 * 8 + 1])
@pytest.mark.parametrize("n", [2, 3])
def test_a_window_whose_first_page_lies_tiles_into_the_row(tile_pages, n,
                                                           window):
    tile_pages(n)
    walk_case([63, 40, 2 * 8 + 1], mb=8, window=window)


# the served geometries, scaled down, each under the tile its own
# shapes give: phi and Mistral (group 4, several KV heads, 64-page
# tables, phi's window tables of nine), JoyAI (one head of 640 with
# values 512 wide: 80 and 64 here; a 128-page table)
@pytest.mark.parametrize("geometry", [
    dict(hkv=5, group=4, d=32, mb=64, bt=4),
    dict(hkv=5, group=4, d=32, mb=9, bt=4, window=32),
    dict(hkv=4, group=4, d=32, mb=64, bt=4, window=256),
    dict(w=80, v_width=64, group=8, mb=128, bt=4),
], ids=["phi-full", "phi-window", "mistral", "joyai"])
def test_the_served_geometries_scaled_down(geometry, monkeypatch):
    from nbdistributed_tpu.ops import decode
    seen = []
    real = decode._pages_per_tile
    monkeypatch.setattr(decode, "_pages_per_tile",
                        lambda *a: seen.append(real(*a)) or seen[-1])
    t_max = geometry["mb"] * geometry["bt"]
    walk_case([t_max - 1, 5, -1, t_max // 3], **geometry)
    assert seen == [geometry["mb"]]         # tiny pages: one tile a row


@pytest.mark.parametrize("pool, width, pages", [
    (((10, 64, 128),) * 2, 64, 6),          # phi's shared layer
    (((10, 64, 128),) * 2, 9, 6),           # its window layers' table
    (((8, 64, 128),) * 2, 64, 8),           # Mistral
    (((1, 64, 640),), 128, 25),             # JoyAI's latent pool
    (((8, 64, 128),) * 2, 3, 3),            # the table's width caps it
    (((64, 64, 512),) * 2, 64, 1),          # a page over the budget
], ids=["phi", "phi-window", "mistral", "joyai", "narrow", "huge"])
def test_pages_a_tile_come_from_the_operands_shapes(pool, width, pages):
    from nbdistributed_tpu.ops.decode import _pages_per_tile
    leaves = [jax.ShapeDtypeStruct((2, 17) + sh, jnp.bfloat16)
              for sh in pool]
    assert _pages_per_tile(leaves, width) == pages


# The TPU interpreter runs a copy when it is waited for, starts every
# buffer as NaN and watches for a buffer read while a copy into it is
# in flight: a wait left out, a tile read before its wait, a slot not
# fetched and not masked all show here and not in the plain interpreter
@pytest.mark.parametrize("kind", ["gqa", "window", "latent"])
@pytest.mark.parametrize("n", [1, 2, 3])
def test_the_double_buffer_under_the_race_detecting_interpreter(
        tile_pages, monkeypatch, n, kind):
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu
    from nbdistributed_tpu.ops import decode
    tile_pages(n)
    monkeypatch.setattr(decode, "_use_interpret", lambda: pltpu.InterpretParams(
        dma_execution_mode="on_wait", detect_races=True,
        uninitialized_memory="nan"))
    kw = {"gqa": {}, "window": dict(window=2 * 8 + 3),
          "latent": dict(w=32, v_width=24, group=4)}[kind]
    walk_case([63, 2, -1, 31, 17, -1, 0], mb=8, **kw)
    assert not interpret_pallas_call.races.races_found


def test_the_steps_kernel_has_no_grid_axis_over_the_tables_pages(model):
    """The grid is the rows: a call's cost cannot go with the table's
    width, which only the scalar-prefetched table itself carries."""
    cfg, params = model
    srv = DecodeServer(params, dataclasses.replace(cfg, use_flash=True),
                       max_batch=3, max_len=5 * 8, pad_to=4,
                       kv_block_tokens=8)
    assert srv._paged.max_blocks == 5
    jaxpr = jax.make_jaxpr(srv._step_fn)(
        params, srv._cache, srv._paged.device_table(), srv._lens,
        srv._last, srv._active, srv._key)

    def calls(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                calls(sub, out)
        return out

    found = calls(jaxpr.jaxpr, [])
    assert found
    for params_ in found:
        assert tuple(params_["grid_mapping"].grid) == (3,)
        assert params_["name"] == "nbd_flash_decode_paged"


# ----------------------------------------------------------------------
# a chunk of new tokens: many queries a row, causal among themselves

CHUNK = 2 * BT


def dense_view(pool, layer, row_ids):
    """One row's pages of one layer as a dense ``(1, Hkv, T, W)``
    buffer a leaf: what the dense attention paths take."""
    def one(c):
        g = jnp.take(c[layer], row_ids, axis=0)         # (MB, Hkv, bt, W)
        return g.transpose(1, 0, 2, 3).reshape(1, c.shape[2], T, -1)
    return {n: one(c) for n, c in pool.items()}


def poison(pool, row_ids, written):
    """NaN (the int8 leaves: their scales) in the trash block, in every
    block the row does not own, and at every position of the row at or
    past ``written``: what no real token of the row has written."""
    owned = np.asarray(row_ids)
    at = np.arange(T).reshape(MB, BT) >= written          # (MB, bt)
    out = {}
    for n, c in pool.items():
        if c.dtype == jnp.int8:
            out[n] = c
            continue
        bad = np.ones(c.shape[1:4:2], bool)               # (NB+1, bt)
        bad[owned] = at
        out[n] = jnp.where(jnp.asarray(bad)[None, :, None, :, None],
                           jnp.nan, c)
    return out


def chunk_table(seed=3):
    """One row whose pages lie scattered over the pool, out of order."""
    return jnp.asarray(np.random.default_rng(seed).permutation(NB)[:MB],
                       jnp.int32)


@pytest.fixture(params=[2 * BT, 512], ids=["2-page-tiles", "one-tile"])
def tile_keys(request, monkeypatch):
    """The key tile of the chunk's loop: two pages (several trips a
    row) or, as shipped, more than these rows hold."""
    from nbdistributed_tpu.ops import decode
    monkeypatch.setattr(decode, "_PREFILL_TILE_KEYS", request.param)


@pytest.mark.parametrize("start, length, window", [
    (0, CHUNK, None),                   # a first chunk, whole
    (CHUNK, CHUNK, None),               # one chunk in
    (T - CHUNK, CHUNK, None),           # several in: the row's last
    (BT + 3, CHUNK, None),              # a start inside a page
    (CHUNK, 5, None),                   # a padded tail
    (0, 1, None),                       # one real token
    (CHUNK, CHUNK, 5),                  # a window inside the chunk
    (T - CHUNK, 11, BT + 3),            # a window that skips whole pages
])
@pytest.mark.parametrize("hkv", [1, 2])
def test_chunk_call_matches_the_dense_rows_attention(hkv, start, length,
                                                     window, tile_keys):
    """GQA: the chunk's real queries against ``_cached_attention`` over
    a dense copy of the same pages; what no real token wrote is NaN in
    the pool the call reads."""
    pool, row_ids, layer = make_pool(hkv, seed=start + length), \
        chunk_table(), 1
    q = jax.random.normal(jax.random.PRNGKey(4),
                          (1, CHUNK, hkv * GROUP, D))
    scale = 1.0 / np.sqrt(D)
    got = paged_prefill_attention(
        q, *(poison(pool, row_ids, start + length)[n] for n in "kv"),
        layer, row_ids[None], jnp.asarray([start]),
        jnp.asarray([length]), scale=scale, window=window)
    view = dense_view(pool, layer, row_ids)
    want = _cached_attention(
        q, view["k"], view["v"], start + jnp.arange(CHUNK)[None], scale,
        window=window)
    np.testing.assert_allclose(
        np.asarray(got).reshape(1, CHUNK, -1)[:, :length],
        np.asarray(want)[:, :length], atol=3e-6, rtol=1e-5)
    assert np.isfinite(np.asarray(got)).all()       # the padded tail too


def test_an_int8_pools_chunk_takes_its_scales():
    pool, row_ids = make_pool(2, quantized=True), chunk_table()
    q = jax.random.normal(jax.random.PRNGKey(4), (1, CHUNK, 4, D))
    view = dense_view(pool, 0, row_ids)
    k, v = (view[n].astype(jnp.float32) * view[n + "_s"] for n in "kv")
    bad = poison(pool, row_ids, CHUNK + 9)
    got = paged_prefill_attention(
        q, bad["k"], bad["v"], 0, row_ids[None], CHUNK, 9, scale=0.25,
        k_s=bad["k_s"], v_s=bad["v_s"])
    want = _cached_attention(q, k, v, CHUNK + jnp.arange(CHUNK)[None],
                             0.25)
    np.testing.assert_allclose(np.asarray(got).reshape(1, CHUNK, -1)[:, :9],
                               np.asarray(want)[:, :9], atol=2e-5)


@pytest.fixture(scope="module")
def latent():
    cfg = tiny_latent_moe_config(dtype=jnp.float32)
    params = init_latent_moe_model(jax.random.PRNGKey(0), cfg)
    return cfg, params["layers"][0]


@pytest.mark.parametrize("start, length", [
    (0, CHUNK), (CHUNK, CHUNK), (T - CHUNK, CHUNK), (BT + 3, CHUNK),
    (CHUNK, 5)])
def test_latent_chunk_absorbed_over_the_pool_matches_the_up_projected_row(
        latent, start, length, tile_keys):
    """``MLAMixer.attend_paged`` over the latent pages (absorbed: no
    position of the row up-projected) against ``MLAMixer.attend`` over
    a dense copy of them (K and V up-projected from the whole row)."""
    cfg, layer = latent
    mixer = MLAMixer(cfg, None)
    rng = np.random.default_rng(start + length)
    pool = {"ckv": jnp.asarray(rng.normal(size=(
        L, NB + 1, 1, BT, cfg.cache_width)), jnp.float32)}
    row_ids = chunk_table()
    q = jnp.asarray(rng.normal(size=(
        1, CHUNK, cfg.n_heads, cfg.qk_head_dim)), jnp.float32)
    got = mixer.attend_paged(
        q, poison(pool, row_ids, start + length), 1, row_ids[None],
        jnp.asarray([start]), None, layer, length=jnp.asarray([length]))
    want = mixer.attend(q, dense_view(pool, 1, row_ids),
                        start + jnp.arange(CHUNK)[None], layer)
    np.testing.assert_allclose(np.asarray(got)[:, :length],
                               np.asarray(want)[:, :length],
                               atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


def test_chunk_call_validates_its_operands():
    pool, row = make_pool(2), chunk_table()[None]
    q = jnp.zeros((1, CHUNK, 4, D))
    with pytest.raises(ValueError, match="divisible"):
        paged_prefill_attention(q[:, :, :3], pool["k"], pool["v"], 0, row,
                                0, scale=1.0)
    with pytest.raises(ValueError, match="window"):
        paged_prefill_attention(q, pool["k"], pool["v"], 0, row, 0,
                                scale=1.0, window=0)
    with pytest.raises(ValueError, match="latent"):
        paged_prefill_attention(q, pool["k"], None, 0, row, 0, scale=1.0)
    with pytest.raises(ValueError, match="latent"):
        paged_prefill_attention(q, pool["k"], pool["v"], 0, row, 0,
                                scale=1.0, v_width=8)


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
    """Staggered admissions over two slots, a request cancelled with
    a step of its row in flight, and a pool so small that the fourth
    request waits for the blocks the first three give back."""
    out = {}
    out["a"] = srv.submit([5, 9, 2], 7)
    srv.step()                                  # a's first step leaves
    out["b"] = srv.submit([7, 1, 3, 11, 4, 8, 6], 12)   # crosses a page
    srv.step()                                  # a's second, with b
    assert [len(srv.outputs[out[k]]) for k in "ab"] == [2, 1]
    out["c"] = srv.submit([2, 2], 6)                    # waits for a slot
    out["d"] = srv.submit(list(range(1, 20)), 9)        # 4 recycled blocks
    for _ in range(3):
        srv.step()
    # five steps dispatched, four fetched: one token at admission and
    # one a fetched step that ran the row
    assert [len(srv.outputs[out[k]]) for k in "ab"] == [5, 4]
    assert out["b"] in srv._flying.rows.values()
    assert srv.cancel(out["b"])
    srv.run_until_done(max_steps=100)
    assert srv.kv_snapshot()["used"] == 0
    return {k: list(srv.outputs[r]) for k, r in out.items()}


@pytest.mark.parametrize("kv_quantized", [False, True])
def test_server_on_the_kernel_emits_the_einsum_servers_tokens(
        model, kv_quantized):
    kw = dict(max_len=32, kv_blocks=5, kv_quantized=kv_quantized)
    kernel, einsum = serve(model, True, **kw), serve(model, False, **kw)
    # an int8 pool's step gathers (Mosaic refuses a scale page's copy)
    assert (kernel.kv_view_bytes == 0) == (not kv_quantized)
    assert einsum.kv_view_bytes > 0
    got, want = drive(kernel), drive(einsum)
    assert got == want
    assert [len(want[k]) for k in "acd"] == [7, 6, 9]
    assert len(want["b"]) == 4      # cancelled: the step in flight's
    #                                 token for its row was dropped
    assert kernel.kv_read_bytes_total == einsum.kv_read_bytes_total > 0


@pytest.mark.parametrize("use_flash", [True, False])
def test_a_step_leaves_every_block_no_active_slot_owns_bit_identical(
        model, use_flash):
    srv = serve(model, use_flash, max_len=32, prefill_chunk=8,
                interleave_prefill=True)
    srv.submit([5, 9, 2, 7, 1, 3], 4)           # active after admission
    srv.submit(list(range(1, 20)), 4)           # mid-prefill: inactive
    srv.step()                  # one chunk of the second, and the
    #                             first's first step, left in flight
    assert srv._prefilling and list(srv._slot_req) == [0]
    assert srv._flying.rows == {0: 0} and list(srv._run) == [0]
    owned = set(srv._paged.allocator._tables["0"])
    before = jax.tree_util.tree_map(np.asarray, srv._cache)
    lens = np.asarray(srv._lens)                # after that step
    assert lens[0] == 6 + 1 == srv._run[0][0]
    srv._dispatch_step()                        # the next step alone
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


# prompts of one chunk, of several, of a whole number of them, and one
# short of a page edge; a bucketed one
PROMPTS = [list(range(3, 3 + n)) for n in (41, 16, 23, 7)]


def latent_model():
    cfg = tiny_latent_moe_config(dtype=jnp.float32)
    return cfg, init_latent_moe_model(jax.random.PRNGKey(1), cfg)


def serve_chunked(cfg, params, *, poisoned=False, **kw):
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64, pad_to=8,
                       prefill_chunk=16, **kw)
    if poisoned:
        srv._cache = jax.tree_util.tree_map(
            lambda c: jnp.full_like(c, jnp.nan), srv._cache)
    rids = [srv.submit([t % cfg.vocab_size for t in p], 6)
            for p in PROMPTS]
    srv.run_until_done(max_steps=200)
    return [list(srv.outputs[r]) for r in rids], srv


@pytest.mark.parametrize("use_flash", [True, False],
                         ids=["step-in-kernel", "step-gathers"])
@pytest.mark.parametrize("family", ["gqa", "latent"])
def test_chunked_paged_server_serves_the_dense_servers_tokens(
        model, family, use_flash):
    """float32, token for token: a chunk that writes its own pages and
    attends through the table computes what a chunk over the dense
    pool's row does, interleaved with decode steps or not."""
    cfg, params = model if family == "gqa" else latent_model()
    cfg = dataclasses.replace(cfg, use_flash=use_flash)
    want, _ = serve_chunked(cfg, params)
    got, srv = serve_chunked(cfg, params, kv_block_tokens=8,
                             interleave_prefill=True)
    assert got == want
    # 41 -> 3 chunks, 23 -> 2, 16 and 7 one program each
    assert srv.prefill_chunks_total == 7
    got, _ = serve_chunked(cfg, params, kv_block_tokens=8)
    assert got == want


@pytest.mark.parametrize("served", ["chunk", "steps"])
@pytest.mark.parametrize("family", ["gqa", "latent"])
def test_what_no_real_token_wrote_reaches_no_served_token(model, family,
                                                          served):
    """The whole pool NaN before the first admission, trash block and
    all: every page a request is given holds NaN wherever no token of
    it has written yet.  The chunk and the decode kernel keep such keys
    out of both products by position (a probability of zero does not
    clean a NaN), so each request's first token, which its last chunk's
    logits give, and the tokens of the decode steps after it, one of
    which opens a fresh page, are the clean pool's."""
    cfg, params = model if family == "gqa" else latent_model()
    cfg = dataclasses.replace(cfg, use_flash=True)
    kw = dict(kv_block_tokens=8, interleave_prefill=True)
    want, _ = serve_chunked(cfg, params, **kw)
    got, srv = serve_chunked(cfg, params, poisoned=True, **kw)
    if served == "chunk":
        got, want = ([toks[0] for toks in t] for t in (got, want))
    assert got == want


def test_prompts_of_several_lengths_compile_one_program_a_chunk_shape(
        model):
    """``start`` and ``length`` are data: the chunks of every prompt
    longer than the chunk, their padded tails too, run one compiled
    program (a bucketed short prompt is a shape of its own)."""
    cfg, params = model
    srv = DecodeServer(params, dataclasses.replace(cfg, use_flash=True),
                       max_batch=2, max_len=64, pad_to=8,
                       prefill_chunk=16, kv_block_tokens=8)
    for n in (17, 41, 32, 55, 23):
        srv.submit(list(range(1, n + 1)), 2)
    srv.run_until_done(max_steps=200)
    assert srv.prefill_chunks_total == 2 + 3 + 2 + 4 + 2
    assert srv._prefill_fn.program._cache_size() == 1


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


def test_no_intermediate_of_the_prefill_chunk_is_a_row_of_max_len_keys():
    """The chunk program at the benchmark's rehearsal geometry, with
    ``max_len`` a number no width of the model shares: nothing in it
    has ``max_len`` (or the whole table's pages) for an axis, nothing
    outside the hand-on ops is as large as the pool or as a slot's
    dense row.  The parent's program gathered
    the row, ``(L, 1, Hkv, max_len, D)``, and made ``(H, chunk,
    max_len)`` scores."""
    from benchmarks.drivers.train_worker import program_config
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    cfg = json.load(open(os.path.join(
        root, "benchmarks/configs/mistral7b-serve.json")))
    cfg = {**cfg, **cfg["rehearse"]}
    geo, pc = cfg["assumed"], program_config(cfg)
    assert pc.use_flash
    bt, ck = geo["kv_block_tokens"], geo["prefill_chunk"]
    max_len = 37 * bt
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), pc))
    srv = DecodeServer(params, pc, max_batch=geo["max_batch"],
                       max_len=max_len, pad_to=geo["pad_to"],
                       kv_block_tokens=bt, prefill_chunk=ck,
                       interleave_prefill=True)
    widths = {pc.d_model, pc.d_ff, pc.vocab_size, pc.n_heads * pc.head_dim,
              pc.n_kv_heads * pc.head_dim, ck}
    assert not {max_len, srv._paged.max_blocks} & widths
    jaxpr = jax.make_jaxpr(srv._prefill_fn.program)(
        params, srv._cache, srv._paged.device_row(0),
        jnp.zeros((1, ck), jnp.int32), jnp.int32(ck), jnp.int32(ck))

    def shapes(jaxpr, out):
        for eqn in jaxpr.eqns:
            out += [(eqn.primitive.name, tuple(v.aval.shape))
                    for v in eqn.outvars if hasattr(v.aval, "shape")]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                shapes(sub, out)
        return out

    seen = shapes(jaxpr.jaxpr, [])
    rows = [(p, sh) for p, sh in seen
            if max_len in sh or (srv._paged.max_blocks in sh
                                 and len(sh) > 2)]    # not the table
    assert not rows, rows
    leaf = srv._cache["k"]
    row = leaf.size // leaf.shape[1] * srv._paged.max_blocks
    big = [(p, sh) for p, sh in seen
           if int(np.prod(sh, dtype=np.int64)) >= min(leaf.size, row)
           and p not in PASSES_THE_POOL_ON]
    assert not big, big
