"""Latent attention over fine-grained experts (ISSUE 27): the program
against the benchmark's plain reference at a tiny size, seeded weights,
float32, on the CPU.

Full forward = reference; prefill + absorbed decode through the paged
latent pool = the reference's full forward (logits compared); chunked
and bucketed prefill = unchunked; the latent kernel (interpret mode) =
an einsum over a dense latent row; routing = reference where the bias
changes the chosen set; a masked row changes no live row; what
``DecodeServer`` refuses and no longer refuses; the counters."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.model import joyai_reference as R
from benchmarks.model import joyai_weights as W
from nbdistributed_tpu.models import (DecodeServer, config_from_hf_json,
                                      forward_with_cache, generate,
                                      init_kv_cache, init_latent_moe_model,
                                      init_moe_model, init_params,
                                      init_sdar_model, latent_moe_forward,
                                      latent_moe_shardings, tiny_config,
                                      tiny_latent_moe_config,
                                      tiny_moe_config, tiny_sdar_config)
from nbdistributed_tpu.models.paged_kv import make_paged_pool
from nbdistributed_tpu.observability.servingobs import ServingObservatory
from nbdistributed_tpu.ops import grouped
from nbdistributed_tpu.ops.decode import paged_latent_decode_attention
from nbdistributed_tpu.parallel.expert import (routing_load,
                                               shared_routed_ffn,
                                               sigmoid_bias_routing,
                                               softmax_routed_ffn)

pytestmark = [pytest.mark.unit, pytest.mark.serve]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 5


@pytest.fixture(scope="module")
def hf():
    """The benchmark configuration's rehearsal sizes, in float32."""
    with open(os.path.join(
            ROOT, "benchmarks/configs/joyai-flash-serve.json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["rehearse"], "torch_dtype": "float32"}
    return {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, str)) or v is None}


@pytest.fixture(scope="module")
def model(hf):
    cfg = config_from_hf_json(hf, dtype=jnp.float32)
    return cfg, W.make_weights(W.seed_key(SEED), hf)


def tokens(n, seed=0, rows=1, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, (rows, n),
                                                dtype=np.int32)


# ----------------------------------------------------------------------
# program = reference

def test_full_forward_is_the_references(hf, model):
    cfg, params = model
    toks = tokens(48, rows=2)
    ref, margin = R.forward(SEED, hf, toks)
    got = latent_moe_forward(params, jnp.asarray(toks), cfg)
    assert float(margin.min()) > 1e-5      # no choice sits on a tie
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("chunk", [None, 16])
def test_prefill_then_absorbed_paged_decode_is_the_references_forward(
        hf, model, chunk):
    """A prompt prefilled into the paged latent pool (whole, or in
    chunks), then teacher-forced decode steps that read the pool in
    place: every step's logits against the reference's one full
    forward, which has neither cache nor absorption."""
    cfg, params = model
    bt, n_prompt, n_new, slots = 8, 21, 9, 3
    toks = tokens(n_prompt + n_new, seed=3)[0]
    ref = np.asarray(R.forward(SEED, hf, toks[None])[0][0])
    mb = 8
    pool = make_paged_pool(cfg, slots * mb, bt)
    table = np.full((slots, mb), slots * mb, np.int32)
    table[1, :4] = [7, 2, 11, 5]            # slot 1 owns scattered blocks
    row_ids = jnp.asarray(table[1])[None]
    step = chunk or n_prompt
    for at in range(0, n_prompt, step):
        seg = jnp.asarray(toks[at:min(at + step, n_prompt)])[None]
        logits, pool = forward_with_cache(params, seg, pool, at, cfg,
                                          last_only=True,
                                          block_table=row_ids)
    np.testing.assert_allclose(logits[0, 0], ref[n_prompt - 1],
                               rtol=2e-4, atol=2e-4)
    active = jnp.asarray([False, True, False])
    for i in range(n_new):
        pos = n_prompt + i
        last = jnp.zeros((slots,), jnp.int32).at[1].set(int(toks[pos]))
        lens = jnp.zeros((slots,), jnp.int32).at[1].set(pos)
        logits, pool, load = forward_with_cache(
            params, last[:, None], pool, lens, cfg, row_mask=active,
            block_table=jnp.asarray(table), with_moe_load=True)
        np.testing.assert_allclose(logits[1, 0], ref[pos], rtol=2e-4,
                                   atol=2e-4)
        # one live row: it routes k rows to k experts in every layer
        assert [float(v) for v in load] == [cfg.top_k, 1.0, cfg.top_k]


def test_chunked_and_bucketed_prefill_serve_what_unchunked_does(model):
    cfg, params = model
    prompts = [[int(t) for t in tokens(n, seed=n)[0]] for n in (37, 9, 20)]

    def serve(**kw):
        srv = DecodeServer(params, cfg, max_batch=2, max_len=64, **kw)
        rids = [srv.submit(p, 7) for p in prompts]
        srv.run_until_done(200)
        return [srv.outputs[r] for r in rids]

    plain = serve(pad_to=1)
    assert serve(pad_to=16) == plain
    assert serve(pad_to=16, prefill_chunk=16) == plain
    assert serve(pad_to=8, prefill_chunk=8, kv_block_tokens=8,
                 interleave_prefill=True) == plain
    solo = generate(params, jnp.asarray(prompts[0])[None], cfg, 7)
    assert [int(t) for t in solo[0, len(prompts[0]):]] == plain[0]


# ----------------------------------------------------------------------
# the latent kernel

@pytest.mark.parametrize("pos", [[0, 17, 31], [8, 7, 23]])
def test_latent_kernel_is_an_einsum_over_the_dense_latent_row(pos):
    L, S, BT, MB, H, W, R_ = 2, 3, 8, 4, 4, 128, 96
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(L, S * MB + 1, 1, BT, W)),
                       jnp.float32)
    q = jnp.asarray(rng.normal(size=(S, H, W)), jnp.float32)
    ids = rng.permutation(S * MB).reshape(S, MB)
    need = np.asarray(pos)[:, None] // BT + 1
    table = jnp.asarray(np.where(np.arange(MB)[None] < need, ids, S * MB),
                        jnp.int32)
    active = jnp.asarray([True, False, True])
    got = paged_latent_decode_attention(
        q, pool, 1, table, jnp.asarray(pos, jnp.int32), v_width=R_,
        scale=0.3, active=active)
    rows = jnp.take(pool[1], table, axis=0)[:, :, 0].reshape(S, MB * BT, W)
    s = jnp.einsum("shw,stw->sht", q, rows) * 0.3
    keep = jnp.arange(MB * BT)[None, None] <= jnp.asarray(pos)[:, None, None]
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    want = jnp.einsum("sht,str->shr", p, rows[..., :R_])
    want = jnp.where(active[:, None, None], want, 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert got.shape == (S, H, R_)


# a tile of n pages (the kernel walks a row's live pages itself, n a
# trip): a position on a tile's last key and on the next tile's first,
# a long row before a short one, an idle row between live ones, and NaN
# wherever no live row's tokens lie
@pytest.mark.parametrize("pos", [[15, 16, 63], [63, 2, 40], [23, 24, 0]])
@pytest.mark.parametrize("n", [1, 2, 3])
def test_latent_kernel_walks_the_live_pages_in_tiles(monkeypatch, n, pos):
    from nbdistributed_tpu.ops import decode
    monkeypatch.setattr(decode, "_pages_per_tile",
                        lambda pools, width: min(width, n))
    decode._paged_decode_call.clear_cache()         # the count is traced
    L, S, BT, MB, H, W, R_ = 2, 4, 8, 8, 4, 128, 96
    rng = np.random.default_rng(n)
    pos = np.asarray(pos + [MB * BT - 1])           # the last row is idle
    active = np.asarray([True, True, True, False])
    ids = rng.permutation(S * MB).reshape(S, MB)
    owned = active[:, None] & (np.arange(MB)[None] <= pos[:, None] // BT)
    pool = rng.normal(size=(L, S * MB + 1, 1, BT, W)).astype(np.float32)
    pool[:, S * MB] = np.nan
    pool[:, ids[~owned]] = np.nan
    q = rng.normal(size=(S, H, W)).astype(np.float32)
    got = paged_latent_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), 1, jnp.asarray(ids, jnp.int32),
        jnp.asarray(pos, jnp.int32), v_width=R_, scale=0.3,
        active=jnp.asarray(active))
    decode._paged_decode_call.clear_cache()
    keep = (np.arange(MB * BT)[None] <= pos[:, None]) & active[:, None]
    rows = np.where(keep[..., None],
                    pool[1][ids][:, :, 0].reshape(S, MB * BT, W), 0.0)
    s = np.where(keep[:, None], np.einsum("shw,stw->sht", q, rows) * 0.3,
                 -1e30)
    p = np.exp(s - s.max(-1, keepdims=True)) * keep[:, None]
    p = p / np.maximum(p.sum(-1, keepdims=True), 1e-30)
    want = np.einsum("sht,str->shr", p, rows[..., :R_])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_latent_kernel_refuses_a_pool_that_is_not_one_head_of_its_width():
    q = jnp.zeros((2, 4, 128))
    with pytest.raises(ValueError, match="latent pool"):
        paged_latent_decode_attention(
            q, jnp.zeros((1, 3, 2, 8, 128)), 0, jnp.zeros((2, 1), jnp.int32),
            jnp.zeros((2,), jnp.int32), v_width=96, scale=1.0)


# ----------------------------------------------------------------------
# routing

def test_routing_is_the_references_where_the_bias_changes_the_choice(hf):
    z = W.sizes(hf)
    w = W.router_weights(W.seed_key(SEED), 1, hf)
    h = jnp.asarray(np.random.default_rng(1).normal(size=(64, z["D"])),
                    jnp.float32)
    logits = jnp.matmul(h, w["router"], precision="highest")
    gates, idx = sigmoid_bias_routing(
        logits, w["bias"], z["k"], hf["routed_scaling_factor"])
    ref_gates, margin = R.route(h, w, hf)
    dense = np.zeros((64, z["E"]), np.float32)
    np.put_along_axis(dense, np.asarray(idx), np.asarray(gates), 1)
    np.testing.assert_allclose(dense, ref_gates, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 2.5, rtol=1e-5)
    _, unbiased = sigmoid_bias_routing(logits, jnp.zeros_like(w["bias"]),
                                       z["k"], 2.5)
    changed = np.sort(np.asarray(idx)) != np.sort(np.asarray(unbiased))
    assert changed.any()        # a program that drops the bias differs
    assert float(margin.min()) >= 0.0


def test_a_masked_row_changes_no_live_row_and_routes_nowhere(model):
    cfg, params = model
    moe = params["layers"][0]["moe"]
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(4, 1, cfg.d_model)), jnp.float32)
    mask = jnp.asarray([True, False, True, True])[:, None]
    kw = dict(top_k=cfg.top_k, routed_scale=cfg.routed_scale)
    y, load = shared_routed_ffn(x, moe, token_mask=mask, **kw)
    y2, load2 = shared_routed_ffn(x.at[1].set(7.0), moe, token_mask=mask,
                                  **kw)
    live = np.asarray([0, 2, 3])
    np.testing.assert_array_equal(y[live], y2[live])
    assert float(load[2]) == float(load2[2]) == 3 * cfg.top_k
    alone, _ = shared_routed_ffn(x[live], moe, **kw)
    np.testing.assert_allclose(y[live], alone, rtol=1e-6, atol=1e-6)
    full = routing_load(jnp.asarray([[0, 1], [1, 2]]), 4)
    assert [float(v) for v in full] == [3.0, 2.0, 4.0]


# ----------------------------------------------------------------------
# DecodeServer: what it refuses, what it counts

def test_capacity_dispatch_still_refuses_chunks():
    cfg = tiny_moe_config(dtype=jnp.float32, use_flash=False,
                          moe_dispatch="sparse")
    params = init_moe_model(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="capacity-based"):
        DecodeServer(params, cfg, max_batch=2, max_len=32, prefill_chunk=8)
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=16)
    assert srv._pad_to == 1


@pytest.mark.parametrize("family", ["dropless", "latent"])
def test_dropless_experts_take_buckets_and_chunks(family, model):
    if family == "latent":
        cfg, params = model
    else:
        cfg = tiny_moe_config(dtype=jnp.float32, use_flash=False,
                              moe_dispatch="dropless")
        params = init_moe_model(jax.random.PRNGKey(0), cfg)
    prompt = [int(t) for t in tokens(27, seed=9)[0]]
    want = [int(t) for t in generate(
        params, jnp.asarray(prompt)[None], cfg, 6)[0, len(prompt):]]
    srv = DecodeServer(params, cfg, max_batch=2, max_len=48, pad_to=8,
                       prefill_chunk=8)
    assert srv._pad_to == 8
    rid = srv.submit(prompt, 6)
    other = srv.submit(prompt[:5], 6)       # a second live row
    srv.run_until_done(100)
    assert srv.outputs[rid] == want
    assert len(srv.outputs[other]) == 6


def test_kv_read_bytes_and_moe_load_count_what_a_step_touches(model):
    cfg, params = model
    bt = 8
    srv = DecodeServer(params, cfg, max_batch=3, max_len=64, pad_to=8,
                       kv_block_tokens=bt)
    # a page of the latent pool over all layers, at the width stored
    assert srv._page_bytes == cfg.n_layers * bt * cfg.cache_width * 4
    assert srv.kv_view_bytes == 0           # the kernel reads in place
    lens = [13, 20]
    for n in lens:
        srv.submit([int(t) for t in tokens(n, seed=n)[0]], 5)
    srv.step()
    # the step is in flight: its bytes, its routing load and its count
    # enter the account together, when its tokens are fetched
    assert srv.take_account()["kvr"] == [0, 0] and srv.moe_load[2] == 0
    srv.step()                              # dispatches 2, fetches 1
    pages = sum((n + 1 - 1) // bt + 1 for n in lens)
    assert srv.kv_read_bytes_total == pages * srv._page_bytes
    account = srv.take_account()
    assert account["kvr"] == [pages * srv._page_bytes, 1]
    assert account["ahd"] == [1, 1] and account["dc"] == 2
    touched, most, rows = account["moe"]
    assert rows == 2 * cfg.top_k            # two live rows, one idle slot
    assert cfg.top_k <= touched <= 2 * cfg.top_k and 1 <= most <= 2
    assert srv.take_account()["moe"] == [0.0, 0.0, 0.0]


def test_a_dense_model_reports_no_routing_load():
    cfg = tiny_config(dtype=jnp.float32, use_flash=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(tokens(5))
    logits, _, load = forward_with_cache(
        params, toks, init_kv_cache(cfg, 1, 8), 0, cfg, with_moe_load=True)
    assert logits.shape == (1, 5, cfg.vocab_size)
    assert [float(v) for v in load] == [0.0, 0.0, 0.0]
    srv = DecodeServer(params, cfg, max_batch=2, max_len=16)
    rid = srv.submit([1, 2, 3], 3)
    srv.run_until_done(10)
    assert len(srv.outputs[rid]) == 3 and srv.moe_load == [0.0, 0.0, 0.0]
    assert "moe" not in srv.take_account()


def test_ticks_moe_is_the_mean_over_the_steps_and_the_largest_expert():
    obs = ServingObservatory()
    wk = {"sync": 0.01}
    obs.note_tick(1, 0, {"roundtrip": 0.02},
                  {"ph": wk, "kvr": [800, 8],
                   "moe": [1200.0, 5.0, 2048.0]})
    obs.note_tick(2, 0, {"roundtrip": 0.02},
                  {"ph": wk, "kvr": [400, 4], "moe": [480.0, 7.0, 1024.0]})
    ticks = obs.ticks_summary()
    assert ticks["moe"] == {"experts_touched": 140.0, "max_rows": 7.0,
                            "rows_routed": 256.0}
    assert ticks["kv_read_bytes"] == 100
    plain = ServingObservatory()
    plain.note_tick(1, 0, {"roundtrip": 0.02},
                    {"ph": wk, "kvr": [800, 8]})
    assert "moe" not in plain.ticks_summary()


# ----------------------------------------------------------------------
# config keys and the tree

def test_published_config_keys_become_the_programs_config(hf):
    with open(os.path.join(
            ROOT, "benchmarks/configs/joyai-flash-serve.json")) as f:
        cfg = config_from_hf_json(json.load(f))
    assert (cfg.d_model, cfg.n_heads, cfg.n_layers) == (2048, 32, 5)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank) == (1536, 512)
    assert (cfg.latent_width, cfg.cache_width, cfg.qk_head_dim) == (
        576, 640, 192)
    assert (cfg.n_experts, cfg.top_k, cfg.d_expert, cfg.d_ff) == (
        256, 8, 768, 7168)
    assert (cfg.n_dense_layers, cfg.routed_scale) == (1, 2.5)
    assert cfg.n_kv_heads == 1 and cfg.rope_theta == 32e6
    # 1 dense + 4 expert layers, every expert, the whole vocabulary
    assert abs(cfg.num_params() / 1e6 - 5558) < 3


@pytest.mark.parametrize("change, why", [
    ({"model_type": "phi4flash"}, "not supported"),
    ({"n_group": 8}, "group-limited"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"scoring_func": "softmax"}, "sigmoid"),
])
def test_what_the_tree_cannot_run_is_refused_by_name(hf, change, why):
    with pytest.raises(ValueError, match=why):
        config_from_hf_json({**hf, **change})


def test_mistral_keys_still_map_to_the_dense_config():
    with open(os.path.join(
            ROOT, "benchmarks/configs/mistral7b-serve.json")) as f:
        cfg = config_from_hf_json(json.load(f))
    assert (cfg.d_model, cfg.n_kv_heads, cfg.sliding_window) == (
        4096, 8, 4096)


def test_init_and_shardings_have_the_tree_the_weights_module_makes(model):
    cfg, params = model
    own = init_latent_moe_model(jax.random.PRNGKey(0), cfg)
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    assert shapes(own) == shapes(params)
    rules = latent_moe_shardings(cfg)
    assert (jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, params))
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda s: 0, rules, is_leaf=lambda s: isinstance(
                s, jax.sharding.PartitionSpec))))
    flat = jax.tree_util.tree_leaves_with_path(params)
    specs = dict(jax.tree_util.tree_leaves_with_path(
        rules, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)))
    for path, leaf in flat:
        assert len(specs[path]) <= leaf.ndim, path
    assert isinstance(tiny_latent_moe_config(), type(cfg))


@pytest.mark.parametrize("layer", ["shared", "softmax"])
def test_rows_the_grouped_matmul_leaves_unwritten_reach_no_output(
        model, monkeypatch, layer):
    """The TPU's grouped-matmul kernels do not write the rows past the
    covered total (XLA's own on the CPU does, with zeros): whatever they
    hold, a masked token's output stays finite and a live one's
    unchanged, in JoyAI's layer and in SDAR's."""
    if layer == "shared":
        cfg, params = model
        ffn, moe = shared_routed_ffn, params["layers"][0]["moe"]
        kw = dict(top_k=cfg.top_k, routed_scale=cfg.routed_scale)
    else:
        cfg = tiny_sdar_config(dtype=jnp.float32)
        moe = init_sdar_model(jax.random.PRNGKey(3), cfg)["layers"][0]["moe"]
        ffn, kw = softmax_routed_ffn, dict(top_k=cfg.top_k)
    x = jnp.asarray(np.random.default_rng(4).normal(
        size=(5, cfg.d_model)), jnp.float32)
    kw["token_mask"] = jnp.asarray([True, False, True, False, True])
    want, _ = ffn(x, moe, **kw)
    assert bool(jnp.any(want[0] != 0))
    real = jax.lax.ragged_dot

    def unwritten_tail(lhs, rhs, group_sizes, **k):
        out = real(lhs, rhs, group_sizes, **k)
        covered = jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(covered[:, None], out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten_tail)
    got, _ = ffn(x, moe, **kw)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("compiled", [True, False])
def test_which_grouped_matmul_the_expert_layers_run(monkeypatch, compiled):
    """One rule for every expert layer, in ``ops/grouped.py::ragged_dot``:
    the Pallas kernel where it runs compiled (JoyAI's and SDAR's widths
    are whole 256s and take it too), ``jax.lax.ragged_dot`` on the CPU.
    Traced over shapes: no weight is made."""
    if compiled:
        monkeypatch.setattr(grouped, "_use_interpret", lambda: False)
    seen = []

    def spy(name):
        def dot(x, w, group_sizes):
            seen.append(name)
            return jnp.zeros((x.shape[0], w.shape[2]), x.dtype)
        return dot

    monkeypatch.setattr(grouped, "grouped_matmul", spy("pallas"))
    monkeypatch.setattr(jax.lax, "ragged_dot", spy("xla"))
    E, d, f = 4, 2048, 768
    mat = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    moe = {"router": jax.ShapeDtypeStruct((d, E), jnp.float32),
           "w_gate": mat(E, d, f), "w_up": mat(E, d, f),
           "w_down": mat(E, f, d)}
    shared = {**moe, "bias": jax.ShapeDtypeStruct((E,), jnp.float32),
              "shared": {"w_gate": mat(d, f), "w_up": mat(d, f),
                         "w_down": mat(f, d)}}
    jax.eval_shape(lambda x, p: softmax_routed_ffn(x, p, top_k=2),
                   mat(8, d), moe)
    jax.eval_shape(lambda x, p: shared_routed_ffn(
        x, p, top_k=2, routed_scale=2.5), mat(8, d), shared)
    assert seen == ["pallas" if compiled else "xla"] * 6
