"""State-space layers beside window and full attention (ISSUE 31): the
program against the benchmark's plain reference at a tiny size with
every layer kind present, seeded weights, float32, on the CPU.

Each mixer = the reference's layer; prefill then decode through the
three caches = the reference's full forward at every served position
(the ring of window pages wraps, the decode runs past two windows);
padding leaves state alone; a slot is reused cleanly; the cross-decoder
runs once a prompt; cache bytes are the architecture's; the published
keys give the 32 kinds and 3.85 B parameters; ``DecodeServer`` with
interleaved chunks serves the reference's tokens and accounts for the
three kinds.

Tolerances: logits are O(10) and both sides are float32 at ``highest``
precision, differing in the order of sums (online softmax over pages,
a padded score matmul, a chunked scan): 2e-4 absolute and relative, as
``test_latent_moe.py`` allows its family."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.model import phi4flash_reference as R
from benchmarks.model import phi4flash_weights as W
from nbdistributed_tpu.models import (DecodeServer, HybridConfig,
                                      config_from_hf_json,
                                      forward_with_cache,
                                      init_hybrid_model, layer_kinds_for,
                                      make_hybrid_cache, tiny_config,
                                      tiny_hybrid_config)
from nbdistributed_tpu.models.hybrid import (DiffAttnMixer, SSMMixer,
                                             cache_bytes_by_kind,
                                             hybrid_stacks, ring_pages)
from nbdistributed_tpu.observability.servingobs import ServingObservatory

pytestmark = [pytest.mark.unit, pytest.mark.serve]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 5
TOL = dict(rtol=2e-4, atol=2e-4)
BT, CHUNK, ROWS, MAX_LEN, BLOCKS = 8, 16, 3, 128, 40


def config_file():
    with open(os.path.join(
            ROOT, "benchmarks/configs/phi4-mini-flash-serve.json")) as f:
        return json.load(f)


def plain(cfg: dict) -> dict:
    return {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, str)) or v is None}


@pytest.fixture(scope="module")
def hf():
    """The benchmark configuration's rehearsal sizes (8 layers, every
    kind present, window 32), in float32."""
    cfg = config_file()
    return plain({**cfg, **cfg["rehearse"], "torch_dtype": "float32"})


@pytest.fixture(scope="module", params=[False, True],
                ids=["views", "kernel"])
def model(request, hf):
    """``kernel``: the decode step attends inside the Pallas kernel
    (interpreted here), window layers through the shifted table."""
    cfg = config_from_hf_json(hf, dtype=jnp.float32,
                              use_flash=request.param)
    return cfg, jax.jit(functools.partial(W.make_weights, cfg=hf))(
        W.seed_key(SEED))


@pytest.fixture(scope="module")
def views(hf):
    cfg = config_from_hf_json(hf, dtype=jnp.float32, use_flash=False)
    return cfg, jax.jit(functools.partial(W.make_weights, cfg=hf))(
        W.seed_key(SEED))


def tokens(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, n).tolist()


class Rows:
    """Prefill and decode through ``forward_with_cache`` as
    ``DecodeServer`` calls it, one row at a time, with a table laid out
    by hand."""

    def __init__(self, cfg, params, chunk=CHUNK):
        self.cfg, self.params, self.chunk = cfg, params, chunk
        self.cache = make_hybrid_cache(cfg, BLOCKS, BT, rows=ROWS,
                                       max_len=MAX_LEN, chunk=chunk)
        table = np.full((ROWS, MAX_LEN // BT), BLOCKS, np.int32)
        table[1] = 3 + np.arange(MAX_LEN // BT)     # row 1's blocks
        self.table = jnp.asarray(table)
        self._pre = jax.jit(self._prefill, static_argnames=("final",))
        self._dec = jax.jit(self._decode)

    def _prefill(self, cache, seg, start, n, slot, final):
        return forward_with_cache(
            self.params, seg, cache, start, self.cfg,
            token_mask=jnp.arange(seg.shape[1])[None] < n,
            last_index=(n - 1)[None], block_table=self.table[slot][None],
            slot=slot, final=final)

    def _decode(self, cache, last, lens, active):
        return forward_with_cache(self.params, last[:, None], cache, lens,
                                  self.cfg, row_mask=active,
                                  block_table=self.table)

    def prefill(self, prompt, slot=1, width=None):
        """In chunks of ``self.chunk`` (or one segment ``width`` wide);
        -> the last real token's logits."""
        ck = width or self.chunk
        for start in range(0, len(prompt), ck):
            seg = prompt[start:start + ck]
            pad = np.zeros((1, ck), np.int32)
            pad[0, :len(seg)] = seg
            logits, self.cache = self._pre(
                self.cache, jnp.asarray(pad), jnp.int32(start),
                jnp.int32(len(seg)), jnp.int32(slot),
                final=start + ck >= len(prompt))
        return logits[0, 0]

    def decode(self, tok, pos, slot=1):
        one = lambda v, dt: jnp.zeros((ROWS,), dt).at[slot].set(v)
        logits, self.cache = self._dec(
            self.cache, one(tok, jnp.int32), one(pos, jnp.int32),
            one(True, bool))
        return logits[slot, 0]


# ----------------------------------------------------------------------
# (a) each mixer = the reference's layer

def test_chunked_scan_is_the_token_recurrence_across_chunk_boundaries(
        hf, views):
    cfg, _ = views
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     W.layer_weights(W.seed_key(SEED), 2, hf, "ssm"))
    pw = W.program_layer(w, hf, "ssm")
    h = jax.random.normal(jax.random.PRNGKey(0), (45, cfg.d_model))
    ref_out, ref_y = R.mamba(h, w, hf)
    mixer = SSMMixer(cfg)
    state = jnp.zeros((1, cfg.d_state, cfg.d_inner))
    tail = jnp.zeros((1, cfg.d_conv - 1, cfg.d_inner))
    outs, ys = [], []
    for start in range(0, 45, 16):          # 16, 16, 13 real + 3 padded
        seg = h[start:start + 16]
        n = seg.shape[0]
        seg = jnp.concatenate([seg, jnp.ones((16 - n, cfg.d_model))])
        out, y, state, tail = mixer.mix(seg[None], pw, state, tail,
                                        jnp.arange(16)[None] < n)
        outs.append(out[0, :n])
        ys.append(y[0, :n])
    np.testing.assert_allclose(jnp.concatenate(outs), ref_out, **TOL)
    np.testing.assert_allclose(jnp.concatenate(ys), ref_y, **TOL)
    # the state after the padded chunk is the state after 45 tokens:
    # one more real token from it is the recurrence's 46th
    nxt = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.d_model))
    ref46 = R.mamba(jnp.concatenate([h, nxt]), w, hf)[0][45]
    out, *_ = mixer.mix(nxt[None], pw, state, tail, jnp.ones((1, 1), bool))
    np.testing.assert_allclose(out[0, 0], ref46, **TOL)


@pytest.mark.parametrize("kind, layer", [("window", 3), ("full", 5)])
def test_differential_attention_is_the_references(hf, views, kind, layer):
    """The padded-query grouped form over pairs of KV heads, and the
    permutation ``program_layer`` applies, against two plain softmaxes
    a pair of heads in the natural order."""
    cfg, _ = views
    z = W.sizes(hf)
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     W.layer_weights(W.seed_key(SEED), layer, hf, kind))
    pw = W.program_layer(w, hf, kind)
    s = 70
    h = jax.random.normal(jax.random.PRNGKey(2), (s, cfg.d_model))
    heads = lambda name, n: (h @ w["w" + name] + w["b" + name]).reshape(
        s, n, z["Dh"])
    ref = R.diff_attention(heads("q", z["H"]), heads("k", z["Hkv"]),
                           heads("v", z["Hkv"]), w, layer, hf,
                           z["window"] if kind == "window" else None) \
        @ w["wo"] + w["bo"]
    mixer = DiffAttnMixer(cfg, cfg.window_of(kind))
    o = mixer.attend(mixer.project_q(h[None], pw),
                     mixer.project_kv(h[None], pw), jnp.arange(s)[None])
    np.testing.assert_allclose(mixer.out(o, pw, layer)[0], ref, **TOL)


def reference_blocks(hf, toks):
    """The reference layer by layer, keeping what layers hand on."""
    key = W.seed_key(SEED)
    x = W.embed_weights(key, hf).astype(jnp.float32)[jnp.asarray(toks)]
    shared = {}
    for layer, kind in enumerate(W.kinds(hf)):
        w = jax.tree.map(lambda a: a.astype(jnp.float32),
                         W.layer_weights(key, layer, hf, kind))
        x, shared = R.block(x, shared, w, layer, kind, hf)
    return shared


def test_cross_attention_reads_the_full_layers_pages_and_writes_none(
        hf, views):
    """After prefill and decode, the shared pages hold the full
    layer's K and V of every token and nothing else: a cross layer
    that wrote (it has no K/V of its own to write) would show."""
    cfg, params = views
    toks = tokens(40)
    rows = Rows(cfg, params)
    rows.prefill(toks[:29])
    for pos in range(29, 40):
        rows.decode(toks[pos], pos)
    shared = reference_blocks(hf, toks)
    for name in ("k", "v"):
        pages = rows.cache["full"][name][0, 3:8]    # row 1's first 5 blocks
        got = pages.transpose(0, 2, 1, 3).reshape(40, -1)
        np.testing.assert_allclose(got, shared[name].reshape(40, -1), **TOL)
    # and no other block of the pool but the trash block was touched
    assert not np.asarray(rows.cache["full"]["k"][0, :3]).any()
    assert not np.asarray(rows.cache["full"]["k"][0, 8:BLOCKS]).any()


def test_memory_units_take_the_scan_output_before_the_gate(hf, views):
    cfg, params = views
    toks = tokens(24, seed=3)
    got = Rows(cfg, params).prefill(toks)
    ref = R.forward(SEED, hf, np.asarray([toks]))[0, -1]
    gated = R.forward(SEED, hf, np.asarray([toks]),
                      variant="gmu_gated")[0, -1]
    np.testing.assert_allclose(got, ref, **TOL)
    assert float(jnp.abs(gated - ref).max()) > 0.05


# ----------------------------------------------------------------------
# (b), (e) prefill then decode through the three caches

@pytest.mark.parametrize("prompt_len", [1, 31, 32, 33, 57])
def test_prefill_then_decode_is_the_references_forward(hf, model,
                                                       prompt_len):
    """Window 32, chunk 16, pages of 8: a ring of 7 pages (56 tokens).
    31 / 33 are window -+ 1, 57 = window + chunk + page + 1 (the ring
    has wrapped inside the prefill), and every row decodes to 100, past
    two windows, teacher-forced with the reference's input."""
    cfg, params = model
    toks = tokens(100, seed=prompt_len)
    ref = R.forward(SEED, hf, np.asarray([toks]))[0]
    rows = Rows(cfg, params)
    assert rows.cache["window"]["k"].shape[1] == ROWS * 7 + 1
    np.testing.assert_allclose(rows.prefill(toks[:prompt_len]),
                               ref[prompt_len - 1], **TOL)
    for pos in range(prompt_len, 100):
        np.testing.assert_allclose(rows.decode(toks[pos], pos), ref[pos],
                                   **TOL)


def test_a_chunk_that_does_not_end_its_prompt_returns_no_logits(views):
    cfg, params = views
    rows = Rows(cfg, params)
    pad = jnp.asarray([tokens(CHUNK)], jnp.int32)
    logits, _ = rows._pre(rows.cache, pad, jnp.int32(0), jnp.int32(CHUNK),
                          jnp.int32(1), final=False)
    assert logits is None


# ----------------------------------------------------------------------
# (c) padding leaves state and conv tail as the last real token left them

@pytest.mark.parametrize("n, width", [(5, 16), (21, 16), (16, 16)])
def test_padded_positions_leave_state_and_tail_untouched(views, n, width):
    """A padded bucket (5 of 16) and a padded last chunk (21 = 16 + 5
    of 16) against the same prompt run at its exact length."""
    cfg, params = views
    prompt = tokens(n, seed=7)
    padded, exact = Rows(cfg, params, chunk=width), Rows(cfg, params,
                                                         chunk=n)
    np.testing.assert_allclose(padded.prefill(prompt),
                               exact.prefill(prompt), **TOL)
    for name in ("state", "conv"):
        np.testing.assert_allclose(padded.cache["ssm"][name],
                                   exact.cache["ssm"][name], **TOL)
        assert np.asarray(padded.cache["ssm"][name][:, 1]).any()
        # and no other row's state moved
        assert not np.asarray(padded.cache["ssm"][name][:, 0]).any()


def test_an_inactive_row_keeps_its_state_under_a_decode_step(views):
    cfg, params = views
    rows = Rows(cfg, params)
    rows.prefill(tokens(20), slot=1)
    rows.prefill(tokens(9, seed=1), slot=2)
    before = jax.tree.map(np.asarray, rows.cache["ssm"])
    rows.decode(7, 20, slot=1)              # row 2 is inactive
    for name in ("state", "conv"):
        np.testing.assert_array_equal(rows.cache["ssm"][name][:, 2],
                                      before[name][:, 2])
        assert not np.array_equal(rows.cache["ssm"][name][:, 1],
                                  before[name][:, 1])


# ----------------------------------------------------------------------
# (d) a slot is reused cleanly

def server(cfg, params, **kw):
    kw = {"max_batch": 2, "max_len": MAX_LEN, "pad_to": 8,
          "kv_block_tokens": BT, "prefill_chunk": CHUNK,
          "interleave_prefill": True, **kw}
    return DecodeServer(params, cfg, **kw)


def test_a_reused_slot_serves_what_a_fresh_server_does(views):
    """One slot: request A is cut short with a step in flight that
    still runs its row (as a row whose EOS the host learns a step late
    is), so A's row has advanced its state once more when B takes the
    slot.  B's first chunk starts from zeros: B is served as alone."""
    cfg, params = views
    a, b = tokens(19, seed=11), tokens(37, seed=12)
    fresh = server(cfg, params, max_batch=1)
    want = fresh.submit(b, 10)
    fresh.run_until_done(200)

    srv = server(cfg, params, max_batch=1)
    ra = srv.submit(a, 12)
    for _ in range(5):
        srv.step()
    # the step in flight ran A's row past the last token A was given
    emitted = len(srv.outputs[ra])
    assert srv._flying.rows == {0: ra} and emitted < 12
    assert srv.cancel(ra)
    rb = srv.submit(b, 10)          # takes slot 0 behind the surplus step
    assert srv._prefilling and srv._flying is not None
    srv.run_until_done(400)
    assert len(srv.outputs[ra]) == emitted      # its token was dropped
    assert srv.outputs[rb] == fresh.outputs[want]


def test_replay_rebuilds_state_by_prefilling_what_was_emitted(views):
    """What the gateway does after a lost rank: a fresh server is given
    prompt + emitted tokens as the prompt and the budget that is left.
    State, rings and shared pages are rebuilt by that prefill."""
    cfg, params = views
    prompt = tokens(23, seed=21)
    whole = server(cfg, params)
    rid = whole.submit(prompt, 30)
    whole.run_until_done(200)
    out = whole.outputs[rid]
    again = server(cfg, params)
    rid2 = again.submit(prompt + out[:11], 19)
    again.run_until_done(200)
    assert again.outputs[rid2] == out[11:]


# ----------------------------------------------------------------------
# (f), (g) sizes

def test_cache_bytes_by_kind_are_the_architectures(hf):
    """At the published sizes and the cell's geometry: one full layer in
    blocks, eight window layers of at most window + chunk + page tokens
    a row, state in rows."""
    cfg = config_from_hf_json(plain(config_file()))
    rows, max_len, bt, chunk = 64, 4096, 64, 512
    blocks = rows * max_len // bt
    cache = jax.eval_shape(lambda: make_hybrid_cache(
        cfg, blocks, bt, rows=rows, max_len=max_len, chunk=chunk))
    token = 2 * 20 * 64 * 2                 # K and V, 20 heads of 64, bf16
    assert token == 5120
    ring = ring_pages(cfg, bt, max_len, chunk)
    assert ring * bt == 512 + 512 + 64 == 1088
    got = {k: sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree.leaves(v)) for k, v in cache.items()}
    assert got == {
        "full": (blocks + 1) * bt * token,
        "window": 8 * (rows * ring + 1) * bt * token,
        "ssm": 9 * rows * (5120 * 16 * 4 + 5120 * 3 * 2)}
    assert got["ssm"] // rows == 3225600             # 3.23 MB a row
    assert got["window"] < 2.86e9 < 10.7e9 < 8 * rows * max_len * token \
        + 1e8
    assert cache["full"]["k"].shape == (1, blocks + 1, 10, 64, 128)
    # a row that is shorter than the ring holds no more than itself
    assert ring_pages(cfg, bt, 512, None) == 8


def test_published_keys_give_the_32_kinds_and_the_parameter_count(hf):
    published = plain(config_file())
    cfg = config_from_hf_json(published, dtype=jnp.bfloat16)
    assert isinstance(cfg, HybridConfig)
    want = (["ssm", "window"] * 8 + ["ssm", "full"] + ["gmu", "cross"] * 7)
    assert list(cfg.layer_kinds) == want == W.kinds(published)
    assert hybrid_stacks(cfg) == (8, 7)
    assert (cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank) == (
        5120, 16, 4, 160)
    assert (cfg.sliding_window, cfg.norm_eps, cfg.head_dim) == (
        512, 1e-5, 64)
    assert round(cfg.num_params() / 1e6, 1) == 3852.6
    # the tree the weights module makes is the tree the program inits,
    # and holds that many parameters (no second copy of the embedding)
    tiny = config_from_hf_json(hf, dtype=jnp.float32)
    made = jax.eval_shape(functools.partial(W.make_weights, cfg=hf),
                          W.seed_key(0))
    init = jax.eval_shape(lambda k: init_hybrid_model(k, tiny),
                          jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree.map(lambda a: a.shape, t)
    assert shapes(made) == shapes(init)
    assert "lm_head" not in made
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(made)) \
        == tiny.num_params()


@pytest.mark.parametrize("change, why", [
    ({"tie_word_embeddings": False}, "untied head"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"model_type": "phi5"}, "not supported"),
])
def test_what_the_tree_cannot_run_is_refused_by_name(hf, change, why):
    with pytest.raises(ValueError, match=why):
        config_from_hf_json({**hf, **change})


def test_an_order_of_kinds_the_stacks_cannot_run_is_refused():
    assert layer_kinds_for(8, 2) == tiny_hybrid_config().layer_kinds
    with pytest.raises(ValueError, match="layer_kinds must be"):
        hybrid_stacks(tiny_hybrid_config(
            layer_kinds=("ssm", "full", "ssm", "window", "gmu", "cross",
                         "gmu", "cross")))
    with pytest.raises(ValueError, match="pairs two query heads"):
        hybrid_stacks(tiny_hybrid_config(n_kv_heads=8))


def test_a_dense_cache_or_a_mesh_is_refused_with_a_reason(views):
    cfg, params = views
    with pytest.raises(ValueError, match="paged caches"):
        forward_with_cache(params, jnp.zeros((1, 4), jnp.int32), {}, 0, cfg)
    with pytest.raises(ValueError, match="unquantized"):
        server(cfg, params, kv_quantized=True)


# ----------------------------------------------------------------------
# (h) DecodeServer, interleaved chunks, several requests; the account

def test_server_with_interleaved_chunks_serves_the_references_tokens(
        hf, views):
    cfg, params = views
    srv = server(cfg, params, max_batch=3)
    prompts = [tokens(n, seed=30 + n) for n in (5, 37, 16, 50, 9)]
    rids = [srv.submit(p, 20) for p in prompts]
    outs = srv.run_until_done(2000)
    for rid, p in zip(rids, prompts):
        ref = R.forward(SEED, hf, np.asarray([p + outs[rid]]))[0]
        at = len(p) - 1 + np.arange(20)
        gaps = ref[at].max(-1) - ref[at, np.asarray(outs[rid])]
        assert float(gaps.max()) < 2e-4      # the reference's own choice
    acc = srv.take_account()
    # one cross-decoder run a prompt, over all the prompt's keys; the
    # chunk programs: 1 + 3 + 1 + 4 + 1
    assert acc["xdec"] == [5, 10, sum(len(p) for p in prompts)]
    assert acc["pf"] == sum(len(p) for p in prompts)
    steps = acc["kvr"][1]
    assert steps > 0 and acc["st"] == [steps * srv._state_bytes, steps]
    assert set(acc["kvk"]) == {"full", "window"}
    assert acc["kvr"][0] == sum(acc["kvk"].values())
    assert min(acc["kvk"].values()) > 0
    assert srv.take_account()["st"] == [0, 0]


def test_kv_read_bytes_count_each_kind_by_its_window_and_readers(views):
    """One row whose next step writes position 71 (pages of 8): the
    full layer's 9 pages are read by every reading layer (itself and
    the Q cross layers), the window layers read their window's 4 pages
    a layer (positions 40..71 span pages 5..8)."""
    cfg, params = views
    srv = server(cfg, params, max_batch=2)
    srv.submit(tokens(70), 3)
    while not srv._run:                     # five chunks stream in,
        srv.step()                          # and the first step goes
    assert srv._run[0][0] == 71
    page = cfg.kv_pairs * BT * cfg.pair_dim * 4 * 2     # K and V, float32
    p, q = hybrid_stacks(cfg)
    assert [k.page_bytes for k in srv._kinds] == [page * (1 + q), page * p]
    assert [k.window for k in srv._kinds] == [None, 32]
    assert srv._step_kv_read_bytes() == (9 * page * (1 + q), 4 * page * p)
    # a chunk's keys are its window layers': 16 tokens from position 48
    # attend from position 17's page (2) to 63's (7)
    assert srv._chunk_keys(48, 16) == 6 * BT
    assert srv._state_bytes == 2 * cache_bytes_by_kind(srv._cache)["ssm"]
    srv.run_until_done(50)


def test_window_and_readers_come_from_the_kind_for_a_dense_model():
    from nbdistributed_tpu.models import init_params
    cfg = tiny_config(sliding_window=16, dtype=jnp.float32,
                      use_flash=False)
    srv = DecodeServer(init_params(jax.random.PRNGKey(0), cfg), cfg,
                       max_batch=2, max_len=64, kv_block_tokens=8)
    (kind,) = srv._kinds
    assert (kind.name, kind.window) == ("kv", 16)
    assert kind.page_bytes == srv._page_bytes
    assert srv._first_live_page(kind, 40) == 3
    assert "kvk" not in srv.take_account()


def test_rows_of_state_are_the_slots_and_the_snapshot_shows_three_kinds(
        views):
    """A free slot is a free row of state and of window rings, so rows
    never refuse before the slot count does: with blocks to spare the
    third request waits for a slot, not for a row, and the allocator
    (which the gateway mirrors) counts the full layer's blocks alone."""
    cfg, params = views
    srv = server(cfg, params, max_batch=2, kv_blocks=64)
    for seed in range(3):
        srv.submit(tokens(10, seed=seed), 6)
    snap = srv.kv_snapshot()
    assert len(srv._pending) == 1 and snap["free"] == 64 - 2 * 2
    kinds = snap["kinds"]
    assert kinds["full"]["used"] == snap["used"] == 4
    assert kinds["window"]["used"] == kinds["state"]["used"] == 2
    assert kinds["window"]["rows"] == kinds["state"]["rows"] == 2
    assert kinds["window"]["ring_pages"] == 7
    assert {k: v["bytes"] for k, v in kinds.items()} == {
        "full": cache_bytes_by_kind(srv._cache)["full"],
        "window": cache_bytes_by_kind(srv._cache)["window"],
        "state": cache_bytes_by_kind(srv._cache)["ssm"]}
    srv.run_until_done(200)
    assert srv.kv_snapshot()["kinds"]["state"]["used"] == 0


def test_ticks_summary_reads_state_kinds_and_cross_decoder_share():
    obs = ServingObservatory()
    base = {"ph": {}, "kvr": [300, 2], "pfk": [64, 4], "ahd": [2, 2]}
    obs.note_tick(1, 0, {}, {**base, "kvk": {"full": 200, "window": 100},
                             "st": [1000, 2], "xdec": [1, 4, 40]})
    obs.note_tick(2, 0, {}, {**base, "kvk": {"full": 400, "window": 100},
                             "st": [1000, 2], "xdec": [2, 2, 80]})
    t = obs.ticks_summary()
    assert t["state_bytes"] == 500
    assert t["kv_read_bytes_by_kind"] == {"full": 150, "window": 50}
    assert t["cross_decoder_share"] == 0.5
    plain_obs = ServingObservatory()
    plain_obs.note_tick(1, 0, {}, base)
    assert "state_bytes" not in plain_obs.ticks_summary()
