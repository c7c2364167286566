"""Mamba-2, attention and expert layers in an order given as a string
(ISSUE 34): the program against the benchmark's plain reference at a
tiny size with every layer kind present, seeded weights, float32, on
the CPU.

The block form of the recurrence = the reference's token-by-token
recurrence, from a non-zero state, whole blocks and not; the two shares
of an expert layer add up to the reference's uncut layer, and ``(0, E)``
with the SwiGLU form is the function JoyAI's layer called before it
took a share; prefill in chunks then decode through the caches = the
reference's full forward at every served position, with rows of
unequal length and an idle row; padding leaves state alone;
``DecodeServer`` with interleaved chunks serves the reference's own
choice and accounts for state, pages and the experts held.

Tolerances: logits are O(1..10) and both sides are float32 at
``highest`` precision, differing in the order of sums (the block form's
``(Q x Q)`` products against a running state, online softmax over
pages, grouped matmuls over sorted rows): 2e-4 absolute and relative,
as ``test_phi4flash.py`` and ``test_latent_moe.py`` allow theirs.  The
share test's is stated where it is made."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.model import nemotronh_reference as R
from benchmarks.model import nemotronh_weights as W
from nbdistributed_tpu.models import (DecodeServer, NemotronHConfig,
                                      config_from_hf_json,
                                      forward_with_cache,
                                      init_nemotron_h_model,
                                      make_hybrid_cache,
                                      nemotron3_nano_config,
                                      tiny_latent_moe_config,
                                      tiny_nemotron_h_config)
from nbdistributed_tpu.models.hybrid import cache_bytes_by_kind
from nbdistributed_tpu.models.nemotron_h import Mamba2Mixer, check_pattern
from nbdistributed_tpu.observability.servingobs import (TICK_TOTALS,
                                                        ServingObservatory)
from nbdistributed_tpu.parallel.expert import (_dropless_ffn, routing_load,
                                               shared_routed_ffn,
                                               sigmoid_bias_routing)

pytestmark = [pytest.mark.unit, pytest.mark.serve]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 7
TOL = dict(rtol=2e-4, atol=2e-4)
BT, CHUNK, ROWS, MAX_LEN, BLOCKS = 8, 16, 3, 96, 40


def config_file():
    with open(os.path.join(
            ROOT, "benchmarks/configs/nemotron3-nano-serve.json")) as f:
        return json.load(f)


def plain(cfg: dict) -> dict:
    return {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, str)) or v is None}


@pytest.fixture(scope="module")
def hf():
    """The benchmark configuration's rehearsal sizes (``MEM*EME``: every
    kind, a state-space layer after the attention layer, a trailing
    expert layer; 4 of 8 experts held; blocks of 8), in float32."""
    cfg = config_file()
    return plain({**cfg, **cfg["rehearse"], "torch_dtype": "float32"})


def make(hf, use_flash):
    cfg = config_from_hf_json(hf, dtype=jnp.float32, use_flash=use_flash)
    return cfg, jax.jit(functools.partial(W.make_weights, cfg=hf))(
        W.seed_key(SEED))


@pytest.fixture(scope="module", params=[False, True],
                ids=["views", "kernel"])
def model(request, hf):
    """``kernel``: the decode step attends inside the Pallas kernel
    (interpreted here)."""
    return make(hf, request.param)


@pytest.fixture(scope="module")
def views(hf):
    return make(hf, False)


def tokens(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, n).tolist()


class Rows:
    """Prefill and decode through ``forward_with_cache`` as
    ``DecodeServer`` calls it, with a table laid out by hand: rows 0
    and 1 hold requests, row 2 stays idle."""

    def __init__(self, cfg, params, chunk=CHUNK):
        self.cfg, self.params, self.chunk = cfg, params, chunk
        self.cache = make_hybrid_cache(cfg, BLOCKS, BT, rows=ROWS,
                                       max_len=MAX_LEN, chunk=chunk)
        per = MAX_LEN // BT
        table = np.full((ROWS, per), BLOCKS, np.int32)
        table[0] = 20 + np.arange(per)
        table[1] = 3 + np.arange(per)
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

    def decode(self, toks: dict, pos: dict):
        """One step over the rows of ``toks`` (slot -> token) at
        ``pos`` -> logits (ROWS, V)."""
        col = lambda d, dt: jnp.asarray(
            [d.get(s, 0) for s in range(ROWS)], dt)
        logits, self.cache = self._dec(
            self.cache, col(toks, jnp.int32), col(pos, jnp.int32),
            col({s: True for s in toks}, bool))
        return logits[:, 0]


# ----------------------------------------------------------------------
# (a) the block form = the token-by-token recurrence

def mamba_layer(hf, layer=0):
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     W.layer_weights(W.seed_key(SEED), layer, hf, "mamba2"))
    return w, W.program_layer(w, "mamba2")


@pytest.mark.parametrize("chunks", [
    [(16, 16), (16, 16), (16, 16)],     # whole blocks of 8
    [(16, 16), (13, 16)],       # ends inside a block, padded to two
    [(5, 5)],                   # shorter than a block, not padded
    [(12, 12)],                 # a block and a half, not padded
    [(24, 24), (3, 8), (16, 16)],
])
def test_block_form_is_the_token_recurrence_from_a_nonzero_state(
        hf, views, chunks):
    """Chunks of (real tokens, width): multiples of the block (8) and
    not, padded and not, carried from a state that is not zero (the
    reference run over a first stretch of 11 tokens)."""
    cfg, _ = views
    w, pw = mamba_layer(hf)
    total = sum(n for n, _ in chunks)
    h = jax.random.normal(jax.random.PRNGKey(0), (11 + total, cfg.d_model))
    ref_out, ref_state = R.mamba2(h, w, hf)
    _, state0 = R.mamba2(h[:11], w, hf)
    assert float(jnp.abs(state0).max()) > 1e-3
    mixer = Mamba2Mixer(cfg)
    state = state0[None]
    tail = (h[8:11] @ w["w_in"])[None, :, cfg.d_inner:cfg.d_inner
                                 + cfg.conv_width]
    outs, at = [], 11
    for n, width in chunks:
        seg = jnp.concatenate([h[at:at + n],
                               jnp.ones((width - n, cfg.d_model))])
        out, state, tail = mixer.mix(seg[None], pw, state, tail,
                                     jnp.arange(width)[None] < n)
        outs.append(out[0, :n])
        at += n
    np.testing.assert_allclose(jnp.concatenate(outs), ref_out[11:], **TOL)
    np.testing.assert_allclose(state[0], ref_state, **TOL)
    # and the decode step from there is the recurrence's next token
    nxt = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.d_model))
    want = R.mamba2(jnp.concatenate([h, nxt]), w, hf)[0][-1]
    out, *_ = mixer.mix(nxt[None], pw, state, tail, jnp.ones((1, 1), bool))
    np.testing.assert_allclose(out[0, 0], want, **TOL)


def test_a_block_longer_than_the_chunk_and_one_block_a_chunk_agree(
        hf, views):
    cfg, _ = views
    w, pw = mamba_layer(hf, 2)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 32, cfg.d_model))
    zeros = lambda c: (jnp.zeros((1, c.ssm_heads, c.ssm_head_dim,
                                  c.d_state)),
                       jnp.zeros((1, c.d_conv - 1, c.conv_width)))
    valid = jnp.ones((1, 32), bool)
    small = Mamba2Mixer(cfg).mix(h, pw, *zeros(cfg), valid)
    import dataclasses
    big = dataclasses.replace(cfg, ssm_block=128)
    large = Mamba2Mixer(big).mix(h, pw, *zeros(big), valid)
    for a, b in zip(small, large):
        np.testing.assert_allclose(a, b, **TOL)


# ----------------------------------------------------------------------
# (b) the share of experts

def expert_layer(hf, layer, held):
    """(reference weights with ``held``'s experts, the program's moe
    tree with the same)."""
    key = W.seed_key(SEED)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    w = f32(W.layer_weights(key, layer, hf, "experts"))
    ew = f32(W.expert_weights(key, layer, hf, held))
    return {**w, "experts": ew}, W.program_layer(w, "experts", ew)["moe"]


def test_two_shares_add_up_to_the_references_uncut_layer(hf):
    """Shares ``(0, 4)`` and ``(4, 4)`` of 8 experts, the shared expert
    counted once, against the reference's layer over all 8.  Same
    tolerance as the rest: the program sums a token's experts in sorted
    order through a scatter-add, the reference one expert after the
    other."""
    z = W.sizes(hf)
    er, kw = z["Er"], dict(top_k=z["k"], expert="relu2",
                           routed_scale=hf["routed_scaling_factor"])
    assert (er, z["E"]) == (8, 4)
    h = jax.random.normal(jax.random.PRNGKey(4), (37, z["D"]))
    ref_w, _ = expert_layer(hf, 1, (0, er))
    want, _ = R.experts_block(h, ref_w, hf, held=(0, er))
    shared = R.relu2(h, ref_w["shared"])
    got, loads = 0.0, []
    for held in ((0, er // 2), (er // 2, er // 2)):
        ref_share, moe = expert_layer(hf, 1, held)
        y, load = shared_routed_ffn(h, moe, held=held, **kw)
        # the program's share is the reference's given the same share
        np.testing.assert_allclose(
            y, R.experts_block(h, ref_share, hf, held=held)[0], **TOL)
        got = got + y - shared
        loads.append(load)
    np.testing.assert_allclose(got + shared, want, **TOL)
    # every choice falls in one share or the other
    assert float(loads[0][2] + loads[1][2]) == 37 * z["k"]
    assert 0 < float(loads[0][2]) < 37 * z["k"]
    assert float(loads[0][0]) <= er // 2


def test_the_whole_share_in_swiglu_form_is_the_layer_joyai_called():
    """``held=(0, E)``, ``expert="swiglu"`` (the defaults) against the
    function as it stood before it took a share, written out here:
    route, dropless SwiGLU segments over all E, the shared SwiGLU.
    Equal to the bit: the order of operations did not change."""
    cfg = tiny_latent_moe_config(dtype=jnp.float32)
    from nbdistributed_tpu.models import init_latent_moe_model
    moe = init_latent_moe_model(jax.random.PRNGKey(2), cfg)["layers"][0][
        "moe"]
    moe = {**moe, "bias": 0.1 * jax.random.normal(
        jax.random.PRNGKey(3), moe["bias"].shape)}
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 9, cfg.d_model))
    mask = jnp.arange(9)[None, :] < jnp.asarray([9, 4, 0])[:, None]
    E = cfg.n_experts

    def before(x, params, top_k, routed_scale, token_mask):
        xt = x.reshape(-1, x.shape[-1])
        logits = jnp.matmul(xt.astype(jnp.float32), params["router"],
                            precision=jax.lax.Precision.HIGHEST)
        gates, idx = sigmoid_bias_routing(logits, params["bias"], top_k,
                                          routed_scale)
        y = _dropless_ffn(xt, params, gates, idx, E,
                          token_mask=token_mask.reshape(-1))
        s = params["shared"]
        y = y + (jax.nn.silu(xt @ s["w_gate"]) * (xt @ s["w_up"])) \
            @ s["w_down"]
        return y.reshape(x.shape), routing_load(idx, E,
                                                token_mask.reshape(-1))

    kw = dict(top_k=cfg.top_k, routed_scale=cfg.routed_scale,
              token_mask=mask)
    want, want_load = before(x, moe, **kw)
    for extra in ({}, {"held": (0, E), "expert": "swiglu"}):
        got, load = shared_routed_ffn(x, moe, **kw, **extra)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(load, want_load)


@pytest.mark.parametrize("m, sizes", [
    (256, [100, 0, 56, 30]),
    (74, [0, 9, 40, 25]),
    (128, [0, 0, 0, 0]),
    # SDAR's call in small: many groups inside one row tile, empty
    # ones among them, one across the tile's edge (rows 111-140), a
    # total (219) under m
    (256, [20, 0, 13, 31, 7, 0, 40, 30, 9, 16, 0, 25, 12, 8, 5, 3]),
    # four groups share every tile exactly, and the total is m
    (512, [32] * 16)])
def test_grouped_matmul_is_ragged_dot_over_the_rows_in_a_group(m, sizes):
    """The Pallas grouped matmul (interpreted here) against XLA's
    ``ragged_dot`` on the rows the groups cover; rows past their total
    are undefined.  A row count that is not whole tiles is padded."""
    from nbdistributed_tpu.ops.grouped import _column_tile, grouped_matmul
    x = jax.random.normal(jax.random.PRNGKey(0), (m, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (len(sizes), 64, 256)) / 8
    gs = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul(x, w, gs)
    want = jax.lax.ragged_dot(x, w, gs)
    n = sum(sizes)
    assert got.shape == (m, 256)
    np.testing.assert_allclose(got[:n], want[:n], **TOL)
    # tiles at the published widths: a third of w_up's stored columns
    # (3.4 MB of weights a block), a third of w_down's
    assert _column_tile(2688, 1920, 2) == 640
    assert _column_tile(1856, 2688, 2) == 896
    assert _column_tile(2048, 768, 2) == 768 and _column_tile(64, 32, 4) == 32
    # SDAR's: a tile is one expert's whole matrix, 3 MiB
    assert _column_tile(768, 2048, 2) == 2048


@pytest.mark.parametrize("m, sizes", [(256, [100, 0, 56, 30]),
                                      (128, [32] * 4)])
def test_grouped_matmul_has_ragged_dots_derivative(m, sizes):
    """A training step differentiates the expert layer: the kernel's
    derivative is ``ragged_dot``'s, in ``x`` and in ``w``, and the rows
    past the groups' total give and take nothing."""
    from nbdistributed_tpu.ops.grouped import grouped_matmul
    x = jax.random.normal(jax.random.PRNGKey(0), (m, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (len(sizes), 64, 256)) / 8
    gs = jnp.asarray(sizes, jnp.int32)
    covered = (jnp.arange(m) < sum(sizes))[:, None]
    weigh = jax.random.normal(jax.random.PRNGKey(2), (m, 256))
    loss = lambda dot: lambda x, w: jnp.sum(
        jnp.where(covered, dot(x, w, gs), 0) * weigh)
    got = jax.grad(loss(grouped_matmul), (0, 1))(x, w)
    want = jax.grad(loss(jax.lax.ragged_dot), (0, 1))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[0])[sum(sizes):].any()


def test_routing_load_counts_over_the_experts_held():
    idx = jnp.asarray([[0, 3], [3, 4], [1, 4]])     # 4 = held elsewhere
    np.testing.assert_array_equal(routing_load(idx, 4), [3, 2, 4])
    np.testing.assert_array_equal(
        routing_load(idx, 4, jnp.asarray([True, False, True])), [3, 1, 3])


def test_a_share_that_is_not_one_is_refused():
    with pytest.raises(ValueError, match="not a share"):
        check_pattern(tiny_nemotron_h_config(experts_held=(6, 4)))
    with pytest.raises(ValueError, match="pattern must be"):
        check_pattern(tiny_nemotron_h_config(pattern="ME-M*E"))
    with pytest.raises(ValueError, match="at least one"):
        check_pattern(tiny_nemotron_h_config(pattern="MEMMEE"))


# ----------------------------------------------------------------------
# (c) prefill in chunks, then decode, through the caches

@pytest.mark.parametrize("lens", [(1, 21), (16, 33), (37, 8)])
def test_prefill_then_decode_is_the_references_forward(hf, model, lens):
    """Two rows of unequal length and an idle one: each prompt in chunks
    of 16 (blocks of 8; 21, 33 and 37 end inside a block), then both
    rows decode together, teacher-forced with the reference's input, to
    60 and 45 tokens: the shorter row stops first and the other goes on
    beside two idle rows."""
    cfg, params = model
    ends = (60, 45)
    seqs = [tokens(e, seed=10 * n + e) for n, e in zip(lens, ends)]
    refs = [R.forward(SEED, hf, np.asarray([s]))[0] for s in seqs]
    rows = Rows(cfg, params)
    for slot, (n, seq, ref) in enumerate(zip(lens, seqs, refs)):
        np.testing.assert_allclose(rows.prefill(seq[:n], slot=slot),
                                   ref[n - 1], **TOL)
    pos = dict(enumerate(lens))
    while pos:
        logits = rows.decode({s: seqs[s][p] for s, p in pos.items()}, pos)
        for s, p in list(pos.items()):
            np.testing.assert_allclose(logits[s], refs[s][p], **TOL)
            pos[s] = p + 1
            if pos[s] == ends[s]:
                del pos[s]
    # the idle row's state never moved
    for leaf in jax.tree.leaves(rows.cache["ssm"]):
        assert not np.asarray(leaf[2]).any()


def test_a_chunk_that_does_not_end_its_prompt_returns_no_logits(views):
    cfg, params = views
    rows = Rows(cfg, params)
    pad = jnp.asarray([tokens(CHUNK)], jnp.int32)
    logits, _ = rows._pre(rows.cache, pad, jnp.int32(0), jnp.int32(CHUNK),
                          jnp.int32(1), final=False)
    assert logits is None


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
    for got, want in zip(jax.tree.leaves(padded.cache["ssm"]),
                         jax.tree.leaves(exact.cache["ssm"])):
        np.testing.assert_allclose(got, want, **TOL)
        assert np.asarray(got[1]).any()
        assert not np.asarray(got[0]).any()


def test_an_inactive_row_keeps_its_state_under_a_decode_step(views):
    cfg, params = views
    rows = Rows(cfg, params)
    rows.prefill(tokens(20), slot=1)
    rows.prefill(tokens(9, seed=1), slot=0)
    before = [np.asarray(a) for a in jax.tree.leaves(rows.cache["ssm"])]
    rows.decode({1: 7}, {1: 20})                # row 0 is inactive
    for now, was in zip(jax.tree.leaves(rows.cache["ssm"]), before):
        np.testing.assert_array_equal(now[0], was[0])
        assert not np.array_equal(now[1], was[1])


# ----------------------------------------------------------------------
# (d) DecodeServer

def server(cfg, params, **kw):
    kw = {"max_batch": 3, "max_len": MAX_LEN, "pad_to": 8,
          "kv_block_tokens": BT, "prefill_chunk": CHUNK,
          "interleave_prefill": True, **kw}
    return DecodeServer(params, cfg, **kw)


def test_server_with_interleaved_chunks_serves_the_references_choice(
        hf, views):
    """No dense cache, so no ``generate``: each served token is the
    reference's own first choice at its position (to 2e-4 of its best
    logit), over five requests of unequal length on three slots."""
    cfg, params = views
    srv = server(cfg, params)
    prompts = [tokens(n, seed=30 + n) for n in (5, 37, 16, 50, 9)]
    rids = [srv.submit(p, 20) for p in prompts]
    outs = srv.run_until_done(2000)
    for rid, p in zip(rids, prompts):
        ref = R.forward(SEED, hf, np.asarray([p + outs[rid]]))[0]
        at = len(p) - 1 + np.arange(20)
        gaps = ref[at].max(-1) - ref[at, np.asarray(outs[rid])]
        assert float(gaps.max()) < 2e-4
    acc = srv.take_account()
    # a chunk that ends its prompt runs the trailing expert layer:
    # one a prompt; the chunk programs: 1 + 3 + 1 + 4 + 1
    assert acc["xdec"] == [5, 10, sum(len(p) for p in prompts)]
    assert acc["pf"] == sum(len(p) for p in prompts)
    steps = acc["kvr"][1]
    assert steps > 0 and acc["st"] == [steps * srv._state_bytes, steps]
    assert set(acc["kvk"]) == {"full"} and acc["kvr"][0] == acc["kvk"]["full"]
    # experts touched a step, of the 4 held; rows routed at most 2 a row
    touched, most, routed = acc["moe"]
    assert 0 < touched <= 4 * steps and 0 < routed <= 3 * 2 * steps
    assert srv.take_account()["st"] == [0, 0]


def test_a_reused_slot_serves_what_a_fresh_server_does(views):
    cfg, params = views
    a, b = tokens(19, seed=11), tokens(37, seed=12)
    fresh = server(cfg, params, max_batch=1)
    want = fresh.submit(b, 10)
    fresh.run_until_done(200)
    srv = server(cfg, params, max_batch=1)
    ra = srv.submit(a, 12)
    for _ in range(5):
        srv.step()
    assert srv._flying.rows == {0: ra}
    assert srv.cancel(ra)
    rb = srv.submit(b, 10)
    srv.run_until_done(400)
    assert srv.outputs[rb] == fresh.outputs[want]


def test_kinds_of_cache_and_their_bytes(views):
    cfg, params = views
    srv = server(cfg, params, max_batch=2, kv_blocks=64)
    (kind,) = srv._kinds
    page = 2 * cfg.n_kv_heads * BT * cfg.head_dim * 4   # K and V, float32
    assert (kind.name, kind.window, kind.page_bytes) == (
        "full", None, page * cfg.layer_kinds.count("attention"))
    assert srv._state_bytes == 2 * cache_bytes_by_kind(srv._cache)["ssm"]
    srv.submit(tokens(10), 6)
    kinds = srv.kv_snapshot()["kinds"]
    assert set(kinds) == {"full", "state"}
    assert kinds["state"]["used"] == 1 and kinds["full"]["used"] == 2
    srv.run_until_done(100)


def test_tick_totals_outlast_the_ring_and_two_readings_give_a_slice():
    """``ticks.totals`` sums what the ticks counted since the start, so
    the difference of two readings is what ran between them (the
    benchmark's slice under the profiler), whatever the ring forgot."""
    obs = ServingObservatory()
    tick = {"ph": {}, "kvr": [300, 8], "pfk": [64, 2], "ahd": [8, 8],
            "dc": 100, "pf": 700, "st": [1000, 8],
            "moe": [500.0, 9.0, 3000.0]}
    for seq in range(70):                   # the ring holds 64
        obs.note_tick(seq, 0, {}, tick)
    # (what these ticks do not carry, the frames' and the pushes'
    # counts of PR 38, stays 0)
    zero = dict.fromkeys(TICK_TOTALS, 0.0)
    first = obs.ticks_summary()["totals"]
    assert first == {**zero,
                     "steps": 560.0, "dc": 7000.0, "pf": 49000.0,
                     "chunks": 140.0, "state_bytes": 70000.0,
                     "moe_touched": 35000.0, "moe_rows": 210000.0,
                     "kv_bytes": 21000.0}
    obs.note_tick(70, 0, {}, {**tick, "moe": None})
    second = obs.ticks_summary()["totals"]
    assert {k: second[k] - first[k] for k in first} == {
        **zero,
        "steps": 8.0, "dc": 100.0, "pf": 700.0, "chunks": 2.0,
        "state_bytes": 1000.0, "moe_touched": 0.0, "moe_rows": 0.0,
        "kv_bytes": 300.0}


# ----------------------------------------------------------------------
# (e) the published keys

def test_published_keys_give_the_cut_and_the_parameter_counts(hf):
    published = plain(config_file())
    cfg = config_from_hf_json(published, dtype=jnp.bfloat16)
    assert isinstance(cfg, NemotronHConfig)
    assert cfg.pattern == "MEMEM*EMEMEM*E" and cfg.n_layers == 14
    assert (cfg.n_experts, cfg.held, cfg.top_k) == (128, (0, 64), 6)
    assert (cfg.d_inner, cfg.conv_width, cfg.d_state, cfg.ssm_block) == (
        4096, 6144, 128, 128)
    assert (cfg.head_dim, cfg.n_heads, cfg.n_kv_heads) == (128, 32, 2)
    assert cfg.tail_from == 13
    whole = nemotron3_nano_config()
    assert whole.pattern[:14] == cfg.pattern
    assert [whole.pattern.count(c) for c in "ME*"] == [23, 23, 6]
    assert round(whole.num_params() / 1e9, 2) == 31.58
    assert round(cfg.num_params() / 1e6) == 4937
    # state and pages at the cell's geometry: 2.134 MB a row a layer of
    # state and tails, 1,024 B a token a layer of K and V
    cache = jax.eval_shape(lambda: make_hybrid_cache(
        cfg, 128 * 64, 64, rows=128, max_len=4096, chunk=512))
    size = {k: sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(v)) for k, v in cache.items()}
    assert size["ssm"] == 128 * 6 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert size["full"] == 2 * (128 * 64 + 1) * 64 * 1024
    # a leaf a state-space layer, the state's 128 on the lanes
    assert [a.shape for a in cache["ssm"]["state"]] == [
        (128, 64, 64, 128)] * 6
    # an expert's w_up is stored 1,920 wide (15 x 128) and counted 1,856
    assert cfg.d_expert_stored == 1920
    # the tree the weights module makes is the tree the program inits
    tiny = config_from_hf_json(hf, dtype=jnp.float32)
    made = jax.eval_shape(functools.partial(W.make_weights, cfg=hf),
                          W.seed_key(0))
    init = jax.eval_shape(lambda k: init_nemotron_h_model(k, tiny),
                          jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)
    assert shapes(made) == shapes(init)
    padding = (tiny.layer_kinds.count("experts") * tiny.held[1]
               * tiny.d_model * (tiny.d_expert_stored - tiny.d_expert))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(made)) \
        == tiny.num_params() + padding


@pytest.mark.parametrize("change, why", [
    ({"tie_word_embeddings": True}, "tied head"),
    ({"mlp_hidden_act": "silu"}, "relu2"),
    ({"n_group": 2}, "group-limited"),
    ({"hybrid_override_pattern": "ME-M*EM"}, "only layers of kinds"),
    ({"use_conv_bias": False}, "biases"),
])
def test_what_the_tree_cannot_run_is_refused_by_name(hf, change, why):
    with pytest.raises(ValueError, match=why):
        config_from_hf_json({**hf, **change})
