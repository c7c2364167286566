"""Generation by diffusion over blocks (ISSUE 39): the program against
the benchmark's plain reference at a tiny size, seeded weights, float32,
on the CPU.

Prefill of a prompt's whole blocks, then passes over the paged pool =
the reference's full forward under the block-causal mask, with rows of
unequal length, an idle row, a prompt shorter than a block, remainders
of 0 to 3 and a prompt of several chunks; ``DecodeServer`` serves the
reference's own loop, pass for pass, at 4, 2 and 1 passes a block, with
budgets and an EOS that end inside a block; a finished block is
committed by a lane of the pass that opens the row's next one (ISSUE
46): the pages hold what a commit writes, rows wait where lanes are
short, a request's last block takes none, and a lane in flight at a
cancel leaves nothing behind; what no committed token wrote reaches no
stream; which positions are open is state; and the dense family's
programs are the parent's, text for text.

Tolerance: logits are O(1) and both sides are float32 at ``highest``
precision, differing in the order of sums (online softmax over pages,
grouped matmuls over sorted rows): 2e-4 absolute and relative, as
``test_nemotron_h.py`` allows its own.  Streams are compared exactly:
at these sizes no two confidences or logits lie that close."""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.model import sdar_reference as R
from benchmarks.model import sdar_weights as W
from nbdistributed_tpu.messaging import Message
from nbdistributed_tpu.models import (DecodeServer, SDARConfig,
                                      config_from_hf_json,
                                      forward_with_cache, init_params,
                                      init_sdar_model, joyai_flash_config,
                                      llama2_7b_config, mistral_7b_config,
                                      mixtral_8x7b_config,
                                      sdar_30b_a3b_config, smol_135m_config,
                                      tiny_config, tiny_latent_moe_config,
                                      tiny_moe_config, tiny_sdar_config,
                                      tinyllama_1b_config)
from nbdistributed_tpu.models import sdar as sdar_mod
from nbdistributed_tpu.models.paged_kv import PagedKVCache, make_paged_pool
from nbdistributed_tpu.observability.servingobs import (TICK_TOTALS,
                                                        ServingObservatory)
from test_serving_plane import FakeComm, make_mgr
from test_serving_tick import _step, _worker

pytestmark = [pytest.mark.unit, pytest.mark.serve]

SEED = 7
TOL = dict(rtol=2e-4, atol=2e-4)
L, MASK = 4, 511
BT, CHUNK, MAX_LEN = 8, 16, 64

# The row's ``config`` in the catalog, as published.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
# The same keys at the test's size.
HF = {**PUBLISHED, "hidden_size": 64, "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 32, "moe_intermediate_size": 32,
      "num_experts": 8, "num_experts_per_tok": 2, "num_hidden_layers": 2,
      "vocab_size": 512, "torch_dtype": "float32",
      "max_position_embeddings": 256}


def program_config(steps: int = 4, **kw) -> SDARConfig:
    return config_from_hf_json(HF, dtype=jnp.float32, block_length=L,
                               denoise_steps=steps, mask_token_id=MASK, **kw)


@pytest.fixture(scope="module")
def params():
    return jax.jit(functools.partial(W.make_weights, cfg=HF))(
        W.seed_key(SEED))


def server(params, steps: int = 4, **kw) -> DecodeServer:
    kw = {"max_batch": 3, "max_len": MAX_LEN, "pad_to": 8,
          "kv_block_tokens": BT, "prefill_chunk": CHUNK,
          "interleave_prefill": True, **kw}
    return DecodeServer(params, program_config(steps), **kw)


def prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 500, n).tolist() for n in lens]


def reference(prompt, max_new, steps: int = 4):
    toks, when, _ = R.generate(SEED, HF, prompt, max_new, block=L,
                               steps=steps, mask_id=MASK, pad_to=MAX_LEN)
    return toks, when


# ----------------------------------------------------------------------
# the forward: prefill of whole blocks, then passes over the pool


@pytest.mark.parametrize("lens", [(2, 5, 8, 21), (3, 6, 7, 36)])
def test_prefill_then_passes_are_the_references_forward(params, lens):
    """Rows of unequal length, one of them shorter than a block and one
    of several chunks, remainders 0 to 3, and an idle row whose block
    is garbage: a pass's logits at a row's block are the reference's
    full forward over the row's prompt and block, and after a commit
    the next block's are too."""
    cfg, rows = program_config(), len(lens) + 1
    paged = PagedKVCache(slots=rows, max_len=MAX_LEN, n_blocks=40,
                         block_tokens=BT)
    pool = make_paged_pool(cfg, 40, BT)
    seqs = prompts(lens, seed=sum(lens))
    chunk = jax.jit(lambda pool, toks, at, mask, row: forward_with_cache(
        params, toks, pool, at, cfg, token_mask=mask, block_table=row,
        final=False))
    step = jax.jit(lambda pool, block, lens, table: forward_with_cache(
        params, block, pool, lens, cfg, row_mask=active, block_table=table,
        with_moe_load=True))
    active = jnp.asarray([True] * len(seqs) + [False])
    for b, p in enumerate(seqs):
        paged.alloc(b, len(p) + 2 * L)
        whole = len(p) // L * L
        for at in range(0, whole, CHUNK):
            seg = p[at:min(at + CHUNK, whole)]
            toks = np.zeros((1, CHUNK), np.int32)
            toks[0, :len(seg)] = seg
            logits, pool = chunk(pool, jnp.asarray(toks), jnp.int32(at),
                                 jnp.arange(CHUNK)[None] < len(seg),
                                 paged.device_row(b)[None])
            assert logits is None
    lens_dev = jnp.asarray([len(p) // L * L for p in seqs] + [0], jnp.int32)
    block = np.full((rows, L), MASK, np.int32)
    for b, p in enumerate(seqs):
        rest = p[len(p) // L * L:]
        block[b, :len(rest)] = rest
    block[-1] = [9, 9, 9, 9]                    # the idle row's
    rng = np.random.default_rng(1)
    for _ in range(2):
        logits, pool, load = step(pool, jnp.array(block), lens_dev,
                                  paged.device_table())
        assert logits.shape == (rows, L, cfg.vocab_size)
        for b, p in enumerate(seqs):
            at = int(lens_dev[b])
            # laid out in MAX_LEN positions, masks beyond the block:
            # under the mask no block sees a later one
            seq = p[:at] + block[b].tolist()
            seq += [MASK] * (MAX_LEN - len(seq))
            ref = R.forward(SEED, HF, seq, L)["logits"][at:at + L]
            np.testing.assert_allclose(np.asarray(logits[b]), ref, **TOL)
        # rows routed: the live rows' positions, top_k each
        assert float(load[2]) == len(seqs) * L * cfg.top_k
        # "commit": the block becomes context, the next is all masks
        # (jnp.array copies: ``block`` is written again while a step runs)
        seqs = [p[:int(lens_dev[b])] + rng.integers(0, 500, L).tolist()
                for b, p in enumerate(seqs)]
        for b, p in enumerate(seqs):
            block[b] = p[-L:]
        _, pool, _ = step(pool, jnp.array(block), lens_dev,
                          paged.device_table())
        lens_dev = lens_dev + L * active
        block[:len(seqs)] = MASK


def test_a_pass_under_the_pallas_grouped_matmul_is_the_ragged_dots(
        params, monkeypatch):
    """What the chip runs (the experts under ``ops/grouped.py``'s
    kernel, interpreted here) against what the CPU runs, on
    one server's state mid-block with two of four rows idle: the gmm
    leaves the rows past the routed total unwritten, and the idle rows'
    choices sort there.  The live rows' logits agree and are finite,
    and the server's streams under the kernel are the reference's."""
    from nbdistributed_tpu.ops import grouped
    srv = server(params, max_batch=4)
    reqs = list(zip(prompts((18, 5)), (9, 7)))
    for p, m in reqs:
        srv.submit(p, m)
    for _ in range(6):                          # prefilled, into a block
        srv.step()
    live = np.isin(np.arange(4), list(srv._run))
    assert live.sum() == 2 and not live.all()
    cfg, calls = program_config(), []

    def a_pass():
        # a fresh function a path: nothing traced under the other one
        return jax.jit(lambda pool, block, lens, table: forward_with_cache(
            params, block, pool, lens, cfg, row_mask=jnp.asarray(live),
            block_table=table, with_moe_load=True))(
            srv._cache, srv._block["tokens"], srv._lens,
            srv._paged.device_table())

    want, _, want_load = a_pass()
    monkeypatch.setattr(
        grouped, "ragged_dot",
        lambda *a: calls.append(1) or grouped.grouped_matmul(*a))
    got, _, load = a_pass()
    assert len(calls) == 3 * cfg.n_layers
    assert bool(jnp.isfinite(got[live]).all())
    np.testing.assert_allclose(np.asarray(got[live]),
                               np.asarray(want[live]), **TOL)
    np.testing.assert_array_equal(load, want_load)
    # and a server whose passes all run under the kernel
    srv = server(params, max_batch=4)
    rids = [srv.submit(p, m) for p, m in reqs]
    out = srv.run_until_done(300)
    assert len(calls) > 3 * cfg.n_layers
    for (prompt, max_new), rid in zip(reqs, rids):
        want_toks, want_when = reference(prompt, max_new)
        assert out[rid] == want_toks and srv.fixed_at[rid] == want_when


# ----------------------------------------------------------------------
# the server against the published loop


REQUESTS = ((5, 7), (18, 9), (3, 4), (33, 6), (8, 8))


@pytest.fixture(scope="module")
def served(params):
    """Five requests over three rows at four passes a block: a prompt
    shorter than a block, remainders 1, 2, 3 and 0, a prompt of three
    chunks, budgets that end inside a block."""
    srv = server(params)
    reqs = [(p, m) for p, (_, m) in zip(prompts(n for n, _ in REQUESTS),
                                        REQUESTS)]
    rids = [srv.submit(p, m) for p, m in reqs]
    out = srv.run_until_done(500)
    return srv, [(p, out[r], list(srv.fixed_at[r]))
                 for (p, _), r in zip(reqs, rids)]


def test_the_served_stream_is_the_references_generate(served):
    _, requests = served
    for (prompt, toks, when), (_, max_new) in zip(requests, REQUESTS):
        want, want_when = reference(prompt, max_new)
        assert toks == want and when == want_when
    assert R.schedule_faults(requests, L, 4) == 0


@pytest.mark.parametrize("steps", [2, 1])
def test_fewer_passes_a_block_fix_more_positions_a_pass(params, steps):
    srv = server(params, steps)
    reqs = [(p, m) for p, m in zip(prompts((6, 17, 3)), (7, 8, 6))]
    rids = [srv.submit(p, m) for p, m in reqs]
    out = srv.run_until_done(300)
    requests = []
    for (prompt, max_new), rid in zip(reqs, rids):
        want, want_when = reference(prompt, max_new, steps)
        assert out[rid] == want and srv.fixed_at[rid] == want_when
        assert set(want_when) <= set(range(steps))
        requests.append((prompt, out[rid], srv.fixed_at[rid]))
    assert R.schedule_faults(requests, L, steps) == 0
    acct = srv.take_account()["dn"]
    # the rows' passes follow the schedule: L / steps positions a pass;
    # no commit takes a pass, and a request's last block takes no lane
    assert acct[3] <= acct[0] * (L // steps) and acct[1] == 0
    assert acct[4] == acct[2] - len(reqs)


def test_the_account_counts_row_passes_and_the_observatory_reads_it(served):
    srv, requests = served
    acct = srv.take_account()
    passes, commits, blocks, fixed, fused, waits = acct["dn"]
    new = sum(len(t) for _, t, _ in requests)
    # no commit takes a row-pass of its own: every block but a
    # request's last is committed by a lane of the row's next pass
    assert acct["dc"] == new and commits == 0
    assert fused == blocks - len(requests) > 0
    # every token that left was fixed by a denoising pass (a budget's
    # end leaves a few fixed and not emitted), one position a pass
    assert passes == fixed >= new
    assert (passes + commits) / new < 1.1
    assert acct["kvr"][1] == srv.decode_steps_total > 0
    obs = ServingObservatory()
    obs.note_tick(1, 0, {"roundtrip": 0.1}, {**acct, "seq": 1},
                  pushed=(0, new, 5))
    ticks = obs.ticks_summary()
    assert ticks["denoise"] == {
        "passes_per_block": round(passes / blocks, 3),
        "tokens_per_pass": 1.0, "fused_share": 1.0}
    assert {"passes", "commits", "blocks", "fused",
            "lane_waits"} <= set(TICK_TOTALS)
    assert ticks["totals"]["passes"] == passes
    assert ticks["totals"]["blocks"] == blocks
    assert ticks["totals"]["commits"] == 0
    assert ticks["totals"]["fused"] == fused
    assert ticks["totals"]["lane_waits"] == waits
    # a worker that knows no lanes sends four counts: its commits took
    # passes of their own
    old = ServingObservatory()
    old.note_tick(1, 0, {}, {"dc": 8, "dn": [8, 2, 2, 8], "seq": 1})
    assert old.ticks_summary()["denoise"] == {
        "passes_per_block": 5.0, "tokens_per_pass": 1.0,
        "fused_share": 0.0}
    # a server of another family reports no such block
    plain = ServingObservatory()
    plain.note_tick(1, 0, {}, {"dc": 3, "kvr": [10, 3], "seq": 1})
    assert "denoise" not in plain.ticks_summary()


def test_the_status_line_shows_the_share_of_commits_fused(tmp_path, capsys):
    from nbdistributed_tpu.magics.magic import DistributedMagics
    obs = ServingObservatory()
    obs.note_tick(1, 0, {"roundtrip": 0.1},
                  {"dc": 8, "dn": [8, 0, 2, 8, 1, 3], "seq": 1},
                  pushed=(0, 8, 2))
    mgr, _, _ = make_mgr(tmp_path, FakeComm(num_workers=1))
    st = mgr.describe()
    st.setdefault("lat", {}).setdefault("summary", {})["ticks"] = \
        obs.ticks_summary()
    DistributedMagics._render_serve_status(st)
    mgr.stop()
    assert "4 passes/block, 1 fixed/pass, 100% of commits fused" \
        in capsys.readouterr().out


def test_what_no_committed_token_wrote_reaches_no_stream(params, served):
    """NaN in every page of the pool before the first request: pages a
    row does not own, the padded tail of a chunk, an uncommitted block
    of another row.  The streams are the clean server's."""
    _, requests = served
    srv = server(params)
    srv._cache = jax.tree.map(lambda c: jnp.full_like(c, jnp.nan),
                              srv._cache)
    rids = [srv.submit(p, m) for (p, _, _), (_, m) in zip(requests,
                                                          REQUESTS)]
    out = srv.run_until_done(500)
    assert [out[r] for r in rids] == [t for _, t, _ in requests]


def test_an_eos_inside_a_block_ends_the_stream_there(params, served):
    _, requests = served
    prompt, toks, when = requests[1]            # 9 tokens: 2 + 4 + 3
    eos = toks[3]                               # inside the second block
    cut = toks.index(eos) + 1
    srv = server(params, eos_id=eos)
    rid = srv.submit(prompt, 9)
    other = srv.submit(requests[4][0], 8)
    out = srv.run_until_done(300)
    assert out[rid] == toks[:cut] and srv.fixed_at[rid] == when[:cut]
    want = requests[4][1]
    assert out[other] == (want[:want.index(eos) + 1] if eos in want
                          else want)
    assert srv.done() and not srv._run and srv._paged.used_blocks == 0


def test_cancel_mid_block_frees_the_row_and_leaves_nothing_behind(
        params, served):
    _, requests = served
    srv = server(params, max_batch=1)
    rid = srv.submit(requests[3][0], 24)
    for _ in range(7):                          # into its second block
        srv.step()
    assert 0 < len(srv.outputs[rid]) < 24 and srv._run
    assert srv.cancel(rid) and not srv._run
    assert srv._paged.used_blocks == 0
    # the slot's next request sees none of it
    nxt = srv.submit(requests[1][0], 9)
    assert srv.run_until_done(300)[nxt] == requests[1][1]


# ----------------------------------------------------------------------
# the commit rides a lane of the next block's first pass (ISSUE 46)


@pytest.mark.parametrize("steps", [4, 2, 1])
def test_more_rows_than_lanes_serve_the_references_streams(params, steps):
    """Five rows over ``ceil(5 / steps)`` lanes, seven requests with
    remainders 0 to 3 and budgets that end inside a block: the streams
    and the pass that fixed each token are the reference's, whichever
    rows waited for a lane."""
    srv = server(params, steps, max_batch=5)
    assert srv._block["lane"].shape == (-(-5 // steps),)
    lens, budgets = (8, 9, 10, 11, 4, 21, 12), (9, 12, 7, 10, 13, 6, 8)
    reqs = list(zip(prompts(lens, seed=3), budgets))
    rids = [srv.submit(p, m) for p, m in reqs]
    out = srv.run_until_done(500)
    requests = []
    for (prompt, max_new), rid in zip(reqs, rids):
        want, want_when = reference(prompt, max_new, steps)
        assert out[rid] == want and srv.fixed_at[rid] == want_when
        requests.append((prompt, out[rid], srv.fixed_at[rid]))
    assert R.schedule_faults(requests, L, steps) == 0
    passes, commits, blocks, _, fused, _ = srv.take_account()["dn"]
    assert commits == 0 and fused == blocks - len(reqs)
    assert srv.done() and not srv._run and srv._paged.used_blocks == 0


def test_a_lane_leaves_in_the_pages_what_a_commit_writes(params):
    """After a request's blocks but the one it is in, the row's pages
    hold the K/V of the served tokens under the block-causal mask: what
    a pass over the finished block alone (a commit) writes, and what a
    prefill of those tokens writes."""
    cfg = program_config()
    srv = server(params, max_batch=2)
    prompt = prompts((10,), seed=5)[0]
    rid = srv.submit(prompt, 30)
    while len(srv.outputs[rid]) < 14:
        srv.step()
    assert srv._run and srv._flying.fused == 1
    held = int(srv._lens[0])                    # waits for the pass
    seq = prompt + srv.outputs[rid]
    # the pass in flight carries the lane of the block emitted last
    assert held == len(seq) // L * L == 24
    table = srv._paged.device_table()
    pool = make_paged_pool(cfg, srv._cache["k"].shape[1] - 1, BT)
    for at in range(0, held, CHUNK):
        seg = seq[at:min(at + CHUNK, held)]
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :len(seg)] = seg
        _, pool = forward_with_cache(
            params, jnp.asarray(toks), pool, jnp.int32(at), cfg,
            token_mask=jnp.arange(CHUNK)[None] < len(seg),
            block_table=table[:1], final=False)
    pages = np.asarray(table[0, :held // BT])
    for name in ("k", "v"):
        got = np.asarray(srv._cache[name])[:, pages]
        np.testing.assert_allclose(got, np.asarray(pool[name])[:, pages],
                                   **TOL)
        assert np.abs(got).max() > 0.1


def test_rows_that_finish_together_wait_for_the_one_lane(params, served):
    """Three rows seated together finish their blocks in the same pass
    and there is one lane: two wait, then one, and from there the rows
    are a pass apart.  A row that waits runs nothing, so no pass of its
    block is off the schedule, and the streams are unchanged."""
    srv = server(params)
    assert srv._block["lane"].shape == (1,)
    reqs = list(zip(prompts((8, 12, 16), seed=9), (12, 12, 12)))
    rids = [srv.submit(p, m) for p, m in reqs]
    assert len(srv._run) == 3
    out = srv.run_until_done(300)
    requests = []
    for (prompt, max_new), rid in zip(reqs, rids):
        want, want_when = reference(prompt, max_new)
        assert out[rid] == want and srv.fixed_at[rid] == want_when
        assert set(srv.fixed_at[rid]) <= set(range(4))
        requests.append((prompt, out[rid], srv.fixed_at[rid]))
    assert R.schedule_faults(requests, L, 4) == 0
    passes, commits, blocks, fixed, fused, waits = srv.take_account()["dn"]
    # the first finish: two wait, then one; staggered, nobody waits
    assert waits == 3 and commits == 0 and fused == 6 and blocks == 9
    assert passes == fixed == 36


@pytest.mark.parametrize("budget, lanes_used", [(3, 0), (4, 0), (5, 1),
                                                (12, 2)])
def test_a_requests_last_block_takes_no_lane(params, budget, lanes_used):
    """No block attends a request's last: the row leaves at that
    block's last denoising pass, and the passes a request takes are its
    blocks' denoising passes alone."""
    srv = server(params, max_batch=1)
    prompt = prompts((8,), seed=budget)[0]
    rid = srv.submit(prompt, budget)
    steps = 0
    while not srv.done():
        srv.step()
        steps += 1
    want, want_when = reference(prompt, budget)
    assert srv.outputs[rid] == want and srv.fixed_at[rid] == want_when
    passes, commits, blocks, _, fused, waits = srv.take_account()["dn"]
    assert (commits, fused, waits) == (0, lanes_used, 0)
    assert passes == 4 * blocks == 4 * -(-budget // L)
    assert steps == passes + 1                  # and one call to drain


def test_a_cancel_under_a_lane_in_flight_leaves_nothing_behind(
        params, served):
    _, requests = served
    srv = server(params, max_batch=1)
    rid = srv.submit(requests[3][0], 24)
    while not (srv._flying and srv._flying.fused):
        srv.step()
    assert srv._run and len(srv.outputs[rid]) >= 3
    assert srv.cancel(rid) and not srv._run
    assert srv._paged.used_blocks == 0
    # the slot's next request: no lane of the old one, no block of it
    nxt = srv.submit(requests[1][0], 9)
    assert srv.run_until_done(300)[nxt] == requests[1][1]
    assert srv.fixed_at[nxt] == requests[1][2]
    # and an EOS learned with the next block's lane already dispatched
    prompt, toks, when = requests[4]            # two whole blocks
    srv = server(params, max_batch=1, eos_id=toks[2])
    first = srv.submit(prompt, 8)
    second = srv.submit(requests[0][0], 7)
    out = srv.run_until_done(300)
    assert out[first] == toks[:toks.index(toks[2]) + 1]
    assert out[second] == requests[0][1]
    assert srv.fixed_at[second] == requests[0][2]


def test_the_pass_is_one_forward_whose_head_skips_the_lanes(
        params, monkeypatch):
    """The lowered denoise program: one paged-decode call a layer over
    rows + lanes kernel rows, three grouped matmuls a layer, and a head
    whose matmul has rows x L rows: a lane's logits are not computed."""
    from nbdistributed_tpu.ops import decode, grouped
    cfg, rows = program_config(), 6
    srv = server(params, max_batch=rows)
    lanes = sdar_mod.lanes(cfg, rows)
    assert lanes == 2
    calls = {"decode": [], "gmm": 0}
    plain_decode, plain_dot = decode.paged_decode_attention, \
        grouped.ragged_dot

    def counted_decode(q, *a, **kw):
        calls["decode"].append(q.shape)
        return plain_decode(q, *a, **kw)

    def counted_dot(*a):
        calls["gmm"] += 1
        return plain_dot(*a)

    monkeypatch.setattr(decode, "paged_decode_attention", counted_decode)
    monkeypatch.setattr(grouped, "ragged_dot", counted_dot)
    text = srv._step_fn.lower(
        params, srv._cache, srv._paged.device_table(), srv._lens,
        srv._block, srv._active, srv._key).as_text()
    # the folded call recurses once: the outer one carries the block
    outer = [sh for sh in calls["decode"] if len(sh) == 4]
    assert outer == [(rows + lanes, L, cfg.n_heads, cfg.head_dim)] \
        * cfg.n_layers
    assert calls["gmm"] == 3 * cfg.n_layers
    head = f"tensor<{rows}x{L}x{cfg.vocab_size}xf32>"
    assert head in text
    assert f"tensor<{rows + lanes}x{L}x{cfg.vocab_size}xf32>" not in text
    assert f"tensor<{(rows + lanes) * L}x{cfg.vocab_size}xf32>" not in text


def test_a_rows_block_lands_where_its_tokens_go_and_is_staged_lean():
    """``write_token`` with a block a row: each row's ``L`` tokens at
    its page and offset, an idle row's in the trash block, every other
    entry untouched; and three operations a row a leaf (slice, reshape,
    update) beside ten a row for its two wrapped scalars, not the
    seventeen a row a leaf of one token a row: at 160 rows of 7
    unrolled layers they are what tracing the pass costs."""
    from nbdistributed_tpu.models.paged_kv import write_token
    rng = np.random.default_rng(2)
    pool = {k: jnp.asarray(rng.normal(size=(2, 6, 2, BT, 4)), jnp.float32)
            for k in ("k", "v")}
    new = {k: jnp.asarray(rng.normal(size=(3, 2, L, 4)), jnp.float32)
           for k in ("k", "v")}
    table = jnp.asarray([[0, 1], [2, 3], [4, 4]], jnp.int32)
    pos = jnp.asarray([4, 8, 0], jnp.int32)
    active = jnp.asarray([True, True, False])
    write = lambda pool, new: write_token(pool, jnp.int32(1), new, table,
                                          pos, active)
    got = write(pool, new)
    for k in ("k", "v"):
        want = np.array(pool[k])
        want[1, 0, :, 4:8] = new[k][0]          # row 0: page 0, offset 4
        want[1, 3, :, 0:4] = new[k][1]          # row 1: its second page
        want[1, 5, :, 0:4] = new[k][2]          # idle: the trash block
        np.testing.assert_array_equal(np.asarray(got[k]), want)
    eqns = jax.make_jaxpr(write)(pool, new).eqns
    per_row = [e for e in eqns if e.primitive.name in (
        "slice", "squeeze", "reshape", "broadcast_in_dim", "select_n",
        "lt", "add", "dynamic_update_slice")]
    assert sum(e.primitive.name == "dynamic_update_slice"
               for e in eqns) == 3 * 2
    assert len(per_row) <= 3 * (2 * 3 + 10) + 8 < 3 * 2 * 17


def test_a_fixed_token_that_equals_the_mask_id_stays_fixed():
    """Which positions are open is state: a pass that chooses the mask
    id fixes it, and the next pass fixes another position."""
    cfg = tiny_sdar_config(dtype=jnp.float32)
    block = sdar_mod.fresh_block(cfg, 2)
    logits = np.zeros((2, cfg.block_length, cfg.vocab_size), np.float32)
    logits[0, 2, cfg.mask_token_id] = 9.0       # most confident: [MASK]
    logits[0, 0, 7] = 5.0
    logits[0, 1, 8] = 3.0
    logits[0, 3, 9] = 1.0
    active = jnp.asarray([True, False])
    seen = []
    for s in range(cfg.denoise_steps):
        block, out = sdar_mod.denoise(jnp.asarray(logits), block, active,
                                      cfg)
        seen.append(np.asarray(out["when"][0]).tolist())
        # the pass that fixes the last open position finishes the block
        assert np.asarray(out["done"]).tolist() \
            == [s == cfg.denoise_steps - 1, False]
    assert seen[0] == [sdar_mod.OPEN, sdar_mod.OPEN, 0, sdar_mod.OPEN]
    assert seen[-1] == [1, 2, 0, 3]
    final = [7, 8, cfg.mask_token_id, 9]
    assert np.asarray(out["tokens"][0]).tolist() == final
    # the block went out and waits for a lane; the row's next block is
    # all masks at pass 0; the idle row stays
    assert np.asarray(block["closed"][0]).tolist() == final
    assert (np.asarray(block["tokens"]) == cfg.mask_token_id).all()
    assert (np.asarray(block["when"]) == sdar_mod.OPEN).all()
    assert np.asarray(block["at"]).tolist() == [0, 0]
    # a lane is the finished block at the position ``lens`` stands at;
    # the row's open block sits a block further and ``lens`` moves
    lens = jnp.asarray([8, 4], jnp.int32)
    table = jnp.arange(6, dtype=jnp.int32).reshape(2, 3)
    toks, at, rows, taking, moved = sdar_mod.with_lanes(
        {**block, "lane": jnp.asarray([0], jnp.int32)}, lens, table,
        active, cfg)
    assert np.asarray(toks[2]).tolist() == final
    assert np.asarray(at).tolist() == [12, 4, 8]
    assert np.asarray(rows).tolist() == [[0, 1, 2], [3, 4, 5], [0, 1, 2]]
    assert np.asarray(taking).tolist() == [True, False, True]
    assert np.asarray(moved).tolist() == [12, 4]
    # no row named: the lane takes no part and nothing moves
    *_, taking, moved = sdar_mod.with_lanes(block, lens, table, active, cfg)
    assert np.asarray(taking).tolist() == [True, False, False]
    assert np.asarray(moved).tolist() == [8, 4]


# ----------------------------------------------------------------------
# the reference's own forms, and what the cell's check reads


def test_the_one_pass_form_of_served_gaps_is_the_loop(served):
    _, requests = served
    kw = dict(block=L, steps=4, mask_id=MASK)
    one = R.served_gaps(SEED, HF, requests, MAX_LEN, **kw)
    loop = R.served_gaps(SEED, HF, requests, MAX_LEN, one_pass=False, **kw)
    whole = sum((len(p) + len(t)) // L * L - max(len(p) // L * L, 0)
                - (len(p) - len(p) // L * L) for p, t, _ in requests)
    assert len(one["token_gap"]) == whole and one["skipped"] == loop["skipped"]
    for k in ("token_gap", "pick_gap", "margin"):
        np.testing.assert_allclose(one[k], loop[k], atol=1e-5)
    # the program served the reference's own choices
    assert one["token_gap"].max() == 0 and one["pick_gap"].max() == 0


def test_a_wrong_token_and_a_wrong_pick_are_seen(served):
    _, requests = served
    kw = dict(block=L, steps=4, mask_id=MASK)
    prompt, toks, when = requests[4]            # two whole blocks
    wrong = [(prompt, [(t + 1) % 500 for t in toks], when)]
    assert R.served_gaps(SEED, HF, wrong, MAX_LEN, **kw)["token_gap"].min() \
        > 0.01
    # the order of two passes swapped: the later position was not the
    # more confident one
    swap = {0: 1, 1: 0}
    order = [(prompt, toks, [swap.get(w, w) for w in when])]
    assert R.served_gaps(SEED, HF, order, MAX_LEN, **kw)["pick_gap"].max() \
        > 0


@pytest.mark.parametrize("when, faults", [
    ([0, 1, 2, 3, 0, 1, 2, 3], 0),
    ([0, 0, 2, 3, 0, 1, 2, 3], 1),      # a pass fixed two, one none
    ([0, 1, 2, 3, 0, 1, 2, 4], 1),      # a pass outside the schedule
    ([0, 0, 0, 0, 1, 1, 1, 1], 2),      # passes skipped in both blocks
])
def test_passes_off_schedule_are_counted_a_block(when, faults):
    reqs = [(list(range(8)), list(range(8)), when)]
    assert R.schedule_faults(reqs, L, 4) == faults


def test_a_remainder_and_a_cut_block_are_held_to_the_schedule():
    # remainder 1: three open positions, passes 0..2; the budget cuts
    # the second block after two tokens
    ok = [([1] * 5, [2] * 5, [1, 0, 2, 3, 1])]
    assert R.schedule_faults(ok, L, 4) == 0
    assert R.schedule_faults([([1] * 5, [2] * 5, [1, 0, 3, 3, 1])], L, 4) == 1
    assert R.schedule_faults([([1] * 5, [2] * 5, [1, 0, 2, 1, 1])], L, 4) == 1
    assert R.schedule_faults([([1] * 5, [2] * 5, [1, 0, 2])], L, 4) == 1


# ----------------------------------------------------------------------
# the worker and the gateway carry the record


def test_a_finished_streams_passes_ride_the_reply_to_the_result(
        params, served, tmp_path):
    _, requests = served
    prompt, toks, when = requests[0]
    w = _worker(server(params))
    d = _step(w, 1, admit=[{"rid": "a", "prompt": prompt, "max_new": 7}],
              steps=4)
    assert "passes" not in d and d["tick"]["dn"][0] > 0
    assert len(d["tick"]["dn"]) == 6
    while "a" not in d["finished"]:
        d = _step(w, 2, steps=8)
    assert d["passes"] == {"a": when}
    mgr, _, _ = make_mgr(tmp_path, FakeComm(num_workers=1))
    got = mgr.submit("t", prompt, 7)
    assert "passes" not in mgr.result(got["rid"])
    mgr._apply_reply({"passes": {got["rid"]: when, "gone": [0]}}, 0)
    res = mgr.result(got["rid"])
    assert res["passes"] == when and res["passes_from"] == 0


# ----------------------------------------------------------------------
# configuration


def test_the_published_config_maps_to_the_family():
    cfg = config_from_hf_json(PUBLISHED, dtype=jnp.bfloat16)
    assert cfg == sdar_30b_a3b_config()
    assert (cfg.head_dim, cfg.n_heads, cfg.n_kv_heads) == (128, 32, 4)
    assert cfg.head_dim != cfg.d_model // cfg.n_heads
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff) == (128, 8, 768)
    assert cfg.qk_norm and cfg.moe_dispatch == "dropless"
    assert (cfg.block_length, cfg.denoise_steps, cfg.fixed_per_pass,
            cfg.mask_token_id) == (4, 4, 1, 151669)
    # ISSUE 39's count: 19.14 M a layer outside its experts, 4,718,592
    # an expert, 622.3 M in embedding and head
    layer = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2048 * 128 \
        + 2 * 2048 + 2 * 128
    assert layer == 19_140_864
    assert cfg.num_params() == 48 * (layer + 128 * 4_718_592) \
        + 2 * 151_936 * 2048 + 2048
    # the generation settings ride the file beside the published keys
    cut = config_from_hf_json({**PUBLISHED, "num_hidden_layers": 6,
                               "block_length": 8, "denoise_steps": 2})
    assert (cut.n_layers, cut.block_length, cut.fixed_per_pass) == (6, 8, 4)
    tree = jax.eval_shape(lambda: init_sdar_model(jax.random.PRNGKey(0),
                                                  cut))
    assert len(tree["layers"]) == 6
    assert tree["layers"][0]["wq"].shape == (2048, 4096)
    assert tree["layers"][0]["q_norm"].shape == (128,)
    assert tree["layers"][0]["moe"]["w_gate"].shape == (128, 2048, 768)
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree)) \
        == cut.num_params()


@pytest.mark.parametrize("change, why", [
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"tie_word_embeddings": True}, "tied head"),
    ({"denoise_steps": 3}, "must divide"),
    ({"mask_token_id": 151936}, "outside the vocabulary"),
])
def test_what_the_tree_cannot_run_is_refused_by_name(change, why):
    with pytest.raises(ValueError, match=why):
        config_from_hf_json({**PUBLISHED, **change})


@pytest.mark.parametrize("size", ["max_len", "kv_block_tokens", "pad_to",
                                  "prefill_chunk"])
def test_a_block_server_takes_whole_blocks_only(size):
    cfg = tiny_sdar_config(dtype=jnp.float32)
    shapes = jax.eval_shape(lambda: init_sdar_model(jax.random.PRNGKey(0),
                                                    cfg))
    kw = {"max_batch": 2, "max_len": 64, "pad_to": 8, "kv_block_tokens": 8,
          "prefill_chunk": 16, "interleave_prefill": True}
    kw[size] += 2
    with pytest.raises(ValueError, match="multiples of it"):
        DecodeServer(shapes, cfg, **kw)


def test_a_block_server_is_greedy_and_reads_its_pool_in_place():
    cfg = tiny_sdar_config(dtype=jnp.float32)
    shapes = jax.eval_shape(lambda: init_sdar_model(jax.random.PRNGKey(0),
                                                    cfg))
    for bad in ({"temperature": 0.7}, {"kv_quantized": True}):
        with pytest.raises(ValueError, match="greedy"):
            DecodeServer(shapes, cfg, max_batch=2, max_len=64, **bad)
    with pytest.raises(ValueError, match="greedy"):
        DecodeServer(shapes, dataclasses.replace(cfg, use_flash=False),
                     max_batch=2, max_len=64)
    with pytest.raises(ValueError, match="paged pool"):
        forward_with_cache(shapes, jnp.zeros((1, 4), jnp.int32), {}, 0, cfg)


@pytest.mark.parametrize("preset, want", [
    (tiny_config, 32), (smol_135m_config, 64), (tinyllama_1b_config, 64),
    (mistral_7b_config, 128), (llama2_7b_config, 128),
    (tiny_moe_config, 32), (mixtral_8x7b_config, 128),
    (joyai_flash_config, 64), (tiny_latent_moe_config, 16),
    (tiny_sdar_config, 32), (sdar_30b_a3b_config, 128)])
def test_head_dim_defaults_to_what_every_preset_had(preset, want):
    cfg = preset()
    assert cfg.head_dim == want
    if not isinstance(cfg, SDARConfig):
        assert cfg.head_dim == cfg.d_model // cfg.n_heads
    # and survives the copies the tree makes of a config
    assert dataclasses.replace(cfg, use_flash=False).head_dim == want
    assert type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32}).head_dim \
        == want


# ----------------------------------------------------------------------
# block length 1 is the causal mask: the dense family's programs


# sha256 of ``lower(...).as_text()`` of the two programs below on the
# parent's tree (commit 71a2d83, jax 0.9.0, CPU): one integer
# generalises both paged attention calls, and at 1 nothing of the dense
# family's programs may move.
PARENT_TEXT = {
    "step": "8c21db52f7a209083114705867b1d7b3159bfe933d70018dc40f0e2303e66c0f",
    "chunk": "5b66cc10bc4fb3a07e2e3595517a7dd3a17aa1c149a9750014d33901f33095f4",
}


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_the_dense_familys_programs_lower_to_the_parents_text(program):
    if jax.__version__ != "0.9.0":
        pytest.skip("the parent's text was taken under jax 0.9.0")
    cfg = mistral_7b_config(d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                            d_ff=128, vocab_size=512, max_seq_len=256,
                            sliding_window=32, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    srv = DecodeServer(shapes, cfg, max_batch=4, max_len=128, pad_to=16,
                       kv_block_tokens=16, prefill_chunk=32,
                       interleave_prefill=True)
    if program == "step":
        text = srv._step_fn.lower(
            shapes, srv._cache, srv._paged.device_table(), srv._lens,
            srv._last, srv._active, srv._key).as_text()
    else:
        text = srv._prefill_fn.program.lower(
            shapes, srv._cache, srv._paged.device_row(0),
            jnp.zeros((1, 32), jnp.int32), jnp.int32(0),
            jnp.int32(32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[program]
