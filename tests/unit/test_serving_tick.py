"""The serving tick's account on the worker's side (ISSUE 25), against a
real :class:`DecodeServer` over ``tiny_config`` and a bare worker object:
the server's phase seconds, the ``serve_step`` handler's ``tick`` block
(``ph``, ``cmp``, ``seq``, ``turnaround``), the names of the jitted
serving programs, the one counter of the paged layer.  In-process on the
CPU, no fleet; kept out of the ``slow`` tier (a tiny model, a few
steps), so it counts where the driver counts."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nbdistributed_tpu.messaging import Message
from nbdistributed_tpu.models import generate, init_params, tiny_config
from nbdistributed_tpu.models import serving as serving_mod
from nbdistributed_tpu.models.serving import STEP_PHASES, DecodeServer
from nbdistributed_tpu.observability.servingobs import WORKER_PHASES
from nbdistributed_tpu.runtime import worker as worker_mod

pytestmark = [pytest.mark.unit, pytest.mark.serve, pytest.mark.obs]


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_config(dtype=jnp.float32, use_flash=False, n_layers=1)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _paged(setup, **kw):
    cfg, params = setup
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 32)
    return DecodeServer(params, cfg, pad_to=4, kv_block_tokens=8, **kw)


class CountingClock:
    """``perf_counter`` that returns 0, 1, 2, ...: every phase is a
    whole number of reads, so sums are exact."""

    def __init__(self):
        self.n = 0

    def __call__(self):
        self.n += 1
        return float(self.n - 1)


def _fake_time(monkeypatch, *modules):
    clk = CountingClock()
    real = serving_mod.time
    stub = types.SimpleNamespace(perf_counter=clk, time=real.time,
                                 monotonic=real.monotonic)
    for mod in modules:
        monkeypatch.setattr(mod, "time", stub)
    return clk


def _spy(monkeypatch, srv):
    """The order in which a server talks to the device: ``d`` for a
    decode step dispatched, ``f`` for a step's tokens fetched."""
    events = []
    real_step, real_get = srv._step_fn, jax.device_get

    def step_fn(*a):
        events.append("d")
        return real_step(*a)

    def device_get(x):
        events.append("f")
        return real_get(x)

    srv._step_fn = step_fn
    monkeypatch.setattr(jax, "device_get", device_get)
    return events


def solo(setup, prompt, n, eos=None):
    """What ``generate`` alone emits for the prompt, cut after ``eos``."""
    cfg, params = setup
    out = generate(params, jnp.asarray(prompt, jnp.int32)[None], cfg, n)
    toks = [int(t) for t in np.asarray(out)[0][len(prompt):]]
    return toks[:toks.index(eos) + 1] if eos in toks else toks


# ----------------------------------------------------------------------
# DecodeServer


def test_step_phase_names_are_the_fixed_set(setup):
    srv = _paged(setup)
    assert STEP_PHASES == ("prefill", "dispatch", "sync", "emit")
    assert tuple(srv.phase_s) == STEP_PHASES
    assert set(STEP_PHASES) < set(WORKER_PHASES)
    assert set(WORKER_PHASES) - set(STEP_PHASES) == {"admit", "collect"}
    assert srv.tick is None


def test_phase_seconds_are_monotone_and_sum_to_the_steps_wall_time(
        setup, monkeypatch):
    srv = _paged(setup)
    events = _spy(monkeypatch, srv)
    clk = _fake_time(monkeypatch, serving_mod)
    seen = dict(srv.phase_s)
    t_sub0 = clk.n
    srv.submit([5, 9, 2], 4)
    # an admission is prefill, whoever calls it: [t0, t1] around it
    assert srv.phase_s["prefill"] - seen["prefill"] == clk.n - t_sub0 - 1
    calls = []
    while not srv.done():
        before, n0 = dict(srv.phase_s), clk.n
        del events[:]
        srv.step()
        wall = clk.n - n0 - 1          # last read minus first read
        delta = {k: srv.phase_s[k] - before[k] for k in STEP_PHASES}
        assert all(v >= 0 for v in delta.values())       # monotone
        assert sum(delta.values()) == wall
        assert delta["sync"] == 1 and delta["dispatch"] == 1
        calls.append("".join(events))
    # three decode steps: the first call has nothing to fetch, the next
    # two dispatch a step before they fetch the one before it, and the
    # last, with no row left to run, drains
    assert calls == ["d", "df", "df", "f"]
    # a step with nothing to decode is prefill alone
    before, n0 = dict(srv.phase_s), clk.n
    assert srv.step() == {}
    assert srv.phase_s["prefill"] - before["prefill"] == clk.n - n0 - 1
    assert srv.phase_s["sync"] == before["sync"]


def test_trailing_admission_of_a_step_counts_as_prefill(setup, monkeypatch):
    """One slot, two requests: the step that finishes the first (the
    call that fetches its last token) admits the second, and that
    admission is prefill, not emit."""
    srv = _paged(setup, max_batch=1)
    r1 = srv.submit([5, 9, 2], 2)
    r2 = srv.submit([7, 1], 2)                  # waits for the slot
    srv.step()                                  # r1's last step leaves
    assert len(srv.outputs[r1]) == 1 and not srv.outputs[r2]
    clk = _fake_time(monkeypatch, serving_mod)
    before, n0 = dict(srv.phase_s), clk.n
    srv.step()                                  # finishes r1, admits r2
    assert srv.outputs[r2], "the second request was admitted"
    delta = {k: srv.phase_s[k] - before[k] for k in STEP_PHASES}
    assert delta == {"prefill": 2, "dispatch": 1, "sync": 1, "emit": 1}
    assert sum(delta.values()) == clk.n - n0 - 1


def test_kv_view_bytes_is_what_a_step_gathers_into_dense_views(setup):
    """The einsum fallback gathers every slot's whole table once a
    layer; a step whose kernel reads the pool in place gathers
    nothing, and that 0 is the counter that says it engaged."""
    cfg, params = setup
    srv = _paged(setup, max_batch=2, max_len=32)
    # layers x rows x max_len x KV heads x head dim x (K and V) x itemsize
    want = cfg.n_layers * 2 * 32 * cfg.n_kv_heads * cfg.head_dim * 2 * 4
    assert srv.kv_view_bytes == want
    # a row of one page (the default block of 64) gathers the page
    one_page = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4)
    assert one_page.kv_view_bytes == want * 64 // 32
    flash = dataclasses.replace(cfg, use_flash=True)
    in_place = DecodeServer(params, flash, max_batch=2, max_len=32,
                            pad_to=4, kv_block_tokens=8)
    assert in_place.kv_view_bytes == 0


def test_kv_read_bytes_counts_the_live_pages_of_the_active_slots(setup):
    """Each decode step adds, for every row it runs, the pages from
    its window's first to the one its new token lands in, all layers,
    K and V, by the position the row was dispatched at and when the
    step's tokens are fetched; the handler reports the tick's delta
    with its steps."""
    cfg, _ = setup
    srv = _paged(setup, max_batch=2, max_len=32)
    # one page: block tokens x KV heads x head dim x (K, V) x itemsize
    page = cfg.n_layers * 8 * cfg.n_kv_heads * cfg.head_dim * 2 * 4
    assert srv._page_bytes == page
    srv.submit([5, 9, 2, 7, 1, 3, 4], 6)       # pos 7 is the page edge
    srv.submit([5, 9], 6)
    srv.step()                                  # writes pos 7 and pos 2
    # in flight: a step counts when its tokens are fetched
    assert (srv.kv_read_bytes_total, srv.decode_steps_total) == (0, 0)
    srv.step()                                  # pos 8: a second page
    assert (srv.kv_read_bytes_total, srv.decode_steps_total) == (
        page * (1 + 1), 1)
    srv.step()
    assert srv.kv_read_bytes_total == page * (2 + 2 + 1)
    # the account is what the server did since it last gave one
    account = srv.take_account()
    assert account["kvr"] == [page * (2 + 2 + 1), 2]
    assert account["ahd"] == [2, 2] and account["dc"] == 4
    w = _worker(srv)
    tick = _step(w, 1, steps=2)["tick"]
    assert tick["kvr"] == [page * 2 * (2 + 1), 2]
    # a row of one page (the default block of 64) reads it every step
    one_page = DecodeServer(setup[1], cfg, max_batch=2, max_len=32,
                            pad_to=4)
    one_page.submit([5, 9], 3)
    one_page.step()
    one_page.step()
    assert (one_page.kv_read_bytes_total, one_page.decode_steps_total) == (
        page * 64 // 8, 1)


def test_kv_read_bytes_leaves_out_pages_below_the_window(setup):
    cfg, params = setup
    win = dataclasses.replace(cfg, sliding_window=8)
    srv = DecodeServer(params, win, max_batch=1, max_len=32, pad_to=4,
                       kv_block_tokens=8)
    srv.submit(list(range(1, 18)), 4)           # first step writes pos 17
    srv.step()
    srv.step()                                  # fetches the first
    # keys [10, 17]: pages 1 and 2 of three
    assert srv.kv_read_bytes_total == 2 * srv._page_bytes


def test_prefill_keys_counts_the_pages_a_chunk_program_attends(setup):
    """Each chunk program adds the keys from the page of its first
    token's window to the page of its last real token, whole pages:
    ``start + length`` rounded up to a page with no window.  The
    handler reports the tick's delta with the chunk programs run,
    wherever in the handler they ran."""
    cfg, params = setup
    srv = _paged(setup, max_batch=2, max_len=64, prefill_chunk=8,
                 interleave_prefill=True)
    w = _worker(srv)
    # 21 tokens in chunks of 8, one a step: starts 0, 8, 16
    tick = _step(w, 1, admit=[{"rid": "a", "prompt": list(range(1, 22)),
                               "max_new": 4}], steps=2)["tick"]
    assert tick["pfk"] == [8 + 16, 2]
    tick = _step(w, 2, steps=1)["tick"]         # the tail: 5 real of 8
    assert tick["pfk"] == [24, 1]               # 16 + 5 -> three pages
    assert (srv.prefill_keys_total, srv.prefill_chunks_total) == (48, 3)
    # a short prompt is one bucketed program, run inside the admission
    tick = _step(w, 3, admit=[{"rid": "b", "prompt": [5, 9, 2],
                               "max_new": 2}], steps=0)["tick"]
    assert tick["pfk"] == [8, 1]
    assert _step(w, 4, steps=1)["tick"]["pfk"] == [0, 0]
    # below a window the pages are not read, and not counted
    win = DecodeServer(params, dataclasses.replace(cfg, sliding_window=8),
                       max_batch=1, max_len=64, pad_to=4,
                       kv_block_tokens=8, prefill_chunk=8)
    win.submit(list(range(1, 30)), 2)           # starts 0, 8, 16, 24 (5)
    # keys (start - 8, start + length): 1, 2, 2, 2 pages
    assert (win.prefill_keys_total, win.prefill_chunks_total) == (56, 4)
    # a row of one page (the default block of 64): every chunk's keys
    # are that page's
    one_page = DecodeServer(params, cfg, max_batch=1, max_len=64, pad_to=4,
                            prefill_chunk=8)
    one_page.submit(list(range(1, 22)), 2)
    assert (one_page.prefill_keys_total,
            one_page.prefill_chunks_total) == (3 * 64, 3)


@pytest.mark.parametrize("block", [8, 64])
def test_serving_programs_are_named_for_what_they_are(setup, block):
    """The benchmark's ``docs_prefill_program_share`` matches the
    profile's "XLA Modules" names by regex: pin them, a row several
    pages or one."""
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4,
                       kv_block_tokens=block)
    step = srv._step_fn.lower(
        params, srv._cache, srv._paged.device_table(), srv._lens,
        srv._last, srv._active, srv._key).as_text()
    assert "module @jit_nbd_decode_step_paged " in step
    # the jitted program sits behind a wrapper that resolves the
    # slot's block table
    text = srv._prefill_fn.program.lower(
        params, srv._cache, srv._paged.device_row(0),
        jnp.zeros((1, 4), jnp.int32), jnp.int32(0),
        jnp.int32(3)).as_text()
    assert "module @jit_nbd_prefill_paged " in text


# ----------------------------------------------------------------------
# the serve_step handler


def _worker(server):
    w = object.__new__(worker_mod.DistributedWorker)
    w.rank = 0
    w._serve = {"serve": worker_mod._WorkerServe(server)}
    w._serve_snap = None
    return w


def _step(w, seq, admit=(), steps=2, release=()):
    msg = Message(msg_type="serve_step", data={
        "tenant": "serve", "admit": list(admit), "steps": steps,
        "release": list(release), "seq": seq})
    return w._handle_serve_step(msg).data


def test_first_tick_reports_no_turnaround_and_old_keys_are_unchanged(
        setup):
    w = _worker(_paged(setup))
    d = _step(w, 7, admit=[{"rid": "a", "prompt": [5, 9, 2],
                            "max_new": 6}])
    tick = d["tick"]
    # what the gateway read before this PR, under the same names
    assert {"now", "step_s", "pf", "dc"} <= set(tick)
    # two steps dispatched, the first fetched, the second in flight
    assert tick["pf"] == 3 and tick["dc"] == 1 and tick["step_s"] > 0
    assert d["emitted"]["a"]["o"] == 0 and len(d["emitted"]["a"]["t"]) == 2
    assert set(d) == {"status", "emitted", "finished", "errors", "active",
                      "slots", "pending", "tick", "pfp"}
    # and what is new
    assert tick["seq"] == 7 and "turnaround" not in tick
    assert tuple(sorted(tick["ph"])) == tuple(sorted(WORKER_PHASES))
    assert tick["ph"]["prefill"] > 0 and tick["ph"]["sync"] > 0
    assert len(tick["cmp"]) == 2
    d2 = _step(w, 8)
    assert d2["tick"]["seq"] == 8 and d2["tick"]["turnaround"] >= 0
    assert w._serve["serve"].server.tick == 8


def test_handler_phases_sum_to_the_handlers_wall_time(setup, monkeypatch):
    w = _worker(_paged(setup))
    _step(w, 1, admit=[{"rid": "a", "prompt": [5, 9, 2], "max_new": 9}])
    clk = _fake_time(monkeypatch, serving_mod, worker_mod)
    n0 = clk.n
    d = _step(w, 2, admit=[{"rid": "b", "prompt": [7, 1], "max_new": 9}])
    ph = d["tick"]["ph"]
    # first read is the handler's entry, last its reply being built
    assert sum(ph.values()) == clk.n - n0 - 1
    assert all(v >= 0 for v in ph.values())
    # one admission (2 reads apart) and two steps' first phases
    assert ph["prefill"] == 1 + 2 and ph["sync"] == 2
    # the next entry is the read after this reply was built
    assert _step(w, 3)["tick"]["turnaround"] == 1.0


def test_tick_cmp_counts_a_compile_on_the_first_use_of_a_bucket_only(
        setup):
    w = _worker(_paged(setup, max_batch=2, max_len=64))
    # run one request to its end: every program but the new bucket's
    d = _step(w, 1, admit=[{"rid": "a", "prompt": [5, 9, 2],
                            "max_new": 3}], steps=4)
    assert d["finished"] == ["a"] and d["tick"]["cmp"][0] >= 1
    _step(w, 2, release=["a"])
    long = list(range(1, 10))               # bucket 12, not 4
    d = _step(w, 3, admit=[{"rid": "b", "prompt": long, "max_new": 2}])
    assert d["tick"]["cmp"][0] >= 1 and d["tick"]["cmp"][1] > 0
    assert d["tick"]["ph"]["prefill"] >= d["tick"]["cmp"][1] * 0.5
    _step(w, 4, release=["b"])
    d = _step(w, 5, admit=[{"rid": "c", "prompt": long[::-1],
                            "max_new": 2}])
    assert d["tick"]["cmp"] == [0, 0.0]


# ----------------------------------------------------------------------
# one producer, one reader


def test_a_quantity_added_to_the_servers_account_reaches_note_tick_unnamed(
        setup, tmp_path, monkeypatch):
    """A new counter is an edit to ``DecodeServer.take_account`` and to
    ``ServingObservatory.note_tick``: the handler, the wire and the
    gateway's tick hand the block through whole.  Here a server whose
    account carries ``xq`` and a reader that looks for it, with the real
    ``_handle_serve_step`` and the real ``ServingManager._tick``
    between them; the tick's record in the ring (the source of
    ``summary()["ticks"]``) is made from the same block."""
    import time

    from nbdistributed_tpu.gateway.serving import ServingManager

    real_account = DecodeServer.take_account
    monkeypatch.setattr(
        DecodeServer, "take_account",
        lambda self: dict(real_account(self), xq=[self.n_active, 7]))
    w = _worker(_paged(setup))

    class BridgeComm:
        """One in-process worker behind the comm's surface."""
        num_workers = 1

        def dead_ranks(self):
            return set()

        def post(self, ranks, msg_type, data=None):
            pass

        def send_to_ranks(self, ranks, msg_type, data=None, **kw):
            if msg_type != "serve_step":
                return {0: Message(msg_type="response",
                                   data={"status": "open"})}
            return {0: w._handle_serve_step(
                Message(msg_type=msg_type, data=data))}

    mgr = ServingManager(BridgeComm(), str(tmp_path), world_size=1,
                         max_batch=2, max_len=32, pad_to=4, steps=2,
                         kv_block_tokens=8, step_timeout=30.0)
    seen = []
    real_note = mgr.obs.note_tick

    def note_tick(seq, rank, gateway, tick, **kw):
        seen.append((seq, tick.get("xq")))
        return real_note(seq, rank, gateway, tick, **kw)

    monkeypatch.setattr(mgr.obs, "note_tick", note_tick)
    mgr.start()
    try:
        rid = mgr.submit("t1", [5, 9, 2], 6)["rid"]
        deadline = time.monotonic() + 60
        while not mgr.result(rid)["done"]:
            assert time.monotonic() < deadline
            time.sleep(0.02)
    finally:
        mgr.stop()
    assert seen and all(xq is not None and xq[1] == 7 for _, xq in seen)
    assert any(xq[0] == 1 for _, xq in seen)    # a row was decoding
    ring = list(mgr.obs._ticks)
    assert [t["seq"] for t in ring] == [seq for seq, _ in seen]
    assert sum(t["kvr"][1] for t in ring) == 5  # six tokens, one at admission
    assert mgr.describe()["lat"]["summary"]["ticks"]["count"] == len(seen)


# ----------------------------------------------------------------------
# the step in flight (ISSUE 30): step n + 1 is dispatched before step
# n's tokens are fetched, within a tick and across the reply


@pytest.mark.parametrize("block", [8, 64], ids=lambda b: f"block{b}")
def test_streams_served_a_step_ahead_equal_generates(setup, block):
    """Staggered admission, a long prompt chunked in between the decode
    steps, and slots used again: every request's tokens are
    ``generate``'s, token for token (float32), so the parent's."""
    cfg, params = setup
    reqs = [([5, 9, 2], 7), ([7, 1, 3, 11, 4, 2, 8, 6, 1, 9, 4, 4, 2, 7], 5),
            ([2, 2], 6), ([3, 1, 4, 1, 5, 9], 3), ([8], 9)]
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4,
                       kv_block_tokens=block, prefill_chunk=4,
                       interleave_prefill=True)
    rids = [srv.submit(*reqs[0])]
    srv.step()
    rids.append(srv.submit(*reqs[1]))           # streams in by chunks
    srv.step()
    rids += [srv.submit(*r) for r in reqs[2:]]  # wait for freed slots
    srv.run_until_done(max_steps=100)
    for rid, (prompt, n) in zip(rids, reqs):
        assert srv.outputs[rid] == solo(setup, prompt, n), rid
    assert srv.kv_snapshot()["used"] == 0 and srv._flying is None
    # all but the steps that drained had their successor behind them
    assert 0 < srv.ahead_steps_total < srv.decode_steps_total


def test_a_chunk_is_launched_from_host_values_with_a_step_in_flight(setup):
    """What the prefill program's wrapper is handed holds no device
    value, so launching a chunk reads nothing back: a read would wait
    for the step in flight and leave the chip idle meanwhile."""
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4,
                       kv_block_tokens=8, prefill_chunk=4,
                       interleave_prefill=True)
    srv.submit([5, 9, 2], 9)
    srv.step()
    seen, real = [], srv._prefill_fn

    def prefill(params, pool, *args):
        seen.append(args)
        return real(params, pool, *args)

    srv._prefill_fn = prefill
    rid = srv.submit(list(range(1, 11)), 3)     # three chunks
    for written in (4, 8):
        flying = srv._flying
        srv.step()                      # a chunk, then the next step
        assert srv.prefill_progress() == {rid: (written, 10)}
        assert srv._flying is not flying is not None
    assert [a[1:] for a in seen] == [(1, 0, 4), (1, 4, 4)]
    for prompt, *ints in seen:
        assert type(prompt) is np.ndarray and prompt.shape == (1, 4)
        assert all(type(v) is int for v in ints)
    srv.run_until_done(max_steps=30)
    assert srv.outputs[rid] == solo(setup, list(range(1, 11)), 3)


def test_an_eos_is_learned_one_step_late_and_its_surplus_token_dropped(
        setup):
    """The step after the EOS has left before the EOS is fetched: it
    runs the row once more, its token is dropped, and the request that
    takes the slot next is served as if alone."""
    prompt, n = [5, 9, 2], 8
    toks = solo(setup, prompt, n)
    eos = toks[2]
    want = solo(setup, prompt, n, eos)
    srv = _paged(setup, max_batch=1, eos_id=eos)
    r1 = srv.submit(prompt, n)
    r2 = srv.submit([7, 1, 3], 5)               # waits for the slot
    while r1 not in srv.finished:
        srv.step()
    assert srv.outputs[r1] == want and want[-1] == eos
    # one token at admission, one a step, and the surplus step, which
    # is still in flight with the slot already the next request's
    assert srv._flying is not None and srv._flying.rows == {0: r1}
    assert srv._slot_req == {0: r2} and not srv.done()
    srv.run_until_done(max_steps=50)
    assert srv.outputs[r1] == want
    assert srv.outputs[r2] == solo(setup, [7, 1, 3], 5, eos)
    fetched = sum(len(srv.outputs[r]) - 1 for r in (r1, r2))
    assert srv.decode_steps_total == fetched + 1
    assert srv.decode_tokens_total == fetched


def test_cancel_and_release_of_a_row_with_a_step_in_flight(setup):
    srv = _paged(setup, max_batch=1, kv_blocks=2)
    r1 = srv.submit([5, 9, 2], 9)               # both blocks
    srv.step()
    srv.step()
    assert srv._flying.rows == {0: r1} and len(srv.outputs[r1]) == 2
    with pytest.raises(ValueError, match="in flight"):
        srv.release(r1)
    assert srv.cancel(r1) and srv.kv_snapshot()["used"] == 0
    # the slot and its pages go to the next request while the step
    # that still runs the cancelled row is in flight
    r2 = srv.submit([7, 1], 4)
    assert srv._flying.rows == {0: r1} and srv._slot_req == {0: r2}
    assert srv.step() == {}                     # r1's token is dropped
    srv.run_until_done(max_steps=20)
    assert len(srv.outputs[r1]) == 2
    assert srv.outputs[r2] == solo(setup, [7, 1], 4)
    assert len(srv.release(r1)) == 2 and len(srv.release(r2)) == 4


def test_done_counts_the_step_in_flight_and_run_until_done_drains_it(
        setup, monkeypatch):
    srv = _paged(setup)
    events = _spy(monkeypatch, srv)
    rid = srv.submit([5, 9, 2], 2)              # one decode step
    assert srv.step() == {}                     # dispatched, not fetched
    assert events == ["d"] and srv._flying is not None
    assert not srv._run and not srv.done()
    assert len(srv.outputs[rid]) == 1 and rid not in srv.finished
    srv.run_until_done(max_steps=1)             # a call with no row drains
    assert events == ["d", "f"] and srv._flying is None and srv.done()
    assert srv.outputs[rid] == solo(setup, [5, 9, 2], 2)
    assert srv.step() == {} and events == ["d", "f"]


def test_a_tick_of_eight_dispatches_eight_and_the_first_emits_seven(setup):
    """Through the handler: the step dispatched last stays in flight
    over the reply, so a server's first tick emits a token less than
    it dispatched and every later one as many; a request's tokens
    arrive at contiguous offsets; ``kvr``, ``dc`` and ``ahd`` count
    the same steps, the fetched ones, the first tick included."""
    from nbdistributed_tpu.observability.servingobs import \
        ServingObservatory
    srv = _paged(setup, max_len=64)
    w = _worker(srv)
    obs = ServingObservatory()
    admit = [{"rid": "a", "prompt": [5, 9, 2], "max_new": 20},
             {"rid": "b", "prompt": [7, 1], "max_new": 30}]
    got = {"a": [], "b": []}
    ticks = []
    for seq in range(1, 4):
        d = _step(w, seq, admit=admit if seq == 1 else (), steps=8)
        for rid, em in d["emitted"].items():
            assert em["o"] == len(got[rid])     # contiguous
            got[rid] += em["t"]
        ticks.append(d["tick"])
        obs.note_tick(seq, 0, {}, d["tick"])
        assert srv._flying is not None
    # the token of the admission, then 7, 8 and (a's last 4 of 20) 4
    assert [len(got["a"]), len(got["b"])] == [20, 1 + 7 + 8 + 8]
    assert d["finished"] == ["a"]
    assert [t["dc"] for t in ticks] == [2 * 7, 2 * 8, 4 + 8]
    assert [t["kvr"][1] for t in ticks] == [7, 8, 8]
    assert [t["ahd"] for t in ticks] == [[7, 7], [8, 8], [8, 8]]
    assert obs.ticks_summary()["ahead"] == 1.0
    # the last tick drains: 5 steps left to dispatch, 6 to fetch
    d = _step(w, 4, steps=8)
    assert d["tick"]["ahd"] == [5, 6] and srv.done()
    obs.note_tick(4, 0, {}, d["tick"])
    assert obs.ticks_summary()["ahead"] == round(28 / 29, 4)
    got["b"] += d["emitted"]["b"]["t"]
    assert got["a"] == solo(setup, [5, 9, 2], 20)
    assert got["b"] == solo(setup, [7, 1], 30)
