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
import pytest

from nbdistributed_tpu.messaging import Message
from nbdistributed_tpu.models import init_params, tiny_config
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
    clk = _fake_time(monkeypatch, serving_mod)
    seen = dict(srv.phase_s)
    t_sub0 = clk.n
    srv.submit([5, 9, 2], 4)
    # an admission is prefill, whoever calls it: [t0, t1] around it
    assert srv.phase_s["prefill"] - seen["prefill"] == clk.n - t_sub0 - 1
    while not srv.done():
        before, n0 = dict(srv.phase_s), clk.n
        srv.step()
        wall = clk.n - n0 - 1          # last read minus first read
        delta = {k: srv.phase_s[k] - before[k] for k in STEP_PHASES}
        assert all(v >= 0 for v in delta.values())       # monotone
        assert sum(delta.values()) == wall
        assert delta["sync"] == 1 and delta["dispatch"] == 1
    # a step with nothing to decode is prefill alone
    before, n0 = dict(srv.phase_s), clk.n
    assert srv.step() == {}
    assert srv.phase_s["prefill"] - before["prefill"] == clk.n - n0 - 1
    assert srv.phase_s["sync"] == before["sync"]


def test_trailing_admission_of_a_step_counts_as_prefill(setup, monkeypatch):
    """One slot, two requests: the step that finishes the first admits
    the second, and that admission is prefill, not emit."""
    srv = _paged(setup, max_batch=1)
    srv.submit([5, 9, 2], 2)
    r2 = srv.submit([7, 1], 2)                  # waits for the slot
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
    """Each decode step adds, for every active slot, the pages from
    its window's first to the one its new token lands in, all layers,
    K and V; the handler reports the tick's delta with its steps."""
    cfg, _ = setup
    srv = _paged(setup, max_batch=2, max_len=32)
    # one page: block tokens x KV heads x head dim x (K, V) x itemsize
    page = cfg.n_layers * 8 * cfg.n_kv_heads * cfg.head_dim * 2 * 4
    assert srv._page_bytes == page
    srv.submit([5, 9, 2, 7, 1, 3, 4], 6)       # pos 7 is the page edge
    srv.submit([5, 9], 6)
    srv.step()                                  # writes pos 7 and pos 2
    assert (srv.kv_read_bytes_total, srv.decode_steps_total) == (
        page * (1 + 1), 1)
    srv.step()                                  # pos 8: a second page
    assert srv.kv_read_bytes_total == page * (2 + 2 + 1)
    # the account is what the server did since it last gave one
    assert srv.take_account()["kvr"] == [page * (2 + 2 + 1), 2]
    w = _worker(srv)
    tick = _step(w, 1, steps=2)["tick"]
    assert tick["kvr"] == [page * 2 * (2 + 1), 2]
    # a row of one page (the default block of 64) reads it every step
    one_page = DecodeServer(setup[1], cfg, max_batch=2, max_len=32,
                            pad_to=4)
    one_page.submit([5, 9], 3)
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
    assert tick["pf"] == 3 and tick["dc"] == 2 and tick["step_s"] > 0
    assert d["emitted"]["a"]["o"] == 0 and len(d["emitted"]["a"]["t"]) == 3
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
