"""Unit tests for the serving observatory (ISSUE 18): the telescoping
stage decomposition (sum == e2e and TTFT == admit+queue+kv_alloc+prefill
EXACTLY, by construction), the clock-corrected TPOT clamp, the KV
fragmentation scan, the utilization ring/gauges, the {tenant,rank}
series-retirement pin, the autoscaler audit record shape; and the
tick's account (ISSUE 25): the ring of 64, phases that sum to the
handler's time, the clamped wire, the slow-tick rule."""

import math

import pytest

from nbdistributed_tpu.observability import metrics as obs_metrics
from nbdistributed_tpu.observability import servingobs
from nbdistributed_tpu.observability.servingobs import (
    SERVE_STAGES, ServingObservatory, format_serve_stage_table,
    format_serve_waterfall, largest_free_run)

pytestmark = [pytest.mark.unit, pytest.mark.obs, pytest.mark.serve]


class FakeClock:
    """Deterministic ``now()`` the tests advance by hand."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


class FakeOffsets:
    """Stand-in for ``ClockEstimator``: fixed per-rank offsets."""

    def __init__(self, offsets):
        self._off = offsets

    def offset(self, rank):
        return self._off.get(rank, 0.0)


def _drive_one(obs, clk, rid="r-1", tenant="tn", rank=0):
    """One full lifecycle with known stage widths; returns the
    completion record."""
    obs.begin(rid, tenant, t_submit=clk.t)
    clk.advance(0.010)                       # admit
    obs.note_admit(rid, t=clk.t)
    clk.advance(0.050)                       # queue
    obs.note_placed(rid, rank, kv_alloc_s=0.004, need_blocks=3,
                    pf_total=2, t=clk.t)
    clk.advance(0.030)                       # kv_alloc+prefill tail
    obs.note_emission(rid, rank, 1, t_recv=clk.t, emit_s=0.001)
    obs.note_decode(rid, 0.008)
    clk.advance(0.020)
    obs.note_emission(rid, rank, 2, t_recv=clk.t, emit_s=0.001)
    obs.note_decode(rid, 0.008)
    clk.advance(0.005)                       # deliver
    return obs.complete(rid, "completed", t_finish=clk.t)


def test_stage_sum_is_exactly_e2e():
    clk = FakeClock()
    obs = ServingObservatory(now=clk)
    rec = _drive_one(obs, clk)
    assert rec is not None and rec["status"] == "completed"
    total = sum(rec["stages"][s] for s in SERVE_STAGES)
    # Telescoping gateway anchors: exact up to the record rounding
    # (6 decimal places), not a tolerance band.
    assert math.isclose(total, rec["e2e_s"], abs_tol=1e-5), \
        (total, rec["e2e_s"], rec["stages"])
    assert all(rec["stages"][s] >= 0.0 for s in SERVE_STAGES)


def test_ttft_identity_and_kv_alloc_cap():
    clk = FakeClock()
    obs = ServingObservatory(now=clk)
    rec = _drive_one(obs, clk)
    st = rec["stages"]
    assert math.isclose(
        rec["ttft_s"],
        st["admit"] + st["queue"] + st["kv_alloc"] + st["prefill"],
        abs_tol=1e-9)
    # The TTFT tail [placed, first_tok] was 30ms: measured alloc 4ms
    # fits, prefill is the remainder.
    assert math.isclose(st["admit"], 0.010, abs_tol=1e-6)
    assert math.isclose(st["queue"], 0.050, abs_tol=1e-6)
    assert math.isclose(st["kv_alloc"], 0.004, abs_tol=1e-6)
    assert math.isclose(st["prefill"], 0.026, abs_tol=1e-6)
    # An alloc measurement LARGER than the tail is capped, never
    # negative-prefill.
    obs2 = ServingObservatory(now=clk)
    obs2.begin("r-2", "tn", t_submit=clk.t)
    obs2.note_admit("r-2", t=clk.t)
    obs2.note_placed("r-2", 0, kv_alloc_s=5.0, t=clk.t)
    clk.advance(0.010)
    obs2.note_emission("r-2", 0, 1, t_recv=clk.t)
    rec2 = obs2.complete("r-2", "completed", t_finish=clk.t)
    assert math.isclose(rec2["stages"]["kv_alloc"], 0.010,
                        abs_tol=1e-6)
    assert rec2["stages"]["prefill"] == 0.0


def test_decode_emit_split_capped_to_span():
    """Worker durations only SPLIT the [first, last] span: inflated
    decode/emit attributions cap out and decode_wait stays >= 0."""
    clk = FakeClock()
    obs = ServingObservatory(now=clk)
    obs.begin("r-3", "tn", t_submit=clk.t)
    obs.note_admit("r-3", t=clk.t)
    obs.note_placed("r-3", 1, t=clk.t)
    obs.note_emission("r-3", 1, 1, t_recv=clk.t)
    clk.advance(0.020)                       # span = 20ms
    obs.note_emission("r-3", 1, 1, t_recv=clk.t, emit_s=9.0)
    obs.note_decode("r-3", 9.0)              # wildly over-attributed
    rec = obs.complete("r-3", "completed", t_finish=clk.t)
    st = rec["stages"]
    assert math.isclose(st["decode"], 0.020, abs_tol=1e-6)
    assert st["emit"] == 0.0 and st["decode_wait"] == 0.0
    total = sum(st[s] for s in SERVE_STAGES)
    assert math.isclose(total, rec["e2e_s"], abs_tol=1e-5)


def test_tpot_prefers_corrected_worker_stamps():
    """Worker stamps skewed +5s are corrected by the per-rank offset
    before the inter-token mean — gateway arrival jitter never enters
    when stamps are present."""
    clk = FakeClock()
    obs = ServingObservatory(clock=FakeOffsets({1: 5.0}), now=clk)
    obs.begin("r-4", "tn", t_submit=clk.t)
    obs.note_admit("r-4", t=clk.t)
    obs.note_placed("r-4", 1, t=clk.t)
    t0 = clk.t
    obs.note_emission("r-4", 1, 1, t_recv=clk.t, t_worker=t0 + 5.0)
    clk.advance(0.500)                       # noisy gateway arrival
    obs.note_emission("r-4", 1, 3, t_recv=clk.t,
                      t_worker=t0 + 5.0 + 0.120)
    rec = obs.complete("r-4", "completed", t_finish=clk.t)
    # 120ms worker span over 3 inter-token gaps = 40ms, NOT the
    # 500/3 ms the gateway clock would give.
    assert math.isclose(rec["tpot_s"], 0.040, abs_tol=1e-6)


def test_tpot_clamped_nonnegative_on_offset_error():
    clk = FakeClock()
    obs = ServingObservatory(clock=FakeOffsets({1: 10.0}), now=clk)
    obs.begin("r-5", "tn", t_submit=clk.t)
    obs.note_placed("r-5", 1, t=clk.t)
    t0 = clk.t
    # A bad offset estimate makes corrected stamps run BACKWARD.
    obs.note_emission("r-5", 1, 1, t_recv=clk.t, t_worker=t0 + 10.0)
    clk.advance(0.050)
    obs.note_emission("r-5", 1, 2, t_recv=clk.t, t_worker=t0 + 9.5)
    rec = obs.complete("r-5", "completed", t_finish=clk.t)
    assert rec["tpot_s"] == 0.0


def test_tpot_gateway_fallback_without_stamps():
    clk = FakeClock()
    obs = ServingObservatory(now=clk)
    obs.begin("r-6", "tn", t_submit=clk.t)
    obs.note_placed("r-6", 0, t=clk.t)
    obs.note_emission("r-6", 0, 1, t_recv=clk.t)
    clk.advance(0.100)
    obs.note_emission("r-6", 0, 2, t_recv=clk.t)
    rec = obs.complete("r-6", "completed", t_finish=clk.t)
    assert math.isclose(rec["tpot_s"], 0.050, abs_tol=1e-6)


def test_drop_and_unknown_rids_are_safe():
    clk = FakeClock()
    obs = ServingObservatory(now=clk)
    obs.begin("r-7", "tn")
    obs.drop("r-7")
    assert obs.dropped == 1
    assert obs.complete("r-7", "completed") is None
    # note_* on never-begun rids must not create ghosts.
    obs.note_admit("ghost")
    obs.note_emission("ghost", 0, 1)
    obs.note_decode("ghost", 0.1)
    assert obs.records() == [] and obs.completed == 0


def test_summary_and_renderers():
    clk = FakeClock()
    obs = ServingObservatory(now=clk)
    for i in range(4):
        _drive_one(obs, clk, rid=f"r-{i}")
    s = obs.summary()
    assert s["count"] == 4
    assert set(s["stages"]) == set(SERVE_STAGES)
    # Stage shares are fractions of mean e2e and roughly total 1.
    assert 0.95 < sum(v["share"] for v in s["stages"].values()) < 1.05
    table = format_serve_stage_table(s)
    assert "decode" in table and "ttft" in table
    wf = format_serve_waterfall(obs.records(2))
    assert "tok" in wf and "r-3" in wf
    blk = obs.status_block(records=2)
    assert blk["enabled"] and len(blk["records"]) == 2


# ---------------------------------------------------------------------
# fragmentation scan + utilization telemetry


def test_largest_free_run():
    assert largest_free_run([]) == 0
    assert largest_free_run([7]) == 1
    assert largest_free_run([3, 1, 2, 9]) == 3
    assert largest_free_run([5, 5, 6]) == 2          # dupes collapse
    assert largest_free_run(range(10)) == 10


def test_util_ring_summary_and_gauges():
    clk = FakeClock()
    obs = ServingObservatory(now=clk)
    for placed in (1, 2):
        obs.note_util(
            ranks={0: {"placed": placed, "slots": 2, "kv_used": 4,
                       "kv_free": 12, "frag": 7, "pending": 1}},
            prefill_toks=8, decode_toks=2, backlog=3,
            tenant="util-tn", t=clk.advance(0.1))
    u = obs.util_summary()
    assert u["count"] == 2
    assert math.isclose(u["fill_mean"], 0.75, abs_tol=1e-9)
    assert u["fill_max"] == 1.0
    assert math.isclose(u["prefill_share"], 16 / 20, abs_tol=1e-9)
    assert u["ranks"]["0"]["frag"] == 7
    j = obs_metrics.registry().to_json()["gauges"]
    assert j['nbd_serve_batch_fill_ratio{tenant="util-tn"}'] == 1.0
    assert j['nbd_kv_frag_largest_run{rank="0",tenant="util-tn"}'] \
        == 7.0
    assert j['nbd_serve_defer_depth{rank="0",tenant="util-tn"}'] == 1.0
    obs_metrics.registry().remove_label_series("tenant", "util-tn")


def test_tenant_eviction_retires_rank_labeled_series():
    """Satellite 1 pin: the per-rank KV gauges carry {tenant, rank}
    labels, so tenant eviction's ``remove_label_series('tenant', ...)``
    retires EVERY rank's series for that tenant — nothing accumulates
    for the daemon's lifetime."""
    reg = obs_metrics.registry()
    for rank in ("0", "1", "all"):
        reg.gauge("nbd_kv_blocks_used", "t",
                  {"tenant": "evict-me", "rank": rank}).set(3)
        reg.gauge("nbd_kv_blocks_free", "t",
                  {"tenant": "evict-me", "rank": rank}).set(5)
    reg.histogram("nbd_serve_stage_seconds", "t",
                  {"stage": "decode", "tenant": "evict-me"}).observe(.1)
    assert reg.remove_label_series("tenant", "evict-me") == 7
    text = reg.prometheus_text()
    assert "evict-me" not in text


# ---------------------------------------------------------------------
# the tick's account (ISSUE 25): ring, telescoping, wire, slow ticks

GW = {"place": 0.001, "roundtrip": 0.5, "apply": 0.002, "util": 0.001,
      "journal": 0.0005, "notify": 0.0003}
WK = {"admit": 0.001, "prefill": 0.0, "dispatch": 0.02, "sync": 0.42,
      "emit": 0.01, "collect": 0.002}


def _tick(obs, seq, *, gw=None, wk=None, cmp=(0, 0.0), turnaround=0.01,
          idled=False, rank=0):
    return obs.note_tick(
        seq, rank, dict(GW, **(gw or {})),
        {"ph": dict(WK, **(wk or {})), "cmp": list(cmp),
         "turnaround": turnaround}, idled=idled)


def test_ticks_block_present_with_no_finished_request():
    obs = ServingObservatory(now=FakeClock())
    tk = obs.summary()["ticks"]
    assert tk == {"count": 0, "compiles": 0, "compile_ms": 0.0,
                  "kv_view_bytes": 0, "kv_read_bytes": 0,
                  "prefill_keys": 0, "ahead": 0.0, "slow": [],
                  "totals": dict.fromkeys(servingobs.TICK_TOTALS, 0.0)}
    _tick(obs, 1)
    s = obs.summary()
    assert s["count"] == 0 and "stages" not in s
    assert s["ticks"]["count"] == 1
    assert s["ticks"]["sync"]["p50"] == 420.0


def test_tick_ring_keeps_64():
    obs = ServingObservatory(now=FakeClock())
    for seq in range(1, 101):
        _tick(obs, seq, cmp=(1, 0.5) if seq <= 36 else (0, 0.0))
    tk = obs.ticks_summary()
    assert servingobs.TICK_RING == 64 and tk["count"] == 64
    # ticks 37..100 remain: the compiles of the first 36 have left
    assert tk["compiles"] == 0 and tk["compile_ms"] == 0.0
    _tick(obs, 101, cmp=(2, 0.25))
    tk = obs.ticks_summary()
    assert tk["compiles"] == 2 and tk["compile_ms"] == 250.0


def test_kv_read_bytes_is_the_mean_over_the_rings_decode_steps():
    """``kvr`` = [bytes, steps] a tick: the summary divides the ring's
    bytes by the ring's steps (a tick with no step adds nothing)."""
    obs = ServingObservatory(now=FakeClock())
    for seq, kvr in enumerate([(800, 8), (0, 0), (1000, 2), None], 1):
        obs.note_tick(seq, 0, dict(GW), {"ph": dict(WK), "kvr": kvr})
    assert obs.ticks_summary()["kv_read_bytes"] == 180


@pytest.mark.parametrize("pfk, want", [
    ([(1024, 2), (0, 0), (4096, 2)], 1280),     # ring's keys / chunks
    ([(512, 1), None], 512),                    # an old worker's tick
    ([None, None], 0)])                         # no worker counts
def test_prefill_keys_is_the_mean_over_the_rings_chunk_programs(pfk, want):
    """``pfk`` = [keys, chunks] a tick; a worker that sends none (an
    older one, or a tick without the key) adds nothing and breaks
    nothing."""
    obs = ServingObservatory(now=FakeClock())
    for seq, one in enumerate(pfk, 1):
        obs.note_tick(seq, 0, dict(GW), {"ph": dict(WK), "pfk": one})
    assert obs.ticks_summary()["prefill_keys"] == want
    assert obs.summary()["ticks"]["prefill_keys"] == want


@pytest.mark.parametrize("ahd, want", [
    ([(7, 7), (0, 0), (8, 8), (3, 4)], round(18 / 19, 4)),
    ([(8, 8), None], 1.0),                      # a tick without the key
    ([None, None], 0.0)])                       # a worker that is serial
def test_ahead_is_the_share_of_the_rings_steps_fetched_with_a_successor(
        ahd, want):
    """``ahd`` = [steps fetched with the next step already dispatched,
    steps fetched] a tick: the summary divides the ring's sums."""
    obs = ServingObservatory(now=FakeClock())
    for seq, one in enumerate(ahd, 1):
        obs.note_tick(seq, 0, dict(GW), {"ph": dict(WK), "ahd": one})
    assert obs.ticks_summary()["ahead"] == want
    assert obs.summary()["ticks"]["ahead"] == want


def test_worker_phases_sum_to_the_handler_time_exactly():
    """The handler's time IS the sum of its phases (they telescope on
    the worker's clock); the period adds the turnaround, host leaves
    prefill and sync out, gateway_self leaves the round trip out."""
    obs = ServingObservatory(now=FakeClock())
    wk = {"admit": 0.25, "prefill": 0.5, "dispatch": 0.125,
          "sync": 1.0, "emit": 0.0625, "collect": 0.03125}
    _tick(obs, 1, wk=wk, gw={"roundtrip": 2.0}, turnaround=0.5)
    (rec,) = obs._ticks
    assert rec["handler"] == sum(wk.values()) == 1.96875
    assert rec["period"] == 0.5 + 1.96875
    assert rec["wire"] == 2.0 - 1.96875
    tk = obs.ticks_summary()
    assert tk["period_ms"]["p50"] == 2468.75
    assert tk["host"]["p50"] == 468.75          # admit+dispatch+emit+collect
    assert tk["gateway_self"]["p50"] == 4.0     # place+apply+util
    for k in servingobs.WORKER_PHASES + servingobs.GATEWAY_PHASES:
        assert set(tk[k]) == {"p50", "p99", "mean"}


def test_wire_is_clamped_at_zero():
    obs = ServingObservatory(now=FakeClock())
    # a handler that (by rounding, or a redelivered reply) reads
    # longer than the round trip around it
    _tick(obs, 1, gw={"roundtrip": 0.4})
    assert obs._ticks[0]["wire"] == 0.0
    assert obs.ticks_summary()["wire"]["p50"] == 0.0


def test_idled_and_first_ticks_stay_out_of_the_period():
    obs = ServingObservatory(now=FakeClock())
    _tick(obs, 1, turnaround=None)              # a server's first tick
    _tick(obs, 2, turnaround=30.0, idled=True)  # waited for work
    assert "period_ms" not in obs.ticks_summary()
    assert "turnaround" not in obs.ticks_summary()
    _tick(obs, 3, turnaround=0.047)
    tk = obs.ticks_summary()
    assert tk["period_ms"] == {"p50": 500.0, "p99": 500.0}
    assert tk["turnaround"]["p50"] == 47.0
    assert tk["count"] == 3 and tk["slow"] == []


@pytest.mark.parametrize("base_sync, sync_s, warm, slow", [
    (0.1, 0.35, 20, False),   # 0.393 s against 3 x 0.143 s = 0.429 s
    (0.1, 0.40, 20, True),    # 0.443 s: over 3x the median
    (0.42, 0.90, 20, False),  # 0.943 s: under 1 s and 3 x 0.463 s
    (0.42, 0.98, 20, True),   # 1.023 s: over 1 s, under 3x the median
    (0.1, 0.40, 3, False),    # too few ticks for a median
    (0.1, 0.98, 3, True),     # the 1 s rule needs no ring
])
def test_slow_tick_rule(base_sync, sync_s, warm, slow):
    obs = ServingObservatory(now=FakeClock())
    for seq in range(warm):
        assert _tick(obs, seq, wk={"sync": base_sync}) is None
    got = _tick(obs, 99, wk={"sync": sync_s}, cmp=(1, 0.3))
    assert (got is not None) == slow
    kept = obs.ticks_summary()["slow"]
    assert len(kept) == (1 if slow else 0)
    if slow:
        assert got == kept[0]
        assert got["seq"] == 99 and got["cmp"] == [1, 0.3]
        assert got["worker_ms"]["sync"] == sync_s * 1e3
        assert set(got["worker_ms"]) == set(servingobs.WORKER_PHASES)
        assert set(got["gateway_ms"]) >= set(servingobs.GATEWAY_PHASES)
        assert got["period_ms"] == pytest.approx(
            (0.01 + sum(dict(WK, sync=sync_s).values())) * 1e3)


def test_slow_ticks_kept_to_eight_under_a_fake_clock():
    clk = FakeClock(1000.0)
    obs = ServingObservatory(now=clk)
    for seq in range(12):
        clk.advance(2.0)
        assert _tick(obs, seq, wk={"sync": 1.5}) is not None
    slow = obs.ticks_summary()["slow"]
    assert [t["seq"] for t in slow] == list(range(4, 12))
    assert slow[-1]["t_wall"] == 1024.0 and slow[0]["t_wall"] == 1010.0
