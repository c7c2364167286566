"""Set-up on one timeline (ISSUE 37), the pure parts: the stage list,
the merged view, the lines the status magics print, the attach
time-out's stage, and the compile watch's split against a real
persistent cache in two fresh processes."""

import json
import os
import subprocess
import sys
import time

import pytest

from nbdistributed_tpu.manager import ProcessManager
from nbdistributed_tpu.observability import bringup, flightrec

pytestmark = [pytest.mark.unit, pytest.mark.obs]

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class _Flight:
    def __init__(self):
        self.events = []

    def record(self, etype, **fields):
        self.events.append({"t": etype, **fields})


def _walk(world: int) -> tuple[bringup.Stages, _Flight]:
    """A worker's walk through its stages, the recorder bound late."""
    st = bringup.Stages("interpreter", time.time() - 0.5)
    st.enter("import_jax")
    flight = _Flight()
    st.bind(flight)
    for stage in bringup.STAGES[2:]:
        if stage == "rendezvous" and world == 1:
            continue
        st.enter(stage)
    st.finish()
    return st, flight


@pytest.mark.parametrize("world", [1, 2])
def test_stages_are_contiguous_and_in_order(world):
    st, _ = _walk(world)
    names = [s for s, _t0, _d in st.done]
    assert names == [s for s in bringup.STAGES
                     if world > 1 or s != "rendezvous"]
    for (_s, t0, dur), (_n, nxt_t0, _d) in zip(st.done, st.done[1:]):
        assert nxt_t0 == pytest.approx(t0 + dur, abs=2e-6)
    assert st.done[0][2] >= 0.5      # from the process's creation


def test_flight_records_say_which_stage_begins():
    st, flight = _walk(2)
    recs = [e for e in flight.events if e["t"] == "bringup"]
    assert [e["stage"] for e in recs] == list(bringup.STAGES)
    # `interpreter` ended before the recorder opened: bind wrote it
    assert [e["next"] for e in recs] == list(bringup.STAGES[1:]) + [None]
    assert [[e["stage"], e["t0"], e["dur"]] for e in recs] == st.done


@pytest.mark.parametrize("upto,where", [
    (2, "rendezvous"), (4, "namespace"), (6, None)])
def test_stage_in_names_the_stage_last_entered(upto, where):
    _, flight = _walk(2)
    events = [{"t": "worker_start"}] + flight.events[:upto]
    got = bringup.stage_in(events, now=time.time() + 171.0)
    if where is None:
        assert got is None
    else:
        assert got[0] == where and got[1] == pytest.approx(171.0, abs=1.0)
    assert bringup.stage_in([{"t": "hello"}], time.time()) is None


def _lists(delayed: int, delay: float):
    """Two ranks' lists, `delayed` held back before its rendezvous."""
    out = {}
    for rank in (0, 1):
        own = 1.0 + (delay if rank == delayed else 0.0)
        wait = 0.1 + (0.0 if rank == delayed else delay)
        out[rank] = [["interpreter", 100.0, own],
                     ["rendezvous", 100.0 + own, wait],
                     ["connect", 100.0 + own + wait, 0.2]]
    return out


@pytest.mark.parametrize("delayed", [0, 1])
def test_merge_names_the_rank_the_others_waited_for(delayed):
    stages = _lists(delayed, 3.0)
    # the other rank attaches last: after the rendezvous it is a race
    attached = {delayed: 104.31, 1 - delayed: 104.33}
    view = bringup.merge(stages, {0: 100.0, 1: 100.01}, attached,
                         (100.02, 104.4))
    assert view["critical_rank"] == delayed
    assert view["ranks"][1 - delayed]["stages"]["rendezvous"] == \
        pytest.approx(3.1)
    assert view["spawn_s"] == pytest.approx(0.02)
    assert view["wait_s"] == pytest.approx(4.38)
    assert view["attach_s"] == pytest.approx(4.4)
    for rank, row in view["ranks"].items():
        assert row["attach_s"] == pytest.approx(
            attached[rank] - (100.0 if rank == 0 else 100.01))
        assert abs(row["unaccounted_s"]) < 0.05
    assert view["unaccounted_s"] == view["ranks"][delayed]["unaccounted_s"]


def test_merge_before_the_first_heartbeat_and_without_a_spawner():
    view = bringup.merge({}, {0: 10.0, 1: 10.0}, {0: 14.0, 1: 15.0},
                         (10.1, 15.1))
    assert view["critical_rank"] == 1           # the last to attach
    assert view["ranks"][0] == {"stages": None, "attach_s": 4.0}
    assert "unaccounted_s" not in view
    adopted = bringup.merge({0: [["connect", 1.0, 0.5]]}, {}, {0: 2.0},
                            None)
    assert adopted["ranks"][0]["stages"] == {"connect": 0.5}
    assert "attach_s" not in adopted and "spawn_s" not in adopted
    assert bringup.merge({}, {}, {}, None)["critical_rank"] is None


def test_max_compile_is_each_numbers_maximum():
    a = {"trace_s": 1.0, "lower_s": 0.2, "backend_s": 5.0,
         "cache_load_s": 0.0, "hits": 0, "misses": 3,
         "slowest": [["jit(step)", 4.0, "miss"]]}
    b = {"trace_s": 0.5, "lower_s": 0.4, "backend_s": 0.1,
         "cache_load_s": 0.7, "hits": 3, "misses": 0,
         "slowest": [["jit(step)", 0.6, "hit"], ["jit(x)", 0.1, "hit"]]}
    got = bringup.max_compile([a, b, None])
    assert got == {"trace_s": 1.0, "lower_s": 0.4, "backend_s": 5.0,
                   "cache_load_s": 0.7, "hits": 3, "misses": 3,
                   "slowest": [["jit(step)", 4.0, "miss"],
                               ["jit(step)", 0.6, "hit"],
                               ["jit(x)", 0.1, "hit"]]}
    assert bringup.max_compile([]) == {}


def test_status_lines_one_a_rank_with_the_critical_rank_marked():
    view = bringup.merge(_lists(1, 3.0), {0: 100.0, 1: 100.0},
                         {0: 104.3, 1: 104.3}, (100.0, 104.4))
    view["compile"] = {0: {"trace_s": 1.5, "lower_s": 0.5,
                           "backend_s": 2.0, "cache_load_s": 0.25,
                           "hits": 4, "misses": 1,
                           "slowest": [["jit(step)", 1.9, "miss"]]}}
    lines = bringup.format_lines(view)
    assert lines[0].startswith("   rank 0: interpreter 1.00 · "
                               "rendezvous 3.10 · connect 0.20")
    assert "critical" not in lines[0] and lines[1].endswith("← critical")
    assert "fleet: spawn 0.00 · wait 4.40 = attach 4.40s" in lines[2]
    assert "rank 0 compile: trace 1.50 · lower 0.50 · backend 2.00" \
        in lines[3] and "4 hits / 1 misses" in lines[3]
    assert "jit(step) 1.90s miss" in lines[4]
    waiting = bringup.format_lines(bringup.merge(
        {}, {0: 1.0}, {0: 2.0}, (1.0, 2.0)))
    assert "first heartbeat" in waiting[0]


def test_pool_lines_add_the_daemon_and_the_serve_start():
    block = {"attach": {"interpreter_s": 1.0, "daemon_s": 0.7,
                        "spawn_s": 0.01, "wait_s": 4.0, "attach_s": 4.01,
                        "unaccounted_s": 0.0, "critical_rank": 0,
                        "tenant_attach_s": 0.002},
             "open": {"spec_s": 2.5, "build_s": 0.3, "kernels_s": None},
             "compile": {"trace_s": 1.0, "lower_s": 1.0, "backend_s": 0.0,
                         "cache_load_s": 0.5, "hits": 2, "misses": 0,
                         "slowest": []},
             "ranks": {"0": {"stages": {"interpreter": 1.0},
                             "attach_s": 4.0, "unaccounted_s": 0.0}}}
    text = "\n".join(bringup.format_pool_lines(block))
    assert "rank 0: interpreter 1.00" in text and "← critical" in text
    assert "daemon 0.70 · tenant attach 0.00 · serve open: spec 2.50 " \
           "· build 0.30" in text and "kernels" not in text
    assert "slowest rank's compile: trace 1.00" in text
    assert bringup.format_pool_lines({"attach": {}, "ranks": {}}) == []


def test_process_start_time_is_the_processs_creation():
    code = ("import time; t = time.time(); import sys; "
            f"sys.path.insert(0, {REPO!r}); "
            "from nbdistributed_tpu.observability import bringup; "
            "print(t - bringup.process_start_time())")
    t0 = time.time()
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True, timeout=60)
    age = float(out.stdout)
    # created after our stamp, to a clock tick, and before its own
    assert -0.02 <= age <= time.time() - t0


class _Proc:
    def __init__(self, pid, rc=None):
        self.pid, self._rc = pid, rc

    def poll(self):
        return self._rc


class _IO:
    def tail(self, n=40):
        return ""


def test_attach_timeout_names_the_stage_a_rank_is_in(tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv("NBD_RUN_DIR", str(tmp_path))
    pid = 424242
    ring = flightrec.FlightRecorder(
        flightrec.ring_path(str(tmp_path), "rank2", pid))
    t0 = time.time() - 175.0
    ring.record("worker_start", rank=2)
    ring.record("bringup", stage="interpreter", t0=t0, dur=1.0,
                next="import_jax")
    ring.record("bringup", stage="import_jax", t0=t0 + 1.0, dur=3.0,
                next="backend")     # it entered `backend` 171 s ago
    pm = ProcessManager()
    pm.processes = {1: _Proc(11, rc=17), 2: _Proc(pid), 3: _Proc(99)}
    pm.io = {r: _IO() for r in pm.processes}
    text = pm.startup_diagnostics()
    assert "rank 2: still running (pid 424242, never attached) in " \
           "`backend` for 171 s" in text
    assert "rank 1: exited with code 17\n" in text        # no stage
    assert "rank 3: still running (pid 99, never attached)\n" in text


# ----------------------------------------------------------------------
# the compile watch's split, against a real persistent cache

_COMPILE = """
import json, sys
sys.path.insert(0, {repo!r})
import jax, numpy as np
from nbdistributed_tpu.observability import telemetry as T
T.install_compile_watch()
jax.config.update("jax_compilation_cache_dir", {cache!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

@jax.jit
def inner(x):
    return x @ x

@jax.jit
def program_under_test(x):
    return inner(x).sum() + 1.0

before = T.compile_snapshot()
program_under_test(np.ones((32, 32), np.float32)).block_until_ready()
print("SPLIT " + json.dumps({{"split": T.compile_split(),
                              "before": before,
                              "after": T.compile_snapshot()}}))
"""


@pytest.fixture(scope="module")
def cold_then_warm(tmp_path_factory):
    """One jitted function compiled into an empty cache directory, then
    the same function in a fresh process."""
    cache = str(tmp_path_factory.mktemp("xla_cache"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _COMPILE.format(repo=REPO, cache=cache)],
            env=env, capture_output=True, text=True, timeout=180)
        assert out.returncode == 0, out.stderr[-2000:]
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("SPLIT ")][-1]
        runs.append(json.loads(line[6:]))
    return dict(zip(("cold", "warm"), runs))


@pytest.mark.parametrize("run,hits,misses", [("cold", 0, 1),
                                             ("warm", 1, 0)])
def test_compile_watch_tells_a_compile_from_a_cache_load(
        cold_then_warm, run, hits, misses):
    got = cold_then_warm[run]
    split = got["split"]
    assert (split["hits"], split["misses"]) == (hits, misses)
    assert split["trace_s"] > 0 and split["lower_s"] > 0
    if run == "cold":
        assert split["backend_s"] > 0 and split["cache_load_s"] == 0
    else:
        assert split["cache_load_s"] > 0 and split["backend_s"] == 0
    name, secs, how = split["slowest"][0]
    assert name == "jit(program_under_test)" and secs > 0
    assert how == ("miss" if run == "cold" else "hit")
    # compile_snapshot keeps its meaning: one event a program made
    # ready, a cache load included
    assert got["before"] == [0, 0.0] and got["after"][0] == 1


def test_nested_traces_are_counted_once():
    """An outer jit's trace holds its inner jits' traces: only the
    outermost adds its seconds."""
    from nbdistributed_tpu.observability.telemetry import _CompileWatch

    class Watch(_CompileWatch):     # counters of its own
        trace_s = lower_s = backend_s = 0.0

    ev = "/jax/core/compile/"
    Watch._on_enter(ev + "jaxpr_trace_duration", 0.0, fun_name="outer")
    Watch._on_enter(ev + "jaxpr_trace_duration", 0.0, fun_name="inner")
    Watch._on_duration(ev + "jaxpr_trace_duration", 0.2, fun_name="inner")
    Watch._on_duration(ev + "jaxpr_trace_duration", 0.3, fun_name="outer")
    Watch._on_enter(ev + "jaxpr_to_mlir_module_duration", 0.0)
    Watch._on_enter(ev + "jaxpr_trace_duration", 0.0)   # a kernel body
    Watch._on_duration(ev + "jaxpr_trace_duration", 0.05)
    Watch._on_duration(ev + "jaxpr_to_mlir_module_duration", 0.1)
    assert Watch.trace_s == pytest.approx(0.3)
    assert Watch.lower_s == pytest.approx(0.1)
    # a listener installed mid-compile sees an exit without its enter
    Watch._on_duration(ev + "jaxpr_trace_duration", 0.01)
    assert Watch.trace_s == pytest.approx(0.31)


def test_the_last_resort_sweep_spares_what_this_process_did_not_spawn():
    """``%dist_shutdown``'s sweep kills this kernel's own lost workers
    and nobody else's: by pattern alone it took every fleet on the
    machine with it (other kernels', a pool's, the test files' beside
    this one under pytest-xdist)."""
    from nbdistributed_tpu.magics.magic import DistributedMagics

    name = "nbdistributed_tpu.runtime.worker"      # in argv, not run
    nap = "import time; time.sleep(60)"
    mine = subprocess.Popen([sys.executable, "-c", nap, name])
    # somebody else's worker: a grandchild, its parent is not us (and
    # that parent's own argv does not spell the name)
    other = subprocess.Popen(
        [sys.executable, "-c",
         "import subprocess, sys, time\n"
         "name = '.'.join(['nbdistributed_tpu', 'runtime', 'worker'])\n"
         f"p = subprocess.Popen([sys.executable, '-c', {nap!r}, name])\n"
         "print(p.pid, flush=True)\ntime.sleep(60)"],
        stdout=subprocess.PIPE, text=True)
    try:
        theirs = int(other.stdout.readline())
        DistributedMagics._nuclear_shutdown()
        assert mine.wait(timeout=10) == -9
        os.kill(theirs, 0)                         # still there
        assert other.poll() is None
    finally:
        for proc in (mine, other):
            proc.kill()
            proc.wait(timeout=10)
        try:
            os.kill(theirs, 9)
        except (ProcessLookupError, NameError):
            pass
