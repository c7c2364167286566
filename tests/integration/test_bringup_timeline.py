"""Set-up on one timeline (ISSUE 37) on the CPU fleet: every rank's
stages after ``%dist_init``, the same through ``%dist_pool start`` ->
``%dist_attach --tenant`` -> ``serve_status()["bringup"]`` with every
key a new benchmark metric names, and a worker held back before its
rendezvous.

Each fixture brings its fleet up, reads what the tests need and takes
the fleet down again at once; the tests assert on what was read.
"""

import contextlib
import glob
import io
import os
import time

import pytest

from benchmarks import harness as H
from nbdistributed_tpu.manager import ProcessManager, wait_until_ready
from nbdistributed_tpu.messaging import CommunicationManager
from nbdistributed_tpu.observability import bringup

pytestmark = [pytest.mark.integration, pytest.mark.obs]

WORLD = 2
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = """
from nbdistributed_tpu.models import init_params, tiny_config
import jax
cfg = tiny_config()
params = init_params(jax.random.PRNGKey(0), cfg)
"""
NEW_METRICS = sorted(
    os.path.basename(p)[:-5]
    for p in glob.glob(os.path.join(REPO, "benchmarks", "metrics", "*.json"))
    if "serve_status.bringup" in open(p).read()
    or '"bringup_covered"' in open(p).read())


def _shell():
    from IPython.testing.globalipapp import get_ipython, start_ipython
    shell = start_ipython() or get_ipython()
    shell.run_line_magic("load_ext", "nbdistributed_tpu")
    return shell


def _magic(shell, name, line) -> str:
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        shell.run_line_magic(name, line)
    return buf.getvalue()


def _until(read, ready, seconds=20.0):
    """Poll ``read()`` until ``ready(value)``: the workers' lists ride
    the first heartbeat, the compile split the next telemetry sample."""
    deadline = time.time() + seconds
    while True:
        value = read()
        if ready(value) or time.time() > deadline:
            return value
        time.sleep(0.25)


def _all_listed(view) -> bool:
    return all(row["stages"] for row in view["ranks"].values()) \
        and len(view["ranks"]) == WORLD


@pytest.fixture(scope="module")
def fleet():
    from nbdistributed_tpu.magics.magic import DistributedMagics as DM
    shell = _shell()
    got = {"banner": _magic(
        shell, "dist_init",
        f"-n {WORLD} --backend cpu --attach-timeout 180 -t 120")}
    try:
        assert DM._comm is not None, got["banner"][-2000:]
        got["at_once"] = DM._comm.bringup()
        got["status_at_once"] = _magic(shell, "dist_status", "")
        got["view"] = _until(DM._comm.bringup, _all_listed)
        got["pulled"] = {
            r: m.data["bringup"] for r, m in
            DM._comm.send_to_all("get_status", timeout=30).items()}
        got["logs"] = {r: DM._pm.io[r].tail(40) for r in range(WORLD)}
    finally:
        _magic(shell, "dist_shutdown", "")
    return got


@pytest.mark.parametrize("rank", range(WORLD))
def test_every_rank_holds_the_stages_in_order(fleet, rank):
    stages = fleet["pulled"][rank]["stages"]
    assert [s for s, _t0, _d in stages] == list(bringup.STAGES)
    for (_s, t0, dur), (_n, nxt, _d) in zip(stages, stages[1:]):
        assert dur >= 0 and nxt == pytest.approx(t0 + dur, abs=2e-6)
    # what rode the heartbeat is the list get_status pulls
    assert fleet["view"]["ranks"][rank]["stages"] == \
        {s: d for s, _t0, d in stages}
    # interpreter and import_jax are whole seconds of Python; the
    # others are there even where they are short
    by = dict((s, d) for s, _t0, d in stages)
    assert by["interpreter"] > 0.1 and by["import_jax"] > 0.1


@pytest.mark.parametrize("rank", range(WORLD))
def test_the_stages_account_for_the_attach(fleet, rank):
    row = fleet["view"]["ranks"][rank]
    assert abs(row["unaccounted_s"]) < 0.2
    assert row["attach_s"] == pytest.approx(
        sum(row["stages"].values()), abs=0.2)


def test_the_fleets_view_and_the_banner_share_their_stamps(fleet):
    view = fleet["view"]
    assert view["critical_rank"] in range(WORLD)
    assert abs(view["unaccounted_s"]) < 0.2
    assert view["attach_s"] == pytest.approx(
        view["spawn_s"] + view["wait_s"], abs=1e-5)
    # the wait ends a poll after the last rank attached, never before
    slowest = max(r["attach_s"] for r in view["ranks"].values())
    assert slowest - 0.05 <= view["attach_s"] <= slowest + 2.5
    assert f"attach {view['attach_s']:.1f}s" in fleet["banner"]
    # before the first heartbeat the spawner's half is already there
    assert fleet["at_once"]["attach_s"] == view["attach_s"]


def test_dist_status_prints_one_line_a_rank_and_the_compile_split(fleet):
    out = fleet["status_at_once"]     # idle ranks' replies fill it in
    assert "⏱ bring-up (s):" in out
    for rank in range(WORLD):
        line = next(ln for ln in out.splitlines()
                    if ln.startswith(f"   rank {rank}: "))
        for stage in bringup.STAGES:
            assert f"{stage} " in line
        assert f"rank {rank} compile: trace " in out
    assert out.count("← critical") == 1
    assert "fleet: spawn " in out and "cache 0 hits / 0 misses" in out


@pytest.mark.parametrize("rank", range(WORLD))
def test_the_workers_log_says_the_timelines_seconds(fleet, rank):
    by = {s: d for s, _t0, d in fleet["pulled"][rank]["stages"]}
    log = fleet["logs"][rank]
    assert (f"joining jax.distributed world ({WORLD} processes) after "
            f"interpreter {by['interpreter']:.2f}s, import_jax "
            f"{by['import_jax']:.2f}s...") in log
    assert (f"global_devices={WORLD} (interpreter "
            f"{by['interpreter']:.2f}s, import_jax "
            f"{by['import_jax']:.2f}s, rendezvous "
            f"{by['rendezvous']:.2f}s, backend {by['backend']:.2f}s)") in log


# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def pool(tmp_path_factory):
    from nbdistributed_tpu.magics.magic import DistributedMagics as DM
    shell = _shell()
    run_dir = str(tmp_path_factory.mktemp("pool"))
    got = {}
    t0 = time.time()
    out = _magic(shell, "dist_pool",
                 f"start -n {WORLD} --backend cpu --run-dir {run_dir}")
    try:
        assert "pool up" in out, out[-2000:]
        _magic(shell, "dist_attach", f"--tenant alice {run_dir}")
        client = DM._tenant
        assert client is not None
        got["stopwatch_s"] = time.time() - t0
        shell.user_ns["bringup_spec"] = SPEC
        out = _magic(shell, "dist_serve",
                     "start --spec bringup_spec --max-batch 2 "
                     "--max-len 32 --pad-to 4")
        assert "serving as tenant" in out, out[-2000:]
        _magic(shell, "dist_serve",
               "submit --prompt 5,9,2 --max-new 4 --wait")
        got["status"] = _until(
            client.serve_status,
            lambda st: (st["bringup"]["compile"].get("trace_s", 0) > 0
                        and st["bringup"]["attach"].get("connect_s")
                        is not None
                        and len(st["bringup"]["ranks"]) == WORLD
                        and all(r["stages"] for r in
                                st["bringup"]["ranks"].values())),
            seconds=40.0)
        got["pool_status"] = _magic(shell, "dist_pool", "status")
        _magic(shell, "dist_serve", "stop")
    finally:
        _magic(shell, "dist_pool", f"stop --run-dir {run_dir}")
        _magic(shell, "dist_shutdown", "")
    return got


@pytest.mark.parametrize("name", NEW_METRICS)
def test_serve_status_holds_what_each_new_metric_reads(pool, name):
    obs = {"serve_status": pool["status"],
           "spans": {"fleet_attach_s": pool["stopwatch_s"]}}
    value = H.read_metric(name, obs)
    assert isinstance(value, float) and value >= 0.0
    if name == "attach_covered_share":
        assert 50.0 < value <= 100.5


def test_there_are_thirteen_new_metrics():
    assert len(NEW_METRICS) == 13


def test_the_gateways_block_is_one_timeline(pool):
    b = pool["status"]["bringup"]
    a = b["attach"]
    crit = b["ranks"][str(a["critical_rank"])]
    assert {f"{s}_s": d for s, d in crit["stages"].items()} == \
        {k: a[k] for k in a if k[:-2] in bringup.STAGES}
    assert list(crit["stages"]) == list(bringup.STAGES)
    assert abs(a["unaccounted_s"]) < 0.2
    assert a["attach_s"] == pytest.approx(a["spawn_s"] + a["wait_s"],
                                          abs=1e-5)
    # a daemon's interpreter and imports, then a hello's handling
    assert 0.05 < a["daemon_s"] < 60 and 0 <= a["tenant_attach_s"] < 5
    assert set(b["open"]) == {"spec_s", "build_s", "kernels_s"}
    assert all(v > 0 for v in b["open"].values())
    c = b["compile"]
    assert c["trace_s"] > 0 and c["lower_s"] > 0
    assert c["backend_s"] + c["cache_load_s"] > 0
    assert len(c["slowest"]) == 8
    assert all(how in ("hit", "miss", "uncached")
               for _n, _s, how in c["slowest"])


def test_dist_pool_status_prints_the_timeline(pool):
    out = pool["pool_status"]
    assert "⏱ bring-up (s):" in out
    for rank in range(WORLD):
        assert f"   rank {rank}: interpreter " in out
    assert out.count("← critical") == 1
    assert "daemon " in out and "tenant attach " in out
    assert "serve open: spec " in out and "build " in out
    assert "slowest rank's compile: trace " in out


# ----------------------------------------------------------------------

DELAY_S = 2.0
_HOLD = """
import sys, time
argv = sys.orig_argv
if ("nbdistributed_tpu.runtime.worker" in argv and "--rank" in argv
        and argv[argv.index("--rank") + 1] == "{rank}"):
    time.sleep({delay})
"""


@pytest.fixture(scope="module", params=[0, 1])
def held_back(request, tmp_path_factory):
    """A world whose rank ``param`` sleeps before it reaches ``main()``
    (a ``sitecustomize`` on its path): held back before its
    rendezvous."""
    late = request.param
    site = tmp_path_factory.mktemp(f"site{late}")
    (site / "sitecustomize.py").write_text(
        _HOLD.format(rank=late, delay=DELAY_S))
    comm = CommunicationManager(num_workers=WORLD, timeout=60)
    pm = ProcessManager()
    try:
        pm.start_workers(
            WORLD, comm.port, backend="cpu",
            extra_env={"PYTHONPATH": os.pathsep.join(
                [str(site), REPO, os.environ.get("PYTHONPATH", "")])})
        wait_until_ready(comm, pm, 180)
        view = _until(comm.bringup, _all_listed)
    finally:
        pm.shutdown()
        comm.shutdown()
    return late, view


def test_a_rank_held_back_is_the_critical_rank(held_back):
    late, view = held_back
    assert view["critical_rank"] == late
    mine = view["ranks"][late]["stages"]
    other = view["ranks"][1 - late]["stages"]
    assert mine["interpreter"] >= other["interpreter"] + DELAY_S - 0.5


def test_the_others_rendezvous_grows_by_the_delay(held_back):
    late, view = held_back
    mine = view["ranks"][late]["stages"]
    other = view["ranks"][1 - late]["stages"]
    # the rank on time waits for the late one inside its rendezvous
    assert other["rendezvous"] >= mine["rendezvous"] + DELAY_S - 1.0
    assert other["rendezvous"] >= DELAY_S - 1.0
    for row in view["ranks"].values():
        assert abs(row["unaccounted_s"]) < 0.2
