"""End-to-end integration: real worker subprocesses on the CPU backend.

This is the test tier the reference only declared in packaging but never
shipped (SURVEY §4): spawn N actual worker processes, form a real
``jax.distributed`` world with cross-process gloo collectives (the
CUDA→Gloo fallback analog, reference: worker.py:146-149), and drive the
full control plane: execute, streaming, variables, sync, status, death.
"""

import time

import numpy as np
import pytest

from nbdistributed_tpu.manager import ProcessManager, wait_until_ready
from nbdistributed_tpu.messaging import CommunicationManager, WorkerDied

pytestmark = [pytest.mark.integration]

WORLD = 2
ATTACH_TIMEOUT = 120  # worker startup imports jax (~5s) + rendezvous


@pytest.fixture(scope="module")
def cluster():
    comm = CommunicationManager(num_workers=WORLD, timeout=60)
    pm = ProcessManager()
    pm.add_death_callback(lambda rank, rc: comm.mark_worker_dead(rank))
    try:
        pm.start_workers(WORLD, comm.port, backend="cpu")
        wait_until_ready(comm, pm, ATTACH_TIMEOUT)
    except Exception:
        pm.shutdown()
        comm.shutdown()
        raise
    yield comm, pm
    comm.post(list(range(WORLD)), "shutdown")
    time.sleep(0.5)
    pm.shutdown()
    comm.shutdown()


def outputs(responses):
    return {r: m.data.get("output") for r, m in responses.items()}


def test_execute_on_all_ranks(cluster):
    comm, _ = cluster
    out = outputs(comm.send_to_all("execute", "rank * 10 + 1"))
    assert out == {0: "1", 1: "11"}


def test_namespace_persists(cluster):
    comm, _ = cluster
    comm.send_to_all("execute", "stash = rank + 100")
    out = outputs(comm.send_to_all("execute", "stash"))
    assert out == {0: "100", 1: "101"}


def test_world_formed(cluster):
    comm, _ = cluster
    out = outputs(comm.send_to_all("execute", "jax.device_count()"))
    assert out == {0: str(WORLD), 1: str(WORLD)}


def test_every_worker_uses_the_one_compile_cache(cluster):
    """Unset, JAX_COMPILATION_CACHE_DIR resolves to the fixed
    in-checkout directory on every rank; set, the worker leaves JAX's
    own reading of it alone (runtime/compile_cache.py)."""
    import os

    from nbdistributed_tpu.runtime import compile_cache

    comm, _ = cluster
    want = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or compile_cache.DEFAULT_DIR)
    out = outputs(comm.send_to_all(
        "execute", "jax.config.jax_compilation_cache_dir"))
    assert out == {0: repr(want), 1: repr(want)}


def test_worker_refuses_a_backend_it_cannot_get():
    """A worker launched --backend tpu where no TPU exists exits
    non-zero naming the mismatch — it never attaches on CPU (there is
    no listener here: the check comes before the control plane)."""
    import os
    import subprocess
    import sys

    from nbdistributed_tpu.manager import topology
    if topology.available_tpu_chips():
        pytest.skip("this host has TPU chips")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "nbdistributed_tpu.runtime.worker",
         "--rank", "0", "--world-size", "1", "--control-port", "1",
         "--backend", "tpu"],
        env=env, text=True, capture_output=True, timeout=120)
    assert proc.returncode != 0
    assert "--backend tpu" in proc.stderr
    assert "cannot initialise" in proc.stderr \
        or "refusing to attach" in proc.stderr


def test_cross_process_all_reduce(cluster):
    comm, _ = cluster
    out = outputs(comm.send_to_all(
        "execute",
        "r = all_reduce(jnp.ones(4) * (rank + 1))\nfloat(r[0])",
        timeout=180))
    # ranks contribute 1s and 2s -> everyone sees 3.0
    assert out == {0: "3.0", 1: "3.0"}


def test_cross_process_all_gather(cluster):
    comm, _ = cluster
    out = outputs(comm.send_to_all(
        "execute", "g = all_gather(jnp.float32(rank))\ng.shape[0]",
        timeout=180))
    assert out == {0: str(WORLD), 1: str(WORLD)}


def test_broadcast_from_root(cluster):
    comm, _ = cluster
    comm.send_to_ranks([0], "execute", "payload = jnp.arange(3.0) + 7")
    comm.send_to_ranks([1], "execute", "payload = jnp.zeros(3)")
    out = outputs(comm.send_to_all(
        "execute", "payload = broadcast(payload, root=0)\nfloat(payload[0])",
        timeout=180))
    assert out == {0: "7.0", 1: "7.0"}


def test_streaming_output_arrives_during_execution(cluster):
    comm, _ = cluster
    got = []
    comm.set_output_callback(lambda rank, d: got.append((rank, d)))
    comm.send_to_all("execute",
                     "import time\nfor i in range(3):\n"
                     "    print('tick', i)\n    time.sleep(0.05)")
    texts = [d["text"].strip() for _, d in got if d["stream"] == "stdout"]
    assert texts.count("tick 0") == WORLD
    assert texts.count("tick 2") == WORLD
    comm.set_output_callback(lambda rank, d: None)


def test_get_var_array_roundtrip(cluster):
    comm, _ = cluster
    comm.send_to_all("execute", "w = jnp.arange(6.0).reshape(2, 3) * (rank+1)")
    resp = comm.send_to_rank(1, "get_var", "w")
    assert resp.data["array"] and resp.data["shape"] == [2, 3]
    np.testing.assert_allclose(
        resp.bufs["value"], np.arange(6.0).reshape(2, 3) * 2)


def test_set_var_pushes_array(cluster):
    comm, _ = cluster
    comm.send_to_all("set_var", {"name": "injected"},
                     bufs={"value": np.full((2, 2), 5.0, np.float32)})
    out = outputs(comm.send_to_all("execute", "float(injected.sum())"))
    assert out == {0: "20.0", 1: "20.0"}


def test_get_var_missing_name(cluster):
    comm, _ = cluster
    resp = comm.send_to_rank(0, "get_var", "no_such_name")
    assert "error" in resp.data


def test_sync_barrier(cluster):
    comm, _ = cluster
    resp = comm.send_to_all("sync", timeout=120)
    assert all(m.data["status"] == "synced" for m in resp.values())


def test_status_probe(cluster):
    comm, _ = cluster
    resp = comm.send_to_rank(0, "get_status")
    st = resp.data
    assert st["rank"] == 0
    assert st["world_size"] == WORLD
    assert st["backend"] == "cpu"
    assert st["global_device_count"] == WORLD


def test_namespace_info(cluster):
    comm, _ = cluster
    comm.send_to_all("execute", "probe_arr = jnp.zeros((3, 4))")
    resp = comm.send_to_rank(0, "get_namespace_info")
    info = resp.data["namespace_info"]
    assert info["probe_arr"]["kind"] == "array"
    assert info["probe_arr"]["shape"] == [3, 4]
    assert info["rank"]["kind"] == "scalar"
    assert info["all_reduce"]["kind"] == "callable"


def test_error_cell_reports_per_rank(cluster):
    comm, _ = cluster
    resp = comm.send_to_all("execute", "1 / 0")
    for m in resp.values():
        assert "ZeroDivisionError" in m.data["traceback"]
    # workers stay healthy afterwards
    out = outputs(comm.send_to_all("execute", "'alive'"))
    assert out == {0: "'alive'", 1: "'alive'"}


def test_checkpoint_save_restore_roundtrip(cluster, tmp_path):
    comm, _ = cluster
    path = str(tmp_path / "ck")
    comm.send_to_all("execute",
                     "ck_w = jnp.ones((2, 3)) * (rank + 1)\n"
                     "ck_step = 40 + rank")
    resp = comm.send_to_all("checkpoint", {"action": "save", "path": path,
                                           "names": ["ck_w", "ck_step"]})
    for m in resp.values():
        assert m.data["status"] == "save", m.data
        assert m.data["summary"]["ck_w"]["bytes"] == 24
    # clobber, then restore and verify per-rank values came back
    comm.send_to_all("execute", "ck_w = None; ck_step = None")
    resp = comm.send_to_all("checkpoint",
                            {"action": "restore", "path": path,
                             "names": None})
    for m in resp.values():
        assert m.data["status"] == "restore", m.data
    out = outputs(comm.send_to_all(
        "execute", "(float(ck_w[0, 0]), ck_step)"))
    assert out == {0: "(1.0, 40)", 1: "(2.0, 41)"}


def test_checkpoint_missing_name_errors_cleanly(cluster, tmp_path):
    comm, _ = cluster
    resp = comm.send_to_all(
        "checkpoint", {"action": "save", "path": str(tmp_path / "ck2"),
                       "names": ["no_such_var"]})
    for m in resp.values():
        assert "no_such_var" in m.data["error"]


def test_multihost_local_plan_runs_real_workers():
    """Drive the multi-host code path end-to-end with 'local' hosts:
    the plan's argv/env must bring up a real 2-process world."""
    comm = CommunicationManager(num_workers=2, timeout=60)
    pm = ProcessManager()
    pm.add_death_callback(lambda rank, rc: comm.mark_worker_dead(rank))
    try:
        world = pm.start_workers_multihost(
            "local:2", comm.port, coordinator_host="127.0.0.1",
            backend="cpu")
        assert world == 2
        wait_until_ready(comm, pm, ATTACH_TIMEOUT)
        out = outputs(comm.send_to_all("execute", "rank + 40"))
        assert out == {0: "40", 1: "41"}
        out = outputs(comm.send_to_all(
            "execute", "float(all_reduce(jnp.ones(2))[0])", timeout=180))
        assert out == {0: "2.0", 1: "2.0"}
    finally:
        comm.post([0, 1], "shutdown")
        time.sleep(0.5)
        pm.shutdown()
        comm.shutdown()


def test_reduce_scatter_psum_scatter_path(cluster):
    """One device per process -> the true psum_scatter collective."""
    comm, _ = cluster
    out = outputs(comm.send_to_all(
        "execute",
        "rs = reduce_scatter(jnp.arange(4.0) + rank)\n"
        "[float(v) for v in rs]", timeout=180))
    # sum over ranks: [0+1, 1+2, 2+3, 3+4] = [1,3,5,7]; rank r gets
    # chunk r of the leading axis (2 elements each).
    assert out == {0: "[1.0, 3.0]", 1: "[5.0, 7.0]"}


def test_all_reduce_quantized_cross_process(cluster):
    comm, _ = cluster
    out = outputs(comm.send_to_all(
        "execute",
        "q = all_reduce_quantized(jnp.ones(300) * (rank + 1))\n"
        "round(float(q.mean()), 2)", timeout=180))
    # exact sum = 3.0 everywhere; int8 blockwise keeps it within 1%
    assert all(2.9 < float(v) < 3.1 for v in out.values()), out


def test_reduce_scatter_fallback_op_max(cluster):
    """Non-sum ops use the all_reduce+slice fallback path."""
    comm, _ = cluster
    out = outputs(comm.send_to_all(
        "execute",
        "rm = reduce_scatter(jnp.arange(4.0) * (rank + 1), op='max')\n"
        "[float(v) for v in rm]", timeout=180))
    # elementwise max over ranks = [0,2,4,6]; rank r gets chunk r
    assert out == {0: "[0.0, 2.0]", 1: "[4.0, 6.0]"}


def test_heartbeat_carries_busy_state(cluster):
    """The serial worker loop cannot answer probes mid-cell, so the
    heartbeat thread reports busy state out-of-band: during a long
    execute, pings carry {busy_type, busy_s} with busy_s growing;
    after completion they go back to idle (no payload)."""
    import threading

    comm, _ = cluster
    done = threading.Event()

    def _send():
        comm.send_to_all("execute",
                         "import time\ntime.sleep(7)\n'long done'",
                         timeout=120)
        done.set()

    t = threading.Thread(target=_send, daemon=True)
    t.start()
    try:
        # Wait for a ping that reports the execute in progress.
        deadline = time.time() + 30
        seen = None
        while time.time() < deadline:
            ping = comm.last_ping(0)
            if ping and ping[1].get("busy_type") == "execute":
                seen = ping[1]
                break
            time.sleep(0.2)
        assert seen is not None, "no busy ping within 30s"
        assert seen["busy_s"] >= 0
        # A later ping must show the busy time growing.
        first = seen["busy_s"]
        deadline = time.time() + 20
        while time.time() < deadline:
            ping = comm.last_ping(0)
            if ping[1].get("busy_s", -1) > first + 1.0:
                break
            time.sleep(0.2)
        else:
            raise AssertionError("busy_s did not grow across pings")
    finally:
        assert done.wait(60), "long cell never completed"
        t.join(timeout=10)
    # Idle again: the next ping drops the busy payload.  (The
    # collective-position piggyback — "col", the hang watchdog's
    # skew signal — legitimately persists while idle; only the busy
    # fields must clear.)
    deadline = time.time() + 15
    while time.time() < deadline:
        ping = comm.last_ping(0)
        if ping and ping[1].get("busy_s") is None:
            break
        time.sleep(0.2)
    else:
        raise AssertionError(f"ping still busy after completion: "
                             f"{comm.last_ping(0)}")


def test_interrupt_aborts_cell_workers_survive(cluster):
    """%dist_interrupt semantics: SIGINT aborts the running cell with a
    KeyboardInterrupt error response; the workers keep serving."""
    import threading

    comm, pm = cluster
    result = {}

    def _send():
        result.update(comm.send_to_all(
            "execute", "import time\nfor _ in range(600):\n"
                       "    time.sleep(0.1)", timeout=120))

    t = threading.Thread(target=_send, daemon=True)
    t.start()
    time.sleep(1.0)  # let the cell start running
    signaled = pm.interrupt()
    assert signaled == [0, 1]
    t.join(timeout=30)
    assert not t.is_alive(), "interrupt did not abort the cell"
    for m in result.values():
        assert "KeyboardInterrupt" in m.data["error"]
    out = outputs(comm.send_to_all("execute", "'still here'"))
    assert out == {0: "'still here'", 1: "'still here'"}


def test_interrupt_while_idle_is_harmless(cluster):
    comm, pm = cluster
    pm.interrupt()
    time.sleep(0.5)
    out = outputs(comm.send_to_all("execute", "1 + 1"))
    assert out == {0: "2", 1: "2"}


def test_interrupt_storm_no_deaths_no_lost_replies(cluster):
    """Regression for the three interrupt races fixed in rounds 2-3:
    (a) a deferred KeyboardInterrupt surfacing outside the designed
    windows killed the worker or dropped a reply; (b) a KI between
    sock.recv and the buffer append lost bytes, desynced the stream,
    and made the coordinator declare a live worker dead; (c) the
    round-2 tail race — a SIGINT delivered to a lazily-spawned,
    mask-unblocked XLA/gloo thread defeated the main thread's pthread
    mask and escaped the run loop as a BaseException mid-dispatch
    (root-caused and closed in round 3 by the Python-level gated
    handler, runtime/interrupt.py; the module context mattered because
    earlier tests' cells had compiled JAX programs, spawning exactly
    those threads).  Rapid idle interrupts interleaved with cells
    hammer all three windows; any TransportError/WorkerDied here is a
    real regression — there is no xfail."""
    comm, pm = cluster
    # The tail race needed SIGINT-unblocked native threads in the
    # worker: force their existence even standalone (a jit compile
    # spawns XLA pool threads during the user-code window).
    warm = comm.send_to_all(
        "execute",
        "_storm_warm = jax.jit(lambda x: (x @ x).sum())"
        "(jnp.ones((64, 64))).block_until_ready()", timeout=120)
    # A silently-failed warm-up would leave no XLA pool threads and
    # reduce this regression test to the already-fixed common paths.
    assert all("error" not in m.data for m in warm.values()), \
        {r: m.data for r, m in warm.items()}
    for i in range(25):
        pm.interrupt(None)
        # The probe must always get a reply per rank: either it ran
        # normally or the late signal aborted it as a clean
        # KeyboardInterrupt error.  A timeout here IS the dropped-
        # reply bug this test exists to catch — never swallow it.
        # Generous deadline: under full-suite CPU contention a slow
        # reply is not the bug class this guards.
        probe = comm.send_to_all("execute", "'probe'", timeout=60)
        for r, m in probe.items():
            ok = (m.data.get("output") == "'probe'"
                  or "KeyboardInterrupt" in (m.data.get("error")
                                             or ""))
            assert ok, (i, r, m.data)
        out = outputs(comm.send_to_all("execute", f"{i} * 2",
                                       timeout=60))
        assert out == {r: str(i * 2) for r in range(WORLD)}, (i, out)
    assert pm.alive_ranks() == list(range(WORLD))


def test_params_pytree_pull_push_without_pickle():
    """A model-params pytree crosses an
    allow_pickle=False control plane — treedef as JSON, leaves as raw
    buffers — and round-trips arrays + structure exactly.  A 1-worker
    world with pickle DISABLED on the coordinator channel: any pickle
    fallback would raise CodecError at decode."""
    import jax

    from nbdistributed_tpu.messaging.codec import unflatten_pytree_wire

    comm = CommunicationManager(num_workers=1, timeout=60,
                                allow_pickle=False)
    pm = ProcessManager()
    pm.add_death_callback(lambda rank, rc: comm.mark_worker_dead(rank))
    try:
        pm.start_workers(1, comm.port, backend="cpu")
        wait_until_ready(comm, pm, ATTACH_TIMEOUT)
        comm.send_to_all(
            "execute",
            "from nbdistributed_tpu.models import init_params, "
            "tiny_config\n"
            "_cfg = tiny_config()\n"
            "params = init_params(jax.random.PRNGKey(0), _cfg)")
        resp = comm.send_to_rank(0, "get_var", "params", timeout=60)
        assert resp.data.get("pytree") is not None, resp.data
        pulled = unflatten_pytree_wire(resp.data["pytree"], resp.bufs)

        # Structure + every leaf must match the same init done here.
        from nbdistributed_tpu.models import init_params, tiny_config
        want = init_params(jax.random.PRNGKey(0), tiny_config())
        assert (jax.tree_util.tree_structure(pulled)
                == jax.tree_util.tree_structure(
                    jax.tree_util.tree_map(np.asarray, want)))
        for got, exp in zip(jax.tree_util.tree_leaves(pulled),
                            jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(exp))

        # Push the pytree back under a new name (same pickle-free
        # path in the other direction) and check a leaf on the worker.
        from nbdistributed_tpu.messaging.codec import flatten_pytree_wire
        meta, bufs = flatten_pytree_wire(pulled)
        comm.send_to_rank(0, "set_var",
                          {"name": "params2", "pytree": meta},
                          bufs=bufs, timeout=60)
        out = comm.send_to_rank(0, "execute",
                                "bool(jnp.array_equal(params2['embed'],"
                                " params['embed']))", timeout=60)
        assert out.data["output"] == "True"
    finally:
        comm.post([0], "shutdown")
        time.sleep(0.3)
        pm.shutdown()
        comm.shutdown()


def test_scatter_gather_reduce_cross_process(cluster):
    """dist.scatter/gather/reduce across a real 2-process gloo world:
    scatter hands each rank the ROOT's row (non-root feeds garbage and
    root=1, so a no-communication or root-ignoring implementation
    fails), gather stacks on root only, reduce lands on root only."""
    comm, _ = cluster
    out = outputs(comm.send_to_all(
        "execute",
        "stk = (jnp.stack([jnp.full(2, 10.0), jnp.full(2, 20.0)])\n"
        "       if rank == 1 else jnp.full((2, 2), -99.0))\n"
        "s = dist.scatter(stk, root=1)\n"
        "float(s[0])", timeout=120))
    assert out == {0: "10.0", 1: "20.0"}
    out = outputs(comm.send_to_all(
        "execute",
        "try:\n"
        "    dist.scatter(jnp.zeros((2, 2)), root=5)\n"
        "    bad = 'no raise'\n"
        "except ValueError as e:\n"
        "    bad = 'out of range' in str(e)\n"
        "bad", timeout=120))
    assert out == {0: "True", 1: "True"}
    out = outputs(comm.send_to_all(
        "execute",
        "g = dist.gather(jnp.full(2, rank + 1.0), root=1)\n"
        "'none' if g is None else str(g.shape)", timeout=120))
    assert out == {0: "'none'", 1: "'(2, 2)'"}
    out = outputs(comm.send_to_all(
        "execute",
        "r = dist.reduce(jnp.ones(3) * (rank + 1), root=0)\n"
        "'none' if r is None else str(float(r[0]))", timeout=120))
    assert out == {0: "'3.0'", 1: "'none'"}
