"""Pre-spawn resource validation + bring-up timeout diagnostics.

The reference validates its GPU-id list against torch.cuda before any
spawn (reference: magic.py:454-488); these tests cover the TPU analog
(chip-count probe vs the requested topology) and the elapsed/budget
timeout message (a 240 s wait once reported "did not attach within 2s"
— the poll interval)."""

import pytest

from nbdistributed_tpu.manager import topology
from nbdistributed_tpu.manager.process_manager import wait_until_ready


def _env(rank, world_size, **kw):
    """tpu_worker_env with one (arbitrary) TPU-runtime port per rank —
    the spawner's job in production (find_free_ports)."""
    return topology.tpu_worker_env(
        rank, world_size, tpu_ports=list(range(7001, 7001 + world_size)),
        **kw)


def test_available_chips_from_device_nodes(monkeypatch):
    monkeypatch.setattr(
        "glob.glob",
        lambda pat: [f"/dev/accel{i}" for i in range(4)]
        if "accel" in pat else [])
    assert topology.available_tpu_chips() == 4


def test_available_chips_from_vfio_groups(monkeypatch):
    """vfio group numbers are IOMMU groups, not chip indices: the
    one-chip v5e machine exposes /dev/vfio/3 (plus the /dev/vfio/vfio
    container node, which the digit glob never matches)."""
    monkeypatch.setattr(
        "glob.glob",
        lambda pat: ["/dev/vfio/3"] if "vfio" in pat else [])
    assert topology.available_tpu_chips() == 1
    assert topology.detect_backend() == "tpu"


def test_available_chips_unknown(monkeypatch):
    monkeypatch.setattr("glob.glob", lambda pat: [])
    assert topology.available_tpu_chips() is None
    assert topology.detect_backend() == "cpu"


def test_tpu_worker_env_pins_platform():
    """A coordinator held to the CPU (this sandbox exports
    JAX_PLATFORMS=cpu; tier-1 adds virtual-device XLA_FLAGS) must not
    hand that to workers it was asked to start on chips."""
    base = {"JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "KEEP": "1"}
    env = _env(0, 1, base=base)
    # TPU first (the default backend), host CPU kept for staging; an
    # explicit list makes a platform that cannot initialise an error.
    assert env["JAX_PLATFORMS"] == "tpu,cpu"
    assert "XLA_FLAGS" not in env
    assert env["KEEP"] == "1"
    assert base["JAX_PLATFORMS"] == "cpu"      # caller's dict untouched
    assert topology.worker_env(0, 1, "tpu", tpu_ports=[7001], base=base)[
        "JAX_PLATFORMS"] == "tpu,cpu"


def test_tpu_worker_env_uses_given_ports():
    envs = [topology.tpu_worker_env(r, 4, tpu_ports=[7001, 7002, 7003,
                                                     7004], base={})
            for r in range(4)]
    assert {e["TPU_PROCESS_ADDRESSES"] for e in envs} == {
        "localhost:7001,localhost:7002,localhost:7003,localhost:7004"}
    assert [e["TPU_PROCESS_PORT"] for e in envs] == [
        "7001", "7002", "7003", "7004"]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
    with pytest.raises(ValueError, match="one TPU-runtime port"):
        topology.tpu_worker_env(0, 4, tpu_ports=[7001], base={})


def test_validate_rejects_oversubscription(monkeypatch):
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 1)
    with pytest.raises(ValueError) as e:
        topology.validate_tpu_request(8, 1)
    msg = str(e.value)
    assert "8" in msg and "has 1" in msg and "-n 1" in msg


def test_validate_accounts_chips_per_worker(monkeypatch):
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 4)
    with pytest.raises(ValueError, match="= 8 TPU chips"):
        topology.validate_tpu_request(2, 4)
    topology.validate_tpu_request(1, 4)  # fits: no raise


def test_validate_passes_when_unknown(monkeypatch):
    """No probe signal -> trust the user (workers will report)."""
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: None)
    topology.validate_tpu_request(8, 1)


def test_validate_rejects_unsupported_grid(monkeypatch):
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 8)
    with pytest.raises(ValueError, match="unsupported"):
        topology.validate_tpu_request(3, 1)


def test_start_workers_tpu_fails_fast_before_spawn(monkeypatch):
    """%dist_init -n 8 on a 1-chip host must fail in <1s with an
    actionable message and zero children spawned."""
    from nbdistributed_tpu.manager import ProcessManager

    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 1)
    pm = ProcessManager()
    with pytest.raises(ValueError, match="host has 1"):
        pm.start_workers(8, 55555, backend="tpu")
    assert not pm.processes


# ---------------------------------------------------------------------
# explicit chip pinning (--chips): the reference's --gpu-ids analog
# (reference: magic.py:454-488 validation, process_manager.py:107-112
# assignment/recycling)

def test_parse_chips():
    assert topology.parse_chips("2,3") == [2, 3]
    assert topology.parse_chips(" 0, 1 ,3") == [0, 1, 3]


def test_parse_chips_bad_format():
    with pytest.raises(ValueError, match="comma-separated integers"):
        topology.parse_chips("2,x")
    with pytest.raises(ValueError, match="comma-separated integers"):
        topology.parse_chips("2;3")
    with pytest.raises(ValueError, match=">= 0"):
        topology.parse_chips("0,-1")


def test_chip_pinning_env_non_contiguous(monkeypatch):
    """--chips 2,3 on a shared host: rank r pins chips[r], not r."""
    for rank, want in ((0, "2"), (1, "3")):
        env = _env(rank, 2, chips=[2, 3], base={})
        assert env["TPU_VISIBLE_CHIPS"] == want
        assert env["TPU_PROCESS_BOUNDS"] == "1,2,1"
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"


def test_chip_pinning_env_multi_chip_worker():
    """chips_per_worker=2 with an explicit list: consecutive slices."""
    env0 = _env(0, 2, chips_per_worker=2,
                chips=[4, 5, 6, 7], base={})
    env1 = _env(1, 2, chips_per_worker=2,
                chips=[4, 5, 6, 7], base={})
    assert env0["TPU_VISIBLE_CHIPS"] == "4,5"
    assert env1["TPU_VISIBLE_CHIPS"] == "6,7"


def test_chip_pinning_env_short_list_raises():
    """A short chip list raises at env-construction time (never the
    reference's modulo recycling, process_manager.py:107-112 — TPU
    runtime processes cannot share a chip), so direct callers of
    tpu_worker_env that bypass validate_tpu_request still cannot pin
    two workers to one chip."""
    with pytest.raises(ValueError, match="never recycled"):
        _env(1, 2, chips=[5], base={})
    with pytest.raises(ValueError, match="never recycled"):
        _env(1, 2, chips_per_worker=2,
             chips=[0, 1, 2], base={})
    # Duplicates in a long-enough list are equally chip-sharing.
    with pytest.raises(ValueError, match="duplicate ids"):
        _env(0, 2, chips_per_worker=2,
             chips=[0, 1, 0, 1], base={})


def test_grid_blocks_no_phantom_ids():
    """The consecutive-run fallback never emits ids past total_chips
    (partial trailing blocks are dropped, not padded)."""
    for total, cpw in ((8, 3), (4, 3), (8, 5)):
        for b in topology._grid_blocks(total, cpw):
            assert all(c < total for c in b), (total, cpw, b)
            assert len(b) == cpw


def test_validate_chips_non_v5e_host_skips_geometry(monkeypatch):
    """A probed count outside the v5e grid table (e.g. a 16-entry
    pool) must skip the subgrid checks — never re-anchor them to the
    request size."""
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 16)
    assert topology.validate_tpu_request(1, 2, chips=[2, 3]) == 16


def test_validate_chips_adjacency(monkeypatch):
    """chips_per_worker>1 requires each worker's slice to be an
    aligned physical subgrid block of the host grid (the TPU runtime
    carves a contiguous (cx,cy) subgrid per process)."""
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 8)
    with pytest.raises(ValueError, match="physical subgrid"):
        topology.validate_tpu_request(2, 2, chips=[0, 2, 4, 6])
    with pytest.raises(ValueError, match="physical subgrid"):
        topology.validate_tpu_request(1, 2, chips=[1, 2])  # unaligned
    topology.validate_tpu_request(2, 2, chips=[0, 1, 2, 3])  # ok
    topology.validate_tpu_request(1, 2, chips=[2, 3])        # ok
    topology.validate_tpu_request(2, 2, chips=[2, 3, 0, 1])  # any order


def test_validate_chips_subgrid_blocks_cpw4(monkeypatch):
    """4 chips/worker on a (2,4) v5e-8: the physical 2x2 subgrids are
    {0,1,4,5} / {2,3,6,7} under the row-major id map — NOT consecutive
    id runs.  The validator and the default env derive from the same
    carve, so the blocks agree."""
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 8)
    topology.validate_tpu_request(2, 4, chips=[0, 1, 4, 5, 2, 3, 6, 7])
    with pytest.raises(ValueError, match="physical subgrid"):
        # A consecutive id run is a 1x4 strip, contradicting the
        # declared 2x2 TPU_CHIPS_PER_PROCESS_BOUNDS carve.
        topology.validate_tpu_request(2, 4, chips=list(range(8)))
    env0 = _env(0, 2, chips_per_worker=4, base={})
    env1 = _env(1, 2, chips_per_worker=4, base={})
    assert env0["TPU_VISIBLE_CHIPS"] == "0,1,4,5"
    assert env1["TPU_VISIBLE_CHIPS"] == "2,3,6,7"
    assert env0["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,2,1"
    assert env0["TPU_PROCESS_BOUNDS"] == "1,2,1"


def test_multi_chip_default_carve_is_host_aware(monkeypatch):
    """A 4-chip worker on an 8-chip host must get a 2x2 block of the
    HOST's (2,4) grid — {0,1,4,5} — not the (2,2) grid's {0,1,2,3};
    the env carve and validate_tpu_request agree on the geometry."""
    env = _env(0, 1, chips_per_worker=4,
               host_chips=8, base={})
    assert env["TPU_VISIBLE_CHIPS"] == "0,1,4,5"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 8)
    topology.validate_tpu_request(1, 4, chips=[0, 1, 4, 5])   # ok
    with pytest.raises(ValueError, match="physical subgrid"):
        topology.validate_tpu_request(1, 4, chips=[0, 1, 2, 3])
    # Without host info the requested total is the grid (standalone
    # 4-chip host): a (2,2) grid is one block, consecutive ids.
    env = _env(0, 1, chips_per_worker=4, base={})
    assert env["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
    # Explicit non-first blocks still span a coherent process grid:
    # workers on blocks {4,5} and {6,7} of the (2,4) host sit in one
    # grid row of blocks -> process bounds 1,2.
    env = _env(0, 2, chips_per_worker=2,
               chips=[4, 5, 6, 7], host_chips=8,
               base={})
    assert env["TPU_PROCESS_BOUNDS"] == "1,2,1"


def test_validate_chips_rectangle_and_ordering(monkeypatch):
    """Diagonal block picks are rejected (the TPU process grid is a
    rectangle: 2 workers on blocks {0,1}+{6,7} of a (2,4) host would
    declare 4 process slots); out-of-range ids get the range error,
    not a misleading subgrid message."""
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 8)
    with pytest.raises(ValueError, match="rectangle"):
        topology.validate_tpu_request(2, 2, chips=[0, 1, 6, 7])
    with pytest.raises(ValueError, match="rectangle"):
        topology.validate_tpu_request(2, 2, chips=[2, 3, 4, 5])
    topology.validate_tpu_request(2, 2, chips=[0, 1, 4, 5])  # a column
    with pytest.raises(ValueError, match="Invalid chip IDs: \\[8, 9\\]"):
        topology.validate_tpu_request(2, 2, chips=[0, 1, 8, 9])
    assert topology.validate_tpu_request(2, 2,
                                         chips=[0, 1, 2, 3]) == 8
    # tpu_worker_env falls back to the linear carve (never an
    # inconsistent rectangle) when handed a non-rectangular pick, and
    # raises (not IndexError) when the host has too few blocks.
    env = _env(0, 2, chips_per_worker=2,
               chips=[0, 1, 6, 7], host_chips=8,
               base={})
    assert env["TPU_PROCESS_BOUNDS"] == "1,2,1"
    with pytest.raises(ValueError, match="subgrid block"):
        _env(1, 2, chips_per_worker=4,
             host_chips=4, base={})


def test_validate_chips_not_enough(monkeypatch):
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 8)
    with pytest.raises(ValueError, match="Not enough chip IDs"):
        topology.validate_tpu_request(4, 1, chips=[2, 3])
    with pytest.raises(ValueError, match="Need 4"):
        topology.validate_tpu_request(2, 2, chips=[0, 1, 2])


def test_validate_chips_duplicates(monkeypatch):
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 8)
    with pytest.raises(ValueError, match="duplicate chip IDs"):
        topology.validate_tpu_request(2, 1, chips=[3, 3])


def test_validate_chips_invalid_vs_available(monkeypatch):
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 4)
    with pytest.raises(ValueError) as e:
        topology.validate_tpu_request(2, 1, chips=[2, 9])
    msg = str(e.value)
    assert "Invalid chip IDs: [9]" in msg
    assert "[0, 1, 2, 3]" in msg


def test_validate_chips_ok(monkeypatch):
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 4)
    topology.validate_tpu_request(2, 1, chips=[2, 3])   # no raise
    # Extra ids beyond the need are allowed (first N used) and not
    # held against availability.
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 2)
    topology.validate_tpu_request(2, 1, chips=[0, 1, 9])


def test_validate_chips_unknown_count(monkeypatch):
    """No probe signal: format/count/dup checks still apply, the
    availability AND subgrid-geometry checks are skipped (a (1,2)
    block at ids [2,3] is legal on a real v5e-8 even though a
    2-chip grid alone wouldn't contain it)."""
    monkeypatch.setattr(topology, "available_tpu_chips", lambda: None)
    topology.validate_tpu_request(2, 1, chips=[6, 7])
    assert topology.validate_tpu_request(1, 2, chips=[2, 3]) is None


def test_start_workers_rejects_bad_chip_request(monkeypatch):
    from nbdistributed_tpu.manager import ProcessManager

    monkeypatch.setattr(topology, "available_tpu_chips", lambda: 4)
    pm = ProcessManager()
    with pytest.raises(ValueError, match="Not enough chip IDs"):
        pm.start_workers(4, 55555, backend="tpu", chips=[1, 2])
    assert not pm.processes


class _FakeComm:
    num_workers = 4

    def connected_ranks(self):
        return [0, 2]

    def wait_for_workers(self, timeout):
        import time
        time.sleep(min(timeout, 0.01))
        raise TimeoutError(f"within {timeout:.0f}s")  # inner message


class _FakePM:
    def check_startup_failure(self):
        pass


def test_wait_until_ready_reports_elapsed_and_budget():
    with pytest.raises(TimeoutError) as e:
        wait_until_ready(_FakeComm(), _FakePM(), 0.05, poll_s=0.01)
    msg = str(e.value)
    assert "budget 0s" in msg or "budget" in msg
    assert "[1, 3]" in msg, f"should name missing ranks: {msg}"
    assert "within 0s" in msg  # elapsed, not the 0.01s poll interval
