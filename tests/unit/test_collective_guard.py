"""Runtime collective-hazard guard: eager world-collectives must raise
at CALL time when invoked from a cell running on a strict subset of
the mesh (they would otherwise deadlock — the absent ranks never
join), and the executor response must carry the runtime collective
count + cell hash for the coordinator's per-cell record."""

import pytest

from nbdistributed_tpu.runtime import collective_guard as cg

pytestmark = [pytest.mark.unit]


def teardown_function(_fn):
    cg.end_cell()          # never leak cell state between tests


def test_subset_cell_raises_at_call_time():
    cg.begin_cell([0], world=4)
    with pytest.raises(cg.CollectiveHazardError, match="deadlock"):
        cg.check("all_reduce")


def test_full_mesh_cell_passes_and_counts():
    cg.begin_cell([0, 1, 2, 3], world=4)
    cg.check("all_reduce")
    cg.check("barrier")
    assert cg.end_cell() == 2


def test_unknown_targets_pass():
    """Raw-string execute requests (direct callers)
    carry no target info: the guard must not fire."""
    cg.begin_cell(None, world=4)
    cg.check("all_reduce")
    assert cg.end_cell() == 1


def test_inactive_outside_cells():
    """A collective called outside any cell (worker sync handler,
    user threads) sees inactive state and passes."""
    cg.check("barrier")            # no begin_cell - must not raise


def test_single_process_world_passes():
    cg.begin_cell([0], world=1)
    cg.check("all_reduce")
    assert cg.end_cell() == 1


def test_eager_collectives_call_guard(monkeypatch):
    """The real collectives module consults the guard before any
    communication: with subset state active, a 1-process all_reduce
    (normally an identity) must raise — proving the hook fires ahead
    of the transport, where the multi-process case would block."""
    from nbdistributed_tpu.parallel import collectives

    cg.begin_cell([0], world=2)
    try:
        for fn, args in ((collectives.all_reduce, (1.0,)),
                         (collectives.all_gather, (1.0,)),
                         (collectives.broadcast, (1.0,)),
                         (collectives.barrier, ()),
                         (collectives.reduce_scatter, ([1.0, 2.0],)),
                         (collectives.all_reduce_quantized, (1.0,))):
            with pytest.raises(cg.CollectiveHazardError):
                fn(*args)
    finally:
        cg.end_cell()


def test_cell_hash_stable():
    assert cg.cell_hash("x = 1") == cg.cell_hash("x = 1")
    assert cg.cell_hash("x = 1") != cg.cell_hash("x = 2")
    assert len(cg.cell_hash("anything")) == 12


def test_executor_response_carries_count(monkeypatch):
    """Worker-level wiring: _handle_execute publishes targets, runs
    the cell, and stamps collective_ops + cell_sha1 on the reply."""
    from nbdistributed_tpu.messaging.codec import Message
    from nbdistributed_tpu.runtime import worker as worker_mod

    from nbdistributed_tpu.observability.flightrec import _NullRecorder

    class _W:
        rank = 0
        world_size = 2
        namespace = {"cg": cg}
        _stream = staticmethod(lambda text, kind: None)
        _flight = _NullRecorder()
        # Untagged requests resolve to the base namespace (tenant
        # namespaces are the gateway suite's concern).
        _ns_for = worker_mod.DistributedWorker._ns_for

    handle = worker_mod.DistributedWorker._handle_execute
    w = _W()
    msg = Message(msg_type="execute",
                  data={"code": "cg.check('fake_op')\n1+1",
                        "target_ranks": [0, 1]})
    reply = handle(w, msg)
    assert reply.data["status"] == "success"
    assert reply.data["collective_ops"] == 1
    assert reply.data["cell_sha1"] == cg.cell_hash(
        "cg.check('fake_op')\n1+1")
    # Subset targets: the in-cell collective raises -> error reply,
    # which still arrives (never a hang) and still carries the count.
    msg2 = Message(msg_type="execute",
                   data={"code": "cg.check('fake_op')",
                         "target_ranks": [0]})
    reply2 = handle(w, msg2)
    assert "CollectiveHazard" in reply2.data.get("traceback", "")
    assert reply2.data["collective_ops"] == 1


def test_composite_collectives_count_once():
    """dist.scatter/gather/reduce delegate to guarded primitives but
    one user-level call must record ONE op (the nested() suppression),
    and the subset raise names the composite, not the inner op."""
    import jax.numpy as jnp

    from nbdistributed_tpu.parallel import collectives

    cg.begin_cell([0, 1], world=2)  # full mesh: counts, no raise
    # world_size()==1 here (unit env), so the w==1 identity path runs
    # after the guard check — the count is what we're testing.
    collectives.gather(jnp.ones(2))
    collectives.reduce(jnp.ones(2))
    assert cg.end_cell() == 2
    cg.begin_cell([0], world=2)
    with pytest.raises(cg.CollectiveHazardError, match="gather"):
        collectives.gather(jnp.ones(2))
    cg.end_cell()
