"""Unit tests for the training integrity guard (ISSUE 19).

Pure-logic tier, single process: device fingerprints, the audit
majority vote, corrupt-spec plumbing, the TrainGuard skip/rollback
state machine (driven by a fake step fn so every verdict is scripted),
one real jitted guarded step proving the bitwise-unchanged skip, and
the checkpoint integrity manifest.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from nbdistributed_tpu.resilience import faults  # noqa: E402
from nbdistributed_tpu.resilience import trainguard as tg  # noqa: E402

pytestmark = [pytest.mark.unit, pytest.mark.guard]


# ----------------------------------------------------------------------
# fingerprints

def _flip_bit(arr: np.ndarray, bitpos: int) -> np.ndarray:
    out = arr.copy()
    view = out.view(np.uint8).reshape(-1)
    view[bitpos // 8] ^= np.uint8(1 << (bitpos % 8))
    return out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16",
                                   "int32", "uint8", "bool"])
def test_leaf_fingerprint_changes_on_any_single_bit(dtype):
    x = jnp.asarray(np.arange(96) % 7, jnp.dtype(dtype))
    base = tuple(int(v) for v in np.asarray(tg.leaf_fingerprint(x)))
    host = np.asarray(x)
    nbits = host.size * host.dtype.itemsize * 8
    # every byte gets one probed bit; exhaustive would be slow
    for bitpos in range(0, nbits, 8):
        flipped = jnp.asarray(_flip_bit(host, bitpos))
        got = tuple(int(v)
                    for v in np.asarray(tg.leaf_fingerprint(flipped)))
        assert got != base, f"bit {bitpos} flip not detected ({dtype})"


def test_leaf_fingerprint_deterministic():
    x = jnp.asarray(np.random.default_rng(0).normal(size=1000),
                    jnp.float32)
    a = np.asarray(tg.leaf_fingerprint(x))
    b = np.asarray(tg.leaf_fingerprint(jnp.asarray(np.asarray(x))))
    assert (a == b).all()


def test_tree_fingerprint_sees_leaf_order():
    a = jnp.ones((4, 4), jnp.float32)
    b = jnp.zeros((4, 4), jnp.float32)
    assert (tg.tree_fingerprint({"p": a, "q": b})
            != tg.tree_fingerprint({"p": b, "q": a}))


def test_tree_fingerprint_empty_tree():
    assert tg.tree_fingerprint({}) == (0, 0)


def test_tree_fingerprint_stable_across_calls():
    t = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
         "b": jnp.ones((8,), jnp.bfloat16)}
    assert tg.tree_fingerprint(t) == tg.tree_fingerprint(t)


# ----------------------------------------------------------------------
# majority vote

def test_vote_unanimous_ok():
    v = tg.vote([(1, 2)] * 4)
    assert v.ok and v.majority_rank is None and v.minority == ()


def test_vote_majority_names_minority():
    v = tg.vote([(1, 2), (9, 9), (1, 2)])
    assert not v.ok
    assert v.majority_rank == 0          # lowest rank in the majority
    assert v.minority == (1,)


def test_vote_two_rank_split_has_no_majority():
    v = tg.vote([(1, 2), (9, 9)])
    assert not v.ok and v.majority_rank is None
    assert set(v.minority) == {0, 1}


def test_vote_three_way_tie_has_no_majority():
    v = tg.vote([(1, 1), (2, 2), (3, 3)])
    assert not v.ok and v.majority_rank is None


# ----------------------------------------------------------------------
# corrupt specs

def test_corrupt_spec_roundtrip():
    c = faults.CorruptSpec(rank=1, step=7, name="w1", mode="scale",
                           bits=3, scale=0.5, count=4)
    assert faults.CorruptSpec.from_spec(c.spec()).spec() == c.spec()


def test_corrupt_spec_validation():
    with pytest.raises(ValueError):
        faults.CorruptSpec(rank=-1, step=0)
    with pytest.raises(ValueError):
        faults.CorruptSpec(rank=0, step=0, mode="nope")
    with pytest.raises(ValueError):
        faults.CorruptSpec.from_spec({"rank": 0})  # needs step too


def test_corrupt_due_is_one_shot_with_ge_step():
    plan = faults.FaultPlan(seed=3, corrupt=[
        {"rank": 1, "step": 5, "name": "*"}])
    assert plan.has_corrupt()
    assert plan.corrupt_due(0, 99) == []          # wrong rank
    assert plan.corrupt_due(1, 4) == []           # too early
    due = plan.corrupt_due(1, 8)                  # fired late (>=)
    assert len(due) == 1
    assert plan.corrupt_due(1, 9) == []           # one-shot


def test_corrupt_plan_spec_roundtrip():
    plan = faults.FaultPlan(seed=3, corrupt=[
        {"rank": 0, "step": 2, "mode": "bitflip", "bits": 2}])
    again = faults.FaultPlan.from_spec(plan.spec())
    assert [c.spec() for c in again.corrupt] \
        == [c.spec() for c in plan.corrupt]


def test_apply_corrupt_bitflip_deterministic_and_localized():
    tree = {"w1": jnp.zeros((8, 8), jnp.float32),
            "w2": jnp.zeros((8,), jnp.float32)}
    spec = faults.CorruptSpec(rank=0, step=1, name="w2")
    t1, leaf1 = tg.apply_corrupt(tree, spec, seed=11)
    t2, leaf2 = tg.apply_corrupt(tree, spec, seed=11)
    assert leaf1 == leaf2 and "w2" in leaf1
    np.testing.assert_array_equal(np.asarray(t1["w2"]),
                                  np.asarray(t2["w2"]))
    # the named leaf changed, the other leaf did not
    assert (np.asarray(t1["w2"]) != np.asarray(tree["w2"])).any()
    np.testing.assert_array_equal(np.asarray(t1["w1"]),
                                  np.asarray(tree["w1"]))
    # a different seed flips a different bit
    t3, _ = tg.apply_corrupt(tree, spec, seed=12)
    assert (np.asarray(t3["w2"]).view(np.uint32)
            != np.asarray(t1["w2"]).view(np.uint32)).any()


def test_apply_corrupt_scale_mode():
    tree = {"w": jnp.ones((16,), jnp.float32)}
    spec = faults.CorruptSpec(rank=0, step=1, name="w", mode="scale",
                              scale=4.0, count=3)
    out, _ = tg.apply_corrupt(tree, spec, seed=5)
    host = np.asarray(out["w"])
    assert (host == 4.0).sum() == 3 and (host == 1.0).sum() == 13


def test_apply_corrupt_unknown_leaf_raises():
    with pytest.raises(ValueError, match="no param leaf"):
        tg.apply_corrupt({"w": jnp.zeros(3)},
                         faults.CorruptSpec(rank=0, step=1,
                                            name="nope"))


# ----------------------------------------------------------------------
# TrainGuard state machine (scripted verdicts via a fake step fn)

class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _fake_guard(script, **kw):
    """TrainGuard over a fake step fn whose per-call verdicts come
    from ``script`` (list of (ok, loss) tuples, repeated last).  The
    fake returns dict-aux ``{"ok", "gnorm"}`` — the documented
    fallback lane for hand-built steps."""
    params = {"w": jnp.arange(4.0)}
    opt = {"m": jnp.zeros(4)}
    calls = {"n": 0}

    def fake_fn(p, o, batch):
        ok, loss = script[min(calls["n"], len(script) - 1)]
        calls["n"] += 1
        newp = {"w": p["w"] + 1.0}
        newo = {"m": o["m"] + 1.0}
        if ok:
            return newp, newo, jnp.float32(loss), \
                {"ok": jnp.asarray(True), "gnorm": jnp.float32(1.0)}
        # a real guarded step skips on-device: state passes through
        return p, o, jnp.float32(loss), \
            {"ok": jnp.asarray(False), "gnorm": jnp.float32(np.inf)}

    kw.setdefault("audit_every", 0)
    kw.setdefault("snapshot_every", 4)
    kw.setdefault("skip_budget", 2)
    g = tg.TrainGuard(fake_fn, params, opt, rank=0,
                      clock=_FakeClock(), **kw)
    g._lag = 0  # resolve every verdict immediately
    return g


def test_guard_counts_skips_and_preserves_state():
    g = _fake_guard([(True, 1.0)] * 3 + [(False, 1.0)] + [(True, 1.0)])
    for _ in range(3):
        g.step(None)
    w3 = np.asarray(g.params["w"]).copy()
    g.step(None)                       # the scripted skip
    d = g.describe()
    assert d["skips"] == 1 and d["skip_streak"] == 1
    np.testing.assert_array_equal(np.asarray(g.params["w"]), w3)
    g.step(None)                       # healthy step clears the streak
    assert g.describe()["skip_streak"] == 0
    assert g.describe()["rollbacks"] == 0


def test_guard_blown_skip_budget_rolls_back():
    # 4 good steps (snapshot at 4), then skips forever: budget 2 blows
    # on the third consecutive skip and restores the step-4 snapshot.
    g = _fake_guard([(True, 1.0)] * 5 + [(False, 1.0)])
    for _ in range(5):
        g.step(None)
    w_snap = np.asarray(g.params["w"]).copy() - 1.0  # params at step 4
    for _ in range(3):
        g.step(None)
    d = g.describe()
    assert d["rollbacks"] == 1 and d["skips"] == 3
    assert d["skip_streak"] == 0       # rollback resets the streak
    np.testing.assert_array_equal(np.asarray(g.params["w"]), w_snap)
    assert "rollback" in [e["kind"] for e in d["events"]]


def test_guard_speculative_snapshot_dropped_on_late_skip():
    # lag deep enough that the step-4 snapshot happens while the bad
    # step-2 verdict is still pending — the resolve must then drop it.
    g = _fake_guard([(True, 1.0), (True, 1.0), (False, 1.0),
                     (True, 1.0)], skip_budget=10)
    g._lag = 50
    for _ in range(6):
        g.step(None)
    g.finish()
    steps = [s[0] for s in g._snapshots]
    assert steps == [0], steps         # the step-4 snapshot is gone
    assert "snapshot_dropped" in [e["kind"] for e in g._events]


def test_guard_disabled_passthrough():
    g = _fake_guard([(False, 1.0)])    # every step would skip
    tg.set_enabled(False)
    try:
        for _ in range(3):
            g.step(None)
        # host machinery bypassed: no verdicts resolved, no skips
        assert g.describe()["skips"] == 0
        assert g.step_index == 3
    finally:
        tg.set_enabled(True)


def test_guard_finish_drains_pending():
    g = _fake_guard([(False, 1.0)], skip_budget=0)
    g._lag = 50                        # nothing resolves in-loop
    for _ in range(4):
        g.step(None)
    assert g.describe()["skips"] == 0  # still pending
    d = g.finish()
    assert d["skips"] == 4


# ----------------------------------------------------------------------
# spike detector

def test_spike_detector_confirms_after_streak():
    sd = tg.SpikeDetector(window=8, nmad=3.0, confirm=2,
                          min_history=8)
    for x in [1.0, 1.1, 0.9, 1.0, 1.05, 0.95, 1.0, 1.0]:
        assert sd.observe(x) == "ok"   # warmup fills the window
    assert sd.observe(50.0) == "suspect"
    assert sd.observe(50.0) == "confirmed"


def test_spike_detector_suspects_stay_out_of_history():
    sd = tg.SpikeDetector(window=8, nmad=3.0, confirm=3,
                          min_history=8)
    for _ in range(8):
        sd.observe(1.0)
    for _ in range(2):
        assert sd.observe(50.0) in ("suspect", "confirmed")
    # healthy loss resets the streak; baseline still ~1.0 because the
    # suspect losses never entered the rolling history
    assert sd.observe(1.0) == "ok"
    assert sd.observe(50.0) == "suspect"


def test_guard_confirmed_spike_rolls_back():
    # SpikeDetector's min_history default is 16: 17 healthy losses
    # fill the baseline, then two spikes confirm and roll back.
    script = [(True, 1.0)] * 17 + [(True, 99.0), (True, 99.0)]
    g = _fake_guard(script, skip_budget=0, snapshot_every=4,
                    spike_window=16, spike_nmad=3.0, spike_confirm=2)
    for _ in range(19):
        g.step(None)
    d = g.describe()
    assert d["spikes"] >= 1
    assert d["rollbacks"] == 1


# ----------------------------------------------------------------------
# one real jitted guarded step

def _real_guarded():
    import optax

    from nbdistributed_tpu.parallel import data_parallel
    from nbdistributed_tpu.parallel import mesh as mesh_mod

    m = mesh_mod.make_mesh({"dp": 1}, devices=jax.devices()[:1])

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    params = {"w": jnp.asarray(
        np.random.default_rng(0).normal(size=(8, 4)), jnp.float32)}
    opt = optax.adam(1e-2)
    p, _ = data_parallel.ddp_init(
        jax.tree_util.tree_map(jnp.copy, params), None, m)
    s = jax.jit(opt.init)(p)
    step = data_parallel.make_ddp_step(loss_fn, opt, m, guard=True)
    return step, p, s


def test_real_guarded_step_skips_bitwise():
    step, p, s = _real_guarded()
    good = (jnp.ones((4, 8)), jnp.zeros((4, 4)))
    bad = (jnp.full((4, 8), jnp.nan), jnp.zeros((4, 4)))
    p, s, loss, aux = step(p, s, good)
    v = np.asarray(aux["v"])
    assert v.shape == (3,) and v[0] == 1.0          # ok lane
    assert np.isclose(v[1], float(loss))            # loss lane
    before = {k: np.asarray(x).copy()
              for k, x in jax.tree_util.tree_leaves_with_path(
                  {"p": p, "s": s})}
    p2, s2, loss2, aux2 = step(p, s, bad)
    assert np.asarray(aux2["v"])[0] == 0.0          # skip verdict
    after = {k: np.asarray(x)
             for k, x in jax.tree_util.tree_leaves_with_path(
                 {"p": p2, "s": s2})}
    for k in before:
        assert (before[k].reshape(-1).view(np.uint8)
                == after[k].reshape(-1).view(np.uint8)).all(), \
            f"{k} changed"


def test_real_guarded_ddp_step_skips_on_one_bad_shard(monkeypatch):
    """Over four shards the gradients are summed by sends inside the
    backward (ISSUE 36); the fused finite check reads the *summed*
    gradients, so rows that are non-finite on ONE shard still skip the
    update on all, bitwise."""
    import optax

    from nbdistributed_tpu.parallel import data_parallel, overlap
    from nbdistributed_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(overlap, "EXCHANGE_MIN_SIZE", 1 << 16)
    n = 4
    m = mesh_mod.make_mesh({"dp": n}, devices=jax.devices()[:n])

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    params = {"w": jnp.asarray(
        np.random.default_rng(0).normal(size=(512, 256)), jnp.float32)}
    opt = optax.adam(1e-2)
    p, _ = data_parallel.ddp_init(params, None, m)
    s = jax.jit(opt.init)(p)
    step = data_parallel.make_ddp_step(loss_fn, opt, m, guard=True,
                                       donate=False)
    x = np.ones((8, 512), np.float32)
    y = np.zeros((8, 256), np.float32)
    good = mesh_mod.shard_batch((x, y), m)
    x_bad = x.copy()
    x_bad[5, 3] = np.nan                # a row of the third shard
    bad = mesh_mod.shard_batch((x_bad, y), m)
    assert data_parallel.collectives_of(
        step.lower(p, s, good).compile())["async_sends"] == n - 1

    p1, s1, _, aux = step(p, s, good)
    assert np.asarray(aux["v"])[0] == 1.0
    assert not np.array_equal(np.asarray(p1["w"]), np.asarray(p["w"]))
    p2, s2, _, aux2 = step(p1, s1, bad)
    assert np.asarray(aux2["v"])[0] == 0.0          # skip verdict
    for a, b in zip(jax.tree_util.tree_leaves((p1, s1)),
                    jax.tree_util.tree_leaves((p2, s2))):
        for shard_a, shard_b in zip(a.addressable_shards,
                                    b.addressable_shards):
            assert (np.asarray(shard_a.data).reshape(-1).view(np.uint8)
                    == np.asarray(shard_b.data).reshape(-1).view(np.uint8)
                    ).all()


def test_real_guard_metrics_and_unguarded_api():
    import optax

    from nbdistributed_tpu.observability import metrics as obs_metrics
    from nbdistributed_tpu.parallel import data_parallel
    from nbdistributed_tpu.parallel import mesh as mesh_mod

    step, p, s = _real_guarded()
    g = tg.TrainGuard(step, p, s, rank=0, audit_every=0,
                      snapshot_every=0, skip_budget=0)
    g._lag = 0
    skips = obs_metrics.registry().counter("nbd_guard_skips_total")
    base = skips.value
    g.step((jnp.full((4, 8), jnp.nan), jnp.zeros((4, 4))))
    g.finish()
    assert skips.value == base + 1
    # guard=False keeps the legacy 3-tuple contract
    m = mesh_mod.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step3 = data_parallel.make_ddp_step(
        lambda prm, b: jnp.mean((b[0] @ prm["w"] - b[1]) ** 2),
        optax.sgd(1e-2), m, guard=False)
    out = step3(g.params, jax.jit(optax.sgd(1e-2).init)(g.params),
                (jnp.ones((4, 8)), jnp.zeros((4, 4))))
    assert len(out) == 3


def test_trainguard_rejects_unguarded_step():
    def bare(p, o, b):
        return p, o, jnp.float32(0.0)

    g = tg.TrainGuard(bare, {"w": jnp.zeros(2)}, {"m": jnp.zeros(2)},
                      rank=0, audit_every=0, snapshot_every=0)
    with pytest.raises(TypeError, match="guard=True"):
        g.step(None)


# ----------------------------------------------------------------------
# checkpoint integrity manifest

def test_checkpoint_manifest_verifies_and_refuses(tmp_path):
    import json
    import os
    import zipfile

    from nbdistributed_tpu.runtime import checkpoint

    ns = {"params": {"w": jnp.arange(16.0).reshape(4, 4)}}
    path = str(tmp_path / "ck")
    checkpoint.save(path, ns, ["params"], rank=0, world_size=1)
    assert checkpoint.verify_rank(path, 0) == []

    # flip one payload byte inside arrays.npz: verify must name it and
    # restore must refuse
    d = os.path.join(path, "rank_0")
    zpath = os.path.join(d, "arrays.npz")
    with zipfile.ZipFile(zpath) as z:
        names = z.namelist()
        blobs = {n: bytearray(z.read(n)) for n in names}
    victim = [n for n in names if n.startswith("params")][0]
    blobs[victim][-1] ^= 0xFF
    with zipfile.ZipFile(zpath, "w") as z:
        for n in names:
            z.writestr(n, bytes(blobs[n]))
    problems = checkpoint.verify_rank(path, 0)
    assert problems and any("crc32" in p for p in problems)
    with pytest.raises(ValueError, match="integrity"):
        checkpoint.restore(path, {}, ["params"], rank=0)

    # back-compat: a pre-crc32 manifest is reported unverifiable, not
    # silently clean
    mpath = os.path.join(d, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    for entry in manifest["entries"].values():
        for meta in entry["leaves"]:
            meta.pop("crc32", None)
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    problems = checkpoint.verify_rank(path, 0)
    assert problems and any("no crc32" in p for p in problems)
