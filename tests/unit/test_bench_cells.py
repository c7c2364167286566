"""The bench worker cells must at least EXECUTE — a syntax error or
API drift in a TPU-only cell would otherwise surface only on budgeted
chip time.  Each cell is exec'd
here at toy scale via config/size substitution; numbers are not
asserted, only successful execution and JSON-parseable output."""

import json

import pytest

import bench

# Heavy (exec real model cells at toy scale): excluded from the fast
# product-path tier (`pytest -m "not slow"`).
pytestmark = [pytest.mark.unit, pytest.mark.slow]


def run_cell(src: str) -> dict:
    """exec a bench cell and parse its trailing json.dumps expression
    the way the worker REPL would (evaluate the last expression)."""
    import ast

    tree = ast.parse(src)
    last = tree.body.pop()
    assert isinstance(last, ast.Expr), "bench cells end in json.dumps"
    ns: dict = {}
    exec(compile(tree, "<cell>", "exec"), ns)
    out = eval(compile(ast.Expression(last.value), "<cell>", "eval"), ns)
    return json.loads(out)


def test_mfu_cell_executes():
    cell = bench.MFU_CELL.format(peak=1e30, shape="(1, 64, 2)",
                                 reps="(2, 2)", tr_start="2 * _B",
                                 extra_cfg=", max_seq_len=128",
                                 cfg_name="tiny_config")
    res = run_cell(cell)
    assert res["fwd_tokens_per_s"] > 0 and res["train_tokens_per_s"] > 0


def test_spec_cell_executes_batched():
    cell = bench.SPEC_CELL.replace("smol_135m_config", "tiny_config")
    cell = cell.replace("_N1, _N2, _G, _B = 16, 64, 4, 4",
                        "_N1, _N2, _G, _B = 4, 8, 2, 2")
    cell = cell.replace("use_flash=True", "use_flash=False")
    res = run_cell(cell)
    # tok_per_s rows are None when measurement noise wins (tiny CPU
    # deltas); execution + sample bookkeeping is what's asserted.
    for name in ("plain", "spec_selfdraft", "plain_b4",
                 "spec_selfdraft_b4", "spec_int4draft_b4"):
        assert res[name + "_tok_per_s"] is None \
            or res[name + "_tok_per_s"] > 0
        lo, hi = res[name + "_lo_hi_s"]
        assert lo > 0 and hi > 0
    assert res["batch"] == 2
    assert 0 <= res["mean_accepted"] <= 2
    assert 0 <= res["int4draft_mean_accepted"] <= 2


def test_decode7b_cell_executes_at_toy_scale():
    cell = bench.DECODE7B_CELL.replace("llama2_7b_config", "tiny_config")
    cell = cell.replace("_N1, _N2, _CL = 8, 32, 2048",
                        "_N1, _N2, _CL = 2, 4, 64")
    cell = cell.replace("use_flash=True", "use_flash=False")
    res = run_cell(cell)
    for name in ("int8", "int4"):
        v = res[name + "_tok_per_s"]
        assert v is None or v > 0
        lo, hi = res[name + "_lo_hi_s"]
        assert lo > 0 and hi > 0
        assert res[name + "_weight_gb"] >= 0  # rounds to 0 at toy scale
        r = res[name + "_roofline_pct_v5e"]
        assert r is None or r >= 0
    # The int4 tree must stream fewer bytes than the int8 one — compare
    # the unrounded weight trees (the _gb keys round to 0.0 at toy
    # scale, which would make the assertion vacuous).
    import jax
    import jax.numpy as jnp

    from nbdistributed_tpu.models import (init_params, quantize_params,
                                          quantize_params4, tiny_config)

    def nbytes(t):
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(t))

    p = init_params(jax.random.PRNGKey(0),
                    tiny_config(dtype=jnp.float32, use_flash=False))
    assert nbytes(quantize_params4(p)) < nbytes(quantize_params(p))


def test_decode_cell_executes():
    cell = bench.DECODE_CELL.replace("smol_135m_config", "tiny_config")
    cell = cell.replace("_N1, _N2, _ML = 32, 256, 512",
                        "_N1, _N2, _ML = 2, 6, 64")
    cell = cell.replace("use_flash=True", "use_flash=False")
    res = run_cell(cell)
    for k in ("bf16", "int8", "int8_kv8"):
        # tok_per_s is None when noise wins the tiny CPU delta; the
        # sample bookkeeping must always be present and positive.
        assert res[k + "_tok_per_s"] is None or res[k + "_tok_per_s"] > 0
        lo, hi = res[k + "_lo_hi_s"]
        assert lo > 0 and hi > 0
        assert res[k + "_bytes_per_tok_mb"] > 0
    # int8 weights + int8 KV must stream fewer bytes than bf16, and
    # nibble-packed int4 fewer again (the packed uint8 array is
    # exactly half the int8 weight bytes plus group scales).
    assert (res["int8_kv8_bytes_per_tok_mb"]
            < res["bf16_bytes_per_tok_mb"])
    assert (res["int4_kv8_bytes_per_tok_mb"]
            < res["int8_kv8_bytes_per_tok_mb"])


def test_serve_cell_executes():
    cell = bench.SERVE_CELL.replace("smol_135m_config", "tiny_config")
    cell = cell.replace("_N, _B, _L = 48, 4, 16",
                        "_N, _B, _L = 6, 2, 4")
    cell = cell.replace("use_flash=True", "use_flash=False")
    res = run_cell(cell)
    assert res["server_tok_per_s"] > 0
    assert res["sequential_tok_per_s"] > 0
    assert res["batch"] == 2 and res["new_tokens"] == 6


def test_run_families_bails_after_consecutive_spawn_failures():
    """Two consecutive SPAWN_FAILED results (no chip answering) must stop
    the family sweep instead of paying the attach timeout per
    remaining family."""
    calls = []

    def fake_measure(backend, name, cell, timeout):
        calls.append(name)
        return bench.SPAWN_FAILED

    extra: dict = {}
    fams = [(n, "cell", 1) for n in ("a", "b", "c", "d")]
    bench.run_families("tpu", fams, extra, measure=fake_measure)
    assert calls == ["a", "b"]
    assert extra == {}


def test_run_families_single_spawn_failure_continues():
    """A lone spawn failure (transient flap) must not end the sweep,
    and a later success resets the failure counter."""
    results = {"a": bench.SPAWN_FAILED, "b": {"x": 1},
               "c": bench.SPAWN_FAILED, "d": {"y": 2}}
    calls = []

    def fake_measure(backend, name, cell, timeout):
        calls.append(name)
        return results[name]

    extra: dict = {}
    fams = [(n, "cell", 1) for n in ("a", "b", "c", "d")]
    bench.run_families("tpu", fams, extra, measure=fake_measure)
    assert calls == ["a", "b", "c", "d"]
    assert extra == {"b": {"x": 1}, "d": {"y": 2}}


def test_run_families_budget_skips_remaining(monkeypatch):
    """Once the family-stage budget is exhausted, remaining families
    are skipped loudly instead of risking the driver's outer deadline
    (the one JSON line must always print)."""
    import time

    monkeypatch.setenv("NBD_BENCH_FAMILY_BUDGET_S", "0.05")
    calls = []

    def slow_measure(backend, name, cell, timeout):
        calls.append(name)
        time.sleep(0.06)
        return {"v": 1}

    extra: dict = {}
    fams = [(n, "cell", 1) for n in ("a", "b", "c")]
    bench.run_families("tpu", fams, extra, measure=slow_measure)
    assert calls == ["a"]          # budget spent during 'a'
    assert extra == {"a": {"v": 1}}


def test_run_families_cell_failure_is_not_spawn_failure():
    """None (cell failed, world healthy) never trips the bail-out."""
    calls = []

    def fake_measure(backend, name, cell, timeout):
        calls.append(name)
        return None

    extra: dict = {}
    fams = [(n, "cell", 1) for n in ("a", "b", "c")]
    bench.run_families("tpu", fams, extra, measure=fake_measure)
    assert calls == ["a", "b", "c"]
    assert extra == {}


def test_chained_delta_ms_measures_positive_time():
    """The shared chained-scan protocol (ops/timing.py — used by the
    bench flash cell, tune_flash, and the preflight probe) must
    produce a positive per-call time with honest host timing."""
    import jax.numpy as jnp

    from nbdistributed_tpu.ops.timing import chained_delta_ms

    x = jnp.full((256, 256), 0.5, jnp.float32)
    ms, samples = chained_delta_ms(lambda c: (c @ c) * 1e-3, x,
                                   n1=2, n2=10, reps=3)
    assert len(samples["lo_s"]) == 3 and len(samples["hi_s"]) == 3
    assert all(t > 0 for t in samples["lo_s"] + samples["hi_s"])
    assert ms > 0


def test_peak_is_looked_up_by_device_kind():
    """The MFU denominator comes from a table keyed by device_kind,
    evaluated on the worker that holds the device; an unknown device
    is a KeyError, never a default."""
    import types

    def peak_on(kind):
        fake = types.SimpleNamespace(devices=lambda: [
            types.SimpleNamespace(device_kind=kind)])
        return eval(bench.PEAK_EXPR, {"_jax": fake})

    assert peak_on("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        peak_on("cpu")


def test_moe_dispatch_cell_executes():
    cell = bench.MOE_CELL.replace(
        "_DM, _DF, _NL, _B, _S, _steps = 1024, 2048, 8, 8, 1024, 3",
        "_DM, _DF, _NL, _B, _S, _steps = 64, 128, 2, 2, 32, 1")
    cell = cell.replace("use_flash=True", "use_flash=False")
    cell = cell.replace("n_heads=16, n_kv_heads=4", "n_heads=4, n_kv_heads=2")
    res = run_cell(cell)
    # Rows are None when measurement noise wins the tiny CPU delta
    # ("noise won: say so" — same contract as the decode cells).
    for mode in ("dense", "sparse", "dropless"):
        v = res["small_" + mode + "_tok_per_s"]
        assert v is None or v > 0
    for mode in ("sparse", "dropless"):
        v = res["big_" + mode + "_tok_per_s"]
        assert v is None or v > 0
    assert res["big_tokens"] == 64
