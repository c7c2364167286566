"""Test bootstrap: force the CPU backend with 8 virtual devices.

This file is imported before any test touches a device, and backends
initialise lazily, so a config update here decides the platform even
when the environment says otherwise.  Subprocess workers spawned by
integration tests get their platform from
``nbdistributed_tpu.manager.topology.cpu_worker_env`` (and pin it
again from their ``--backend`` flag).

Set ``NBD_TEST_TPU=1`` to leave the platform alone and run on the real
chip (only meaningful for the single-device kernel/model tests; Mosaic
enforces block-shape and VMEM rules that CPU interpret mode does not,
so an on-chip pass of ``tests/unit/test_attention.py`` etc. is stronger
evidence than the CPU run).  The tests' float32 tolerances assume
float32 matmuls, which the TPU only does at ``highest`` precision, so
that switch sets it.
"""

import os
import sys

import jax
import pytest

ON_CHIP = bool(os.environ.get("NBD_TEST_TPU"))

if ON_CHIP:
    jax.config.update("jax_default_matmul_precision", "highest")
else:
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    jax.config.update("jax_platforms", "cpu")

REPO_ROOT = os.path.dirname(os.path.abspath(__file__ + "/.."))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


@pytest.fixture
def chip_tol():
    """``chip_tol(cpu, chip)``: the tolerance a comparison holds on the
    CPU, or — only under ``NBD_TEST_TPU=1`` — the wider one the chip
    was measured to need (its ``highest`` float32 matmul is multi-pass
    bf16 and its exp/log round differently from numpy's).  The CPU bar
    never moves for the chip's sake."""
    return lambda cpu, chip: chip if ON_CHIP else cpu

# ---------------------------------------------------------------------
# Segfault mitigation for long single-process runs: XLA's CPU backend
# intermittently crashed inside backend_compile_and_load at ~80% of the
# full suite (two different tests, both clean in isolation, box idle,
# RAM free) — consistent with per-process accumulation of hundreds of
# compiled executables, not with any single test.  Dropping executable
# references periodically keeps the accumulation bounded; every test
# after a clear simply recompiles (slower, correct).
_CLEAR_EVERY = int(os.environ.get("NBD_TEST_CLEAR_CACHES_EVERY", "150"))
_test_counter = {"n": 0}


def pytest_runtest_teardown(item, nextitem):
    _test_counter["n"] += 1
    if _CLEAR_EVERY and _test_counter["n"] % _CLEAR_EVERY == 0:
        try:
            import jax

            jax.clear_caches()
        except Exception:
            pass
