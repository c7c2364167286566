"""No chip, no number: the tools that exist to run on the accelerator
exit non-zero on a machine without one, say why, and print no result
row — they never measure some other device instead."""

import os
import subprocess
import sys

import pytest

from nbdistributed_tpu.manager import topology

pytestmark = [pytest.mark.integration]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(script: str) -> subprocess.CompletedProcess:
    if topology.available_tpu_chips():
        pytest.skip("this host has TPU chips")
    return subprocess.run([sys.executable, script], cwd=REPO_ROOT,
                          text=True, capture_output=True, timeout=240)


def test_chip_smoke_without_a_chip_fails_fast_and_says_why():
    proc = _run("chip_smoke.py")
    assert proc.returncode == 2
    assert "no fleet on TPU devices" in proc.stderr
    assert "--backend tpu" in proc.stderr      # the worker's own words
    last = proc.stdout.strip().splitlines()[-1]
    assert not last.startswith("{"), "no result line without a chip"


def test_chip_smoke_verdict_line_has_exactly_ok_and_device(capsys,
                                                           monkeypatch):
    """What reads the smoke's last line takes {"ok", "device"} and no
    third key; the detail goes on the SUMMARY line before it."""
    import json
    sys.path.insert(0, REPO_ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO_ROOT)
    monkeypatch.setenv("NBD_SMOKE_RUN", "")  # Smoke() exports its marker
    smoke = chip_smoke.Smoke(1)
    smoke.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    smoke.layers = 3
    smoke.phases = {p: {"ok": True, "seconds": 0.0}
                    for p in chip_smoke.PHASES}
    assert smoke.report() == 0
    *_, summary, last = capsys.readouterr().out.strip().splitlines()
    assert json.loads(last) == {"ok": True, "device": smoke.device}
    assert summary.startswith("SUMMARY ")
    assert json.loads(summary[len("SUMMARY "):])["claim"] is None
    smoke.phases["serve"]["ok"] = False
    assert smoke.report() == 1
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": False, "device": smoke.device}

