"""Drive a whole run past the harness's look for a chip (``--rehearse``:
the CPU, tiny sizes) with the timed path broken underneath, and see
``correct`` come out false; and see a stall move the end-to-end metrics.
Slow (a fleet per run): about half a minute each."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(cell, *extra, seconds=4):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "11", "--seconds", str(seconds), "--rehearse", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert "no_measurement" in line and list(line)[-1] == "compared"
    return line


CELLS = [c["name"] for c in json.load(open(os.path.join(
    ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_well_formed_and_correct(cell):
    line = run(cell)
    assert line["correct"] and line["metrics"]["setup_s"]["value"] > 0
    assert len(line["metrics"]) >= 2


def test_a_step_that_does_nothing_is_not_correct():
    line = run("m7b_train_1chip", "--broken", "state_unchanged")
    assert not line["correct"]
    bad = {c["name"] for c in line["compared"] if c["value"] > c["limit"]}
    assert "change_gap" in bad


def test_a_token_altered_where_it_is_produced_is_not_correct():
    line = run([c for c in CELLS if "serve" in c][0], "--broken", "token",
               seconds=6)
    assert not line["correct"]
    bad = {c["name"] for c in line["compared"] if c["value"] > c["limit"]}
    assert "served_logit_gap_max" in bad


def test_a_stall_moves_the_training_rate():
    base = run("m7b_train_1chip")["metrics"]["train_tokens_per_s"]["value"]
    hit = run("m7b_train_1chip", "--broken", "stall")
    assert hit["metrics"]["train_tokens_per_s"]["value"] < 0.9 * base


def test_without_a_chip_there_is_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode != 0 and p.stdout.strip() == ""
