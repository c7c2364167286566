"""The harness is driven by data: everything is found by file name, and
a cell is added with new files and one new entry, no edit."""

import json
import os

import pytest

from benchmarks import harness as H


def test_finds_config_traffic_metric_and_reader_by_name():
    bench = H.load_benchmark()
    for cell in bench["workloads"]:
        cfg = H.config_of(bench, cell, rehearse=False)
        assert cfg["hidden_size"] == 4096 and cfg["num_hidden_layers"] >= 1
        traffic = H.traffic_of(cell, rehearse=False)
        __import__("benchmarks.drivers." + traffic["driver"])
        for kind in ("end_to_end", "per_layer"):
            names = [m["name"] for m in H.metrics_for(bench, cell["name"], kind)]
            assert names, (cell["name"], kind)
            for n in names:
                spec = H.load_json("metrics", n + ".json")
                __import__("benchmarks.readers." + spec["reader"])
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]


def test_run_py_names_no_cell_config_or_metric():
    src = open(os.path.join(H.BENCH_DIR, "run.py")).read() \
        + open(os.path.join(H.BENCH_DIR, "harness.py")).read()
    bench = H.load_benchmark()
    names = [x["name"] for k in ("workloads", "configs", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert not [n for n in names if n in src]


@pytest.fixture
def throwaway():
    """The Open-questions cell m7b_serve_mixed under a throw-away name:
    two new files (a traffic mix, a metric) and one new entry."""
    made = []

    def put(rel, obj):
        path = os.path.join(H.BENCH_DIR, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(obj, f)
        made.append(path)

    chat = H.load_json("traffic", "chat_open.json")
    put("traffic/zz_mixed.json", dict(chat, prompt_len=[32, 3072],
                                      why="throw-away: chat and docs lengths"))
    put("metrics/zz_mixed_ttft_p95_ms.json", {
        "reader": "value", "args": {"path": "loadgen.ttft_p95_ms"},
        "unit": "ms", "better": "lower", "source": "host_clock",
        "layer": "gateway", "moves": "ttft_p50_ms",
        "workloads": ["zz_serve_mixed"]})
    yield
    for path in made:
        os.remove(path)


def test_a_cell_is_added_with_new_files_and_one_entry(throwaway):
    bench = H.load_benchmark()
    bench["workloads"].append({
        "name": "zz_serve_mixed", "config": bench["configs"][-1]["name"],
        "traffic": "zz_mixed", "chips": 1, "why": "throw-away"})
    bench["per_layer"].append({"name": "zz_mixed_ttft_p95_ms", "unit": "ms",
                               "workloads": ["zz_serve_mixed"]})
    cell = H.find_cell(bench, "zz_serve_mixed")
    traffic = H.traffic_of(cell, rehearse=False)
    assert traffic["prompt_len"] == [32, 3072]
    names = [m["name"] for m in H.metrics_for(bench, "zz_serve_mixed",
                                              "per_layer")]
    assert "zz_mixed_ttft_p95_ms" in names
    obs = {"loadgen": {"ttft_p95_ms": 12.5}}
    assert H.read_metric("zz_mixed_ttft_p95_ms", obs) == 12.5
    # a reader that finds nothing to read returns nothing
    assert H.read_metric("zz_mixed_ttft_p95_ms", {}) is None
    from benchmarks import loadgen
    plan = loadgen.plan(traffic, 1, 20.0, 32000)
    assert max(len(r["prompt"]) for r in plan) > 2000


def test_every_share_reader_returns_nothing_without_a_trace():
    for name in ("mosaic_time_share", "flash_attn_roofline",
                 "device_idle_share", "train_mfu"):
        assert H.read_metric(name, {"e2e": {}}) is None
