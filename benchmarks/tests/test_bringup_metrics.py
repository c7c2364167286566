"""The set-up metrics that read the program's own ``bringup`` block
(``serve_status.bringup``): each resolves to a number on a recorded
``serve_status`` (``recorded_serve_status.json``: a one-rank CPU pool's
reply, cut to the block), and to nothing, without raising, on a program
that keeps no such block."""

import json
import os

import pytest

from benchmarks import harness as H

NEW = ["attach_daemon_s", "attach_interpreter_s", "attach_import_jax_s",
       "attach_backend_s", "attach_namespace_s", "attach_connect_s",
       "serve_open_spec_s", "serve_open_build_s", "setup_trace_lower_s",
       "setup_backend_compile_s", "setup_cache_load_s",
       "setup_cache_misses", "attach_covered_share"]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(os.path.dirname(__file__),
                           "recorded_serve_status.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", NEW)
def test_metric_reads_a_number_from_the_recorded_reply(recorded, name):
    value = H.read_metric(name, recorded)
    assert isinstance(value, float) and value >= 0.0


@pytest.mark.parametrize("name", NEW)
def test_metric_reads_nothing_from_a_program_without_the_block(name):
    parent = {"spans": {"fleet_attach_s": 12.0},
              "serve_status": {"status": "serving", "lat": {}}}
    assert H.read_metric(name, parent) is None
    assert H.read_metric(name, {}) is None


def test_trace_and_lower_are_summed(recorded):
    c = recorded["serve_status"]["bringup"]["compile"]
    assert H.read_metric("setup_trace_lower_s", recorded) == \
        pytest.approx(c["trace_s"] + c["lower_s"])


def test_covered_share_is_the_stages_over_the_stopwatch(recorded):
    a = recorded["serve_status"]["bringup"]["attach"]
    share = H.read_metric("attach_covered_share", recorded)
    assert share == pytest.approx(
        100.0 * (a["daemon_s"] + a["attach_s"] + a["tenant_attach_s"])
        / recorded["spans"]["fleet_attach_s"])
    assert 50.0 < share <= 100.0


def test_new_metrics_are_listed_for_the_serving_cells_only():
    bench = H.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    serving = [c["name"] for c in bench["workloads"]
               if H.traffic_of(c, False)["driver"].startswith("serve")]
    for name in NEW:
        assert by_name[name]["workloads"] == serving
        assert by_name[name]["moves"] == "setup_s"
        assert by_name[name]["layer"] == "fleet bring-up"
