"""The reduction from a profiler trace to busy time, idle share, device
time per named scope and labelled idle gaps, on a small synthetic trace;
and the reading of a recorded .xplane.pb."""

import json
import os

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(DATA, "synthetic_trace.json")) as f:
        return trace.reduce(json.load(f)["events"])


def test_busy_is_the_union_of_device_ops_in_the_window(reduced):
    assert reduced["window_s"] == pytest.approx(1000e-6)
    assert reduced["busy_s"] == pytest.approx(450e-6)
    assert reduced["devices"] == 1


def test_device_time_per_program_and_top_level_op(reduced):
    assert reduced["op_s"] == pytest.approx(
        {
            "jit_fused_verify %fusion.1 fusion": 50e-6,
            "jit_fused_verify %while.2 while": 200e-6,
            "jit_fused_verify %copy.4 copy": 150e-6,
            "jit_other %fusion.5 fusion": 100e-6,
        }
    )
    assert reduced["module_s"] == pytest.approx(
        {"jit_fused_verify": 450e-6, "jit_other": 300e-6}
    )
    assert reduced["module_runs"] == {"jit_fused_verify": 1, "jit_other": 1}


def test_idle_gaps_are_labelled_by_the_open_harness_span(reduced):
    bd = trace.breakdown(reduced)
    gaps = dict(bd["idle_gaps"])
    assert gaps == pytest.approx(
        {"readback": 400e-6, "encode": 100e-6, "host: no span": 50e-6}
    )
    assert sum(gaps.values()) + reduced["busy_s"] == pytest.approx(1000e-6)
    assert bd["device_ops"][0] == [
        "jit_fused_verify %while.2 while", pytest.approx(200e-6)
    ]
    assert len(bd["device_ops"]) <= trace.TOP


def test_labels():
    assert trace.op_label("%x.1 = f32[2]{0} add(f32[2]{0} %a, f32[2]{0} %b)") == "%x.1 add"
    assert trace.module_label("jit_fused_verify(1215)") == "jit_fused_verify"
    evs = [{"start_ns": 0, "dur_ns": 10}, {"start_ns": 2, "dur_ns": 3},
           {"start_ns": 12, "dur_ns": 1}]
    assert trace.top_level(evs) == [evs[0], evs[2]]


def test_reads_a_recorded_xplane():
    events = trace.load_events(
        trace.find_xplane(DATA)
    )
    red = trace.reduce(events)
    assert red["window_s"] > 0
    assert red["devices"] == 0  # recorded on a CPU: no device plane
    names = {e["name"] for e in events}
    assert "bench/encode" in names


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace.reduce([])
