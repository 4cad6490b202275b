"""The program's stage spans in a profiler trace: reading them beside the
harness's, the gap-label rule (harness span, then program span, then
"host: no span"), reduce() left as it was, the readers of the per-layer
metrics they feed, and the issuance spans of the mint cell's closed
loop on the native core.

data/bridged_window.xplane.pb was recorded on the CPU: inside
"bench/window", a "bench/encode" harness span around a program span
"stream.encode"; then "issue.mint_round" around "issue.verify"; then
"issue.sign" on another thread; with stretches of no span between them.
A CPU trace has no device plane, so the tests lay device ops over it.
"""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import program_spans as ps
from benchmark import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
BRIDGED = os.path.join(DATA, "bridged_window.xplane.pb")
CPU_WINDOW = os.path.join(DATA, "cpu_window.xplane.pb")
SYNTHETIC = os.path.join(DATA, "synthetic_trace.json")


@pytest.fixture(scope="module")
def bridged():
    return ps.load_events(BRIDGED)


def _span(events, name):
    (e,) = [e for e in events if e["name"] == name]
    return e


def _mid(e):
    return e["start_ns"] + e["dur_ns"] / 2


def _device_ops_with_gaps_at(events, points, width_ns=1000.0):
    """Device op events covering the window but for a gap of width_ns
    centred on each point."""
    w = _span(events, trace.WINDOW_SPAN)
    lo, hi = w["start_ns"], w["start_ns"] + w["dur_ns"]
    edges = [lo]
    for p in sorted(points):
        edges += [p - width_ns / 2, p + width_ns / 2]
    edges.append(hi)
    return [
        {"plane": "/device:TPU:0", "line": "XLA Ops",
         "name": "%op.1 = f32[] add()", "start_ns": a, "dur_ns": b - a,
         "stats": {}}
        for a, b in zip(edges[0::2], edges[1::2])
    ]


def test_program_spans_are_read_beside_the_harness(bridged):
    names = {e["name"] for e in bridged}
    assert {"bench/window", "bench/encode"} <= names
    assert {"coconut/stream.encode", "coconut/issue.mint_round",
            "coconut/issue.verify", "coconut/issue.sign"} <= names
    sign = _span(bridged, "coconut/issue.sign")
    assert sign["stats"]["fanout"] == 1
    assert sign["line"] == "python"
    # trace.load_events keeps only the harness's events
    assert not any(
        e["name"].startswith("coconut/") for e in trace.load_events(BRIDGED)
    )


def test_program_span_s_per_label_inside_the_window(bridged):
    spans = ps.program_span_s(bridged)
    assert set(spans) == {"stream.encode", "issue.mint_round",
                          "issue.verify", "issue.sign"}
    assert all(len(d) == 1 for d in spans.values())
    assert spans["issue.mint_round"][0] > spans["issue.verify"][0] > 0.009


def test_gap_label_rule_harness_then_program_then_none(bridged):
    no_span = _span(bridged, "bench/window")["start_ns"] + 2.5e6
    points = {
        "encode": _mid(_span(bridged, "bench/encode")),
        "issue.verify": _mid(_span(bridged, "coconut/issue.verify")),
        "issue.mint_round": _span(bridged, "coconut/issue.mint_round")[
            "start_ns"] + 2.5e6,
        "issue.sign": _mid(_span(bridged, "coconut/issue.sign")),
        ps.NO_SPAN: no_span,
    }
    events = bridged + _device_ops_with_gaps_at(bridged, points.values())
    gaps = ps.gaps(events)
    assert sorted(label for label, _ in gaps) == sorted(points)
    assert all(s == pytest.approx(1e-6) for _, s in gaps)
    assert ps.unattributed_idle_share(gaps) == pytest.approx(20.0)
    # reduce() finds the same gaps and labels only the harness's
    red = trace.reduce(events)
    assert sorted(s for _, s in red["gaps"]) == pytest.approx(
        sorted(s for _, s in gaps)
    )
    assert sorted(label for label, _ in red["gaps"]) == [
        "encode"] + [ps.NO_SPAN] * 4


def test_summary_of_a_window(bridged):
    points = [_mid(_span(bridged, "coconut/issue.sign"))]
    out = ps.summary(bridged + _device_ops_with_gaps_at(bridged, points))
    assert [label for label, _ in out["idle_gaps"]] == ["issue.sign"]
    assert out["unattributed_idle_share"] == 0.0
    assert out["program_spans"]["issue.sign"]["n"] == 1
    assert out["program_spans"]["issue.sign"]["median_ms"] > 9


def test_unattributed_share_without_idle_time():
    assert ps.unattributed_idle_share([]) is None


def _reduce_synthetic():
    with open(SYNTHETIC) as f:
        return trace.reduce(json.load(f)["events"])


PINNED = {
    CPU_WINDOW: {
        "window_s": 0.03404329, "busy_s": 0.0, "devices": 0, "op_s": {},
        "module_s": {}, "module_runs": {}, "gaps": [],
    },
    SYNTHETIC: {
        "window_s": 0.001, "busy_s": 0.00045, "devices": 1,
        "op_s": {
            "jit_fused_verify %fusion.1 fusion": 5e-05,
            "jit_fused_verify %while.2 while": 0.0002,
            "jit_fused_verify %copy.4 copy": 0.00015,
            "jit_other %fusion.5 fusion": 0.0001,
        },
        "module_s": {"jit_fused_verify": 0.00045, "jit_other": 0.0003},
        "module_runs": {"jit_fused_verify": 1, "jit_other": 1},
        "gaps": [("readback", 0.0002), ("readback", 0.0002),
                 ("encode", 0.0001), ("host: no span", 5e-05)],
    },
}


@pytest.mark.parametrize("path", sorted(PINNED), ids=os.path.basename)
def test_reduce_is_unchanged_on_the_recorded_traces(path):
    if path.endswith(".json"):
        red = _reduce_synthetic()
    else:
        red = trace.reduce(trace.load_events(path))
        # the program's events beside them change nothing
        assert trace.reduce(ps.load_events(path)) == red
    want = PINNED[path]
    assert set(red) == set(want)
    for k, v in want.items():
        if k == "gaps":
            assert [g[0] for g in red[k]] == [g[0] for g in v]
            assert [g[1] for g in red[k]] == pytest.approx([g[1] for g in v])
        else:
            assert red[k] == pytest.approx(v), k


def test_program_events_leave_reduce_unchanged(bridged):
    points = [_mid(_span(bridged, "coconut/issue.sign"))]
    dev = _device_ops_with_gaps_at(bridged, points)
    harness_only = [e for e in bridged if not e["name"].startswith("coconut/")]
    assert trace.reduce(bridged + dev) == trace.reduce(harness_only + dev)


def test_gaps_match_reduce_on_the_synthetic_trace():
    with open(SYNTHETIC) as f:
        events = json.load(f)["events"]
    assert sorted(ps.gaps(events)) == sorted(
        tuple(g) for g in trace.reduce(events)["gaps"]
    )


# --- readers ----------------------------------------------------------------


def _run(**hist):
    return SimpleNamespace(hist=hist, counts={}, counters={}, span_s={})


@pytest.mark.parametrize(
    "metric,hist,samples,want",
    [
        ("sign_ms.mint", "bridge_issue_sign_s", [0.3, 0.1, 0.2], 200.0),
        ("mint_round_ms.mint", "bridge_issue_mint_round_s", [0.5, 0.7], 500.0),
        ("encode_ms.verify", "bridge_stream_encode_s", [0.01, 0.02], 15.0),
    ],
)
def test_reader(metric, hist, samples, want):
    from benchmark import spec

    reader = spec.reader(metric)
    assert reader.read(metric, _run(**{hist: samples})) == pytest.approx(want)
    # a program without the span (the parent's) gives nothing to read
    assert reader.read(metric, _run()) is None


# --- the mint cell on the native core ---------------------------------------


def test_mint_cell_records_issuance_spans_in_the_ring():
    from bench_cpu_backend import NativeBackend
    from benchmark import run as bench_run
    from benchmark import spec
    from coconut_tpu.obs import trace as otrace

    tracer = otrace.enable(ring=65536)
    try:
        result, _ = bench_run.run_cell(
            spec.load(), "mint.q2-3of5.closed", 2**33 + 11, 1, False,
            device={"platform": "cpu", "kind": "cpu", "count": 1},
            backend_factory=NativeBackend, overrides={"max_batch": 4},
            traffic_overrides={"chains": 8, "warm_mints": 8,
                               "max_wait_ms": 2000, "reference_sample": 0},
        )
        spans = tracer.tail()
    finally:
        otrace.disable()
    assert result["correct"], result["checks"]
    names = {s.name for s in spans}
    assert {"sign", "mint_round", "unblind", "aggregate", "verify",
            "release"} <= names
    signs = [s for s in spans if s.name == "sign"]
    assert len({s.attrs["authority"] for s in signs}) >= 3
    rounds = {s.span_id: s for s in spans if s.name == "mint_round"}
    verify = [s for s in spans if s.name == "verify" and s.parent_id in rounds]
    assert verify


def test_traced_mint_cell_through_the_tool():
    """The tool's traced run at test size on the CPU: the cell's window,
    the program's spans read back from the profiler trace, and the
    per-layer readers fed by the bridge. No device plane on a CPU, so no
    gaps."""
    from bench_cpu_backend import NativeBackend
    from benchmark import spec

    out = ps.traced_cell(
        spec.load(), "mint.q2-3of5.closed", 2**33 + 13, 1,
        {"platform": "cpu", "kind": "cpu", "count": 1},
        backend_factory=NativeBackend, overrides={"max_batch": 4},
        traffic_overrides={"chains": 8, "warm_mints": 8,
                           "max_wait_ms": 2000, "reference_sample": 0},
    )
    assert out["run"]["correct"], out["run"]["checks"]
    assert out["window_e2e"]["mints_per_s"] > 0
    spans = out["program"]["program_spans"]
    for label in ("issue.sign", "issue.mint_round", "issue.verify",
                  "issue.release", "prep.dispatch", "prep.device"):
        assert spans[label]["n"] > 0, label
    metrics = out["run"]["metrics"]
    assert metrics["sign_ms.mint"]["value"] > 0
    assert metrics["mint_round_ms.mint"]["value"] > 0
    assert out["program"]["idle_gaps"] == []
    assert trace.load_events.__module__ == "benchmark.trace"
