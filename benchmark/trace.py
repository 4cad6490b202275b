"""The profiler trace of a traced run, reduced to device busy time,
device time per program and per top-level op, and idle gaps labelled by
the harness span open on the host at the time.

A trace is read into plain event dicts {plane, line, name, start_ns,
dur_ns, stats}; `reduce` works on those alone, so it is tested on a small
recorded trace without a chip. Device planes are the "/device:..." planes;
their "XLA Ops" line holds one event per operation run, nested (a while
loop's event contains its body's), named by the HLO instruction's text,
and the "XLA Modules" line one per program run. Host spans are the
harness's own `jax.profiler.TraceAnnotation`s, named "bench/<span>", on
the same clock. Only those lines are read: a TPU trace holds some 100,000
op events per second.
"""

import glob
import os
import re

WINDOW_SPAN = "bench/window"
SPAN_PREFIX = "bench/"
TOP = 10


DEVICE_LINES = ("XLA Ops", "XLA Modules")


def load_events(xplane_path):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(SPAN_PREFIX):
                    continue
                out.append(
                    {
                        "plane": plane.name,
                        "line": line.name,
                        "name": name,
                        "start_ns": float(ev.start_ns),
                        "dur_ns": float(ev.duration_ns),
                        "stats": {} if device else dict(ev.stats),
                    }
                )
    return out


def profiler_options():
    """Host annotations and device ops; no Python function tracing, which
    would slow the host and fill the trace."""
    from jax.profiler import ProfileOptions

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def find_xplane(trace_dir):
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def is_device(ev):
    return ev["plane"].startswith("/device:")


_HLO = re.compile(r"^(%[\w.-]+) = .*? ([a-z][a-z0-9-]*)\(")


def op_label(name):
    """A short label of an op event: "%while.25 while" from the HLO text
    "%while.25 = (...) while(...), ...", else the name itself."""
    m = _HLO.match(name)
    return "%s %s" % m.groups() if m else name[:80]


def module_label(name):
    """ "jit_fused_verify" from "jit_fused_verify(12155026882629946862)"."""
    return name.split("(")[0]


def top_level(events):
    """The events not contained in an earlier event of the same line."""
    out, end = [], None
    for e in sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"])):
        stop = e["start_ns"] + e["dur_ns"]
        if end is not None and stop <= end:
            continue
        out.append(e)
        end = stop if end is None else max(end, stop)
    return out


def _union(intervals):
    """Merged [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(events):
    """{window_s, busy_s, devices, op_s, module_s, module_runs, gaps} over
    the harness's traced window.

    busy_s: the union of the intervals in which an operation ran on a
    device, averaged over the devices that ran any. op_s: device seconds
    per top-level op ("<program> <op label>"), nested ops not counted
    twice; module_s / module_runs: device seconds and runs per program.
    gaps: [(label, seconds)] of every idle interval between device
    operations, labelled by the innermost harness span open at the gap's
    midpoint on any host thread ("host: no span" otherwise)."""
    windows = [e for e in events if e["name"] == WINDOW_SPAN]
    if not windows:
        raise ValueError("trace holds no %s span" % WINDOW_SPAN)
    w = windows[0]
    lo, hi = w["start_ns"], w["start_ns"] + w["dur_ns"]
    dev = [e for e in events if is_device(e)]
    modules = [e for e in dev if e["line"] == "XLA Modules"]
    spans = [
        e
        for e in events
        if not is_device(e)
        and e["name"].startswith(SPAN_PREFIX)
        and e["name"] != WINDOW_SPAN
    ]

    module_s, module_runs = {}, {}
    by_plane = {}
    for e in modules:
        s, t = _clip(e["start_ns"], e["start_ns"] + e["dur_ns"], lo, hi)
        by_plane.setdefault(e["plane"], []).append(
            (e["start_ns"], module_label(e["name"]))
        )
        if t <= s:
            continue
        m = module_label(e["name"])
        module_s[m] = module_s.get(m, 0.0) + (t - s) / 1e9
        module_runs[m] = module_runs.get(m, 0) + 1

    op_s, per_plane = {}, {}
    planes = {e["plane"] for e in dev}
    for plane in planes:
        lines = {e["line"] for e in dev if e["plane"] == plane}
        line = "XLA Ops" if "XLA Ops" in lines else "XLA Modules"
        ops = top_level(
            [e for e in dev if e["plane"] == plane and e["line"] == line]
        )
        starts = sorted(by_plane.get(plane, []))
        for e in ops:
            s, t = _clip(e["start_ns"], e["start_ns"] + e["dur_ns"], lo, hi)
            if t <= s:
                continue
            prog = _enclosing(starts, e["start_ns"])
            key = op_label(e["name"])
            if prog:
                key = "%s %s" % (prog, key)
            op_s[key] = op_s.get(key, 0.0) + (t - s) / 1e9
            per_plane.setdefault(plane, []).append((s, t))

    busy, gaps = [], []
    for plane, ivs in per_plane.items():
        merged = _union(ivs)
        busy.append(sum(t - s for s, t in merged) / 1e9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_label((a + b) / 2, spans), (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "devices": len(busy),
        "op_s": op_s,
        "module_s": module_s,
        "module_runs": module_runs,
        "gaps": gaps,
    }


def _enclosing(starts, t):
    """The program whose run started last at or before t."""
    import bisect

    i = bisect.bisect_right(starts, (t, "\uffff")) - 1
    return starts[i][1] if i >= 0 else None


def _label(t_ns, spans):
    best = None
    for e in spans:
        if e["start_ns"] <= t_ns <= e["start_ns"] + e["dur_ns"]:
            if best is None or e["dur_ns"] < best["dur_ns"]:
                best = e
    return best["name"][len(SPAN_PREFIX):] if best else "host: no span"


def breakdown(red):
    """The result line's "breakdown": the device ops that took most time
    and the longest idle gaps, summed by label, TOP of each."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:TOP]
    by_label = {}
    for label, s in red["gaps"]:
        by_label[label] = by_label.get(label, 0.0) + s
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "device_ops": [[n, s] for n, s in ops],
        "idle_gaps": [[n, s] for n, s in gaps],
    }
