"""The program's own stage spans in a traced run's profiler trace.

While a profiler session collects, the program opens a
`jax.profiler.TraceAnnotation` named "coconut/<ns>.<span>" around each of
its stage spans (coconut_tpu/obs/trace.py), on the profiler's clock beside
the device's ops. `trace.load_events` keeps only the harness's "bench/"
host events, so the result line's breakdown labels an idle gap that no
harness span covers "host: no span". This module reads the same trace
with the program's spans as well:

  load_events     trace.load_events' events plus the "coconut/" host
                  events (`reduce` ignores them: its output is unchanged);
  program_span_s  each program span's durations inside the window;
  gaps            every idle gap `reduce` finds, labelled by the
                  innermost harness span open at its midpoint on any host
                  thread, else the innermost program span open there,
                  else "host: no span";
  unattributed_idle_share
                  the share of the device's idle time still labelled
                  "host: no span".

    python3 -m benchmark.program_spans --workload <cell> --seed <n> \\
        --seconds <s>

runs the cell traced, as `benchmark.run --trace 1` does, and prints one
JSON object: the run's own line (correct, metrics, breakdown), the
cell's end-to-end numbers of the traced window, the gaps by these
labels and the program spans' durations.
"""

import argparse
import json
import sys

from . import trace

PROGRAM_PREFIX = "coconut/"
NO_SPAN = "host: no span"
PREFIXES = (trace.SPAN_PREFIX, PROGRAM_PREFIX)


def load_events(xplane_path):
    """As trace.load_events, with the program's "coconut/" host events."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in trace.DEVICE_LINES:
                continue
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(PREFIXES):
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


def _window(events):
    for e in events:
        if e["name"] == trace.WINDOW_SPAN:
            return e["start_ns"], e["start_ns"] + e["dur_ns"]
    raise ValueError("trace holds no %s span" % trace.WINDOW_SPAN)


def _host_spans(events, prefix):
    return [
        e
        for e in events
        if not trace.is_device(e)
        and e["name"].startswith(prefix)
        and e["name"] != trace.WINDOW_SPAN
    ]


def program_span_s(events):
    """{label: [seconds]} of the program spans ("issue.sign") that started
    and ended inside the window."""
    lo, hi = _window(events)
    out = {}
    for e in _host_spans(events, PROGRAM_PREFIX):
        if lo <= e["start_ns"] and e["start_ns"] + e["dur_ns"] <= hi:
            label = e["name"][len(PROGRAM_PREFIX):]
            out.setdefault(label, []).append(e["dur_ns"] / 1e9)
    return out


def _innermost(t_ns, spans, prefix):
    best = None
    for e in spans:
        if e["start_ns"] <= t_ns <= e["start_ns"] + e["dur_ns"]:
            if best is None or e["dur_ns"] < best["dur_ns"]:
                best = e
    return best["name"][len(prefix):] if best else None


def gaps(events):
    """[(label, seconds)], longest first, of the idle intervals between
    device operations in the window that `trace.reduce` finds, labelled
    harness span first, then program span, then "host: no span"."""
    lo, hi = _window(events)
    dev = [e for e in events if trace.is_device(e)]
    harness = _host_spans(events, trace.SPAN_PREFIX)
    program = _host_spans(events, PROGRAM_PREFIX)
    out = []
    for plane in sorted({e["plane"] for e in dev}):
        lines = {e["line"] for e in dev if e["plane"] == plane}
        line = "XLA Ops" if "XLA Ops" in lines else "XLA Modules"
        ivs = []
        for e in trace.top_level(
            [e for e in dev if e["plane"] == plane and e["line"] == line]
        ):
            s, t = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
            if t > s:
                ivs.append((s, t))
        merged = trace._union(ivs)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                label = (
                    _innermost(mid, harness, trace.SPAN_PREFIX)
                    or _innermost(mid, program, PROGRAM_PREFIX)
                    or NO_SPAN
                )
                out.append((label, (b - a) / 1e9))
    out.sort(key=lambda g: -g[1])
    return out


def unattributed_idle_share(gap_list):
    """% of the idle time in `gap_list` labelled "host: no span"; None
    without idle time."""
    idle = sum(s for _, s in gap_list)
    if idle <= 0:
        return None
    return 100.0 * sum(s for lab, s in gap_list if lab == NO_SPAN) / idle


def summary(events, top=trace.TOP):
    """The program's view of a traced window, for the tool's line."""
    g = gaps(events)
    by_label = {}
    for label, s in g:
        by_label[label] = by_label.get(label, 0.0) + s
    spans = program_span_s(events)
    return {
        "idle_gaps": sorted(by_label.items(), key=lambda kv: -kv[1])[:top],
        "idle_s": sum(s for _, s in g),
        "unattributed_idle_share": unattributed_idle_share(g),
        "program_spans": {
            label: {
                "n": len(d),
                "total_s": sum(d),
                "median_ms": 1e3 * sorted(d)[(len(d) - 1) // 2],
            }
            for label, d in sorted(spans.items())
        },
    }


def traced_cell(bench, name, seed, seconds, device, **kw):
    """Run one cell traced (benchmark.run.run_cell with --trace 1, `kw`
    passed on) and return the tool's line."""
    from . import run as bench_run

    kept = {}
    load = trace.load_events

    def keep(path):
        # the harness reads the trace through trace.load_events and then
        # deletes it: keep the program's events
        kept["events"] = load_events(path)
        return kept["events"]

    trace.load_events = keep
    try:
        result, run = bench_run.run_cell(
            bench, name, seed, seconds, True, device=device, **kw
        )
    finally:
        trace.load_events = load
    out = {
        "run": result,
        "window_e2e": run.e2e,
        "window_s": run.window_s,
    }
    if "events" in kept:
        out["program"] = summary(kept["events"])
    return out


def main(argv=None):
    from . import run as bench_run
    from . import spec

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = spec.load()
    cell = spec.cell(bench, args.workload)
    try:
        device = bench_run.device_info(cell["chips"])
    except bench_run.NoChip as e:
        print("refused: %s" % e, file=sys.stderr)
        return 2
    bench_run.enable_cache()
    out = traced_cell(bench, args.workload, args.seed, args.seconds, device)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import os

    try:
        code = main()
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # the engine's threads are daemons; end without waiting on them
    os._exit(code)
