"""Trace export: JSONL span records.

One span object per line (`write_jsonl` / `export_jsonl`), the same
schema the flight recorder embeds (`span_records`) — greppable,
streamable, joins against the dead-letter log on trace_id. For a
timeline beside the device's ops, take a `jax.profiler` trace: the
program's stage spans are bridged into it (obs/trace.py).
"""

import json

from . import trace as _trace


def span_records(spans):
    """JSON-ready dicts for Span objects (dicts pass through), t0 order."""
    recs = [s if isinstance(s, dict) else s.to_dict() for s in spans]
    return sorted(recs, key=lambda r: (r["t0"], r["span_id"]))


def write_jsonl(spans, path):
    """One span record per line; returns the record count."""
    recs = span_records(spans)
    # lint: allow(durability, on-demand trace export artifact - rewritten
    # whole per call, nothing re-reads it across a crash)
    with open(path, "w") as f:
        for rec in recs:
            f.write(json.dumps(rec, sort_keys=True) + "\n")
    return len(recs)


def read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def export_jsonl(path, tracer=None):
    """Dump the (global) tracer's finished-span ring as JSONL; returns
    the record count."""
    tracer = tracer if tracer is not None else _trace.get_tracer()
    spans = tracer.tail() if tracer is not None else []
    return write_jsonl(spans, path)
