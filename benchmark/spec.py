"""BENCHMARK.json and the files it names, found by name.

A cell names a configuration and a traffic mix. The configuration's file
is the one its entry names; a traffic mix is benchmark/traffic/<name>.json,
whose "kind" names the driver benchmark/drivers/<kind>.py. A per-layer
metric is read by benchmark/layers/<name up to the first '.'>.py. Adding
any of these is adding files and entries: nothing here changes.
"""

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))


def load(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError("no %s named %r in BENCHMARK.json" % (what, name))


def cell(bench, name):
    return _by_name(bench["workloads"], name, "workload")


def config(bench, name, root=ROOT):
    entry = _by_name(bench["configs"], name, "config")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def traffic(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def driver(kind):
    return importlib.import_module("benchmark.drivers." + kind)


def reader(metric_name):
    return importlib.import_module(
        "benchmark.layers." + metric_name.split(".")[0]
    )


def end_to_end(bench, cell_name):
    """The end-to-end metrics this cell reports."""
    return [
        m
        for m in bench["end_to_end"]
        if cell_name in m.get("workloads", [cell_name])
    ]


def per_layer(bench, cell_name):
    """The per-layer metrics this cell reports in its traced run: those
    that list it, and those without a list whose `moves` it reports."""
    moved = {m["name"] for m in end_to_end(bench, cell_name)}
    return [
        m
        for m in bench["per_layer"]
        if (
            cell_name in m["workloads"]
            if "workloads" in m
            else m["moves"] in moved
        )
    ]
