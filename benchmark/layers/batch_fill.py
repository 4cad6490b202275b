"""batch_fill.<ns>: real lanes over dispatched lanes in the window, in %,
from the engine's "<ns>_batched_requests" and "<ns>_pad_lanes" counters
(program_counter). <ns> is the engine's metric namespace of the cell's
program, named by the driver in run.counts["engine_ns"]."""


def read(name, run):
    ns = run.counts.get("engine_ns")
    if ns is None:
        return None
    real = run.counters.get(ns + "_batched_requests", 0)
    pad = run.counters.get(ns + "_pad_lanes", 0)
    if real + pad == 0:
        return None
    return 100.0 * real / (real + pad)
