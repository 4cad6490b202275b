"""batch_wait_p95_ms.<ns>: 95th percentile (nearest rank) of the engine's
"<ns>_batch_wait_s" samples taken in the window, oldest request's
admission to its batch's assembly, in ms (program_counter)."""

from ..stats import percentile


def read(name, run):
    ns = run.counts.get("engine_ns")
    samples = run.hist.get("%s_batch_wait_s" % ns) if ns else None
    if not samples:
        return None
    return 1e3 * percentile(samples, 95)
