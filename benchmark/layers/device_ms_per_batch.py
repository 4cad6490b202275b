"""device_ms_per_batch.<cell kind>: device busy milliseconds in the traced
window over the batches the driver completed in it (device_trace)."""


def read(name, run):
    red, n = run.reduced, run.counts.get("batches")
    if not red or red["busy_s"] <= 0 or not n:
        return None
    return red["busy_s"] * 1e3 / n
