"""sign_ms.<cell kind>: median (nearest rank) duration of the program's
"issue.sign" span taken in the window, one authority's blind-sign of one
fan-out on its thread, in ms (program_span: the span's
"bridge_issue_sign_s" histogram, fed while the profiler collects)."""

from ..stats import percentile


def read(name, run):
    samples = run.hist.get("bridge_issue_sign_s")
    if not samples:
        return None
    return 1e3 * percentile(samples, 50)
