"""mint_round_ms.<cell kind>: median (nearest rank) duration of the
program's "issue.mint_round" span taken in the window: unblind, Lagrange
aggregate and verify-before-release of one fan-out, in ms (program_span:
the span's "bridge_issue_mint_round_s" histogram, fed while the profiler
collects)."""

from ..stats import percentile


def read(name, run):
    samples = run.hist.get("bridge_issue_mint_round_s")
    if not samples:
        return None
    return 1e3 * percentile(samples, 50)
