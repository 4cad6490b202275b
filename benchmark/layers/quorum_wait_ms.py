"""quorum_wait_ms.<cell kind>: median (nearest rank) of the issuance
service's "issue_quorum_wait_s" samples taken in the window, a fan-out's
dispatch to its t-th partial signature, in ms (program_counter)."""

from ..stats import percentile


def read(name, run):
    samples = run.hist.get("issue_quorum_wait_s")
    if not samples:
        return None
    return 1e3 * percentile(samples, 50)
