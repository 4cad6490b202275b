"""Observability: scoped timers + counters + bounded latency histograms
(SURVEY.md §5 "metrics" mandate).

The reference has no observability at all (errors are the only signal —
SURVEY §5); this module provides the minimum the framework's own survey
demands: per-phase wall-clock timers — "encode" (host limb encode),
"kernel" (device dispatch), "readback" (device->host transfer) — monotonic
counters "verifies" / "batches" / "transfer_bytes", bounded latency
histograms with percentile readout (the serving layer's per-request
SLO surface), and a `snapshot()` the bench harness embeds in its JSON output
so TPU claims are auditable.

The stream supervision layer (stream.py / retry.py) reports its fault
handling through the same counters so `snapshot()` is the single audit
surface: "retries" (re-attempts after a transient backend error),
"fallbacks" (batches re-dispatched on the fallback backend after retries
exhausted), "bisections" (grouped-failure splits while isolating culprit
credentials), "dead_letters" (culprits appended to the dead-letter JSONL),
and "checkpoint_quarantined" (corrupt state files moved aside on resume).

The RLC batch verifier (PR 16, coconut_tpu/batchverify.py + the
backends' *_combined entry points) adds: "verify_batched_checks"
(combined RLC predicate evaluations — one per batch plus one per
bisection probe), "verify_batched_fallbacks" (combined batches that
rejected and fell back to the bisection ladder), "verify_bisection_depth"
(ladder splits while attributing a rejected combined batch — depth per
incident is the delta across the fallback), and "verify_final_exps"
(final exponentiations dispatched: B per exact batch, 1 per
combined/grouped batch — the <=2-per-combined-batch bench assertion
reads this counter's deltas).

The encode pipeline reports here too: "encode_cache_hits" /
"encode_cache_misses" (the backend's static-operand cache — comb tables,
grouped point uploads, g_tilde — see tpu/backend._static_operands),
"prefetched_batches" (batches encoded+dispatched by verify_stream's
background worker), and the "prefetch_wait" timer (main-thread seconds
blocked waiting on the prefetch queue: near zero means the encode worker
keeps the device fed — pipeline occupancy is 1 - prefetch_wait/wall).

The online serving layer (coconut_tpu/serve/) reports: "serve_admitted" /
"serve_rejected" (admission control), "serve_batches" /
"serve_batched_requests" / "serve_pad_lanes" (coalescing — mean batch
occupancy is batched_requests / (batches * max_batch)), "serve_valid" /
"serve_invalid" / "serve_failed_requests" / "serve_cancelled" (outcomes),
"future_callback_errors" (future done-callbacks that raised — contained,
never propagated into the settling thread), and the "serve_latency_s" /
"serve_batch_wait_s" histograms.

Every OTHER engine program reports the same shape under its own
namespace (`<ns>` is the program's metric namespace: "prep", "issue",
"prove", "showv" — the verify pool keeps the legacy "serve" prefix):
"<ns>_done" (requests settled OK), "<ns>_pad_lanes" (lanes padded to
the program's pad convention), "<ns>_valid" / "<ns>_invalid" (verdict
programs), "<ns>_failed_requests" / "<ns>_cancelled" (failure
outcomes), and the "<ns>_latency_s" histogram. The ragged show-verify
host fallback counts "show_verify_ragged_proofs" (proofs verified on
the ragged path) / "show_verify_ragged_fallback" (batches that took
it).

The mesh-scale dispatcher pool adds PER-DEVICE and placement surfaces:
each device executor `<d>` counts "serve_dev<d>_dispatches" /
"serve_dev<d>_requests" and accumulates the "serve_dev<d>_busy_s" timer
(occupancy over a window is its delta / wall), the adaptive placement
policy counts "serve_placed_single" / "serve_placed_sharded", and
point-in-time GAUGES ("serve_queue_depth", "serve_dev<d>_load" —
`set_gauge`, last-write-wins, reported verbatim under "gauges") expose
the routing state the least-loaded picker saw. `counters_with_prefix` /
`timers_with_prefix` read a whole label family (e.g. "serve_dev")
without enumerating device ids.

The SELF-HEALING pool (serve/health.py + serve/service.py) reports its
recovery ladder here: "serve_quarantined" (circuit-breaker opens),
"serve_probes" (half-open probe batches placed on PROBATION executors),
"serve_probe_failures", "serve_recovered" (breakers closed back to
HEALTHY), "serve_watchdog_timeouts" (hung dispatches expired),
"serve_executor_crashes" (executor-loop crashes contained),
"serve_redistributed_batches" / "serve_redistributed_requests" (unsettled
work re-placed onto survivors), "serve_redispatch_exhausted" (poisonous
batches failed after the hop cap), "serve_shed_bulk" (brownout sheds),
and "rotations" / "rotation_errors" (dead-letter/flight JSONL rotation)
plus "flight_torn_lines" (unparseable flight-recorder lines skipped on
read after a crash mid-append).
Gauges: "serve_dev<d>_health" (the state string), "serve_healthy_executors"
(admissible pool size), "serve_brownout" (0/1 shed-mode flag).

The THRESHOLD-ISSUANCE service (coconut_tpu/issue/) reports under the
"issue" namespace — the same queue/batcher/health machinery re-namespaced
("issue_admitted" / "issue_rejected" / "issue_batches" /
"issue_batched_requests" / "issue_shed_bulk", per-authority
"issue_auth<a>_dispatches" / "issue_auth<a>_busy_s", breaker counters
"issue_quarantined" / "issue_probes" / "issue_probe_failures" /
"issue_recovered", "issue_watchdog_timeouts", "issue_authority_crashes",
"issue_health_tick_errors") plus the quorum-specific surfaces:
"issue_minted" (credentials released — each verified under the
aggregated verkey before release), "issue_hedges" (straggler hedge
dispatches fired) / "issue_hedge_no_spare" (hedges that found no spare
authority), "issue_partials_discarded" (late/duplicate/stale partial
rows dropped by the first-t-wins guard), "issue_corrupt_partials"
(partial rows attributed to a corrupt authority by per-partial
verification), "issue_redispatched" (coverage re-dispatches to spare
authorities), "issue_cancelled_signs" (queued signs canceled after the
quorum resolved), "issue_sign_skips" (popped signs skipped because the
fan-out had already resolved), "issue_quorum_unreachable" (fan-outs
failed with QuorumUnreachableError), "issue_mint_failures" /
"issue_failed_requests" / "issue_cancelled" (failure outcomes).
Histograms: "issue_quorum_wait_s" (dispatch -> t-th partial, the quorum
assembly latency), "issue_latency_s" (admission -> release, the
client-facing SLO), "issue_batch_wait_s" (coalescing delay). Gauges:
"issue_auth<a>_health", "issue_healthy_authorities",
"issue_queue_depth", "issue_brownout".

The REPLICA LIFECYCLE layer (engine/lifecycle.py, PR 14) reports under
"lifecycle_*" and "elastic_*": gauges "lifecycle_state" (0 warming /
1 up / 2 draining / 3 closed), "lifecycle_warmup_s" (boot's manifest
replay wall time), "lifecycle_manifest_shapes" (shapes loaded at boot);
counters "lifecycle_warmed_shapes" / "lifecycle_warm_skipped" /
"lifecycle_warm_errors" (manifest replay outcomes),
"lifecycle_manifest_corrupt" / "lifecycle_manifest_save_errors" /
"lifecycle_manifest_unserializable" (artifact integrity — corruption
degrades to a cold boot, never a failed one), and
"lifecycle_cache_config_errors" (persistent compilation cache could not
be configured). Elastic pool sizing: gauges "elastic_active_executors" /
"elastic_depth" / "elastic_busy_fraction"; counters "elastic_parked" /
"elastic_unparked" (engine-level park/respawn), "elastic_grown" /
"elastic_shrunk" (controller decisions that acted), and
"elastic_emergency_unparked" (parked spares pressed into service when
every active executor died). The fleet adds the lifecycle routing
proof: "gateway_warmed" / "gateway_drain_observed" (directory
transitions), "gateway_drain_handoffs" (closed-replica refusals failed
over), and per-placement-state "gateway_placed_<state>" — the
rolling-restart drill asserts "gateway_placed_warming" and
"gateway_placed_draining" stay zero.

The DURABLE STATE plane (coconut_tpu/state/, PR 17) reports the
journal: "wal_appends" (records framed into a WAL) vs "wal_fsyncs"
(fdatasync calls — the gap between the two IS the group-commit
amortization, one sync per engine batch rather than per lane),
"wal_torn_tails" (torn trailing frames truncated on open — exactly
once per torn crash), "wal_replayed_records" (records re-applied from
segments on open), "wal_segments_rotated" (bounded-rotation events);
the store: "state_records_applied" (in-memory applies, local + remote),
"state_snapshots" / "state_snapshot_loads" / "state_snapshot_corrupt"
(a CRC-failed snapshot is quarantined `.corrupt` and the store rebuilds
from the WAL — degrade, never trust), "state_compactions"
(snapshot+WAL-truncate cycles); anti-entropy: "state_antientropy_pulls"
(gap pages pulled from peers), "state_antientropy_dropped" (pulls
suppressed by injected partition chaos), "state_replicator_errors"
(pull-loop failures — a dead peer is survivable, another peer or a
later sweep serves the gap), "gateway_state_pulls" (MSG_STATE_PULL
requests served — also while DRAINING: state transfer is how facts
escape a dying replica); and the nullifier set: "nullifier_commits"
(accepted shows durably journaled BEFORE their futures resolve),
"nullifier_double_spends" (replays rejected with DoubleSpendError),
"nullifier_probe_hits" (device-probe pre-verify hits),
"nullifier_probe_errors" (advisory probe failures — detection degrades
to commit time, never admits a double-spend), "nullifier_commit_errors"
(WAL-append failures that turned would-be accepts into
TransientBackendError: no resolve without durability),
"gateway_tenant_store_errors" / "dead_letter_index_errors" /
"dead_letter_errors" (lazy-durability write failures in the adopted
subsystems, counted and survived), and "dead_letter_torn_lines"
(unparseable dead-letter JSONL lines skipped on read — a crash
mid-append tears at most the final line).

The APPLICATION SCENARIO layer (coconut_tpu/scenarios/, PR 19) reports
under "scenario_*": "scenario_started" (workflows admitted by the
population driver) and one terminal counter per outcome —
"scenario_completed", "scenario_rejected" (EXPECTED typed rejections:
petition re-sign / e-cash double-spend, the protections firing),
"scenario_retry_exhausted", "scenario_deadline", "scenario_failed"
(unattributed errors — the acceptance bar is zero), and
"scenario_cancelled" (drain-cancelled runs — dangling futures, also
zero on a clean drain); every started workflow lands in EXACTLY ONE of
these, so started == the terminal sum is the no-lost-workflow check.
Plus "scenario_retries" (typed-transient step re-submissions),
"scenario_deferred" (arrivals refused by the bounded in-flight
window), "scenario_thinking" (arrivals skipped because the sampled
user was busy or in think-time), "scenario_hook_errors" (terminal-hook
exceptions contained), and "scenario_elastic_tick_errors" (elastic
controller ticks that raised — sizing degrades, the run continues).
The breaker journal (serve/health.py + ExecutionEngine
.attach_health_journal, PR 19) adds "health_journal_errors": journal
writes that raised inside a state transition — durability degrades to
in-memory, the transition itself never fails.

THREAD SAFETY: the serving layer is the first multi-threaded writer
(admission happens on client threads while the supervisor thread settles
batches), so every mutation and `snapshot()` runs under one module lock —
the bare defaultdict updates this module started with race under free
threading. Still zero-cost when unused: no background threads, no deps.

Histograms are bounded: `observe(name, seconds)` keeps a fixed-size window
of the most recent samples (plus exact count/total/max over the full run),
so a million-request serving run holds kilobytes, not a sample per request.
Percentiles in `snapshot()` are therefore over the retained window — recent
behavior, which is what an SLO monitor wants anyway.

Request-scoped observability is separate but joins here: while tracing is
enabled (coconut_tpu/obs, COCONUT_TRACE=1) `snapshot()` embeds a
"trace_stages" section — per-span-name count/total/mean, the queue-wait /
coalesce / encode / device / demux breakdown that separates "slow device"
from "slow batcher" — via `register_provider`, so this module never
imports obs (providers are injected, not imported).

While a `jax.profiler` session collects, obs.trace bridges every stage
span into the profiler trace as a "coconut/<ns>.<span>" annotation and
observes its duration in the "bridge_<ns>_<span>_s" histogram (e.g.
"bridge_issue_sign_s", "bridge_stream_encode_s") — the same stages a
host-side reader sees without parsing the trace. Device time itself
comes only from the profiler trace: `python3 -m benchmark.run ...
--trace 1` reduces it per program and per top-level op
(benchmark/trace.py); host-side phases are what these timers capture.
"""

import threading
import time
from collections import defaultdict, deque
from contextlib import contextmanager

_lock = threading.RLock()
_timers = defaultdict(float)
_counts = defaultdict(int)
_hists = {}
_gauges = {}
_providers = {}  # snapshot section name -> zero-arg callable

# per-histogram retained-sample window (memory bound; count/total/max stay
# exact over the full run)
HIST_WINDOW = 4096


@contextmanager
def timer(name):
    """Accumulate wall-clock seconds under `name`
    (e.g. "encode", "kernel", "readback")."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        with _lock:
            _timers[name] += dt


def count(name, n=1):
    """Add n to the counter `name` (e.g. "verifies", "transfer_bytes")."""
    with _lock:
        _counts[name] += n


def get_count(name):
    """Current value of counter `name` (0 if never counted)."""
    with _lock:
        return _counts.get(name, 0)


def counters_with_prefix(prefix):
    """{name: value} for every counter whose name starts with `prefix` —
    how the serving report reads a whole per-device family
    ("serve_dev<d>_dispatches") without enumerating device ids."""
    with _lock:
        return {k: v for k, v in _counts.items() if k.startswith(prefix)}


def timers_with_prefix(prefix):
    """{name: seconds} for every timer whose name starts with `prefix`
    (the per-device busy-time family)."""
    with _lock:
        return {k: v for k, v in _timers.items() if k.startswith(prefix)}


def set_gauge(name, value):
    """Set the point-in-time gauge `name` (e.g. "serve_queue_depth", a
    device executor's current load): last-write-wins, reported verbatim
    by snapshot() under "gauges" — unlike counters these go DOWN."""
    with _lock:
        _gauges[name] = value


def get_gauge(name, default=None):
    with _lock:
        return _gauges.get(name, default)


def observe(name, seconds):
    """Record one sample in the bounded histogram `name` (e.g.
    "serve_latency_s"). Keeps the most recent HIST_WINDOW samples for
    percentile readout plus exact count/total/max over the full run."""
    with _lock:
        h = _hists.get(name)
        if h is None:
            h = _hists[name] = {
                "count": 0,
                "total": 0.0,
                "max": 0.0,
                "window": deque(maxlen=HIST_WINDOW),
            }
        h["count"] += 1
        h["total"] += seconds
        if seconds > h["max"]:
            h["max"] = seconds
        h["window"].append(seconds)


def percentile(samples, q):
    """q-th percentile (q in [0, 100]) of `samples` by the nearest-rank
    method. Tiny-window behavior is PINNED, not emergent:

      n == 0  ->  None (there is no sample to report — never a fabricated
                  zero);
      n == 1  ->  the single sample, for EVERY q including 0 and 100;
      q outside [0, 100] -> ValueError (previously q=-5 silently read the
                  min and q=200 the max — a caller bug masquerading as a
                  statistic).

    Small-n honest in general: p99 of 10 samples is the max, not an
    interpolated fiction."""
    if not 0.0 <= q <= 100.0:
        raise ValueError("percentile q must be in [0, 100] (got %r)" % (q,))
    if not samples:
        return None
    import math

    s = sorted(samples)
    rank = max(0, min(len(s) - 1, math.ceil(q / 100.0 * len(s)) - 1))
    return s[rank]


def percentile_summary(samples, qs=(50, 95, 99)):
    """{"p50": ..., ...} nearest-rank readout with the tiny-window policy
    of `percentile` made structural: n=0 returns an EMPTY dict (absent
    keys, not None-or-zero values), n=1 returns the single sample under
    every requested quantile."""
    if not samples:
        return {}
    return {"p%g" % q: percentile(samples, q) for q in qs}


def _hist_readout(h):
    window = list(h["window"])
    n = h["count"]
    ps = percentile_summary(window)
    return {
        "count": n,
        "mean_s": round(h["total"] / n, 6) if n else None,
        "p50_s": round(ps["p50"], 6) if ps else None,
        "p95_s": round(ps["p95"], 6) if ps else None,
        "p99_s": round(ps["p99"], 6) if ps else None,
        "max_s": round(h["max"], 6),
    }


def hist_totals(name):
    """(count, total_seconds) of histogram `name` over the FULL run —
    exact, not window-bounded. (0, 0.0) if nothing was observed. The
    RPC loadgen reads deltas of these to split client-observed latency
    into engine time vs wire overhead (rpc_overhead_s)."""
    with _lock:
        h = _hists.get(name)
        if h is None:
            return 0, 0.0
        return h["count"], h["total"]


def register_provider(name, fn):
    """Register a zero-arg callable whose result snapshot() embeds under
    `name` — how obs.trace contributes the per-stage span breakdown
    without this module importing it."""
    with _lock:
        _providers[name] = fn


def unregister_provider(name):
    with _lock:
        _providers.pop(name, None)


def snapshot():
    """{"timers_s": {...}, "counters": {...}[, "histograms": {...}]
    [, <provider sections>]} — current totals; histogram readouts
    (count / mean / p50 / p95 / p99 / max over the retained window)
    appear once anything has been observe()d; provider sections (e.g.
    "trace_stages" while tracing is enabled) appear while registered and
    non-empty."""
    with _lock:
        snap = {
            "timers_s": {k: round(v, 6) for k, v in sorted(_timers.items())},
            "counters": dict(sorted(_counts.items())),
        }
        if _hists:
            snap["histograms"] = {
                k: _hist_readout(h) for k, h in sorted(_hists.items())
            }
        if _gauges:
            snap["gauges"] = dict(sorted(_gauges.items()))
        providers = list(_providers.items())
    # provider callables run OUTSIDE the lock (they may take their own)
    for name, fn in providers:
        section = fn()
        if section:
            snap[name] = section
    return snap


def reset():
    with _lock:
        _timers.clear()
        _counts.clear()
        _hists.clear()
        _gauges.clear()


def rate(counter, timer_name):
    """counter / timer seconds, or None if either is missing/zero."""
    with _lock:
        t = _timers.get(timer_name)
        c = _counts.get(counter)
    if not t or not c:
        return None
    return c / t
