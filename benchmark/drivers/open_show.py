"""open_show: people showing credentials at a gate, arriving as an open
loop at a fixed rate, verified through `ProtocolEngine.submit_show_verify`
with the challenge recomputed by the verifier.

Traffic parameters: rate_per_s offered; pool_size show proofs made from
the seed and cycled, one in tampered_every with a revealed value the
holder does not have; max_wait_ms, the engine's coalescing deadline;
max_depth_batches, its admission bound in batches; warm_batches full
batches through the engine before the window; reference_sample proofs
(besides every tampered one) judged by the plain reference after it.

Arrivals: one fixed list of exponential gaps, scaled to the window and
shuffled by the seed, gives each request a due time (see `arrivals`). One
thread submits each request at its due time, or at once when it runs
late, and records how late it ran. A request's latency runs from its due
time to its future's done callback; `latency_p95_ms` is the 95th
percentile (nearest rank) over every request due in the window. After
the window each outstanding request is waited for up to drain_s. One
that is refused or fails counts as failed and one that never answers as
unanswered; both take the whole wait as their latency, and either makes
the run not correct.

Controls: "one_bool_per_batch" runs the program's RLC-combined show
verify and hands each batch's pairing verdict (ANDed with the lane's own
Schnorr bit) to every lane, without the bisection that attributes it.
"""

import random
import threading
import time

from .. import deploy
from ..harness import log
from ..stats import percentile

CONTROLS = ("one_bool_per_batch",)


def arrivals(rate, seconds, seed):
    """Due offsets (s) in [0, seconds) of round(rate * seconds) requests:
    one fixed set of exponential gaps, scaled to fill the window exactly,
    the same for every seed and in the seed's order. Every seed offers the
    same number of requests at the same rate; only the order of the gaps,
    and so where the bursts fall, changes."""
    n = max(1, round(rate * seconds))
    base = random.Random(0xA77)
    gaps = [base.expovariate(1.0) for _ in range(n)]
    scale = seconds / sum(gaps)
    random.Random(seed).shuffle(gaps)
    out, t = [], 0.0
    for g in gaps:
        out.append(t)
        t += g * scale
    return out


class OpenLoop:
    """Submits at due times and stamps completions. `clock` and `sleep`
    are injectable so the timing can be tested without waiting."""

    def __init__(self, submit, due, clock=time.perf_counter, sleep=time.sleep):
        self.submit, self.due = submit, due
        self.clock, self.sleep = clock, sleep
        self.late = []
        self.done_at = [None] * len(due)
        self.outcome = [None] * len(due)  # (ok, value) once settled
        self._left = len(due)
        self._cv = threading.Condition()

    def _settled(self, k, ok, value):
        t = self.clock()
        with self._cv:
            self.done_at[k] = t
            self.outcome[k] = (ok, value)
            self._left -= 1
            self._cv.notify_all()

    def run(self, t0):
        """Submit every request; due times are t0 + offset."""
        for k, off in enumerate(self.due):
            due = t0 + off
            now = self.clock()
            if now < due:
                self.sleep(due - now)
                now = self.clock()
            self.late.append(max(0.0, now - due))
            try:
                fut = self.submit(k)
            except Exception as e:  # refused at admission
                self._settled(k, False, e)
                continue
            fut.add_done_callback(lambda f, k=k: self._done(k, f))

    def _done(self, k, fut):
        try:
            self._settled(k, True, fut.result(timeout=0))
        except Exception as e:
            self._settled(k, False, e)

    def wait(self, timeout):
        end = self.clock() + timeout
        with self._cv:
            while self._left and self.clock() < end:
                self._cv.wait(max(0.0, min(1.0, end - self.clock())))

    def latencies(self, t0, horizon):
        """Per request: done - due; horizon - due where it never settled
        or settled with an error (a failure misses every limit)."""
        return [
            (d if o is not None and o[0] else horizon) - (t0 + off)
            for d, o, off in zip(self.done_at, self.outcome, self.due)
        ]


def run(run):
    from coconut_tpu.engine import ProtocolEngine

    cfg, tr = run.cfg, run.traffic
    if run.control is not None and run.control not in CONTROLS:
        raise ValueError("unknown control %r" % run.control)
    rng = random.Random(run.seed)
    dep = deploy.Deployment(cfg, rng)
    run.mark("keys")
    pool = deploy.show_pool(dep, rng, tr["pool_size"], tr["tampered_every"])
    run.mark("pool")
    batch = cfg["max_batch"]

    be = run.backend()
    if hasattr(be, "encode_show_verify_batch"):
        run.wrap(be, "encode_show_verify_batch", "encode")
    engine = ProtocolEngine(
        dep.signers, dep.params, dep.t,
        count_hidden=cfg["hidden_at_issue"],
        revealed_msg_indices=cfg["revealed_at_show"],
        vk=dep.vk, backend=be, max_batch=batch,
        max_wait_ms=tr["max_wait_ms"],
        max_depth=tr["max_depth_batches"] * batch,
        showv_mode="batched" if run.control else "exact",
    )
    prog = engine._showv
    run.wrap(prog, "assemble", "assemble")
    if run.control:
        _one_bool_per_batch(be)
    engine.start()

    def submit(k):
        proof, shown, *_ = pool[k % len(pool)]
        return engine.submit_show_verify(proof, shown)

    warm_wrong = 0
    try:
        for w in range(tr["warm_batches"]):
            ks = range(w * batch, (w + 1) * batch)
            futs = [submit(k) for k in ks]
            warm_wrong += sum(
                f.result(timeout=600) != pool[k % len(pool)][2]
                for k, f in zip(ks, futs)
            )
        run.mark("warm")
        due = arrivals(tr["rate_per_s"], run.seconds, run.seed)
        loop = OpenLoop(submit, due)
        with run.window():
            t0 = run.window_start
            loop.run(t0)
            left = run.remaining()
            if left > 0:
                time.sleep(left)
        backlog = sum(d is None for d in loop.done_at)
        loop.wait(tr["drain_s"])
        horizon = time.perf_counter()
    finally:
        engine.shutdown(drain=False, timeout=tr["drain_s"])

    lat = loop.latencies(t0, horizon)
    failed = sum(o is None or not o[0] for o in loop.outcome)
    unanswered = sum(o is None for o in loop.outcome)
    wrong = 0
    for k, o in enumerate(loop.outcome):
        if o is not None and o[0]:
            wrong += bool(o[1]) != pool[k % len(pool)][2]
    run.attempted = len(due)
    run.failed = failed
    run.e2e["latency_p95_ms"] = 1e3 * percentile(lat, 95)
    run.counts["engine_ns"] = "showv"
    run.counts["batches"] = run.counters.get("showv_batches", 0)
    third = max(1, len(lat) // 3)
    log(
        "generator requests=%d late_p50_ms=%.3f late_p99_ms=%.3f "
        "late_max_ms=%.3f backlog_at_close=%d latency_p50_ms=%.3f "
        "latency_p50_first_third_ms=%.3f latency_p50_last_third_ms=%.3f"
        % (
            len(due),
            1e3 * percentile(loop.late, 50),
            1e3 * percentile(loop.late, 99),
            1e3 * max(loop.late),
            backlog,
            1e3 * percentile(lat, 50),
            1e3 * percentile(lat[:third], 50),
            1e3 * percentile(lat[-third:], 50),
        )
    )
    disagree, checked = deploy.show_disagreements(
        dep, pool, random.Random(run.seed ^ 0x5EED), tr["reference_sample"]
    )
    log("reference proofs_checked=%d" % checked)
    run.check("unanswered_requests", unanswered, 0)
    run.check("failed_requests", failed - unanswered, 0)
    run.check("warm_verdict_mismatches", warm_wrong, 0)
    run.check("verdict_mismatches", wrong, 0)
    run.check("reference_disagreements", disagree, 0)


def _one_bool_per_batch(be):
    """The control: the combined show verify's batch pairing verdict
    given to every lane, with no bisection to attribute a failure."""
    combined = be.batch_show_verify_combined

    def fn(proofs, vk, params, revealed_msgs_list, challenges, rs=None,
           epoch=None):
        bits, pair_ok = combined(
            proofs, vk, params, revealed_msgs_list, challenges, rs, epoch
        )
        return [b and pair_ok for b in bits], True

    be.batch_show_verify_combined = fn
