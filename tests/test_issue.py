"""Threshold-issuance suite (ISSUE 10): quorum fan-out, first-t-of-n
aggregation, straggler hedging, corrupt-partial attribution, and the
share-id validation satellites.

Economics mirror tests/test_serve.py: the quorum/hedge mechanics run on
STUB signers and a stub minter with injected clocks — resolution order is
proven by gating per-authority events and ADVANCING a fake clock, never
by sleeping in an assert (`_wait` spins on millisecond polls only for the
service's own thread handoffs). The real-crypto end-to-end tests at the
bottom run the full 5-authority t=3 pool with injected crash/hang/corrupt
faults on small parameters and verify every minted credential."""

import threading
import time
from types import SimpleNamespace

import pytest

from coconut_tpu import metrics
from coconut_tpu.errors import (
    GeneralError,
    QuorumUnreachableError,
    TransientBackendError,
)
from coconut_tpu.faults import FaultyBackend, InjectedCrash
from coconut_tpu.issue import (
    HedgePolicy,
    HedgeScheduler,
    IssuanceService,
    QuorumTracker,
    SigningAuthority,
)
from coconut_tpu.issue.quorum import Fanout
from coconut_tpu.obs import trace as otrace
from coconut_tpu.serve import health as _health

pytestmark = pytest.mark.issue


# --- stub world ------------------------------------------------------------


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class StubSign:
    """Stub authority backend: one opaque partial token per request,
    tagged with the share it was 'signed' under."""

    def __init__(self):
        self.calls = 0

    def batch_blind_sign(self, sig_requests, sigkey, params):
        self.calls += 1
        return [("partial", sigkey, req) for req in sig_requests]


class GatedSign(StubSign):
    """Blocks inside the sign until released — the test controls partial
    ARRIVAL ORDER, which is what first-t-wins resolves on."""

    def __init__(self):
        super().__init__()
        self.entered = threading.Event()
        self.release = threading.Event()

    def batch_blind_sign(self, sig_requests, sigkey, params):
        self.entered.set()
        assert self.release.wait(10.0), "gate never released"
        return super().batch_blind_sign(sig_requests, sigkey, params)


class FailingSign(StubSign):
    def batch_blind_sign(self, sig_requests, sigkey, params):
        raise TransientBackendError("injected sign fault")


class CrashingSign(StubSign):
    def batch_blind_sign(self, sig_requests, sigkey, params):
        raise InjectedCrash("injected authority crash")


class StubMinter:
    """Crypto-free minter: aggregation records the winning subset on the
    'credential'; `corrupt_ids` makes any subset containing them fail the
    release gate, with per-partial attribution naming exactly them."""

    def __init__(self, corrupt_ids=()):
        self.corrupt_ids = set(corrupt_ids)
        self.minted_subsets = []

    def unblind(self, blind_rows, sks):
        return blind_rows

    def aggregate(self, subset, sig_rows):
        self.minted_subsets.append(tuple(subset))
        return [
            SimpleNamespace(subset=tuple(subset), row=list(row))
            for row in sig_rows
        ]

    def verify(self, creds, messages_list, subset):
        ok = not any(i in self.corrupt_ids for i in subset)
        return [ok] * len(creds)

    def verify_partial(self, signer_id, sig, messages):
        return signer_id not in self.corrupt_ids


def _signers(n):
    return [
        SimpleNamespace(
            id=i + 1, sigkey="sk%d" % (i + 1), verkey="vk%d" % (i + 1)
        )
        for i in range(n)
    ]


def _svc(n=5, t=3, backends=None, minter=None, clk=None, **kw):
    clk = clk if clk is not None else FakeClock()
    backends = backends if backends is not None else [StubSign() for _ in range(n)]
    kw.setdefault("watchdog_interval_s", None)
    kw.setdefault(
        "watchdog",
        _health.Watchdog(
            clock=clk, k=6.0, min_timeout_s=1.0, initial_timeout_s=5.0
        ),
    )
    kw.setdefault(
        "hedge",
        HedgePolicy(k=3.0, alpha=1.0, initial_delay_s=100.0, min_delay_s=0.0),
    )
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_wait_ms", 2.0)
    svc = IssuanceService(
        _signers(n),
        None,
        t,
        backends=backends,
        minter=minter if minter is not None else StubMinter(),
        clock=clk,
        **kw,
    )
    return svc, clk, backends


def _wait(pred, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, "timed out waiting for " + msg
        time.sleep(0.001)


def _submit_batch(svc, n=2):
    """Submit n orders (n = max_batch triggers an immediate full flush)
    and return their futures."""
    return [
        svc.submit("req%d" % i, ["m%d" % i], "esk%d" % i) for i in range(n)
    ]


def _open_fanout(svc):
    _wait(lambda: svc._tracker.outstanding(), msg="fan-out to open")
    return svc._tracker.outstanding()[0]


@pytest.fixture(autouse=True)
def _fresh_metrics():
    metrics.reset()
    yield
    metrics.reset()


# --- hedge policy / scheduler (pure, fake-clock) ----------------------------


def test_hedge_policy_ema_fold_and_budget_clamp():
    p = HedgePolicy(k=3.0, alpha=0.5, initial_delay_s=9.0, min_delay_s=0.1,
                    max_delay_s=2.0)
    assert p.ema("a") is None
    assert p.budget("a") == 9.0  # no EMA yet: don't hedge around a compile
    p.observe("a", 0.2)
    assert p.ema("a") == pytest.approx(0.2)
    p.observe("a", 0.4)
    assert p.ema("a") == pytest.approx(0.3)  # 0.5*0.4 + 0.5*0.2
    assert p.budget("a") == pytest.approx(0.9)  # k * ema
    p.observe("a", 10.0)
    assert p.budget("a") == 2.0  # clamped to max_delay_s
    p.observe("b", 1e-9)
    assert p.budget("b") == pytest.approx(0.1)  # clamped to min_delay_s
    with pytest.raises(ValueError):
        HedgePolicy(k=0.0)


def test_hedge_scheduler_due_pops_once_and_cancel_drops_fanout():
    clk = FakeClock()
    sched = HedgeScheduler(clock=clk)
    f1 = SimpleNamespace(fid=1)
    f2 = SimpleNamespace(fid=2)
    sched.begin(f1, "a", 0.5, now=0.0)
    sched.begin(f1, "b", 2.0, now=0.0)
    sched.begin(f2, "a", 0.5, now=0.0)
    assert sched.outstanding() == 3
    clk.advance(1.0)
    due = sched.due()
    assert {(f.fid, label) for f, label, _ in due} == {(1, "a"), (2, "a")}
    assert due[0][2] == pytest.approx(0.5)  # overdue_s
    assert sched.due() == []  # popped exactly once
    assert sched.cancel(1) == 1  # drops f1's remaining "b" timer
    sched.end(2, "a")  # already popped: no-op
    assert sched.outstanding() == 0


# --- quorum tracker (pure) --------------------------------------------------


def _fanout(fid=0, n_requests=0):
    reqs = [
        SimpleNamespace(future=SimpleNamespace(done=lambda: False))
        for _ in range(n_requests)
    ]
    return Fanout(fid, reqs, ["sr"] * n_requests, [["m"]] * n_requests,
                  ["sk"] * n_requests, otrace.NOOP, 0.0)


def test_tracker_resolves_exactly_once_on_tth_row():
    clk = FakeClock()
    tr = QuorumTracker(3, clock=clk)
    f = _fanout(n_requests=2)
    tr.open(f)
    clk.advance(0.25)
    assert tr.record(f, 4, ["p", "p"]) is None
    assert tr.record(f, 1, ["p", "p"]) is None
    subset = tr.record(f, 5, ["p", "p"])
    assert subset == [4, 1, 5]  # arrival order, not id order
    assert f.quorum_at == 0.25
    # the quorum-wait histogram observed exactly once
    assert metrics.snapshot()["histograms"]["issue_quorum_wait_s"]["count"] == 1
    # a 4th row while minting does NOT re-resolve
    assert tr.record(f, 2, ["p", "p"]) is None
    assert f.order == [4, 1, 5, 2]


def test_tracker_discards_duplicate_and_stale_rows():
    tr = QuorumTracker(2, clock=FakeClock())
    f = _fanout(n_requests=3)
    tr.open(f)
    assert tr.record(f, 1, ["a", "b", "c"]) is None
    assert tr.record(f, 1, ["a", "b", "c"]) is None  # duplicate authority
    assert metrics.get_count("issue_partials_discarded") == 3
    tr.close_fanout(f)  # resolved: everything after is stale
    assert tr.record(f, 2, ["a", "b", "c"]) is None
    assert metrics.get_count("issue_partials_discarded") == 6
    assert tr.outstanding() == []


def test_tracker_drop_partials_and_next_subset():
    tr = QuorumTracker(2, clock=FakeClock())
    f = _fanout(n_requests=1)
    tr.open(f)
    tr.record(f, 1, ["a"])
    assert tr.record(f, 2, ["b"]) == [1, 2]
    tr.drop_partials(f, {1})  # attribution: authority 1's row is corrupt
    assert tr.next_subset(f) is None  # only one clean row: wait
    assert f.minting is False  # claim released for the next arrival
    assert tr.record(f, 3, ["c"]) == [2, 3]  # skips the dropped row


# --- service: first-t-wins, stale guard -------------------------------------


def test_first_t_wins_resolution_order_and_late_rows_discarded():
    gates = [GatedSign() for _ in range(5)]
    svc, clk, _ = _svc(backends=gates)
    with svc:
        futs = _submit_batch(svc, 2)
        f = _open_fanout(svc)
        for g in gates:
            assert g.entered.wait(5.0)  # fanned out to ALL five
        # release authorities 2, 4, 5 in that order: the quorum is the
        # FIRST three distinct rows, in arrival order
        for sid in (2, 4, 5):
            gates[sid - 1].release.set()
            _wait(lambda: sid in f.partials, msg="row %d" % sid)
        creds = [fut.result(timeout=5.0) for fut in futs]
        assert all(c.subset == (2, 4, 5) for c in creds)
        assert metrics.get_count("issue_minted") == 2
        # stragglers 1 and 3 land late: discarded by the stale guard,
        # never re-minted
        for sid in (1, 3):
            gates[sid - 1].release.set()
        _wait(
            lambda: metrics.get_count("issue_partials_discarded") == 4,
            msg="late rows discarded",
        )
        assert svc.minter.minted_subsets == [(2, 4, 5)]
    assert metrics.get_count("issue_sign_skips") == 0


def test_ready_gate_holds_batch_until_quorum_capacity():
    # with every authority quarantined there is no quorum capacity: the
    # coalesced batch must stay IN the queue, not fan out to nobody
    svc, clk, _ = _svc()
    for auth in svc._authorities:
        svc._health_of(auth.label).on_crash("made unavailable")
    with svc:
        fut = svc.submit("req", ["m"], "esk")
        clk.advance(1.0)
        svc.kick()
        time.sleep(0.05)
        assert svc.depth() == 1  # held by the ready gate
        assert not fut.done()
        # capacity returns: cooldown elapses, probation probes revive the
        # pool and the batch fans out
        clk.advance(10.0)
        svc.health_tick()
        assert fut.result(timeout=5.0).subset is not None
    assert metrics.get_count("issue_minted") == 1


# --- service: hedging -------------------------------------------------------


def test_hedge_fires_at_k_ema_cancels_on_quorum():
    gates = [GatedSign() for _ in range(6)]
    svc, clk, _ = _svc(n=6, t=3, backends=gates)
    spare = svc._authorities[5]
    # authority 6 is BUSY at fan-out time (mid-sign on one dummy fan-out,
    # two more queued): can_accept() is False, so the fan-out targets
    # only 1..5 and 6 is the hedge spare
    dummies = [_fanout(fid=-1), _fanout(fid=-2), _fanout(fid=-3)]
    spare._inbox.extend(dummies)
    # prime every authority's sign EMA: budget = k * 0.1 = 0.3s
    for auth in svc._authorities:
        svc.hedge_policy.observe(auth.label, 0.1)
    with svc:
        assert gates[5].entered.wait(5.0)  # spare stuck on the dummy
        futs = _submit_batch(svc, 2)
        f = _open_fanout(svc)
        assert set(f.targets) == {"1", "2", "3", "4", "5"}
        for sid in (1, 2):
            gates[sid - 1].release.set()
            _wait(lambda: sid in f.partials, msg="row %d" % sid)
        # authorities 3, 4, 5 straggle past k x EMA: the FIRST due hedge
        # takes the only spare; the other two find none
        clk.advance(0.5)
        svc.health_tick()
        assert metrics.get_count("issue_hedges") == 1
        assert metrics.get_count("issue_hedge_no_spare") == 2
        assert "6" in f.targets
        assert spare.queued() == 3  # two queued dummies + the hedged fan-out
        # quorum completes via straggler 3: the hedge loses the race and
        # its queued sign is CANCELED, never run
        gates[2].release.set()
        creds = [fut.result(timeout=5.0) for fut in futs]
        assert all(c.subset == (1, 2, 3) for c in creds)
        _wait(
            lambda: metrics.get_count("issue_cancelled_signs") == 1,
            msg="hedge cancel",
        )
        assert svc._hedges.outstanding() == 0
        # unblock the spare's dummies and the remaining stragglers
        for g in gates:
            g.release.set()
        _wait(
            lambda: metrics.get_count("issue_partials_discarded") == 4,
            msg="late rows discarded",
        )
    assert svc.minter.minted_subsets == [(1, 2, 3)]


# --- service: corrupt-partial attribution -----------------------------------


def test_corrupt_partial_attribution_quarantines_only_culprit():
    gates = [GatedSign() for _ in range(5)]
    minter = StubMinter(corrupt_ids={2})
    svc, clk, _ = _svc(
        backends=gates,
        minter=minter,
        health_policy=_health.HealthPolicy(suspect_after=1, quarantine_after=1),
    )
    with svc:
        futs = _submit_batch(svc, 2)
        f = _open_fanout(svc)
        for sid in (1, 2, 3):
            gates[sid - 1].release.set()
            _wait(lambda: sid in f.partials, msg="row %d" % sid)
        # first mint round used (1, 2, 3) and failed the release gate;
        # attribution names authority 2 ONLY, drops its row, quarantines
        # it, and the fan-out waits for a clean 3rd row
        _wait(
            lambda: metrics.get_count("issue_corrupt_partials") == 1,
            msg="attribution",
        )
        assert svc._health_of("2").state == _health.QUARANTINED
        assert all(
            svc._health_of(a.label).state == _health.HEALTHY
            for a in svc._authorities
            if a.label != "2"
        )
        assert not futs[0].done()  # nothing released from the bad round
        gates[3].release.set()  # authority 4's clean row completes quorum
        creds = [fut.result(timeout=5.0) for fut in futs]
        assert all(c.subset == (1, 3, 4) for c in creds)
        gates[4].release.set()
    assert minter.minted_subsets == [(1, 2, 3), (1, 3, 4)]
    assert metrics.get_count("issue_minted") == 2
    assert metrics.get_count("issue_quarantined") == 1
    # no corrupt credential was ever released
    assert all(2 not in c.subset for c in creds)


# --- service: faults, crashes, hangs ----------------------------------------


def test_sign_fault_marks_target_failed_and_quorum_survives():
    # survivors are GATED: were they free-running stubs, the quorum could
    # resolve before authority 1's sign even pops, the pop would be
    # skipped (first-t-wins), and the fault would never fire
    gates = [GatedSign() for _ in range(4)]
    backends = [FailingSign()] + gates
    svc, clk, _ = _svc(backends=backends)
    with svc:
        futs = _submit_batch(svc, 2)
        _wait(
            lambda: svc._health_of("1").state == _health.SUSPECT,
            msg="sign fault noted",
        )
        for g in gates:
            g.release.set()
        creds = [fut.result(timeout=5.0) for fut in futs]
        assert all(1 not in c.subset for c in creds)
    assert metrics.get_count("issue_minted") == 2
    assert svc._health_of("1").state == _health.SUSPECT


def test_authority_crash_is_contained_and_quorum_survives():
    # gated survivors, same reason as the sign-fault test above: the
    # crash must land before the quorum can resolve and skip it
    gates = [GatedSign() for _ in range(4)]
    backends = [CrashingSign()] + gates
    svc, clk, _ = _svc(backends=backends)
    with svc:
        futs = _submit_batch(svc, 2)
        _wait(
            lambda: metrics.get_count("issue_authority_crashes") == 1,
            msg="crash containment",
        )
        for g in gates:
            g.release.set()
        creds = [fut.result(timeout=5.0) for fut in futs]
        assert all(1 not in c.subset for c in creds)
    assert metrics.get_count("issue_minted") == 2
    assert svc._health_of("1").state == _health.QUARANTINED
    assert not svc._authorities[0].has_worker()


def test_quorum_unreachable_is_typed_and_loud():
    # three of five authorities crash: 2 live < t=3 after the fan-out's
    # failed targets are excluded, and no spare exists
    backends = [CrashingSign(), CrashingSign(), CrashingSign(),
                StubSign(), StubSign()]
    svc, clk, _ = _svc(backends=backends)
    with svc:
        futs = _submit_batch(svc, 2)
        excs = [fut.exception(timeout=5.0) for fut in futs]
    assert all(isinstance(e, QuorumUnreachableError) for e in excs)
    assert excs[0].needed == 3
    assert "retry" in str(excs[0])
    assert metrics.get_count("issue_quorum_unreachable") >= 1
    assert metrics.get_count("issue_minted") == 0


def test_watchdog_expires_hung_sign_quarantines_and_probation_revives():
    gates = [GatedSign() for _ in range(5)]
    svc, clk, _ = _svc(
        backends=gates,
        health_policy=_health.HealthPolicy(probe_after_s=5.0),
    )
    with svc:
        futs = _submit_batch(svc, 2)
        f = _open_fanout(svc)
        assert gates[0].entered.wait(5.0)
        for sid in (2, 3, 4):  # quorum resolves; authority 1 stays hung
            gates[sid - 1].release.set()
            _wait(lambda: sid in f.partials, msg="row %d" % sid)
        [fut.result(timeout=5.0) for fut in futs]
        gates[4].release.set()
        _wait(  # authority 5's late row lands (its watchdog entry ends)
            lambda: metrics.get_count("issue_partials_discarded") == 2,
            msg="authority 5 settling",
        )
        # the hung sign outlives its watchdog budget (initial 5s): the
        # stuck worker is abandoned and the authority quarantined even
        # though the fan-out already resolved without it
        clk.advance(6.0)
        svc.health_tick()
        assert metrics.get_count("issue_watchdog_timeouts") == 1
        assert svc._health_of("1").state == _health.QUARANTINED
        assert not svc._authorities[0].has_worker()
        # the abandoned worker finally returns: its row is STALE (the
        # generation moved on), discarded without touching health
        gates[0].release.set()
        _wait(
            lambda: metrics.get_count("issue_partials_discarded") == 4,
            msg="stale row discarded",
        )
        assert svc._health_of("1").state == _health.QUARANTINED
        # cooldown elapses -> probation respawns a fresh worker and the
        # pool mints with all five again
        clk.advance(10.0)
        svc.health_tick()
        assert svc._authorities[0].has_worker()
        futs2 = _submit_batch(svc, 2)
        assert all(fut.result(timeout=5.0) for fut in futs2)
    assert metrics.get_count("issue_minted") == 4


def test_disabled_mint_path_never_allocates_a_span(monkeypatch):
    """Fan-out, five signs, the mint round and the release with the ring
    and the profiler off: no Span and no profiler bridge is built."""

    def boom(*a, **k):
        raise AssertionError("span allocated while tracing is off")

    monkeypatch.setattr(otrace, "Span", boom)
    monkeypatch.setattr(otrace, "_Bridged", boom)
    svc, clk, _ = _svc()
    with svc:
        futs = _submit_batch(svc)
        creds = [f.result(10.0) for f in futs]
    assert all(c.subset for c in creds)
    assert metrics.get_count("issue_minted") == 2


def test_join_survives_abandon_while_joining():
    """abandon() drops the worker handle while a join() waits on it: the
    join returns False (the stale worker is still mid-sign) instead of
    raising, and a later join has nothing left to wait on."""
    entered, gate = threading.Event(), threading.Event()

    class Svc:
        def _sign_fanout(self, auth, fanout, gen):
            entered.set()
            gate.wait(10.0)

    auth = SigningAuthority(Svc(), _signers(1)[0], backend=StubSign())
    auth.start()
    auth.submit(SimpleNamespace(fid=1))
    assert entered.wait(10.0)
    out = {}

    def joiner():
        try:
            out["joined"] = auth.join(timeout=2.0)
        except Exception as e:  # the race this pins
            out["error"] = e

    t = threading.Thread(target=joiner)
    t.start()
    time.sleep(0.2)  # the joiner is inside Thread.join
    assert auth.abandon() == []
    t.join(10.0)
    gate.set()
    assert out == {"joined": False}
    assert auth.join() is True and not auth.has_worker()


def test_drain_fails_unreachable_fanouts_no_dangling_futures():
    # t=3 of n=3 but one authority never returns: the fan-out can never
    # reach quorum — drain must fail its futures loudly, never hang them
    gates = [GatedSign() for _ in range(3)]
    svc, clk, _ = _svc(n=3, t=3, backends=gates)
    svc.start()
    futs = _submit_batch(svc, 2)
    f = _open_fanout(svc)
    for sid in (1, 2):
        gates[sid - 1].release.set()
        _wait(lambda: sid in f.partials, msg="row %d" % sid)
    assert svc.drain(timeout=0.5) is False  # the hung join times out
    for fut in futs:
        assert fut.done()
        assert isinstance(fut.exception(0), QuorumUnreachableError)
    assert metrics.get_count("issue_quorum_unreachable") >= 1
    gates[2].release.set()  # unblock the worker thread


def test_shutdown_without_drain_refuses_queued_backlog():
    # never started: the queued backlog is refused typed, not signed
    svc, clk, _ = _svc()
    fut = svc.submit("req", ["m"], "esk")
    svc.shutdown(drain=False, timeout=2.0)
    from coconut_tpu.errors import ServiceClosedError

    assert isinstance(fut.exception(0), ServiceClosedError)
    assert metrics.get_count("issue_cancelled") == 1
    with pytest.raises(ServiceClosedError):
        svc.submit("late", ["m"], "esk")


# --- signature.py satellites: share-id validation + batched aggregation -----


def _fake_partials(ids):
    sig = SimpleNamespace(sigma_1="h", sigma_2="s")
    return [(i, sig) for i in ids]


def _fake_verkeys(ids):
    vk = SimpleNamespace(X_tilde="x", Y_tilde=["y"])
    return [(i, vk) for i in ids]


def test_signature_aggregate_rejects_duplicate_ids():
    from coconut_tpu.signature import Signature

    with pytest.raises(GeneralError) as ei:
        Signature.aggregate(3, _fake_partials([1, 2, 2]))
    assert "duplicate signer ids" in str(ei.value)
    assert "[2]" in str(ei.value)  # names the offending id


def test_signature_aggregate_rejects_out_of_range_ids():
    from coconut_tpu.signature import Signature

    for bad in ([0, 1, 2], [-3, 1, 2], [1.5, 1, 2]):
        with pytest.raises(GeneralError) as ei:
            Signature.aggregate(3, _fake_partials(bad))
        assert "out-of-range signer ids" in str(ei.value)


def test_verkey_aggregate_rejects_duplicate_and_bad_ids():
    from coconut_tpu.signature import Verkey

    with pytest.raises(GeneralError) as ei:
        Verkey.aggregate(2, _fake_verkeys([4, 4]))
    assert "duplicate signer ids" in str(ei.value) and "[4]" in str(ei.value)
    with pytest.raises(GeneralError) as ei:
        Verkey.aggregate(2, _fake_verkeys([0, 3]))
    assert "out-of-range signer ids" in str(ei.value) and "[0]" in str(
        ei.value
    )


def test_batch_aggregate_validates_every_request():
    from coconut_tpu.signature import batch_aggregate

    assert batch_aggregate(3, []) == []
    with pytest.raises(GeneralError):
        batch_aggregate(3, [_fake_partials([1, 2, 3]),
                            _fake_partials([1, 1, 2])])


# --- real crypto ------------------------------------------------------------


@pytest.fixture(scope="module")
def issue_world():
    """Small real-crypto world: 2-message params, 3-of-5 SSS keygen, and
    a pool of blind-sign orders (request, messages, elgamal sk)."""
    from coconut_tpu.elgamal import elgamal_keygen
    from coconut_tpu.keygen import trusted_party_SSS_keygen
    from coconut_tpu.params import Params
    from coconut_tpu.signature import SignatureRequest
    from coconut_tpu.sss import rand_fr

    params = Params.new(2, b"test-issue")
    _, _, signers = trusted_party_SSS_keygen(3, 5, params)

    def order():
        msgs = [rand_fr(), rand_fr()]
        sk, pk = elgamal_keygen(params.ctx.sig, params.g)
        req, _ = SignatureRequest.new(msgs, 1, pk, params)
        return req, msgs, sk

    return SimpleNamespace(params=params, signers=signers, order=order)


def _agg_vk(world, ids):
    from coconut_tpu.signature import Verkey

    return Verkey.aggregate(
        3,
        [(s.id, s.verkey) for s in world.signers if s.id in ids],
        ctx=world.params.ctx,
    )


def test_batch_aggregate_bit_identical_to_sequential(issue_world):
    """The batched [B, t] Lagrange MSM must equal per-credential
    Signature.aggregate, and ANY t-subset must interpolate to the SAME
    credential (subset-independence is what makes first-t-wins sound)."""
    from coconut_tpu.signature import (
        BlindSignature,
        Signature,
        batch_aggregate,
        batch_unblind,
    )

    world = issue_world
    orders = [world.order() for _ in range(2)]
    partials = {}  # signer id -> per-order unblinded partial
    for s in world.signers:
        blind = [BlindSignature.new(req, s.sigkey, world.params)
                 for req, _, _ in orders]
        partials[s.id] = batch_unblind(
            blind, [sk for _, _, sk in orders], world.params.ctx
        )
    subsets = [(1, 2, 3), (2, 4, 5), (1, 3, 5)]
    creds_by_subset = []
    for subset in subsets:
        rows = [
            [(i, partials[i][b]) for i in subset] for b in range(len(orders))
        ]
        batched = batch_aggregate(3, rows, ctx=world.params.ctx)
        sequential = [Signature.aggregate(3, row, ctx=world.params.ctx)
                      for row in rows]
        assert batched == sequential  # bit-identical
        vk = _agg_vk(world, set(subset))
        assert all(
            c.verify(msgs, vk, world.params)
            for c, (_, msgs, _) in zip(batched, orders)
        )
        creds_by_subset.append(batched)
    # subset-independence: every t-subset interpolates the same signature
    for other in creds_by_subset[1:]:
        assert other == creds_by_subset[0]


def test_e2e_five_authorities_mint_through_crash_and_hang(issue_world):
    """The acceptance scenario: a 5-authority t=3 pool with one CRASHED
    and one HUNG authority still mints every credential, and each minted
    credential verifies under the Lagrange-aggregated verkey."""
    world = issue_world
    from coconut_tpu.backend import get_backend

    py = get_backend("python")
    backends = [
        py,
        FaultyBackend(py, crash_sign_on=(0,)),  # authority 2 crashes
        FaultyBackend(py, hang_sign_on=(0,), hang_max_s=30.0),  # 3 hangs
        py,
        py,
    ]
    svc = IssuanceService(
        world.signers,
        world.params,
        3,
        backend="python",
        backends=backends,
        max_batch=4,
        max_wait_ms=5.0,
    ).start()
    try:
        orders = [world.order() for _ in range(4)]
        futs = [svc.submit(req, msgs, sk) for req, msgs, sk in orders]
        creds = [fut.result(timeout=120.0) for fut in futs]
    finally:
        backends[2].hang_release.set()
        svc.drain(timeout=30.0)
    vk = _agg_vk(world, {1, 4, 5})
    assert all(
        c.verify(msgs, vk, world.params)
        for c, (_, msgs, _) in zip(creds, orders)
    )
    assert backends[1].crashes == 1
    assert metrics.get_count("issue_authority_crashes") == 1
    assert metrics.get_count("issue_minted") == 4
    assert svc._health_of("2").state == _health.QUARANTINED


def test_e2e_corrupt_partial_never_releases_bad_credential(issue_world):
    """Byzantine authority: one partial comes back with a flipped limb.
    The verify-before-release gate must catch it, attribution must name
    the culprit, and every released credential must still verify."""
    world = issue_world
    from coconut_tpu.backend import get_backend

    py = get_backend("python")
    gates = [GatedSign() for _ in range(2)]  # hold authorities 4, 5 back

    class GatedReal:
        """Delegate to the real signer only after release — pins the
        first-t subset to {1, 2, 3} deterministically."""

        def __init__(self, gate):
            self.gate = gate

        def batch_blind_sign(self, sig_requests, sigkey, params):
            assert self.gate.release.wait(60.0)
            from coconut_tpu.signature import batch_blind_sign

            return batch_blind_sign(sig_requests, sigkey, params, backend=py)

    backends = [
        py,
        FaultyBackend(py, corrupt_partial_on=(0,)),  # authority 2 corrupt
        py,
        GatedReal(gates[0]),
        GatedReal(gates[1]),
    ]
    svc = IssuanceService(
        world.signers,
        world.params,
        3,
        backend="python",
        backends=backends,
        max_batch=2,
        max_wait_ms=5.0,
        health_policy=_health.HealthPolicy(suspect_after=1, quarantine_after=1),
    ).start()
    try:
        orders = [world.order() for _ in range(2)]
        futs = [svc.submit(req, msgs, sk) for req, msgs, sk in orders]
        # the corrupt round happens on subset {1, 2, 3}; releasing
        # authority 4 lets the clean subset complete
        def _attributed():
            return metrics.get_count("issue_corrupt_partials") == 1

        _wait(_attributed, timeout=60.0, msg="corrupt-partial attribution")
        gates[0].release.set()
        creds = [fut.result(timeout=120.0) for fut in futs]
    finally:
        for g in gates:
            g.release.set()
        svc.drain(timeout=30.0)
    vk = _agg_vk(world, {1, 3, 4})
    assert all(
        c.verify(msgs, vk, world.params)
        for c, (_, msgs, _) in zip(creds, orders)
    )
    assert backends[1].corrupted_partials == 1
    assert metrics.get_count("issue_corrupt_partials") == 1
    assert svc._health_of("2").state == _health.QUARANTINED
    assert metrics.get_count("issue_minted") == 2


# --- mixed-workload loadgen -------------------------------------------------


def test_loadgen_mixed_workload_reports_issue_section():
    from coconut_tpu.serve import CredentialService, run_loadgen

    class VerifyStub:
        def batch_verify(self, sigs, msgs, vk, params):
            return [s.sigma_1 is not None and s.ok for s in sigs]

    vsvc = CredentialService(
        VerifyStub(), None, None, max_batch=4, max_wait_ms=1.0,
        watchdog_interval_s=None,
    ).start()
    isvc, _, _ = _svc(clk=time.monotonic, max_batch=4, max_wait_ms=1.0)
    isvc.start()
    try:
        cred = SimpleNamespace(sigma_1=1, sigma_2=1, ok=True)
        report = run_loadgen(
            vsvc,
            [(cred, [0], True)],
            duration_s=0.3,
            arrival="closed",
            concurrency=4,
            issue_service=isvc,
            issue_pool=[("req", ["m"], "esk")],
            issue_fraction=0.5,
        )
    finally:
        vsvc.drain(timeout=10.0)
        isvc.drain(timeout=10.0)
    assert report["issue_fraction"] == 0.5
    issue = report["issue"]
    assert issue["minted"] > 0 and report["completed"] > 0  # both workloads ran
    assert issue["dropped_futures"] == 0
    assert issue["mint_mismatches"] == 0
    assert issue["errors"] == 0
    assert issue["minted"] == metrics.get_count("issue_minted")
    assert report["verdict_mismatches"] == 0


def test_loadgen_issue_fraction_validation():
    from coconut_tpu.serve import run_loadgen

    with pytest.raises(ValueError):
        run_loadgen(None, [1], issue_fraction=0.5)  # no issue_service
    with pytest.raises(ValueError):
        run_loadgen(None, [1], issue_fraction=1.5, issue_service=object(),
                    issue_pool=[1])
