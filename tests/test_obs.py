"""Observability suite: request-scoped tracing, the bridge of stage spans
into a jax.profiler trace, the fault flight recorder, and the metrics
percentile edge cases.

Economics mirror tests/test_serve.py: stub backends, injected clocks,
zero real sleeps — span durations are proven by ADVANCING a fake clock.
Every test that enables tracing does so through the `_traced` fixture so
the global tracer never leaks into other suites (tracing must stay a
zero-cost no-op everywhere else)."""

import glob
import os
import threading
import time
from types import SimpleNamespace

import pytest

from coconut_tpu import metrics
from coconut_tpu.faults import DEAD_LETTER_SCHEMA, DeadLetterLog, FaultyBackend
from coconut_tpu.obs import export as oexport
from coconut_tpu.obs import flight as oflight
from coconut_tpu.obs import trace as otrace
from coconut_tpu.retry import RetryPolicy, call_with_retry
from coconut_tpu.serve.batcher import Batcher, demux, fail_all
from coconut_tpu.serve.queue import RequestQueue
from coconut_tpu.serve.service import CredentialService
from coconut_tpu.stream import verify_stream

pytestmark = pytest.mark.obs


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _cred(ok=True):
    return SimpleNamespace(sigma_1=1, sigma_2=1, ok=ok)


class StubGrouped:
    def batch_verify_grouped(self, sigs, msgs, vk, params):
        return all(s.sigma_1 is not None and getattr(s, "ok", False) for s in sigs)


class StubPerCred:
    def batch_verify(self, sigs, msgs, vk, params):
        return [
            s.sigma_1 is not None and bool(getattr(s, "ok", False)) for s in sigs
        ]


@pytest.fixture(autouse=True)
def _clean_state():
    otrace.disable()
    metrics.reset()
    yield
    otrace.disable()
    metrics.reset()


@pytest.fixture
def clock():
    return FakeClock()


@pytest.fixture
def _traced(clock):
    """Tracing enabled on a fake clock; yields the tracer."""
    yield otrace.enable(clock=clock, ring=256)


# --- zero-cost no-op path --------------------------------------------------


def test_disabled_returns_shared_noop_singleton():
    assert not otrace.enabled() and otrace.get_tracer() is None
    s = otrace.span("x", attr=1)
    assert s is otrace.NOOP and s is otrace.start_span("y")
    with s as inner:
        assert inner is otrace.NOOP
        assert otrace.current() is None
    s.set(a=1).event("e").end()
    otrace.event("nothing")  # no active span, no tracer: silent
    assert otrace.NOOP.trace_id is None and not otrace.NOOP


def test_disabled_path_never_allocates_a_span(monkeypatch):
    """The no-op path must not even construct a Span: poison the class
    and walk every entry point."""

    def boom(*a, **k):
        raise AssertionError("Span allocated while tracing disabled")

    import jax.profiler

    monkeypatch.setattr(otrace, "Span", boom)
    # the profiler bridge neither wraps nor annotates while no session
    # collects
    monkeypatch.setattr(otrace, "_Bridged", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    with otrace.span("a"):
        otrace.event("e", k=1)
    with otrace.span("b", ns="issue", fanout=1) as s:
        assert s is otrace.NOOP
    otrace.start_span("b", root=True)
    otrace.end_span(otrace.NOOP)
    with otrace.use(otrace.NOOP):
        pass


def test_disabled_pool_dispatch_path_never_allocates_a_span(monkeypatch):
    """ISSUE 8 extension of the poison walk: a full submit -> place ->
    per-device dispatch -> settle -> demux cycle through the dispatcher
    POOL (two executors) allocates zero Spans while tracing is off. A
    poisoned allocation would crash an executor loop, sweep the futures
    with the AssertionError, and fail the result() asserts below."""

    def boom(*a, **k):
        raise AssertionError("Span allocated while tracing disabled")

    monkeypatch.setattr(otrace, "Span", boom)
    svc = CredentialService(StubPerCred(), None, None, max_batch=2, devices=2)
    with svc:
        futs = [svc.submit(_cred(), [0]) for _ in range(6)]
        assert all(f.result(10.0) for f in futs)


def test_disabled_stream_path_never_allocates_a_span(monkeypatch):
    """The offline verify stream (prefetch worker, dispatch, device,
    checkpoint) with the ring and the profiler off: no Span, no bridge."""

    def boom(*a, **k):
        raise AssertionError("span allocated while tracing is off")

    monkeypatch.setattr(otrace, "Span", boom)
    monkeypatch.setattr(otrace, "_Bridged", boom)
    state = verify_stream(
        lambda i: ([_cred() for _ in range(4)], [[0]] * 4),
        3, None, None, StubPerCred(),
    )
    assert state.verified == 12


def test_env_flag_parse():
    for off in (None, "", "0", "false", "OFF", "no"):
        assert not otrace._env_enabled(off)
    for on in ("1", "jsonl", "true", "chrome"):
        assert otrace._env_enabled(on)


def test_disabled_serve_path_untouched():
    """With tracing off the serve path still works and futures carry a
    null trace_id."""
    svc = CredentialService(StubPerCred(), None, None, max_batch=2)
    with svc:
        f = svc.submit(_cred(), [0])
        assert f.result(10.0) is True
    assert f.trace_id is None


# --- span mechanics --------------------------------------------------------


def test_nesting_ids_and_contextvar(_traced):
    with otrace.span("a") as a:
        assert otrace.current() is a
        with otrace.span("b") as b:
            assert otrace.current() is b
            assert b.parent_id == a.span_id
            assert b.trace_id == a.trace_id
        assert otrace.current() is a
    assert otrace.current() is None
    assert a.parent_id is None and a.span_id != b.span_id


def test_root_forces_new_trace(_traced):
    with otrace.span("outer") as outer:
        inner = otrace.start_span("batch", root=True)
        assert inner.trace_id != outer.trace_id and inner.parent_id is None
        inner.end()


def test_exact_durations_with_fake_clock(_traced, clock):
    s = otrace.start_span("work")
    clock.advance(2.5)
    s.end()
    assert s.dur == 2.5
    assert s.t0 == 0.0 and s.t1 == 2.5


def test_end_is_idempotent_first_wins(_traced, clock):
    s = otrace.start_span("once")
    clock.advance(1.0)
    s.end(verdict=True)
    clock.advance(5.0)
    s.end(verdict=False)
    assert s.dur == 1.0 and s.attrs["verdict"] is True


def test_events_timestamped_on_fake_clock(_traced, clock):
    with otrace.span("s") as s:
        clock.advance(0.25)
        otrace.event("retry", attempt=1)
        clock.advance(0.25)
        s.event("split", lo=0, hi=4)
    assert s.events == [
        {"ts": 0.25, "name": "retry", "attempt": 1},
        {"ts": 0.5, "name": "split", "lo": 0, "hi": 4},
    ]


def test_use_activates_without_owning_lifetime(_traced):
    s = otrace.start_span("handoff")
    with otrace.use(s):
        assert otrace.current() is s
        with otrace.span("child") as c:
            assert c.parent_id == s.span_id
    assert otrace.current() is None
    assert s.t1 is None  # use() never ends the span
    s.end()


def test_error_attr_recorded_on_raise(_traced):
    with pytest.raises(RuntimeError):
        with otrace.span("bad") as s:
            raise RuntimeError("boom")
    assert s.attrs["error"] == "RuntimeError" and s.t1 is not None


def test_ring_buffer_bounded(clock):
    tracer = otrace.enable(clock=clock, ring=8)
    for i in range(20):
        tracer.start("s%d" % i).end()
    tail = tracer.tail()
    assert len(tail) == 8
    assert [s.name for s in tail] == ["s%d" % i for i in range(12, 20)]
    assert tracer.tail(3) == tail[-3:]


def test_cross_thread_start_and_end(_traced):
    s = otrace.start_span("xthread", root=True)
    t = threading.Thread(target=lambda: s.end(done=True))
    t.start()
    t.join()
    assert s.t1 is not None and s in _traced.tail()


def test_spans_for_follows_batch_link(_traced):
    req = otrace.start_span("request", root=True)
    batch = otrace.start_span("batch", root=True)
    req.set(batch_trace=batch.trace_id)
    child = otrace.start_span("device", parent=batch)
    child.end()
    batch.end()
    req.end()
    names = {s.name for s in _traced.spans_for(req.trace_id)}
    assert names == {"request", "batch", "device"}
    # live spans included: a still-open span of the trace is in the tree
    live = otrace.start_span("queue_wait", parent=req)
    assert live in _traced.spans_for(req.trace_id)


def test_stage_summary_in_metrics_snapshot(_traced, clock):
    with otrace.span("device"):
        clock.advance(2.0)
    with otrace.span("device"):
        clock.advance(1.0)
    stages = metrics.snapshot()["trace_stages"]
    assert stages["device"] == {"count": 2, "total_s": 3.0, "mean_s": 1.5}
    otrace.disable()
    assert "trace_stages" not in metrics.snapshot()


def test_reenable_replaces_tracer(clock):
    t1 = otrace.enable(clock=clock)
    t1.start("old").end()
    t2 = otrace.enable(clock=clock)
    assert t2 is not t1 and t2.tail() == []


# --- export ----------------------------------------------------------------


def test_jsonl_export_roundtrip(tmp_path, _traced, clock):
    with otrace.span("a", k="v"):
        clock.advance(1.0)
        otrace.event("e", n=1)
    path = str(tmp_path / "spans.jsonl")
    assert oexport.export_jsonl(path) == 1
    (rec,) = oexport.read_jsonl(path)
    assert rec["name"] == "a" and rec["dur"] == 1.0
    assert rec["attrs"] == {"k": "v"}
    assert rec["events"] == [{"ts": 1.0, "name": "e", "n": 1}]


# --- serve-path instrumentation --------------------------------------------


def test_admission_starts_trace_and_stamps_future(_traced, clock):
    q = RequestQueue(max_depth=4, clock=clock)
    fut = q.submit(_cred(), [0], lane="bulk")
    assert fut.trace_id is not None
    (req,) = q._lanes["bulk"]
    assert req.span.trace_id == fut.trace_id
    assert req.span.attrs["lane"] == "bulk"
    assert req.queue_span.parent_id == req.span.span_id
    # queue_wait ends with exactly the coalescing delay on the fake clock
    clock.advance(0.75)
    batcher = Batcher(q, max_batch=1, clock=clock)
    (popped,) = batcher.next_batch(block=False)
    assert popped.queue_span.dur == 0.75


def test_rejected_submission_allocates_no_trace(_traced):
    from coconut_tpu.errors import ServiceOverloadedError

    q = RequestQueue(max_depth=1, clock=FakeClock())
    q.submit(_cred(), [0])
    before = len(_traced.live_snapshot())
    with pytest.raises(ServiceOverloadedError):
        q.submit(_cred(), [0])
    assert len(_traced.live_snapshot()) == before


def test_demux_ends_request_span_with_verdict(_traced, clock):
    q = RequestQueue(max_depth=4, clock=clock)
    futs = [q.submit(_cred(), [0]) for _ in range(2)]
    reqs = Batcher(q, max_batch=2, clock=clock).next_batch(block=False)
    demux(reqs, [True, False], clock=clock)
    assert [r.span.attrs["verdict"] for r in reqs] == [True, False]
    assert all(r.span.t1 is not None for r in reqs)
    assert [f.result(0) for f in futs] == [True, False]


def test_fail_all_ends_spans_with_error(_traced, clock):
    q = RequestQueue(max_depth=4, clock=clock)
    q.submit(_cred(), [0])
    reqs = q.drain_pending()
    fail_all(reqs, RuntimeError("swept"))
    (req,) = reqs
    assert req.span.attrs["error"] == "RuntimeError"
    assert req.span.t1 is not None and req.queue_span.t1 is not None


def test_serve_request_span_tree_retry_and_bisection(_traced, clock, tmp_path):
    """The satellite: exact nesting + durations for a serve request that
    survives one retry and one bisection split — fake clock, zero real
    sleeps, supervisor loop driven synchronously."""
    dlq = str(tmp_path / "dead.jsonl")
    backend = FaultyBackend(StubGrouped(), raise_on={0})
    svc = CredentialService(
        backend,
        None,
        None,
        mode="grouped",
        max_batch=4,
        retry_policy=RetryPolicy(max_attempts=2, base_delay=0.0),
        dead_letter_path=dlq,
        clock=clock,
    )
    futs = [svc.submit(_cred(ok=(i != 2)), [0]) for i in range(4)]
    clock.advance(1.0)  # queue wait before the batch is popped
    batch = svc._batcher.next_batch(block=False)
    launched = svc._launch(batch)
    svc._settle(*launched)
    assert [f.result(0) for f in futs] == [True, True, False, True]

    victim = futs[2]
    spans = {s.name: s for s in _traced.spans_for(victim.trace_id)}
    # exact nesting: request -> queue_wait; batch -> coalesce/dispatch/
    # device -> bisect under device's retry ladder context
    req_span = spans["request"]
    assert spans["queue_wait"].parent_id == req_span.span_id
    assert spans["queue_wait"].dur == 1.0
    bspan = spans["batch"]
    assert req_span.attrs["batch_trace"] == bspan.trace_id
    assert bspan.attrs["members"][2] == victim.trace_id
    for stage in ("coalesce", "dispatch", "demux"):
        assert spans[stage].parent_id == bspan.span_id, stage
    assert spans["device"].parent_id == bspan.span_id
    assert spans["bisect"].parent_id == bspan.span_id
    # fake clock never advanced during the batch: stage durs exactly 0
    assert spans["dispatch"].dur == 0.0 and spans["device"].dur == 0.0
    # one retry (injected dispatch fault), then success
    assert [e["name"] for e in spans["dispatch"].events] == ["attempt_failed"]
    retry_events = [e for e in spans["device"].events if e["name"] == "retry"]
    assert len(retry_events) == 1 and retry_events[0]["attempt"] == 2
    # bisection: splits recorded, culprit dead-lettered onto ITS span
    splits = [e for e in spans["bisect"].events if e["name"] == "split"]
    assert splits and splits[0] == {"ts": clock.t, "name": "split", "lo": 0, "hi": 4}
    assert [e["name"] for e in req_span.events] == ["dead_letter"]
    assert req_span.attrs["verdict"] is False
    assert bspan.attrs["result"] == "bisected"
    # the dead-lettered request's span tree names the device that rejected
    # it and which side of the placement policy its batch took (ISSUE 8)
    assert bspan.attrs["device"] == "0"
    assert bspan.attrs["placement"] == "single"
    assert spans["dispatch"].attrs["device"] == "0"
    assert spans["device"].attrs["device"] == "0"
    # dead-letter line joins back on the victim's trace_id
    (rec,) = DeadLetterLog.read(dlq)
    assert rec["trace_id"] == victim.trace_id
    assert rec["schema"] == DEAD_LETTER_SCHEMA
    assert rec["program"] == "verify"
    # flight record rides next to the dead-letter log with the full tree
    (flight,) = oflight.read(dlq)
    assert flight["trace_id"] == victim.trace_id
    assert {s["name"] for s in flight["tree"]} >= {
        "request",
        "queue_wait",
        "batch",
        "coalesce",
        "dispatch",
        "device",
        "bisect",
    }


def test_threaded_serve_smoke_lands_bridged_spans_in_profiler_trace(tmp_path):
    """Real supervisor thread + real clock under a CPU profiler session:
    the serve program's stage spans land in the trace as coconut/serve.*
    annotations, the ring still gets its stages, and the per-request
    roots are not bridged."""
    otrace.enable(ring=256)
    svc = CredentialService(StubPerCred(), None, None, max_batch=2)
    names = {e["name"] for e in _profiled(tmp_path, lambda: _serve(svc))}
    assert {"coconut/serve.coalesce", "coconut/serve.dispatch",
            "coconut/serve.device", "coconut/serve.demux"} <= names
    assert not {"coconut/request", "coconut/queue_wait", "coconut/batch",
                "coconut/serve.request", "coconut/serve.batch"} & names
    stages = metrics.snapshot()["trace_stages"]
    for stage in ("request", "queue_wait", "batch", "dispatch", "device"):
        assert stages[stage]["count"] > 0, stage


def _serve(svc):
    with svc:
        futs = [svc.submit(_cred(), [0]) for _ in range(4)]
        assert all(f.result(10.0) for f in futs)


# --- profiler bridge -------------------------------------------------------


def _profiled(tmp_path, body):
    """Run body() under a CPU jax.profiler session; return the trace's
    "coconut/" host events as plain dicts."""
    import jax
    from jax.profiler import ProfileData

    from benchmark import trace as btrace

    jax.profiler.start_trace(
        str(tmp_path), profiler_options=btrace.profiler_options()
    )
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True
    )
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(otrace.PROFILER_PREFIX):
                    out.append({
                        "name": ev.name,
                        "dur_s": ev.duration_ns / 1e9,
                        "stats": dict(ev.stats),
                    })
    return out


def test_bridge_puts_stage_spans_of_two_threads_in_the_profiler_trace(
    tmp_path,
):
    tracer = otrace.enable(ring=256)  # real clock: durations compared

    def sign():
        with otrace.span("sign", ns="issue", fanout=7, authority="2"):
            with otrace.span("encode"):  # shared code: inherits issue
                time.sleep(0.02)

    def body():
        t = threading.Thread(target=sign)
        t.start()
        with otrace.span("dispatch", ns="stream"):
            with otrace.span("encode"):
                time.sleep(0.03)
        t.join()
        root = otrace.start_span("stream_batch", root=True)
        threading.Thread(target=root.end).start()  # cross-thread: ring only

    events = _profiled(tmp_path, body)
    by_name = {e["name"]: e for e in events}
    assert set(by_name) == {
        "coconut/issue.sign", "coconut/issue.encode",
        "coconut/stream.dispatch", "coconut/stream.encode",
    }
    assert by_name["coconut/issue.sign"]["stats"]["fanout"] == 7
    ring = {}
    for s in tracer.tail():
        ring.setdefault(s.name, []).append(s.dur)
    for label, want in [("sign", "coconut/issue.sign"),
                        ("dispatch", "coconut/stream.dispatch")]:
        (dur,) = ring[label]
        assert abs(by_name[want]["dur_s"] - dur) < 1e-3, label
    enc = sorted(ring["encode"])
    got = sorted([by_name["coconut/issue.encode"]["dur_s"],
                  by_name["coconut/stream.encode"]["dur_s"]])
    assert all(abs(a - b) < 1e-3 for a, b in zip(enc, got))
    hists = metrics.snapshot()["histograms"]
    for h in ("bridge_issue_sign_s", "bridge_issue_encode_s",
              "bridge_stream_dispatch_s", "bridge_stream_encode_s"):
        assert hists[h]["count"] == 1, h
    assert "stream_batch" in ring


def test_bridge_is_silent_without_a_profiler_session(tmp_path):
    tracer = otrace.enable(ring=16)
    with otrace.span("dispatch", ns="stream") as s:
        assert s in tracer.live_snapshot()
    assert not isinstance(otrace.span("x"), otrace._Bridged)
    assert "histograms" not in metrics.snapshot()
    # nothing is emitted outside the session either
    with otrace.span("before", ns="serve"):
        pass
    events = _profiled(tmp_path, lambda: None)
    assert events == []


def test_batcher_opens_backpressure_then_batch_wait(tmp_path):
    """A queued request held by the ready gate reads "backpressure"; once
    the gate opens it waits for its deadline under "batch_wait"; the
    flush closes it."""
    tracer = otrace.enable(ring=64)
    q = RequestQueue(max_depth=4)
    batcher = Batcher(q, max_batch=2, metric_ns="issue")

    def body():
        q.submit(_cred(), [0], max_wait_ms=150.0)
        opens = time.monotonic() + 0.05
        batch = batcher.next_batch(
            block=True, ready=lambda: time.monotonic() > opens
        )
        assert len(batch) == 1

    events = _profiled(tmp_path, body)
    waits = [s for s in tracer.tail() if s.name != "queue_wait"]
    assert [s.name for s in waits] == ["backpressure", "batch_wait"]
    assert waits[0].dur > 0.04 and waits[1].dur > 0.05
    assert {e["name"] for e in events} == {
        "coconut/issue.backpressure", "coconut/issue.batch_wait"
    }


# --- stream-path instrumentation -------------------------------------------


def test_stream_batch_spans_and_checkpoint_events(_traced, tmp_path):
    state = verify_stream(
        lambda i: ([_cred() for _ in range(4)], [[0]] * 4),
        3,
        None,
        None,
        StubGrouped(),
        mode="grouped",
        state_path=str(tmp_path / "state.json"),
    )
    assert state.batches_ok == 3
    batches = [s for s in _traced.tail() if s.name == "stream_batch"]
    assert [s.attrs["batch"] for s in batches] == [0, 1, 2]
    for s in batches:
        assert s.attrs["ok"] is True
        assert [e["name"] for e in s.events] == ["checkpoint"]
        kids = {
            k.name
            for k in _traced.tail()
            if k.parent_id == s.span_id and k.trace_id == s.trace_id
        }
        assert kids == {"dispatch", "device"}


def test_checkpoint_quarantine_writes_flight_record(_traced, tmp_path):
    from coconut_tpu.stream import StreamState

    path = str(tmp_path / "state.json")
    with open(path, "w") as f:
        f.write("{ corrupt")
    st = StreamState(path)
    assert st.quarantined is not None
    (rec,) = oflight.read(path)
    assert rec["reason"] == "checkpoint_quarantine"
    assert rec["quarantined_to"] == st.quarantined


def test_flight_recorder_noop_when_disabled(tmp_path):
    dlq = str(tmp_path / "dead.jsonl")
    DeadLetterLog(dlq).append(batch=0, credential=1, reason="r")
    assert not os.path.exists(oflight.flight_path(dlq))
    assert oflight.record(dlq, "dead_letter") is None


def test_flight_record_includes_recent_tail(_traced, tmp_path):
    for i in range(10):
        otrace.start_span("work%d" % i, root=True).end()
    base = str(tmp_path / "x.jsonl")
    rec = oflight.record(base, "dead_letter", trace_id=None, last_n=4)
    assert rec is not None and len(rec["recent"]) == 4
    assert rec["tree"] == [] and rec["schema"] == 1
    assert oflight.read(base)[0]["reason"] == "dead_letter"


# --- retry ladder events ---------------------------------------------------


def test_call_with_retry_narrates_onto_active_span(_traced):
    from coconut_tpu.errors import TransientBackendError

    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] < 3:
            raise TransientBackendError("hiccup %d" % calls[0])
        return "ok"

    policy = RetryPolicy(max_attempts=3, base_delay=0.0, sleep=lambda s: None)
    with otrace.span("device") as s:
        assert call_with_retry(flaky, policy, key=7) == "ok"
    names = [e["name"] for e in s.events]
    assert names == ["attempt_failed", "retry", "attempt_failed", "retry"]


def test_fallback_event_recorded(_traced):
    from coconut_tpu.errors import TransientBackendError

    def always_bad():
        raise TransientBackendError("dead")

    policy = RetryPolicy(max_attempts=2, base_delay=0.0, sleep=lambda s: None)
    with otrace.span("device") as s:
        out = call_with_retry(always_bad, policy, fallback=lambda: "degraded")
    assert out == "degraded"
    assert [e["name"] for e in s.events][-1] == "fallback"


# --- metrics percentile edge cases (satellite bugfix) -----------------------


def test_percentile_empty_is_none():
    assert metrics.percentile([], 50) is None
    assert metrics.percentile([], 0) is None
    assert metrics.percentile([], 100) is None


def test_percentile_single_sample_for_every_q():
    for q in (0, 1, 50, 95, 99, 100):
        assert metrics.percentile([3.25], q) == 3.25


def test_percentile_rejects_out_of_range_q():
    with pytest.raises(ValueError):
        metrics.percentile([1.0, 2.0], -5)
    with pytest.raises(ValueError):
        metrics.percentile([1.0, 2.0], 200)
    with pytest.raises(ValueError):
        metrics.percentile([], 101)


def test_percentile_summary_tiny_windows():
    assert metrics.percentile_summary([]) == {}
    assert metrics.percentile_summary([2.0]) == {
        "p50": 2.0,
        "p95": 2.0,
        "p99": 2.0,
    }
    two = metrics.percentile_summary([1.0, 9.0])
    assert two == {"p50": 1.0, "p95": 9.0, "p99": 9.0}


def test_hist_readout_single_observation():
    metrics.observe("edge_s", 0.5)
    h = metrics.snapshot()["histograms"]["edge_s"]
    assert h["count"] == 1
    assert h["p50_s"] == h["p95_s"] == h["p99_s"] == 0.5
    assert h["mean_s"] == 0.5 and h["max_s"] == 0.5


def test_nearest_rank_unchanged_for_larger_n():
    samples = list(range(1, 11))  # 1..10
    assert metrics.percentile(samples, 50) == 5
    assert metrics.percentile(samples, 99) == 10
    assert metrics.percentile(samples, 100) == 10
    assert metrics.percentile(samples, 0) == 1
