"""Zero-downtime lifecycle suite (ISSUE 14, marker `lifecycle`).

Covers the PR-14 contract surface:

  - SHAPE MANIFEST: dedup/canonicalization, atomic save, load round-
    trips, and the corruption-never-blocks-boot guarantee;
  - READINESS GATING: LifecycleController promotes WARMING -> UP only
    AFTER the manifest replay finished, boot is idempotent, and a
    drained controller refuses to un-drain;
  - GRACEFUL DRAIN: one deadline shared between the engine drain and
    the manifest save, CLOSED reported at the end, a successor process
    warm-boots from the saved manifest;
  - REPLICA INTEGRATION: beacons report "warming"/"draining" from the
    controller, a draining replica refuses program requests with a
    RETRYABLE ServiceClosedError (and the refusal survives the wire);
  - ROUTER HANDOFF: a draining primary's refusal fails over to a ring
    successor, marks DRAINING (not DOWN) in the directory, and the
    placement audit counters never show a WARMING/DRAINING placement;
  - ELASTIC SIZING: consecutive-sample hysteresis never flaps on a
    single sample, the controller parks/unparks through the engine,
    and a REAL engine's parked executor receives no work while pool
    capacity stays 1.0 (parking is not degradation);
  - ROLLING-RESTART DRILL: a deterministic 3-replica loopback fleet is
    restarted in sequence under mixed traffic — every future settles,
    zero non-retryable client errors, and the router provably never
    places a new session on a WARMING or DRAINING replica.

Everything except the two real-engine tests runs on stub engines and
fake clocks with zero real sleeps."""

import json
import threading
from types import SimpleNamespace

import pytest

from coconut_tpu import metrics, net
from coconut_tpu.backend import get_backend
from coconut_tpu.engine import ProtocolEngine
from coconut_tpu.engine import lifecycle as lc_mod
from coconut_tpu.engine.lifecycle import (
    ElasticController,
    ElasticPolicy,
    LifecycleController,
    ShapeManifest,
)
from coconut_tpu.errors import (
    ServiceClosedError,
    ServiceRetryableError,
    TransientBackendError,
)
from coconut_tpu.keygen import trusted_party_SSS_keygen
from coconut_tpu.net import gossip, rpc, wire
from coconut_tpu.net.router import ReplicaRouter
from coconut_tpu.params import Params
from coconut_tpu.retry import RetryPolicy
from coconut_tpu.serve.queue import ServeFuture
from coconut_tpu.signature import Signature
from coconut_tpu.sss import rand_fr

pytestmark = pytest.mark.lifecycle

MSGS = 3
HIDDEN = 1
REVEALED = [1, 2]
THRESHOLD, TOTAL = 2, 3


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def world():
    params = Params.new(MSGS, b"test-lifecycle")
    _, _, signers = trusted_party_SSS_keygen(THRESHOLD, TOTAL, params)
    return SimpleNamespace(
        params=params,
        signers=signers,
        backend=get_backend("python"),
        codec=wire.WireCodec(params),
    )


class StubLifecycleEngine:
    """Everything LifecycleController + Replica touch, inline-resolved:
    verify futures settle immediately, warm_shapes records its input,
    drain records its deadline."""

    def __init__(self, shapes=(), name="stub"):
        self.name = name
        self._shapes = set(shapes)
        self.warm_calls = []
        self.drain_timeouts = []
        self.calls = 0
        self.depth_value = 0
        self.verdict = True

    def depth(self):
        return self.depth_value

    def shape_keys(self):
        return set(self._shapes)

    def warm_shapes(self, shapes):
        self.warm_calls.append(list(shapes))
        warmed = 0
        for s in shapes:
            self._shapes.add(tuple(s))
            warmed += 1
        return warmed, 0

    def drain(self, timeout=None):
        self.drain_timeouts.append(timeout)
        return True

    def submit_verify(self, sig, messages, lane="interactive",
                      max_wait_ms=None):
        self.calls += 1
        self._shapes.add(("verify", "single", (len(messages),)))
        fut = ServeFuture()
        fut.set_result(self.verdict)
        return fut


# --- tentpole: shape manifest ------------------------------------------------


def test_manifest_dedup_and_canonicalization():
    """Lists and tuples that JSON-round-trip equal ARE equal: one
    manifest entry, tuples inside after canonicalization."""
    m = ShapeManifest(
        shapes=[
            ("verify", "single", (8,)),
            ["verify", "single", [8]],  # same shape, JSON spelling
            ("mint", "single", (4, 2)),
            ("bad-entry",),  # malformed: silently dropped
        ],
        engine_name="eng-a",
    )
    assert len(m) == 2
    assert ("verify", "single", (8,)) in m.shapes
    assert ("mint", "single", (4, 2)) in m.shapes


def test_manifest_save_load_roundtrip(tmp_path):
    path = tmp_path / "shapes.json"
    m = ShapeManifest(
        shapes=[("verify", "single", (8,)), ("prepare", "sharded", (16, 3))],
        engine_name="eng-rt",
    )
    m.save(path)
    # atomic write: no tmp litter next to the artifact
    assert [p.name for p in tmp_path.iterdir()] == ["shapes.json"]
    loaded = ShapeManifest.load(path)
    assert loaded.engine_name == "eng-rt"
    assert loaded.shapes == m.shapes
    # the documented schema-1 artifact layout is a promise
    doc = json.loads(path.read_text())
    assert doc["schema"] == 1
    assert {"program": "verify", "placement": "single", "shape": [8]} in (
        doc["shapes"]
    )


def test_manifest_corruption_never_blocks_boot(tmp_path):
    metrics.reset()
    # missing file: empty manifest, no corruption counted
    assert len(ShapeManifest.load(tmp_path / "absent.json")) == 0
    assert metrics.get_count("lifecycle_manifest_corrupt") == 0
    # garbage bytes
    garbage = tmp_path / "garbage.json"
    garbage.write_bytes(b"\x00not json at all")
    assert len(ShapeManifest.load(garbage)) == 0
    assert metrics.get_count("lifecycle_manifest_corrupt") == 1
    # wrong schema
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({"schema": 99, "shapes": []}))
    assert len(ShapeManifest.load(stale)) == 0
    assert metrics.get_count("lifecycle_manifest_corrupt") == 2
    # a corrupt manifest on disk does not poison the next save
    ShapeManifest(
        shapes=[("verify", "single", (2,))], engine_name="x"
    ).save(garbage)
    assert ShapeManifest.load(garbage).shapes == [("verify", "single", (2,))]


# --- tentpole: readiness gating ----------------------------------------------


def test_boot_promotes_to_up_only_after_replay(tmp_path):
    metrics.reset()
    path = tmp_path / "m.json"
    ShapeManifest(
        shapes=[("verify", "single", (4,)), ("mint", "single", (2,))],
        engine_name="pred",
    ).save(path)
    clock = FakeClock()
    seen_state = []

    class GatingEngine(StubLifecycleEngine):
        def warm_shapes(self, shapes):
            # the boot gate's whole point: still WARMING mid-replay
            seen_state.append(lc.state)
            clock.advance(1.5)
            return super().warm_shapes(shapes)

    eng = GatingEngine()
    lc = LifecycleController(eng, manifest_path=path, clock=clock)
    assert lc.state == lc_mod.WARMING
    assert not lc.ready()
    assert metrics.get_gauge("lifecycle_state") == 0

    assert lc.boot() == (2, 0)
    assert seen_state == [lc_mod.WARMING]
    assert lc.state == lc_mod.UP and lc.ready()
    assert metrics.get_gauge("lifecycle_state") == 1
    assert metrics.get_gauge("lifecycle_manifest_shapes") == 2
    assert metrics.get_gauge("lifecycle_warmup_s") == pytest.approx(1.5)
    assert metrics.get_count("lifecycle_warmed_shapes") == 2
    # the replayed triples are exactly the manifest's, tuples restored
    assert sorted(eng.warm_calls[0], key=repr) == [
        ("mint", "single", (2,)),
        ("verify", "single", (4,)),
    ]
    # idempotent while UP; refuses after drain (a process never un-drains)
    assert lc.boot() == (2, 0)
    lc.begin_drain(timeout=1.0)
    assert lc.boot() is None
    assert lc.state == lc_mod.CLOSED


def test_missing_manifest_boots_cold_but_up(tmp_path):
    metrics.reset()
    eng = StubLifecycleEngine()
    lc = LifecycleController(eng, manifest_path=tmp_path / "never.json")
    assert lc.boot() == (0, 0)
    assert lc.ready()
    assert metrics.get_gauge("lifecycle_manifest_shapes") == 0


# --- tentpole: graceful drain ------------------------------------------------


def test_drain_shares_one_deadline_and_saves_manifest(tmp_path):
    metrics.reset()
    path = tmp_path / "m.json"
    eng = StubLifecycleEngine(shapes=[("verify", "single", (8,))])
    lc = LifecycleController(eng, manifest_path=path)
    lc.boot()

    assert lc.begin_drain(timeout=5.0) is True
    assert lc.state == lc_mod.CLOSED
    assert metrics.get_gauge("lifecycle_state") == 3
    # the engine's join budget is the REMAINDER of the shared deadline,
    # never a fresh 5 s allowance (and never None)
    assert len(eng.drain_timeouts) == 1
    assert eng.drain_timeouts[0] is not None
    assert 0.0 < eng.drain_timeouts[0] <= 5.0
    # manifest persisted for the successor
    assert ShapeManifest.load(path).shapes == [("verify", "single", (8,))]
    # idempotent: no second engine drain
    assert lc.begin_drain(timeout=5.0) is True
    assert len(eng.drain_timeouts) == 1


def test_successor_warm_boots_from_predecessor_manifest(tmp_path):
    """The restart contract end to end: drain writes, successor reads,
    and the successor's replay receives exactly the predecessor's
    dispatched shape set."""
    path = tmp_path / "hand.json"
    old = StubLifecycleEngine(name="old")
    old_lc = LifecycleController(old, manifest_path=path)
    old_lc.boot()
    old.submit_verify(Signature(None, None), [1, 2, 3]).result(1.0)
    old.submit_verify(Signature(None, None), [1]).result(1.0)
    assert old_lc.begin_drain(timeout=2.0)

    new = StubLifecycleEngine(name="new")
    new_lc = LifecycleController(new, manifest_path=path)
    warmed, skipped = new_lc.boot()
    assert (warmed, skipped) == (2, 0)
    assert sorted(new.warm_calls[0], key=repr) == [
        ("verify", "single", (1,)),
        ("verify", "single", (3,)),
    ]
    assert new_lc.ready()


def test_manifest_save_failure_never_fails_drain(tmp_path):
    metrics.reset()

    class UnsaveableEngine(StubLifecycleEngine):
        def shape_keys(self):
            raise RuntimeError("snapshot exploded")

    lc = LifecycleController(
        UnsaveableEngine(), manifest_path=tmp_path / "m.json"
    )
    lc.boot()
    assert lc.begin_drain(timeout=1.0) is True
    assert lc.state == lc_mod.CLOSED
    assert metrics.get_count("lifecycle_manifest_save_errors") == 1


# --- satellite: replica integration (beacon + retryable refusal) -------------


def test_beacon_reports_lifecycle_states(world):
    drain_sig = Signature(world.params.g, world.params.g)
    eng = StubLifecycleEngine()
    lc = LifecycleController(eng)
    rep = rpc.Replica(eng, world.codec, replica_id="rw", lifecycle=lc)
    assert rep.beacon().state == "warming"
    lc.boot()
    assert rep.beacon().state == "healthy"
    # drain via the REPLICA: refusals + beacon flip before the close
    states_mid_drain = []

    class DrainWatchingEngine(StubLifecycleEngine):
        def drain(self, timeout=None):
            # mid-drain: the beacon must already say "draining" and the
            # program path must already refuse with a RETRYABLE error
            states_mid_drain.append(rep2.beacon().state)
            try:
                client.submit_verify(drain_sig, [1]).result(5.0)
                states_mid_drain.append("admitted")
            except ServiceClosedError:
                states_mid_drain.append("refused-retryable")
            return super().drain(timeout=timeout)

    eng2 = DrainWatchingEngine()
    lc2 = LifecycleController(eng2)
    rep2 = rpc.Replica(eng2, world.codec, replica_id="rd", lifecycle=lc2)
    client = rpc.GatewayClient(
        rpc.LoopbackTransport(rep2), world.codec, api_key="k"
    )
    lc2.boot()
    assert rep2.beacon().state == "healthy"
    assert rep2.begin_drain(timeout=5.0) is True
    assert states_mid_drain == ["draining", "refused-retryable"]
    # after the drain the listener is closed: a dead replica, not a liar
    assert rep2.beacon().state == "down"


def test_service_closed_error_retryable_over_wire():
    """Satellite 1: ServiceClosedError is a ServiceRetryableError and
    the wire envelope round-trips it with retryable=True — the router
    on the far side may fail it over."""
    exc = ServiceClosedError("replica 'r0' is draining: resubmit elsewhere")
    assert isinstance(exc, ServiceRetryableError)
    assert exc.retry_after_s == 0.0  # retry elsewhere IMMEDIATELY
    payload = wire.encode_error(exc, program="verify")
    back = wire.decode_error(payload)
    assert type(back) is ServiceClosedError
    assert isinstance(back, ServiceRetryableError)
    assert back.retry_after_s == 0.0


# --- satellite: router drain handoff -----------------------------------------


def _beacon(rid, state="healthy", depth=0):
    return wire.Beacon(rid, state, 1.0, depth, False, 1, 1, 0.0)


def _sig(world):
    # a wire-encodable signature; the stub engines never inspect it
    return Signature(world.params.g, world.params.g)


class GatedDrainEngine(StubLifecycleEngine):
    """Drain blocks on an event: holds the replica in the DRAINING
    window (_draining set, listener still open) so tests can submit
    traffic mid-drain — the window where refusals are the RETRYABLE
    ServiceClosedError. After close() the refusal is a torn connection
    (TransientBackendError), the crash path, by design."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.drain_started = threading.Event()
        self.drain_gate = threading.Event()

    def drain(self, timeout=None):
        self.drain_started.set()
        assert self.drain_gate.wait(10.0), "drain gate never released"
        return super().drain(timeout=timeout)


def _lifecycle_fleet(world, n=3):
    """n stub replicas (each with a LifecycleController) behind loopback
    transports + a router; returns (router, parts) where parts[rid] is a
    mutable SimpleNamespace(engine, lc, replica, transport)."""
    parts, clients = {}, {}
    for i in range(n):
        rid = "r%d" % i
        eng = GatedDrainEngine(name=rid)
        lc = LifecycleController(eng)
        rep = rpc.Replica(eng, world.codec, replica_id=rid, lifecycle=lc)
        t = rpc.LoopbackTransport(rep)
        parts[rid] = SimpleNamespace(
            engine=eng, lc=lc, replica=rep, transport=t
        )
        clients[rid] = rpc.GatewayClient(t, world.codec, api_key="key-a")
    router = ReplicaRouter(
        clients,
        retry_policy=RetryPolicy(
            max_attempts=n + 1,
            base_delay=0.0,
            jitter=0.0,
            retryable=(TransientBackendError, ServiceClosedError),
            sleep=lambda s: None,
        ),
    )
    return router, parts


def test_drain_handoff_settles_on_successor(world):
    metrics.reset()
    router, parts = _lifecycle_fleet(world)
    for p in parts.values():
        p.lc.boot()
    for rid in parts:
        router.directory.observe(router.clients[rid].poll_beacon())
    assert all(s == gossip.UP for s in router.directory.states().values())

    session = "handoff"
    ring = router.candidates(session)
    primary = ring[0]
    # the primary enters its drain window; the directory does NOT know
    # yet — the refusal itself must teach it
    eng = parts[primary].engine
    drained = []
    drainer = threading.Thread(
        target=lambda: drained.append(
            parts[primary].replica.begin_drain(timeout=10.0)
        )
    )
    drainer.start()
    try:
        assert eng.drain_started.wait(5.0)
        fut = router.submit_verify(_sig(world), [1], session=session)
        assert fut.result(5.0) is True
        assert fut.replica_id != primary
        assert fut.replica_id in ring[1:]
        assert router.directory.state(primary) == gossip.DRAINING
        assert metrics.get_count("gateway_drain_handoffs") >= 1
        # graceful: DRAINING, never DOWN — no misplacements either way
        assert metrics.get_count("gateway_placed_draining") == 0
        assert metrics.get_count("gateway_placed_warming") == 0
        # once the directory knows, new sessions never even try it
        fut2 = router.submit_verify(_sig(world), [1], session=session)
        assert fut2.result(5.0) is True
        assert fut2.replica_id != primary
        assert metrics.get_count("gateway_placed_draining") == 0
    finally:
        eng.drain_gate.set()
        drainer.join(5.0)
    assert drained == [True]


# --- satellite: elastic hysteresis -------------------------------------------


def test_elastic_policy_never_flaps_on_single_sample():
    p = ElasticPolicy(
        min_executors=1, max_executors=4, grow_after=2, shrink_after=3
    )
    # one hot sample: NO resize
    assert p.observe(depth=100, busy=1.0, active=2) is None
    # a disagreeing sample resets the streak
    assert p.observe(depth=1, busy=0.5, active=2) is None
    assert p.observe(depth=100, busy=1.0, active=2) is None
    assert p.observe(depth=100, busy=1.0, active=2) == "grow"
    # after acting the streak restarts: no immediate second grow
    assert p.observe(depth=100, busy=1.0, active=3) is None
    # at the cap: grow suppressed even with a full streak
    assert p.observe(depth=100, busy=1.0, active=4) is None
    assert p.observe(depth=100, busy=1.0, active=4) is None

    # shrink needs THREE consecutive idle samples
    assert p.observe(depth=0, busy=0.0, active=4) is None
    assert p.observe(depth=0, busy=0.0, active=4) is None
    assert p.observe(depth=0, busy=0.0, active=4) == "shrink"
    # at the floor: shrink suppressed
    for _ in range(5):
        assert p.observe(depth=0, busy=0.0, active=1) is None


def test_elastic_controller_drives_park_and_unpark():
    metrics.reset()
    clock = FakeClock()

    class ElasticStubEngine:
        def __init__(self):
            self.active = 3
            self.depth_value = 0
            self._executors = ()
            self.parked = []
            self.unparked = []

        def total_depth(self):
            return self.depth_value

        def active_pool_size(self):
            return self.active

        def park_executor(self, label=None):
            self.active -= 1
            self.parked.append("dev%d" % self.active)
            return self.parked[-1]

        def unpark_executor(self, label=None):
            if not self.parked:
                return None
            self.active += 1
            self.unparked.append(self.parked.pop())
            return self.unparked[-1]

    eng = ElasticStubEngine()
    ctl = ElasticController(
        eng,
        policy=ElasticPolicy(
            min_executors=1, grow_after=2, shrink_after=3
        ),
        clock=clock,
    )
    # warm-up sample: no busy fraction to difference over yet
    assert ctl.tick() is None
    # three consecutive idle samples -> ONE park, no flapping after
    decisions = []
    for _ in range(4):
        clock.advance(1.0)
        decisions.append(ctl.tick())
    assert decisions.count("shrink") == 1
    assert eng.parked == ["dev2"]
    assert metrics.get_count("elastic_shrunk") == 1
    # pressure returns: queue floods -> unpark after the grow window
    eng.depth_value = 50
    decisions = []
    for _ in range(3):
        clock.advance(1.0)
        decisions.append(ctl.tick())
    assert decisions.count("grow") == 1
    assert eng.unparked == ["dev2"]
    assert metrics.get_count("elastic_grown") == 1
    # nothing parked + grow signal: acting is a no-op, not a crash
    for _ in range(3):
        clock.advance(1.0)
        ctl.tick()
    assert metrics.get_count("elastic_grown") == 1


def test_elastic_busy_fraction_from_device_timers():
    """sample() differences the serve_dev*_busy_s timers over the
    interval: 1.5 busy-seconds across 3 executors in 1 s -> 0.5."""
    clock = FakeClock()
    eng = SimpleNamespace(
        total_depth=lambda: 0,
        active_pool_size=lambda: 3,
        _executors=tuple(
            SimpleNamespace(busy_timer="serve_dev%d_busy_s" % i)
            for i in range(3)
        ),
    )
    ctl = ElasticController(eng, clock=clock)
    depth, busy, active = ctl.sample()
    assert busy is None  # warm-up
    # fabricate device busy time the way the executors would accrue it
    with metrics._lock:
        for i in range(3):
            metrics._timers["serve_dev%d_busy_s" % i] += 0.5
    clock.advance(1.0)
    depth, busy, active = ctl.sample()
    assert busy == pytest.approx(0.5)
    assert active == 3
    # no further accrual: next interval reads fully idle
    clock.advance(1.0)
    _, busy, _ = ctl.sample()
    assert busy == 0.0


# --- satellite: elastic park/unpark on a REAL engine -------------------------


def test_real_engine_park_is_invisible_to_health(world):
    """Parking shrinks the pool without looking like degradation: the
    capacity fraction stays 1.0 (brownout never trips), the parked
    executor gets NO dispatches, and unpark restores it to service."""
    metrics.reset()
    eng = ProtocolEngine(
        world.signers,
        world.params,
        THRESHOLD,
        count_hidden=HIDDEN,
        revealed_msg_indices=REVEALED,
        backend=world.backend,
        devices=4,
        max_batch=4,
        max_wait_ms=5.0,
    ).start()
    try:
        sig = Signature(world.params.g, world.params.g)
        msgs = [rand_fr() for _ in range(MSGS)]
        assert eng.active_pool_size() == 4
        assert eng.submit_verify(sig, msgs).result(60.0) in (True, False)

        parked = eng.park_executor()
        assert parked is not None
        assert eng.parked_executors() == {parked}
        assert eng.active_pool_size() == 3
        # intentional shrink is NOT degradation
        assert eng._capacity_fraction() == pytest.approx(1.0)
        parked_ex = next(
            ex for ex in eng._executors if ex.label == parked
        )
        assert not parked_ex.has_worker()

        before = dict(metrics.counters_with_prefix("serve_dev"))
        futs = [eng.submit_verify(sig, msgs) for _ in range(12)]
        assert all(f.result(60.0) in (True, False) for f in futs)
        after = metrics.counters_with_prefix("serve_dev")
        key = "serve_dev%s_dispatches" % parked
        assert after.get(key, 0) == before.get(key, 0), (
            "parked executor %s was dispatched to" % parked
        )

        # never parks down to zero
        while eng.park_executor() is not None:
            pass
        assert eng.active_pool_size() == 1
        assert eng.park_executor() is None

        # unpark: the PR 9 respawn path brings it straight back
        label = eng.unpark_executor()
        assert label is not None
        assert eng.active_pool_size() == 2
        revived = next(ex for ex in eng._executors if ex.label == label)
        assert revived.has_worker()
        futs = [eng.submit_verify(sig, msgs) for _ in range(8)]
        assert all(f.result(60.0) in (True, False) for f in futs)
    finally:
        assert eng.drain(timeout=60.0)


# --- tentpole: the rolling-restart drill -------------------------------------


def test_rolling_restart_drill_drops_nothing(world, tmp_path):
    """The PR's acceptance drill, deterministic over loopback: a
    3-replica fleet restarted in sequence under mixed traffic. Every
    future settles, zero non-retryable client errors, the router never
    places a session on a WARMING or DRAINING replica (audited from the
    gateway_placed_* counters), and each restart hands its shape
    manifest to its successor."""
    metrics.reset()
    router, parts = _lifecycle_fleet(world)
    manifest_paths = {
        rid: tmp_path / ("%s.json" % rid) for rid in parts
    }
    for rid, p in parts.items():
        p.lc.manifest_path = manifest_paths[rid]
        p.lc.boot()
    # pollers read THROUGH router.clients so a restarted replica's fresh
    # client is what the next sweep polls (same wiring as the probe)
    gossip_loop = gossip.GossipLoop(
        router.directory,
        {
            rid: (lambda r=rid: router.clients[r].poll_beacon(timeout=2.0))
            for rid in parts
        },
        clock=FakeClock(),
    )
    gossip_loop.step()
    assert all(
        s == gossip.UP for s in router.directory.states().values()
    )

    sig = _sig(world)
    # guaranteed coverage: four sessions ring-primaried on EACH replica,
    # so every drain window provably exercises the graceful handoff
    by_primary = {rid: [] for rid in parts}
    i = 0
    while any(len(v) < 4 for v in by_primary.values()):
        s = "sess-%d" % i
        i += 1
        owner = router.candidates(s)[0]
        if len(by_primary[owner]) < 4:
            by_primary[owner].append(s)
    sessions = [s for v in by_primary.values() for s in v]
    settled = 0

    def traffic(tag):
        nonlocal settled
        futs = [
            router.submit_verify(sig, [1, 2], session=s) for s in sessions
        ]
        for f in futs:
            assert f.result(5.0) is True, "dangling future during %s" % tag
            settled += 1

    traffic("steady-state")

    for rid in sorted(parts):
        old = parts[rid]
        # 1) drain window: refusals are retryable handoffs onto ring
        # successors while in-flight work settles, then manifest saved
        drained = []
        drainer = threading.Thread(
            target=lambda o=old: drained.append(
                o.replica.begin_drain(timeout=10.0)
            )
        )
        drainer.start()
        assert old.engine.drain_started.wait(5.0)
        traffic("drain of %s" % rid)  # refusal -> successor handoff
        old.engine.drain_gate.set()
        drainer.join(5.0)
        assert drained == [True], "drain of %s failed" % rid
        assert manifest_paths[rid].exists()
        gossip_loop.step()  # closed listener -> a miss, not a lie

        # 2) restart: fresh engine + controller, beacon says WARMING
        eng = StubLifecycleEngine(name=rid)
        lc = LifecycleController(
            eng, manifest_path=manifest_paths[rid]
        )
        rep = rpc.Replica(
            eng, world.codec, replica_id=rid, lifecycle=lc
        )
        parts[rid] = SimpleNamespace(
            engine=eng, lc=lc, replica=rep, transport=None
        )
        old_client = router.clients[rid]
        router.clients[rid] = rpc.GatewayClient(
            rpc.LoopbackTransport(rep), world.codec, api_key="key-a"
        )
        old_client.close()
        gossip_loop.step()
        assert router.directory.state(rid) == gossip.WARMING
        # traffic while WARMING: the router must route around it
        traffic("warming of %s" % rid)

        # 3) boot: manifest replayed (warm restart), THEN readmitted
        warmed, _skipped = lc.boot()
        assert warmed >= 1, "successor of %s booted cold" % rid
        assert eng.warm_calls, "manifest replay never reached the engine"
        gossip_loop.step()
        assert router.directory.state(rid) == gossip.UP
        traffic("post-boot of %s" % rid)

    # -- the drill's verdicts ------------------------------------------------
    assert settled == len(sessions) * (1 + 3 * 3)
    # the router provably never misplaced: all placements landed on
    # UP/DEGRADED replicas through three full restart cycles
    assert metrics.get_count("gateway_placed_warming") == 0
    assert metrics.get_count("gateway_placed_draining") == 0
    assert metrics.get_count("gateway_placed_up") > 0
    # every restart was observed as an orderly drain at least once
    assert metrics.get_count("gateway_drain_handoffs") >= 3
    # and the whole fleet ends UP
    assert all(
        s == gossip.UP for s in router.directory.states().values()
    )


# --- compile-cache placement (ISSUE 21) ------------------------------------


@pytest.fixture
def cache_dir_config():
    """Restore JAX's cache-dir setting after a test moves it."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    yield jax
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("env_dir", [None, "/tmp/coconut-env-cache"])
def test_compile_cache_dir_from_env_else_checkout(
    cache_dir_config, monkeypatch, env_dir
):
    """JAX_COMPILATION_CACHE_DIR wins wherever it is set — neither the
    shared helper nor the lifecycle boot hook (even handed its own
    directory) overrides it; unset, the cache is the checkout's
    .jax_cache."""
    import coconut_tpu.tpu as ctpu

    jax = cache_dir_config
    jax.config.update("jax_compilation_cache_dir", env_dir)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    want = env_dir or ctpu.DEFAULT_CACHE_DIR
    assert ctpu.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert lc_mod.configure_compilation_cache("/tmp/coconut-boot-cache")
    want = env_dir or "/tmp/coconut-boot-cache"
    assert jax.config.jax_compilation_cache_dir == want
