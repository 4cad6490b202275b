"""Fleet-gateway suite (ISSUE 13, marker `gateway`).

Covers the PR-13 contract surface:

  - WIRE GOLDENS: byte-exact round-trips for every program
    request/response payload plus pinned golden vectors (hex for
    crypto-free frames, sha256 for deterministic crypto payloads) —
    CTS-RPC/1 is a compatibility promise, so any byte drift fails here;
  - STRICT DECODE: unknown versions, bad magic, truncated frames,
    trailing bytes, over-cap lengths, and non-canonical fields all
    raise DeserializationError instead of half-parsing;
  - TYPED ERROR ENVELOPES: errors.py's stable `code` map, the
    always-finite retry_after_s invariant, and wire round-trips that
    reconstruct the ORIGINAL exception classes;
  - TENANT ADMISSION: fake-clock token-bucket refill, quota exhaustion,
    auth rejection, and the over-quota-tenant-only isolation property;
  - GOSSIP + ROUTING: UP/DEGRADED/DOWN transitions on beacons and
    misses, consistent-hash session affinity, least-loaded spill off a
    demoted primary, data-path failover onto survivors with zero
    dangling futures, and beacon-driven rejoin;
  - END TO END: a full prepare -> mint -> show session through a real
    engine behind a loopback replica, plus both loadgen drivers in
    transport="rpc" mode reporting rpc_overhead_s.

Real crypto on small parameters only where the payload demands it;
everything routing-related runs on stub engines and fake clocks with
zero real sleeps."""

import hashlib
from types import SimpleNamespace

import pytest

from coconut_tpu import metrics, net
from coconut_tpu.backend import get_backend
from coconut_tpu.elgamal import elgamal_keygen
from coconut_tpu.engine import ProtocolEngine
from coconut_tpu.errors import (
    WIRE_ERROR_CODES,
    DeserializationError,
    DkgAbortedError,
    DoubleSpendError,
    EpochRetiredError,
    EpochUnknownError,
    GeneralError,
    PSError,
    QuorumUnreachableError,
    ServiceBrownoutError,
    ServiceClosedError,
    ServiceOverloadedError,
    ServiceRetryableError,
    ShareVerificationError,
    TenantAuthError,
    TenantQuotaError,
    TenantRateLimitError,
    TransientBackendError,
    UnequalNoOfBasesExponents,
    UnsupportedNoOfMessages,
    error_from_wire,
)
from coconut_tpu.keygen import trusted_party_SSS_keygen
from coconut_tpu.net import gossip, rpc, wire
from coconut_tpu.net.router import ReplicaRouter
from coconut_tpu.net.tenant import TenantTable, TokenBucket
from coconut_tpu.params import Params
from coconut_tpu.retry import RetryPolicy
from coconut_tpu.serve.loadgen import run_loadgen, run_session_loadgen
from coconut_tpu.serve.queue import ServeFuture
from coconut_tpu.signature import Signature
from coconut_tpu.sss import rand_fr

pytestmark = pytest.mark.gateway

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
    params = Params.new(MSGS, b"test-gateway")
    _, _, signers = trusted_party_SSS_keygen(THRESHOLD, TOTAL, params)
    return SimpleNamespace(
        params=params,
        signers=signers,
        backend=get_backend("python"),
        codec=wire.WireCodec(params),
    )


@pytest.fixture(scope="module")
def engine(world):
    eng = ProtocolEngine(
        world.signers,
        world.params,
        THRESHOLD,
        count_hidden=HIDDEN,
        revealed_msg_indices=REVEALED,
        backend=world.backend,
        devices=1,
        max_batch=4,
        max_wait_ms=5.0,
    ).start()
    yield eng
    eng.drain(timeout=60.0)


@pytest.fixture(scope="module")
def session_objects(world, engine):
    """One real full session's crypto artifacts, for codec round-trips:
    (messages, elgamal pk/sk, SignatureRequest, randomness, credential,
    proof, challenge, revealed map)."""
    msgs = [rand_fr() for _ in range(MSGS)]
    esk, epk = elgamal_keygen(world.params.ctx.sig, world.params.g)
    sig_req, randomness = engine.submit_prepare(msgs, epk).result(120.0)
    cred = engine.submit_mint(sig_req, msgs, esk).result(120.0)
    proof, challenge, revealed = engine.submit_show_prove(
        cred, msgs
    ).result(120.0)
    return SimpleNamespace(
        msgs=msgs,
        esk=esk,
        epk=epk,
        sig_req=sig_req,
        randomness=randomness,
        cred=cred,
        proof=proof,
        challenge=challenge,
        revealed=revealed,
    )


# --- satellite: wire-format golden vectors ----------------------------------


def test_frame_header_golden():
    """The 12-byte header layout is a compatibility promise — pinned."""
    frame = wire.encode_frame(0x01, b"abc", seq=7)
    # version byte is 04 since PR 19 (scenario nullifier scope on
    # show_verify requests)
    assert frame.hex() == "c0c704010000000700000003616263"
    msg_type, seq, payload = wire.decode_frame(frame)
    assert (msg_type, seq, payload) == (0x01, 7, b"abc")


def test_error_envelope_golden():
    e = ServiceBrownoutError(
        "bulk", 0.5, depth=3, capacity_fraction=0.25, program="prepare"
    )
    env = wire.encode_error(e)
    assert env.hex() == (
        "000862726f776e6f75740007707265706172653fe00000000000000100"
        "4e736572766963652062726f776e6f757420286361706163697479203235"
        "252c2064657074682033293a2062756c6b206c616e65207368656420"
        "e28094207265747279206166746572207e302e3573"
    )
    d = wire.decode_error(env)
    assert type(d) is ServiceBrownoutError
    assert d.code == "brownout"
    assert d.program == "prepare"
    assert d.retry_after_s == 0.5
    assert d.wire_retryable is True


def test_beacon_golden():
    b = wire.Beacon("r2", "brownout", 0.5, 17, True, 2, 4, 12.25)
    assert wire.encode_beacon(b).hex() == (
        "00027232000862726f776e6f75743fe000000000000000000011"
        "01000000020000000440288000"
        "00000000"
        "0000"  # v2: empty epoch window (no key lifecycle)
        "0000"  # v3: empty state-mark set (no StateStore)
    )
    d = wire.decode_beacon(wire.encode_beacon(b))
    assert d.as_dict() == b.as_dict()
    assert d.admissible()  # brownout is DEGRADED, not unroutable
    assert not wire.Beacon(
        "r2", "quarantined", 0.0, 0, False, 0, 4, 0.0
    ).admissible()


def test_beacon_epoch_window_golden():
    """v2 beacons advertise the live key-epoch window: u16 count +
    (u32 epoch, u8 state) entries, ascending epoch order — pinned."""
    b = wire.Beacon(
        "r2", "healthy", 1.0, 0, False, 1, 1, 0.0,
        epochs=((1, "retiring"), (2, "active")),
    )
    enc = wire.encode_beacon(b)
    assert enc.hex().endswith(
        "0002"  # two live epochs
        "0000000102"  # epoch 1: retiring (code 2)
        "0000000201"  # epoch 2: active (code 1)
        "0000"  # v3: empty state-mark set follows the window
    )
    d = wire.decode_beacon(enc)
    assert d.epochs == ((1, "retiring"), (2, "active"))
    assert d.as_dict() == b.as_dict()
    bad = bytearray(enc)
    # the epoch-state byte now sits 2 bytes before the (empty) v3
    # state-mark count — still must refuse, not misparse
    bad[-3] = 0xEE
    with pytest.raises(DeserializationError, match="epoch state"):
        wire.decode_beacon(bytes(bad))


def test_verify_request_golden_digest():
    """Deterministic params + fixed scalars pin the canonical verify
    request payload byte-for-byte (as a digest)."""
    params = Params.new(3, b"gateway-golden")
    codec = wire.WireCodec(params)
    sig = Signature(params.g, params.g)
    payload = codec.encode_request(
        "verify", (sig, [1, 2, 3]), lane="interactive",
        api_key="k", session="s",
    )
    # +4 over v1: the trailing u32 mint epoch (0 here — unpinned sig)
    assert len(payload) == 301
    assert hashlib.sha256(payload).hexdigest() == (
        "c1f36595386d398c6b73b84d97c5c78a1a7a1a4cb0ba68b26adfc1e7c4e30ba5"
    )
    assert codec.encode_response("verify", True).hex() == "01"
    assert codec.encode_response("verify", False).hex() == "00"


def test_all_request_payloads_roundtrip_byte_exact(world, session_objects):
    """encode -> decode -> re-encode is the identity for EVERY program
    request, and decode hands back the engine's exact submit args."""
    so = session_objects
    codec = world.codec
    cases = {
        "verify": (so.cred, so.msgs),
        "prepare": (so.msgs, so.epk),
        "mint": (so.sig_req, so.msgs, so.esk),
        "show_prove": (so.cred, so.msgs),
        "show_verify": (so.proof, so.revealed, so.challenge),
    }
    for program, args in cases.items():
        payload = codec.encode_request(
            program, args, lane="bulk", api_key="ak", session="sess-9"
        )
        prog, lane, api_key, session, dec_args = codec.decode_request(
            wire.REQUEST_TYPES[program], payload
        )
        assert (prog, lane, api_key, session) == (
            program, "bulk", "ak", "sess-9",
        )
        again = codec.encode_request(
            program, dec_args, lane=lane, api_key=api_key, session=session
        )
        assert again == payload, program


def test_all_response_payloads_roundtrip_byte_exact(world, session_objects):
    so = session_objects
    codec = world.codec
    cases = {
        "verify": True,
        "prepare": (so.sig_req, so.randomness),
        "mint": so.cred,
        "show_prove": (so.proof, so.challenge, so.revealed),
        "show_verify": False,
    }
    for program, result in cases.items():
        payload = codec.encode_response(program, result)
        decoded = codec.decode_response(program, payload)
        again = codec.encode_response(program, decoded)
        assert again == payload, program


def test_show_verify_request_none_challenge(world, session_objects):
    """challenge=None (the stranger-verifier path) survives the wire."""
    so = session_objects
    payload = world.codec.encode_request(
        "show_verify", (so.proof, so.revealed, None)
    )
    _, _, _, _, args = world.codec.decode_request(
        wire.REQUEST_TYPES["show_verify"], payload
    )
    assert args[2] is None


# --- satellite: strict decode rejection -------------------------------------


def test_decode_rejects_unknown_version():
    frame = wire.encode_frame(0x01, b"", version=wire.WIRE_VERSION + 1)
    with pytest.raises(DeserializationError, match="version"):
        wire.parse_header(frame)


def test_decode_rejects_bad_magic():
    frame = bytearray(wire.encode_frame(0x01, b""))
    frame[0] ^= 0xFF
    with pytest.raises(DeserializationError, match="magic"):
        wire.parse_header(bytes(frame))


def test_decode_rejects_truncated_header():
    with pytest.raises(DeserializationError, match="truncated"):
        wire.parse_header(wire.encode_frame(0x01, b"")[:-1][:11])


def test_decode_rejects_length_mismatch():
    frame = wire.encode_frame(0x01, b"abcdef")
    with pytest.raises(DeserializationError, match="mismatch"):
        wire.decode_frame(frame[:-2])
    with pytest.raises(DeserializationError, match="mismatch"):
        wire.decode_frame(frame + b"zz")


def test_decode_rejects_over_cap_length():
    import struct

    header = struct.pack(
        ">HBBII", wire.MAGIC, wire.WIRE_VERSION, 0x01, 0,
        wire.MAX_FRAME_BYTES + 1,
    )
    with pytest.raises(DeserializationError, match="cap"):
        wire.parse_header(header)


def test_decode_rejects_trailing_bytes_in_payloads(world):
    env = wire.encode_error(GeneralError("x"))
    with pytest.raises(DeserializationError, match="trailing"):
        wire.decode_error(env + b"\x00")
    beacon = wire.encode_beacon(
        wire.Beacon("r", "healthy", 1.0, 0, False, 1, 1, 0.0)
    )
    with pytest.raises(DeserializationError, match="trailing"):
        wire.decode_beacon(beacon + b"\x00")
    sig = Signature(world.params.g, world.params.g)
    req = world.codec.encode_request("verify", (sig, [1, 2]))
    with pytest.raises(DeserializationError, match="trailing"):
        world.codec.decode_request(
            wire.REQUEST_TYPES["verify"], req + b"\x00"
        )


def test_decode_rejects_truncated_request(world):
    sig = Signature(world.params.g, world.params.g)
    req = world.codec.encode_request("verify", (sig, [1, 2]))
    with pytest.raises(DeserializationError):
        world.codec.decode_request(wire.REQUEST_TYPES["verify"], req[:-5])


def test_decode_rejects_noncanonical_fr(world):
    from coconut_tpu.ops.fields import R

    sig = Signature(world.params.g, world.params.g)
    req = bytearray(world.codec.encode_request("verify", (sig, [R - 1])))
    req[-32:] = b"\xff" * 32  # >= R: non-canonical scalar
    with pytest.raises(DeserializationError, match="non-canonical"):
        world.codec.decode_request(
            wire.REQUEST_TYPES["verify"], bytes(req)
        )


def test_decode_rejects_duplicate_revealed_index():
    payload = (
        (2).to_bytes(2, "big")
        + (1).to_bytes(4, "big") + (5).to_bytes(32, "big")
        + (1).to_bytes(4, "big") + (6).to_bytes(32, "big")
    )
    with pytest.raises(DeserializationError, match="duplicate"):
        wire._read_revealed(payload, 0)


# --- satellite: typed error codes + wire envelopes --------------------------


def test_error_codes_stable_and_unique():
    expected = {
        GeneralError: "general",
        DeserializationError: "bad_request",
        TransientBackendError: "transient",
        ServiceRetryableError: "retryable",
        ServiceOverloadedError: "overloaded",
        ServiceBrownoutError: "brownout",
        QuorumUnreachableError: "quorum_unreachable",
        ServiceClosedError: "closed",
        TenantAuthError: "tenant_auth",
        TenantQuotaError: "tenant_quota",
        TenantRateLimitError: "tenant_rate_limited",
        # PR 15: key-lifecycle refusals travel the same envelope
        ShareVerificationError: "share_rejected",
        DkgAbortedError: "dkg_aborted",
        EpochUnknownError: "epoch_unknown",
        EpochRetiredError: "epoch_retired",
        # PR 17: the replicated nullifier set's terminal rejection
        DoubleSpendError: "double_spend",
        # PR 20: the protocol-shape errors cross the wire as themselves
        UnsupportedNoOfMessages: "unsupported_messages",
        UnequalNoOfBasesExponents: "unequal_bases_exponents",
        PSError: "ps_error",
    }
    for cls, code in expected.items():
        assert cls.code == code
        assert WIRE_ERROR_CODES[code] is cls
    assert len(WIRE_ERROR_CODES) == len(expected)


def test_retry_after_always_finite():
    """The wire invariant: retry_after_s is a finite float >= 0, never
    None — whatever hint the constructor was handed."""
    for hint, want in (
        (None, 0.0),
        (-1.0, 0.0),
        (float("nan"), 0.0),
        (float("inf"), 0.0),
        (0.0, 0.0),
        (0.25, 0.25),
        (3, 3.0),
    ):
        err = ServiceOverloadedError(1, 1, retry_after_s=hint)
        assert isinstance(err.retry_after_s, float)
        assert err.retry_after_s == want


def test_error_from_wire_reconstructs_classes():
    originals = [
        ServiceOverloadedError(4, 4, program="verify", retry_after_s=0.1),
        ServiceBrownoutError("bulk", 0.7, program="prepare"),
        QuorumUnreachableError(3, 1, live=1, program="mint"),
        TenantRateLimitError("acme", 0.5, program="verify"),
        TenantAuthError("unknown API key"),
        TenantQuotaError("acme", 10, 10),
        ServiceClosedError("drained"),
        TransientBackendError("hiccup"),
        DeserializationError("garbage"),
        GeneralError("boom"),
        # PR 15: key-lifecycle refusals
        ShareVerificationError(
            "dealer 2 share for recipient 4 failed Pedersen check",
            dealer_id=2, round="dkg",
        ),
        DkgAbortedError(3, 2, excluded=(1,), program="mint",
                        retry_after_s=0.5),
        EpochUnknownError(9, live=(1, 2)),
        EpochRetiredError(1, live=(2, 3)),
    ]
    for orig in originals:
        decoded = wire.decode_error(wire.encode_error(orig))
        assert type(decoded) is type(orig), orig
        assert decoded.code == orig.code
        assert str(decoded) == str(orig)
        if isinstance(orig, ServiceRetryableError):
            assert decoded.retry_after_s == orig.retry_after_s
            assert decoded.program == orig.program
            assert decoded.wire_retryable


def test_error_from_wire_unknown_code_degrades():
    err = error_from_wire("flux_capacitor", "future error", program="verify")
    assert type(err) is GeneralError
    assert err.code == "flux_capacitor"  # preserved on the instance
    assert GeneralError.code == "general"  # class untouched


# --- satellite: per-tenant admission (fake clock) ---------------------------


def test_token_bucket_refill_horizon():
    clock = FakeClock()
    bucket = TokenBucket(rate_per_s=2.0, burst=2, clock=clock)
    assert bucket.take() == 0.0
    assert bucket.take() == 0.0
    wait = bucket.take()  # empty: 1 token at 2/s -> 0.5s horizon
    assert wait == pytest.approx(0.5)
    clock.advance(0.25)
    assert bucket.take() == pytest.approx(0.25)  # partial refill
    clock.advance(0.25)
    assert bucket.take() == 0.0  # one token back
    clock.advance(100.0)
    assert bucket.take() == 0.0
    assert bucket.take() == 0.0
    assert bucket.take() > 0.0  # capped at burst, not 200 tokens


def test_tenant_admission_gates():
    metrics.reset()
    clock = FakeClock()
    table = TenantTable(clock=clock)
    table.provision("acme", "key-a", rate_per_s=1.0, burst=2, quota=3)
    table.provision("bob", "key-b")  # unmetered

    with pytest.raises(TenantAuthError):
        table.admit("key-zzz")
    assert metrics.get_count("gateway_auth_failures") == 1

    assert table.admit("key-a").tenant_id == "acme"
    assert table.admit("key-a").tenant_id == "acme"
    with pytest.raises(TenantRateLimitError) as exc:
        table.admit("key-a", program="verify")
    assert exc.value.retry_after_s == pytest.approx(1.0)
    assert exc.value.program == "verify"
    assert exc.value.tenant == "acme"
    # the throttled tenant does NOT touch its neighbors
    assert table.admit("key-b").tenant_id == "bob"

    clock.advance(2.0)
    assert table.admit("key-a").used == 3
    clock.advance(10.0)
    with pytest.raises(TenantQuotaError) as exc:  # quota, not bucket
        table.admit("key-a")
    assert (exc.value.used, exc.value.quota) == (3, 3)

    assert metrics.get_count("gateway_tenant_acme_admitted") == 3
    assert metrics.get_count("gateway_tenant_acme_throttled") == 1
    assert metrics.get_count("gateway_tenant_acme_quota_rejected") == 1
    assert metrics.get_count("gateway_tenant_bob_admitted") == 1
    assert metrics.get_count("gateway_tenant_bob_throttled") == 0


def test_duplicate_api_key_rejected():
    table = TenantTable()
    table.provision("a", "same-key")
    with pytest.raises(ValueError, match="duplicate"):
        table.provision("b", "same-key")


# --- satellite: health gossip -----------------------------------------------


def _beacon(rid, state="healthy", depth=0, brownout=False):
    return wire.Beacon(rid, state, 1.0, depth, brownout, 1, 1, 0.0)


def test_directory_transitions():
    metrics.reset()
    d = gossip.HealthDirectory(["r0", "r1"], miss_threshold=2)
    # PR 14: fresh registrations start WARMING, not optimistic-UP — a
    # replica that has never beaconed must not receive traffic
    assert d.states() == {"r0": gossip.WARMING, "r1": gossip.WARMING}
    assert metrics.get_gauge("gateway_up_replicas") == 0
    assert not d.routable("r0") and not d.usable("r0")

    d.observe(_beacon("r0", state="quarantined"))
    assert d.state("r0") == gossip.DEGRADED
    assert not d.routable("r0")
    assert d.usable("r0")
    # WARMING -> DEGRADED is the first beacon landing, not a demotion
    assert metrics.get_count("gateway_demoted") == 0
    assert metrics.get_count("gateway_warmed") == 1

    d.observe(_beacon("r0", brownout=True))
    assert d.state("r0") == gossip.DEGRADED  # browned-out stays demoted

    d.observe(_beacon("r0"))
    assert d.state("r0") == gossip.UP
    assert metrics.get_count("gateway_readmitted") == 1

    d.miss("r1")
    assert d.state("r1") == gossip.WARMING  # below threshold
    d.miss("r1")
    assert d.state("r1") == gossip.DOWN
    assert not d.usable("r1")
    assert metrics.get_gauge("gateway_up_replicas") == 1

    # a fresh admissible beacon readmits a DOWN replica instantly
    d.observe(_beacon("r1", depth=5))
    assert d.state("r1") == gossip.UP
    assert d.queue_depth("r1") == 5
    assert d.queue_depth("rX") == float("inf")

    # lifecycle self-reports pin the view: draining/warming beacons
    # take the replica out of BOTH the routable and spill pools
    d.observe(_beacon("r0", state="draining"))
    assert d.state("r0") == gossip.DRAINING
    assert not d.routable("r0") and not d.usable("r0")
    assert metrics.get_count("gateway_drain_observed") == 1
    d.observe(_beacon("r0", state="warming"))
    assert d.state("r0") == gossip.WARMING
    assert not d.routable("r0") and not d.usable("r0")
    d.observe(_beacon("r0"))
    assert d.state("r0") == gossip.UP


def test_note_draining_soft_demotes():
    metrics.reset()
    d = gossip.HealthDirectory(["r0", "r1"], miss_threshold=3)
    d.observe(_beacon("r0"))
    assert d.state("r0") == gossip.UP
    d.note_draining("r0")
    assert d.state("r0") == gossip.DRAINING
    assert not d.routable("r0") and not d.usable("r0")
    # softer than note_failure: no DOWN, and a fresh healthy beacon
    # (the restarted successor) brings it straight back
    d.observe(_beacon("r0"))
    assert d.state("r0") == gossip.UP
    # note_draining on a DOWN replica must not resurrect it
    d.note_failure("r1")
    d.note_draining("r1")
    assert d.state("r1") == gossip.DOWN


def test_note_failure_is_immediate():
    d = gossip.HealthDirectory(["r0"], miss_threshold=3)
    d.note_failure("r0")
    assert d.state("r0") == gossip.DOWN


def test_gossip_loop_step():
    d = gossip.HealthDirectory(["r0", "r1"], miss_threshold=1)
    beacons = {"r0": _beacon("r0")}

    def poll(rid):
        def _p():
            if rid not in beacons:
                raise ConnectionError("dead")
            return beacons[rid]

        return _p

    loop = gossip.GossipLoop(
        d, {r: poll(r) for r in ("r0", "r1")}, clock=FakeClock()
    )
    loop.step()
    assert d.state("r0") == gossip.UP
    assert d.state("r1") == gossip.DOWN  # miss_threshold=1
    beacons["r1"] = _beacon("r1")
    loop.step()
    assert d.state("r1") == gossip.UP


# --- tentpole: router affinity / spill / failover ---------------------------


class StubEngine:
    """Inline-resolving verify-only engine: deterministic futures, a
    settable queue depth, and a per-replica call count."""

    def __init__(self, verdict=True):
        self.verdict = verdict
        self.calls = 0
        self.depth_value = 0

    def depth(self):
        return self.depth_value

    def submit_verify(self, sig, messages, lane="interactive",
                      max_wait_ms=None):
        self.calls += 1
        fut = ServeFuture()
        fut.set_result(self.verdict)
        return fut


def _stub_fleet(world, n=3, tenants=None):
    """n stub replicas behind loopback transports + a router over them."""
    replicas, transports, clients = {}, {}, {}
    for i in range(n):
        rid = "r%d" % i
        rep = rpc.Replica(
            StubEngine(), world.codec, tenants=tenants, replica_id=rid
        )
        t = rpc.LoopbackTransport(rep)
        replicas[rid] = rep
        transports[rid] = t
        clients[rid] = rpc.GatewayClient(
            t, world.codec, api_key="key-a"
        )
    router = ReplicaRouter(
        clients,
        retry_policy=RetryPolicy(
            max_attempts=n + 1,
            base_delay=0.0,
            jitter=0.0,
            retryable=(TransientBackendError,),
            sleep=lambda s: None,
        ),
    )
    return router, replicas, transports


def _sig(world):
    return Signature(world.params.g, world.params.g)


def test_session_affinity_and_spread(world):
    router, replicas, _ = _stub_fleet(world)
    sig = _sig(world)
    # same session -> same replica, every time
    for session in ("alpha", "beta", "gamma"):
        primary = router.candidates(session)[0]
        for _ in range(5):
            fut = router.submit_verify(sig, [1], session=session)
            assert fut.replica_id == primary
            assert fut.result(5.0) is True
    # many sessions -> more than one replica does work
    for i in range(48):
        router.submit_verify(sig, [1], session="s%d" % i).result(5.0)
    busy = [rid for rid, rep in replicas.items() if rep.engine.calls > 0]
    assert len(busy) >= 2, "consistent hash degenerated onto one replica"


def test_demoted_primary_spills_least_loaded(world):
    metrics.reset()
    router, replicas, _ = _stub_fleet(world)
    session = "sticky"
    ring = router.candidates(session)
    primary, others = ring[0], ring[1:]
    # beacons: primary quarantined, others healthy with distinct depths
    router.directory.observe(_beacon(primary, state="quarantined"))
    router.directory.observe(_beacon(others[0], depth=7))
    router.directory.observe(_beacon(others[1], depth=2))
    chosen = router.route(session)
    assert chosen == others[1]  # least-loaded routable
    assert metrics.get_count("gateway_spills") == 1
    assert metrics.get_count("gateway_affinity_hits") == 0
    # primary readmits -> affinity returns
    router.directory.observe(_beacon(primary))
    assert router.route(session) == primary
    assert metrics.get_count("gateway_affinity_hits") == 1


def test_failover_settles_on_survivor(world):
    metrics.reset()
    router, replicas, transports = _stub_fleet(world)
    sig = _sig(world)
    session = "doomed"
    primary = router.candidates(session)[0]
    transports[primary].kill()
    fut = router.submit_verify(sig, [1], session=session)
    assert fut.result(5.0) is True  # settled via retry on a survivor
    assert fut.replica_id != primary
    assert router.directory.state(primary) == gossip.DOWN
    assert metrics.get_count("gateway_failovers") >= 1


def test_all_replicas_down_raises_typed(world):
    router, _, transports = _stub_fleet(world)
    for t in transports.values():
        t.kill()
    fut = router.submit_verify(_sig(world), [1], session="x")
    with pytest.raises(TransientBackendError):
        fut.result(5.0)


def test_fleet_chaos_zero_dangling_futures(world):
    """Mixed traffic across 3 replicas while one is killed mid-run:
    every future settles (verdict or typed error), the dead replica is
    demoted, and it rejoins via a fresh beacon after revival."""
    router, replicas, transports = _stub_fleet(world)
    loop = router.gossip_loop(clock=FakeClock())
    sig = _sig(world)
    victim = router.candidates("sess-0")[0]

    futures = []
    for i in range(60):
        if i == 20:
            transports[victim].kill()
        futures.append(
            router.submit_verify(sig, [1], session="sess-%d" % (i % 7))
        )
    settled = 0
    for fut in futures:
        try:
            assert fut.result(5.0) is True
        except TransientBackendError:
            pass  # typed, loud — but never dangling
        settled += 1
    assert settled == len(futures)
    loop.step()
    assert router.directory.state(victim) == gossip.DOWN

    transports[victim].revive()
    loop.step()  # fresh healthy beacon readmits
    assert router.directory.state(victim) == gossip.UP
    before = replicas[victim].engine.calls
    for _ in range(5):
        router.submit_verify(sig, [1], session="sess-0").result(5.0)
    assert replicas[victim].engine.calls > before  # traffic returned


def test_tenant_rate_limit_over_the_wire(world):
    """A throttled tenant's refusal crosses the wire as a typed
    retry-after response; other tenants on the SAME replica sail on."""
    clock = FakeClock()
    tenants = TenantTable(clock=clock)
    tenants.provision("slow", "key-slow", rate_per_s=1.0, burst=1)
    tenants.provision("fast", "key-fast")
    rep = rpc.Replica(StubEngine(), world.codec, tenants=tenants)
    t = rpc.LoopbackTransport(rep)
    slow = rpc.GatewayClient(t, world.codec, api_key="key-slow")
    fast = rpc.GatewayClient(t, world.codec, api_key="key-fast")
    sig = _sig(world)

    assert slow.submit_verify(sig, [1]).result(5.0) is True
    with pytest.raises(TenantRateLimitError) as exc:
        slow.submit_verify(sig, [1]).result(5.0)
    assert exc.value.retry_after_s == pytest.approx(1.0)
    for _ in range(5):
        assert fast.submit_verify(sig, [1]).result(5.0) is True
    clock.advance(1.5)
    assert slow.submit_verify(sig, [1]).result(5.0) is True


def test_unknown_program_and_garbage_frames(world):
    rep = rpc.Replica(StubEngine(), world.codec, replica_id="rg")
    # unknown message type -> typed bad_request envelope, not a hang
    resp = rep.handle_frame(wire.encode_frame(0x3F, b"", seq=9))
    msg_type, seq, payload = wire.decode_frame(resp)
    assert (msg_type, seq) == (wire.MSG_ERROR, 9)
    assert type(wire.decode_error(payload)) is DeserializationError
    # undecodable frame -> error envelope with seq 0
    resp = rep.handle_frame(b"\x00" * wire.HEADER_BYTES)
    msg_type, seq, payload = wire.decode_frame(resp)
    assert (msg_type, seq) == (wire.MSG_ERROR, 0)


# --- end to end: real crypto through a loopback replica ---------------------


def test_full_session_over_loopback_rpc(world, engine):
    tenants = TenantTable()
    tenants.provision("acme", "key-acme")
    rep = rpc.Replica(engine, world.codec, tenants=tenants, replica_id="r0")
    client = rpc.GatewayClient(
        rpc.LoopbackTransport(rep), world.codec,
        api_key="key-acme", session="e2e",
    )
    beacon = client.poll_beacon()
    assert beacon.state == "healthy"
    assert beacon.replica_id == "r0"
    assert beacon.executors == 1

    msgs = [rand_fr() for _ in range(MSGS)]
    esk, epk = elgamal_keygen(world.params.ctx.sig, world.params.g)
    sig_req, _rand = client.submit_prepare(msgs, epk).result(120.0)
    cred = client.submit_mint(sig_req, msgs, esk).result(120.0)
    assert client.submit_verify(cred, msgs).result(120.0) is True
    proof, challenge, revealed = client.submit_show_prove(
        cred, msgs
    ).result(120.0)
    assert client.submit_show_verify(
        proof, revealed, challenge
    ).result(120.0) is True
    # a forged credential still verdicts False (not an error) over RPC
    forged = Signature(world.params.g, world.params.g)
    assert client.submit_verify(forged, msgs).result(120.0) is False


def test_loadgen_rpc_transport(world, engine, session_objects):
    so = session_objects
    rep = rpc.Replica(engine, world.codec, replica_id="lg")
    client = rpc.GatewayClient(
        rpc.LoopbackTransport(rep), world.codec
    )
    report = run_loadgen(
        client,
        [(so.cred, so.msgs, True)],
        duration_s=0.4,
        concurrency=2,
        transport="rpc",
    )
    assert report["transport"] == "rpc"
    assert report["completed"] > 0
    assert report["errors"] == 0
    assert report["dropped_futures"] == 0
    assert report["verdict_mismatches"] == 0
    assert report["rpc_overhead_s"] is not None
    assert report["rpc_overhead_s"] >= 0.0


def test_session_loadgen_rpc_transport(world, engine):
    rep = rpc.Replica(engine, world.codec, replica_id="slg")
    client = rpc.GatewayClient(
        rpc.LoopbackTransport(rep), world.codec
    )
    esk, epk = elgamal_keygen(world.params.ctx.sig, world.params.g)
    pool = [([rand_fr() for _ in range(MSGS)], epk, esk)]
    report = run_session_loadgen(
        client, pool, duration_s=0.5, concurrency=2, transport="rpc"
    )
    assert report["transport"] == "rpc"
    assert report["sessions_completed"] > 0
    assert report["errors"] == 0
    assert report["failed_shows"] == 0
    assert report["rpc_overhead_s"] is not None
