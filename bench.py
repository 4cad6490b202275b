"""Driver benchmark — prints ONE JSON line with the north-star metric.

Metric (BASELINE.json): aggregated-credential verifies/sec, batch=1k,
6 attrs, 3-of-5 threshold. The work per credential is the reference's
`Signature::verify` (signature.rs:472-478): one (msg_count+1)-term
OtherGroup MSM + one 2-pairing product check.

The headline `value` is the attribute-grouped combined batch verification
(coconut_tpu/tpu/backend.py `fused_verify_grouped`): the standard
small-exponents batch-verify equation regrouped per verkey component, so a
1024-credential batch costs q+2 pairings TOTAL plus q+2 shared-point MSMs.
Semantics: ONE accept/reject boolean for the whole batch (soundness error
2^-128 per forged credential); per-credential bits come from the fused
per-credential kernel, reported as `percred_verifies_per_sec` (a failing
batch bisects to it). Both paths are differentially tested against the
pure-Python spec (tests/test_backends.py).

Also measured (BASELINE.json configs):
  config 3: batched PoKOfSignature verify (2 hidden / 4 revealed)  [default]
  config 4: threshold issuance, batched blind-sign MSMs            [default]
  config 5: short streamed run through verify_stream               [BENCH_STREAM=1]
  serve lane: loadgen against the online CredentialService         [--serve]
  issue lane: loadgen against the online IssuanceService           [--issue]
  session lane: full-session loadgen against the ProtocolEngine    [--session]
  gateway lane: RPC-vs-direct goodput through the fleet gateway    [--gateway]
  batchverify lane: RLC-combined vs exact verify/show-verify       [--batchverify]
    (ISSUE 16 — B in BENCH_BATCHVERIFY_SIZES, crossover point,
    <= 2 final exps per combined batch; BENCH_BATCHVERIFY=0 skips)
  state lane: show-verify goodput bare vs WAL-backed nullifiers    [--state]
    (ISSUE 17 — group-commit fsync per batch, ratio >=
    BENCH_STATE_MIN_RATIO (0.85); BENCH_STATE=0 skips)
  hashmsm lane: host-vs-device hash-to-G1 + Horner-vs-bucketed MSM [--hashmsm]
    (ISSUE 18 — bit parity + path selection asserted from counters
    everywhere, "new path faster" floor on the real chip only;
    BENCH_HASHMSM=0 skips)

Phase timers (VERDICT round-1 item 9): host encode, device kernel, readback.
Env knobs: BENCH_BATCH (default 1024), BENCH_REPS (default 5),
BENCH_BACKEND (jax|python), BENCH_PERCRED/BENCH_SHOW/BENCH_ISSUE (default 1),
BENCH_STREAM (default 1 — config 5 is driver-captured), BENCH_STREAM_BATCHES
(default 8), BENCH_ISSUE_N (default 1024), BENCH_COMBINED (default 0),
BENCH_MULTIVK (default 0 — 8-verkey rotation datapoint), BENCH_PROFILE
(default 0 — one traced rep of the headline to BENCH_PROFILE_DIR).

Serve lane (`python bench.py --serve`): closed-loop loadgen at saturation
against coconut_tpu/serve (dynamic batching, admission control), embedding
p50/p95/p99 request latency, goodput, mean batch occupancy, and rejection
counts in the same JSON line under "serve". Knobs: BENCH_SERVE_SECONDS
(default 2), BENCH_SERVE_MAX_BATCH (default 4), BENCH_SERVE_CONCURRENCY
(default 2*max_batch), BENCH_SERVE_MODE (per_credential|grouped),
BENCH_SERVE_FORGED (default 1 — forged credentials in the pool),
BENCH_OFFLINE=0 skips the offline lanes so `--serve` can run standalone
(the CPU smoke in ci.sh does exactly that). BENCH_SERVE_DEVICES="1,2,4,8"
additionally runs the dispatcher-pool device-count sweep — per pool size:
goodput, p99 latency, occupancy, per-device dispatch counts, and scaling
efficiency goodput_n/(n*goodput_1) — embedded under "serve"."scaling"
(BENCH_SERVE_SWEEP_SECONDS trims the per-point duration; on the jax
backend each executor pins to a real device, elsewhere executors are
unpinned workers).

Issue lane (`python bench.py --issue`): pure-issuance closed-loop loadgen
(issue_fraction=1.0) against a real BENCH_ISSUE_AUTHORITIES-of-
BENCH_ISSUE_THRESHOLD (default 5, t=3) IssuanceService — quorum fan-out,
first-t-of-n aggregation, verify-before-release on the hot path —
embedding credentials/sec, quorum-wait p50/p95/p99, hedge rate, and mint
outcome counts under "issue". Knobs: BENCH_ISSUE_SECONDS (default 2),
BENCH_ISSUE_MAX_BATCH (default 4), BENCH_ISSUE_CONCURRENCY (default
2*max_batch); BENCH_ISSUE=0 skips (the same gate as the offline config-4
blind-sign lane); composes with --serve and BENCH_OFFLINE=0.

Session lane (`python bench.py --session`): closed-loop FULL protocol
sessions (prepare -> mint -> show_prove -> show_verify, one credential
each) against an engine.ProtocolEngine running all five phases on one
executor pool — embedding sessions/sec, end-to-end session p50/p95/p99,
the per-phase latency breakdown, and the per-program jit-shape counters
(flat after warmup = no cross-program recompiles) under "session".
Knobs: BENCH_SESSION_SECONDS (default 2), BENCH_SESSION_MAX_BATCH
(default 4), BENCH_SESSION_CONCURRENCY (default 2*max_batch),
BENCH_SESSION_AUTHORITIES/BENCH_SESSION_THRESHOLD (default 3, t=2);
BENCH_SESSION=0 skips; composes with the other lanes and
BENCH_OFFLINE=0.

Gateway lane (`python bench.py --gateway`, ISSUE 13): the SAME warm
CredentialService measured twice back-to-back under the closed-loop
verify loadgen — direct submit calls, then through a net.Replica over a
real loopback TCP socket (CTS-RPC/1 frames both ways via
GatewayClient) — embedding both reports, the goodput ratio, and the
measured per-request rpc_overhead_s under "gateway". Asserts RPC
goodput >= BENCH_GATEWAY_MIN_RATIO (default 0.8) of direct. Knobs:
BENCH_GATEWAY_SECONDS (default 2), BENCH_GATEWAY_MAX_BATCH (default 4),
BENCH_GATEWAY_CONCURRENCY (default 2*max_batch); BENCH_GATEWAY=0 skips;
composes with the other lanes and BENCH_OFFLINE=0.

Lifecycle lane (`python bench.py --lifecycle`, ISSUE 14): the
warm-restart headline. A predecessor "process" (a simulated-compile
engine whose per-shape compile wall models the minutes-long cold
compile of a fused program at sub-second scale) serves a shape set,
drains through a real LifecycleController (shape manifest saved), then
two successors race to their first SLO-compliant response: COLD (no
manifest, no persistent compilation cache — every shape pays the full
wall) vs WARM (manifest replayed through warm_shapes + cache hits).
Embeds both restart numbers AND the measured compile_plus_run floor
under "lifecycle"; asserts warm <= BENCH_LIFECYCLE_MAX_FRACTION
(default 0.5) of cold. Knobs: BENCH_LIFECYCLE_COMPILE_S (default 0.3,
the per-shape simulated wall), BENCH_LIFECYCLE_SHAPES (default 3);
BENCH_LIFECYCLE=0 skips; composes with the other lanes and
BENCH_OFFLINE=0.

Key-lifecycle lane (`python bench.py --keylife`, ISSUE 15): goodput
before / during / after a live t/n reshare on a 5-authority engine born
from an online DKG — one proactive refresh plus one 3-of-5 -> 2-of-5
reshare land mid-traffic on a side thread while the closed-loop verify
loadgen keeps driving pre-rollover credentials. Embeds the three goodput
numbers, the during/before degradation ratio, and the after/before
rollover ratio under "keylife"; asserts the during phase stayed non-zero
(zero-downtime rollover) and zero dropped futures. Knobs:
BENCH_KEYLIFE_SECONDS (default 2), BENCH_KEYLIFE_MAX_BATCH (default 4),
BENCH_KEYLIFE_CONCURRENCY (default 2*max_batch); BENCH_KEYLIFE=0 skips;
composes with the other lanes and BENCH_OFFLINE=0.

Chaos-recovery sub-report (ISSUE 9, on by default with --serve;
BENCH_CHAOS=0 skips): a three-phase loadgen pass — clean, then one
injected executor crash + one hung dispatch, then post-fault — against a
BENCH_CHAOS_DEVICES-wide pool (default 4) with a fast watchdog and
probation ladder, embedded under "serve"."chaos_recovery": goodput
before/during/after, the recovery ratio, and the quarantine/watchdog/
redistribution counters. BENCH_CHAOS_SECONDS sets the per-phase duration
(default 0.8).
"""

import json
import os
import sys
import time

NORTH_STAR = 10_000.0  # verifies/sec, BASELINE.json north_star


def _timeit(fn, reps):
    """(best seconds, result) over reps calls."""
    best, out = None, None
    for _ in range(reps):
        t0 = time.time()
        out = fn()
        dt = time.time() - t0
        best = dt if best is None else min(best, dt)
    return best, out


def bench_python(batch, ge, params, vk, sigs, msgs_list, extras):
    from coconut_tpu import metrics
    from coconut_tpu.ps import ps_verify

    with metrics.timer("kernel"):
        bits = [ps_verify(s, m, vk, params) for s, m in zip(sigs, msgs_list)]
    metrics.count("verifies", batch)
    dt = metrics.snapshot()["timers_s"]["kernel"]
    assert all(bits)
    extras["kernel_s"] = round(dt, 3)
    return batch / dt


def bench_serve(ge, params, vk, sigs, msgs_list, extras, backend_name):
    """Online-serving lane: closed-loop loadgen at saturation against the
    dynamic-batching CredentialService; embeds the SLO report (p50/p95/p99
    latency, goodput, mean batch occupancy, rejection counts) under
    extras["serve"], plus a tracing-overhead probe (goodput with
    COCONUT_TRACE off vs on, BENCH_TRACE_OVERHEAD=0 to skip) under
    extras["serve"]["trace_overhead"]. Returns the goodput
    (requests/sec)."""
    from coconut_tpu.serve import CredentialService, run_loadgen
    from coconut_tpu.signature import Signature

    seconds = float(os.environ.get("BENCH_SERVE_SECONDS", "2"))
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", "4"))
    # 2x max_batch closed-loop clients saturate the coalescer: there is
    # always a full batch's worth of backlog, so occupancy reads the
    # batching ceiling rather than arrival luck
    concurrency = int(
        os.environ.get("BENCH_SERVE_CONCURRENCY", str(2 * max_batch))
    )
    mode = os.environ.get("BENCH_SERVE_MODE", "per_credential")
    max_wait_ms = float(os.environ.get("BENCH_SERVE_MAX_WAIT_MS", "20"))

    pool = [(s, m, True) for s, m in zip(sigs, msgs_list)]
    if os.environ.get("BENCH_SERVE_FORGED", "1") == "1":
        # forged credentials in the mix exercise the demux under load (and,
        # in grouped mode, the bisection ladder); the loadgen checks each
        # verdict against its expectation, so a demux bug surfaces as
        # verdict_mismatches, not as silent throughput
        for s, m in list(zip(sigs, msgs_list))[: max(1, len(sigs) // 8)]:
            forged = Signature(s.sigma_1, params.ctx.sig.mul(s.sigma_2, 2))
            pool.append((forged, m, False))

    svc = CredentialService(
        backend_name,
        vk,
        params,
        mode=mode,
        max_batch=max_batch,
        max_wait_ms=max_wait_ms,
    )
    with svc:
        # warm the backend at the serving shape OUTSIDE the timed window
        # (on the jax backend the first batch pays compile time; the
        # loadgen's occupancy/latency deltas start after this settles)
        warm = [
            svc.submit(*pool[i % len(pool)][:2])
            for i in range(max_batch)
        ]
        for f in warm:
            f.result(timeout=600.0)
        report = run_loadgen(
            svc,
            pool,
            duration_s=seconds,
            arrival="closed",
            concurrency=concurrency,
        )
        trace_overhead = None
        if os.environ.get("BENCH_TRACE_OVERHEAD", "1") == "1":
            # tracing-overhead probe (ISSUE 6 acceptance: enabled-tracing
            # goodput within ~5% of disabled): two short back-to-back
            # closed-loop passes against the SAME warm service, tracing
            # off then on. Reported, not asserted — sub-second CPU lanes
            # are too noisy for a hard gate, the BENCH JSON is the audit
            # surface. BENCH_TRACE_OVERHEAD=0 skips.
            from coconut_tpu.obs import trace as otrace

            t_secs = float(os.environ.get("BENCH_TRACE_SECONDS", "1"))
            was_enabled = otrace.enabled()
            otrace.disable()
            off = run_loadgen(
                svc, pool, duration_s=t_secs,
                arrival="closed", concurrency=concurrency,
            )
            otrace.enable()
            on = run_loadgen(
                svc, pool, duration_s=t_secs,
                arrival="closed", concurrency=concurrency,
            )
            if not was_enabled:
                otrace.disable()
            off_g, on_g = off["goodput_per_s"], on["goodput_per_s"]
            trace_overhead = {
                "off_goodput_per_s": off_g,
                "on_goodput_per_s": on_g,
                "overhead_frac": (
                    round((off_g - on_g) / off_g, 4) if off_g else None
                ),
            }
    assert report["dropped_futures"] == 0, (
        "serve lane dropped futures: %r" % (report,)
    )
    assert report["verdict_mismatches"] == 0, (
        "serve lane verdict mismatch: %r" % (report,)
    )
    occ = report["mean_batch_occupancy"]
    assert occ is not None and occ > 0.5, (
        "serve lane under-coalesced at saturation "
        "(mean_batch_occupancy=%r): %r" % (occ, report)
    )
    extras["serve"] = {
        "mode": mode,
        "max_batch": max_batch,
        "max_wait_ms": max_wait_ms,
        **report,
        "trace_overhead": trace_overhead,
    }
    if os.environ.get("BENCH_SERVE_DEVICES"):
        extras["serve"]["scaling"] = _bench_serve_scaling(
            params, vk, pool, backend_name, mode, max_batch, max_wait_ms
        )
    if os.environ.get("BENCH_CHAOS", "1") == "1":
        extras["serve"]["chaos_recovery"] = _bench_chaos_recovery(
            params, vk, pool, backend_name, mode, max_batch, max_wait_ms
        )
    return report["goodput_per_s"]


def bench_issue(ge, params, vk, sigs, msgs_list, extras, backend_name):
    """Threshold-issuance lane (--issue): pure-issuance closed-loop
    loadgen (issue_fraction=1.0) against a REAL t-of-n IssuanceService —
    quorum fan-out, first-t-of-n aggregation, verify-before-release all
    on the hot path. Embeds credentials/sec, quorum-wait p50/p95/p99,
    and the hedge rate under extras["issue"]; returns the goodput
    (credentials/sec). BENCH_ISSUE=0 skips (same gate as the offline
    config-4 blind-sign lane)."""
    from coconut_tpu import metrics
    from coconut_tpu.elgamal import elgamal_keygen
    from coconut_tpu.issue import IssuanceService
    from coconut_tpu.keygen import trusted_party_SSS_keygen
    from coconut_tpu.serve import CredentialService, run_loadgen
    from coconut_tpu.signature import SignatureRequest
    from coconut_tpu.sss import rand_fr

    seconds = float(os.environ.get("BENCH_ISSUE_SECONDS", "2"))
    max_batch = int(os.environ.get("BENCH_ISSUE_MAX_BATCH", "4"))
    concurrency = int(
        os.environ.get("BENCH_ISSUE_CONCURRENCY", str(2 * max_batch))
    )
    total = int(os.environ.get("BENCH_ISSUE_AUTHORITIES", "5"))
    threshold = int(os.environ.get("BENCH_ISSUE_THRESHOLD", "3"))

    _, _, signers = trusted_party_SSS_keygen(threshold, total, params)
    ipool = []
    for _ in range(4 * max_batch):
        msgs = [rand_fr() for _ in range(ge.MSG_COUNT)]
        esk, epk = elgamal_keygen(params.ctx.sig, params.g)
        req, _ = SignatureRequest.new(msgs, 2, epk, params)
        ipool.append((req, msgs, esk))

    isvc = IssuanceService(
        signers, params, threshold, backend=backend_name,
        max_batch=max_batch,
    )
    # the mixed-workload loadgen drives a verify service too; at
    # issue_fraction=1.0 it sits idle but must exist and be started
    vsvc = CredentialService(
        backend_name, vk, params, max_batch=max_batch
    )
    with vsvc, isvc:
        # warm every authority at the serving shape OUTSIDE the timed
        # window (on the jax backend the first sign pays compile time)
        warm = [
            isvc.submit(*ipool[i % len(ipool)]) for i in range(max_batch)
        ]
        for f in warm:
            f.result(timeout=600.0)
        report = run_loadgen(
            vsvc,
            [(sigs[0], msgs_list[0], True)],
            duration_s=seconds,
            arrival="closed",
            concurrency=concurrency,
            issue_service=isvc,
            issue_pool=ipool,
            issue_fraction=1.0,
        )
    issue = report["issue"]
    assert issue["dropped_futures"] == 0, (
        "issue lane dropped futures: %r" % (issue,)
    )
    assert issue["mint_mismatches"] == 0, (
        "issue lane released a falsy mint: %r" % (issue,)
    )
    assert issue["errors"] == 0, "issue lane errors: %r" % (issue,)
    assert issue["minted"] > 0, "issue lane minted nothing: %r" % (issue,)
    qwait = (
        metrics.snapshot()
        .get("histograms", {})
        .get("issue_quorum_wait_s", {})
    )
    extras["issue"] = {
        "authorities": total,
        "threshold": threshold,
        "max_batch": max_batch,
        "concurrency": concurrency,
        **issue,
        "credentials_per_sec": issue["goodput_per_s"],
        "quorum_wait_s": {
            "p50": qwait.get("p50_s"),
            "p95": qwait.get("p95_s"),
            "p99": qwait.get("p99_s"),
        },
        "hedge_rate": (
            round(issue["hedges"] / issue["fanouts"], 4)
            if issue["fanouts"]
            else None
        ),
    }
    return issue["goodput_per_s"]


def bench_session(ge, params, extras, backend_name):
    """Full-session lane (--session): closed-loop FULL protocol sessions
    (prepare -> mint -> show_prove -> show_verify, one credential each)
    against a ProtocolEngine running all five phases on one executor
    pool. Embeds sessions/sec, end-to-end session p50/p95/p99, and the
    per-phase latency breakdown under extras["session"]; returns
    sessions/sec. Knobs: BENCH_SESSION_SECONDS (default 2),
    BENCH_SESSION_MAX_BATCH (default 4), BENCH_SESSION_CONCURRENCY
    (default 2*max_batch), BENCH_SESSION_AUTHORITIES /
    BENCH_SESSION_THRESHOLD (default 3, t=2); BENCH_SESSION=0 skips."""
    from coconut_tpu import metrics
    from coconut_tpu.elgamal import elgamal_keygen
    from coconut_tpu.engine import ProtocolEngine
    from coconut_tpu.keygen import trusted_party_SSS_keygen
    from coconut_tpu.serve import run_session_loadgen
    from coconut_tpu.sss import rand_fr

    seconds = float(os.environ.get("BENCH_SESSION_SECONDS", "2"))
    max_batch = int(os.environ.get("BENCH_SESSION_MAX_BATCH", "4"))
    concurrency = int(
        os.environ.get("BENCH_SESSION_CONCURRENCY", str(2 * max_batch))
    )
    total = int(os.environ.get("BENCH_SESSION_AUTHORITIES", "3"))
    threshold = int(os.environ.get("BENCH_SESSION_THRESHOLD", "2"))

    _, _, signers = trusted_party_SSS_keygen(threshold, total, params)
    pool = []
    for _ in range(4 * max_batch):
        msgs = [rand_fr() for _ in range(ge.MSG_COUNT)]
        esk, epk = elgamal_keygen(params.ctx.sig, params.g)
        pool.append((msgs, epk, esk))
    revealed = list(range(2, ge.MSG_COUNT))

    engine = ProtocolEngine(
        signers, params, threshold,
        count_hidden=2, revealed_msg_indices=revealed,
        backend=backend_name, max_batch=max_batch,
    )
    jit0 = {
        ns: metrics.get_count("%s_jit_shapes" % ns)
        for ns in ("serve", "prep", "prove", "showv")
    }
    with engine:
        # one full warmup session outside the timed window: every
        # program's serving shape compiles here, not in the report
        msgs, epk, esk = pool[0]
        req, _ = engine.submit_prepare(msgs, epk).result(600.0)
        cred = engine.submit_mint(req, msgs, esk).result(600.0)
        proof, chal, rev = engine.submit_show_prove(cred, msgs).result(600.0)
        assert engine.submit_show_verify(proof, rev, chal).result(600.0)
        jit_warm = {
            ns: metrics.get_count("%s_jit_shapes" % ns)
            for ns in ("serve", "prep", "prove", "showv")
        }
        report = run_session_loadgen(
            engine, pool, duration_s=seconds, concurrency=concurrency
        )
    jit_end = {
        ns: metrics.get_count("%s_jit_shapes" % ns)
        for ns in ("serve", "prep", "prove", "showv")
    }
    assert report["errors"] == 0, "session lane errors: %r" % (report,)
    assert report["failed_shows"] == 0, (
        "a minted credential failed show-verify: %r" % (report,)
    )
    assert report["sessions_completed"] > 0, (
        "session lane completed nothing: %r" % (report,)
    )
    extras["session"] = {
        "authorities": total,
        "threshold": threshold,
        "max_batch": max_batch,
        **report,
        # flat counters after warmup = heterogeneous traffic never
        # cross-program recompiled (the engine's multiplexing claim)
        "jit_shapes_after_warmup": jit_warm,
        "jit_shapes_after_run": jit_end,
        "jit_shapes_stable": jit_warm == jit_end,
        "jit_shapes_cold": jit0,
    }
    return report["sessions_per_s"]


def bench_gateway(ge, params, vk, sigs, msgs_list, extras, backend_name):
    """RPC-ingress lane (--gateway, ISSUE 13): measure the wire tax. The
    SAME warm CredentialService is driven twice back-to-back by the
    closed-loop verify loadgen — direct submit calls, then through a
    net.Replica serving CTS-RPC/1 frames on a real loopback TCP socket
    (SocketTransport + GatewayClient). Embeds both reports, the goodput
    ratio, and the measured per-request rpc_overhead_s under
    extras["gateway"]; asserts ratio >= BENCH_GATEWAY_MIN_RATIO
    (default 0.8). Returns the RPC goodput (requests/sec).
    BENCH_GATEWAY=0 skips."""
    from coconut_tpu import net
    from coconut_tpu.serve import CredentialService, run_loadgen

    seconds = float(os.environ.get("BENCH_GATEWAY_SECONDS", "2"))
    max_batch = int(os.environ.get("BENCH_GATEWAY_MAX_BATCH", "4"))
    concurrency = int(
        os.environ.get("BENCH_GATEWAY_CONCURRENCY", str(2 * max_batch))
    )
    min_ratio = float(os.environ.get("BENCH_GATEWAY_MIN_RATIO", "0.8"))

    pool = [(s, m, True) for s, m in zip(sigs, msgs_list)][: 8 * max_batch]
    codec = net.WireCodec(params)
    svc = CredentialService(
        backend_name, vk, params, max_batch=max_batch, max_wait_ms=20.0
    )
    replica = net.Replica(svc, codec, replica_id="bench-r0")
    with svc:
        # warm the backend at the serving shape outside both timed passes
        warm = [
            svc.submit(*pool[i % len(pool)][:2]) for i in range(max_batch)
        ]
        for f in warm:
            f.result(timeout=600.0)
        direct = run_loadgen(
            svc, pool, duration_s=seconds, arrival="closed",
            concurrency=concurrency,
        )
        replica.serve()
        client = net.GatewayClient(net.SocketTransport(replica.address),
                                   codec)
        try:
            rpc = run_loadgen(
                client, pool, duration_s=seconds, arrival="closed",
                concurrency=concurrency, transport="rpc",
            )
        finally:
            client.close()
            replica.close()
    for name, rep in (("direct", direct), ("rpc", rpc)):
        assert rep["completed"] > 0, (
            "gateway lane %s pass completed nothing: %r" % (name, rep)
        )
        assert rep["dropped_futures"] == 0, (
            "gateway lane %s pass dropped futures: %r" % (name, rep)
        )
        assert rep["verdict_mismatches"] == 0, (
            "gateway lane %s pass verdict mismatch: %r" % (name, rep)
        )
    ratio = (
        round(rpc["goodput_per_s"] / direct["goodput_per_s"], 4)
        if direct["goodput_per_s"]
        else None
    )
    assert ratio is not None and ratio >= min_ratio, (
        "RPC ingress costs too much: rpc/direct goodput ratio %r < %r "
        "(direct=%r rpc=%r)"
        % (ratio, min_ratio, direct["goodput_per_s"],
           rpc["goodput_per_s"])
    )
    extras["gateway"] = {
        "max_batch": max_batch,
        "concurrency": concurrency,
        "min_ratio": min_ratio,
        "goodput_ratio": ratio,
        "direct": direct,
        "rpc": rpc,
    }
    return rpc["goodput_per_s"]


def bench_state(ge, params, extras, backend_name):
    """Durable-state lane (--state, ISSUE 17): the WAL tax. The same
    show-verify traffic is driven twice through a ProtocolEngine —
    first bare, then with a StateStore-backed nullifier guard (device
    membership probe + group-commit WAL append per batch) — and the
    goodput ratio must stay >= BENCH_STATE_MIN_RATIO (default 0.85).
    Every show is a FRESH re-randomization of one credential, so every
    lane commits a new nullifier: the durable pass pays the full
    journal cost, one fsync per engine batch (group commit), never one
    per lane — the artifact embeds wal_appends vs wal_fsyncs to prove
    the policy. Knobs: BENCH_STATE_SHOWS (default 64),
    BENCH_STATE_MAX_BATCH (default 4); BENCH_STATE=0 skips."""
    import tempfile

    from coconut_tpu import metrics
    from coconut_tpu.elgamal import elgamal_keygen
    from coconut_tpu.engine import ProtocolEngine
    from coconut_tpu.keygen import trusted_party_SSS_keygen
    from coconut_tpu.sss import rand_fr
    from coconut_tpu.state import StateStore

    n_shows = int(os.environ.get("BENCH_STATE_SHOWS", "64"))
    max_batch = int(os.environ.get("BENCH_STATE_MAX_BATCH", "4"))
    min_ratio = float(os.environ.get("BENCH_STATE_MIN_RATIO", "0.85"))

    _, _, signers = trusted_party_SSS_keygen(2, 3, params)
    revealed = list(range(2, ge.MSG_COUNT))
    msgs = [rand_fr() for _ in range(ge.MSG_COUNT)]
    esk, epk = elgamal_keygen(params.ctx.sig, params.g)

    def _run_pass(store):
        """One timed show-verify pass; returns (goodput, commits)."""
        engine = ProtocolEngine(
            signers, params, 2,
            count_hidden=2, revealed_msg_indices=revealed,
            backend=backend_name, max_batch=max_batch,
            state_store=store,
        )
        with engine:
            req, _ = engine.submit_prepare(msgs, epk).result(600.0)
            cred = engine.submit_mint(req, msgs, esk).result(600.0)
            # each lane shows a FRESH re-randomization: distinct
            # nullifiers, so the durable pass commits on every lane
            # (+1 warm show outside the timed window)
            shows = [
                engine.submit_show_prove(cred, msgs).result(600.0)
                for _ in range(n_shows + 1)
            ]
            proof, chal, rev = shows[0]
            assert engine.submit_show_verify(proof, rev, chal).result(600.0)
            c0 = metrics.get_count("nullifier_commits")
            t0 = time.time()
            futs = [
                engine.submit_show_verify(p, r, c)
                for p, c, r in shows[1:]
            ]
            ok = sum(1 for f in futs if f.result(600.0) is True)
            dt = time.time() - t0
            assert ok == n_shows, (
                "state lane: %d of %d fresh shows verified" % (ok, n_shows)
            )
        return n_shows / dt, metrics.get_count("nullifier_commits") - c0

    goodput_bare, _ = _run_pass(None)
    wal_appends0 = metrics.get_count("wal_appends")
    wal_fsyncs0 = metrics.get_count("wal_fsyncs")
    root = tempfile.mkdtemp(prefix="bench-state-")
    try:
        store = StateStore(root, replica_id="bench-r0")
        goodput_store, commits = _run_pass(store)
        store.close()
    finally:
        import shutil

        shutil.rmtree(root, ignore_errors=True)
    wal_appends = metrics.get_count("wal_appends") - wal_appends0
    wal_fsyncs = metrics.get_count("wal_fsyncs") - wal_fsyncs0

    assert commits == n_shows, (
        "durable pass committed %d nullifiers for %d timed shows"
        % (commits, n_shows)
    )
    # THE fsync policy: group commit per engine batch, never per lane —
    # with max_batch-wide batches the sync count stays well under the
    # lane count (each batch is one append_many = one fsync)
    assert wal_fsyncs <= (n_shows + 1 + max_batch - 1) // max_batch + n_shows // 2, (
        "fsync count %d looks per-lane, not per-batch (%d lanes, "
        "max_batch=%d)" % (wal_fsyncs, n_shows + 1, max_batch)
    )
    assert wal_fsyncs < wal_appends or n_shows < max_batch, (
        "group commit never amortized: %d fsyncs for %d appends"
        % (wal_fsyncs, wal_appends)
    )
    ratio = (
        round(goodput_store / goodput_bare, 4) if goodput_bare else None
    )
    assert ratio is not None and ratio >= min_ratio, (
        "durable nullifier set costs too much: with-store/bare goodput "
        "ratio %r < %r (bare=%r store=%r)"
        % (ratio, min_ratio, goodput_bare, goodput_store)
    )
    extras["state"] = {
        "fsync_policy": "group_commit_per_batch",
        "shows": n_shows,
        "max_batch": max_batch,
        "min_ratio": min_ratio,
        "goodput_bare_per_s": round(goodput_bare, 2),
        "goodput_store_per_s": round(goodput_store, 2),
        "goodput_ratio": ratio,
        "nullifier_commits": commits,
        "wal_appends": wal_appends,
        "wal_fsyncs": wal_fsyncs,
    }
    return ratio


def bench_hashmsm(ge, params, extras, backend_name):
    """Hash/MSM lane (--hashmsm, ISSUE 18): the prepare hash and the
    show-prove sigma MSM, old vs new path, BOTH asserted bit-identical. (1) prepare's
    hash stage: the host path (native cc_hash_to_g1_batch if built,
    else the Python spec) against the device SvdW kernel, messages/s.
    (2) show-prove's sigma MSM stage: the signed-Horner distinct MSM
    against the bucketed Pippenger schedule at a forced window, rows/s.
    Parity is asserted from the outputs AND from counters (the device
    batches/fallbacks and bucketed/horner dispatch counts embedded in
    the artifact). The "new path faster" floor is enforced only on the
    real chip — on the CPU CI mesh the lane proves parity + path
    selection, per the ISSUE 18 acceptance split. Knobs:
    BENCH_HASHMSM_B (default 64), BENCH_HASHMSM_K (default 32),
    BENCH_HASHMSM_WINDOW (default 5), BENCH_HASHMSM_REPS (default 3);
    BENCH_HASHMSM=0 skips."""
    import random as _random

    import jax

    from coconut_tpu import metrics, native
    from coconut_tpu.ops.curve import G1_GEN, g1
    from coconut_tpu.ops.fields import R as _FR
    from coconut_tpu.tpu import backend as tb

    B = int(os.environ.get("BENCH_HASHMSM_B", "64"))
    k = int(os.environ.get("BENCH_HASHMSM_K", "32"))
    window = int(os.environ.get("BENCH_HASHMSM_WINDOW", "5"))
    reps = int(os.environ.get("BENCH_HASHMSM_REPS", "3"))
    on_tpu = jax.default_backend() == "tpu"

    be = tb.JaxBackend()
    rng = _random.Random(0x18)

    def best_of(fn):
        best = None
        for _ in range(reps):
            t0 = time.time()
            out = fn()
            dt = time.time() - t0
            best = dt if best is None else min(best, dt)
        return out, best

    # -- prepare hash stage: host path vs device SvdW kernel ------------
    datas = [b"bench-hashmsm-%d" % i for i in range(B)]
    if native.available():
        old_name = "native"
        old_pts, t_old = best_of(
            lambda: list(native.hash_to_g1_batch(datas))
        )
    else:
        old_name = "spec"
        old_pts, t_old = best_of(
            lambda: [params.ctx.hash_to_sig(d) for d in datas]
        )
    be.hash_to_g1_batch(datas)  # warm/compile outside the clock
    hb0 = metrics.get_count("device_hash_batches")
    new_pts, t_new = best_of(lambda: be.hash_to_g1_batch(datas))
    hash_batches = metrics.get_count("device_hash_batches") - hb0
    assert new_pts == old_pts, "device hash diverges from %s" % old_name
    assert hash_batches == reps, (
        "device path not taken: batches=%d" % hash_batches
    )

    # -- show-prove MSM stage: Horner vs bucketed Pippenger -------------
    pts = [
        [g1.mul(G1_GEN, rng.randrange(1, _FR)) for _ in range(k)]
        for _ in range(B)
    ]
    scal = [[rng.randrange(_FR) for _ in range(k)] for _ in range(B)]
    scal[0][0] = 0
    mode0 = tb._BUCKET_MODE
    try:
        tb._BUCKET_MODE = "off"
        be.msm_g1_distinct(pts, scal)  # warm
        h0 = metrics.get_count("msm_horner_dispatches")
        msm_old, t_msm_old = best_of(
            lambda: be.msm_g1_distinct(pts, scal)
        )
        horner_disp = metrics.get_count("msm_horner_dispatches") - h0
        tb._BUCKET_MODE = window
        be.msm_g1_distinct(pts, scal)  # warm
        b0 = metrics.get_count("msm_bucketed_dispatches")
        msm_new, t_msm_new = best_of(
            lambda: be.msm_g1_distinct(pts, scal)
        )
        bucket_disp = metrics.get_count("msm_bucketed_dispatches") - b0
    finally:
        tb._BUCKET_MODE = mode0
    assert msm_new == msm_old, "bucketed MSM diverges from Horner"
    assert horner_disp == reps and bucket_disp == reps, (
        "MSM path selection wrong: horner=%d bucketed=%d"
        % (horner_disp, bucket_disp)
    )

    hash_speedup = round(t_old / t_new, 4) if t_new else None
    msm_speedup = (
        round(t_msm_old / t_msm_new, 4) if t_msm_new else None
    )

    # -- measured vs model crossover (PR 19, --calibrate companion) -----
    # The cost model picks the schedule; this records whether the LIVE
    # measurement at the benchmark shape agrees, plus where the model
    # puts the crossover (pure arithmetic — probes/probe_pippenger.py
    # --calibrate is the multi-shape measured sweep).
    glv_k = 2 * k if tb._GLV_ENABLED else k
    nbits = 128 if tb._GLV_ENABLED else 255
    model_bucket = tb._bucket_cost(glv_k, nbits, window)
    model_horner = tb._horner_cost(glv_k, nbits)
    model_cross_k = next(
        (
            kk
            for kk in range(1, 4097)
            if min(
                tb._bucket_cost(kk, nbits, w) for w in range(2, 9)
            )
            < tb._horner_cost(kk, nbits)
        ),
        None,
    )
    measured_winner = (
        "bucket" if msm_speedup and msm_speedup > 1.0 else "horner"
    )
    model_winner = "bucket" if model_bucket < model_horner else "horner"
    if on_tpu:
        # the acceptance floor only binds on the device backend
        assert hash_speedup and hash_speedup > 1.0, (
            "device hash slower than %s at B=%d: x%r"
            % (old_name, B, hash_speedup)
        )
        assert msm_speedup and msm_speedup > 1.0, (
            "bucketed MSM slower than Horner at B=%d k=%d: x%r"
            % (B, k, msm_speedup)
        )
    extras["hashmsm"] = {
        "b": B,
        "k": k,
        "window": window,
        "hash_old_path": old_name,
        "hash_old_per_s": round(B / t_old, 2) if t_old else None,
        "hash_new_per_s": round(B / t_new, 2) if t_new else None,
        "hash_speedup": hash_speedup,
        "msm_old_per_s": round(B / t_msm_old, 2) if t_msm_old else None,
        "msm_new_per_s": round(B / t_msm_new, 2) if t_msm_new else None,
        "msm_speedup": msm_speedup,
        "device_hash_batches": hash_batches,
        "msm_horner_dispatches": horner_disp,
        "msm_bucketed_dispatches": bucket_disp,
        "msm_bucket_window": metrics.get_gauge("msm_bucket_window"),
        "calibration": {
            "effective_k": glv_k,
            "model_bucket_cost": round(model_bucket, 1),
            "model_horner_cost": round(model_horner, 1),
            "model_winner": model_winner,
            "measured_winner": measured_winner,
            "model_measured_agree": model_winner == measured_winner,
            "model_crossover_k": model_cross_k,
        },
        "parity_ok": True,
        "timing_floor_enforced": on_tpu,
    }
    return hash_speedup or 0.0


def bench_scenarios(ge, params, extras, backend_name):
    """Application-scenario lane (--scenarios, PR 19): a sustained
    mixed petition/e-cash/access population run against a local
    ProtocolEngine with an ElasticController in the loop, arrivals on
    a compressed diurnal "day" with one flash crowd. The artifact
    embeds the full availability timeline; the lane asserts the ISSUE
    19 acceptance bar: goodput tracks the diurnal curve (peak-half
    completions beat the trough half), the elastic pool size responds
    (at least one park or unpark), p99 stays inside the SLO through
    the flash crowd, every deliberate double-spend/re-sign is a typed
    terminal rejection, and there are zero dangling futures and zero
    unattributed errors. Knobs: BENCH_SCENARIOS_S (day length, default
    48), BENCH_SCENARIOS_BASE/_PEAK (arrival rates, default 0.25/1.0),
    BENCH_SCENARIOS_SLO_S (default 10); BENCH_SCENARIOS=0 skips."""
    import tempfile

    from coconut_tpu import metrics
    from coconut_tpu.engine import ProtocolEngine
    from coconut_tpu.engine.lifecycle import (
        ElasticController,
        ElasticPolicy,
    )
    from coconut_tpu.keygen import trusted_party_SSS_keygen
    from coconut_tpu.scenarios import (
        AccessScenario,
        DiurnalCurve,
        EcashScenario,
        FlashCrowd,
        PetitionScenario,
        Population,
        PopulationDriver,
        RateSchedule,
        ScenarioReport,
    )
    from coconut_tpu.state import StateStore

    duration = float(os.environ.get("BENCH_SCENARIOS_S", "48"))
    base_rate = float(os.environ.get("BENCH_SCENARIOS_BASE", "0.25"))
    peak_rate = float(os.environ.get("BENCH_SCENARIOS_PEAK", "1.0"))
    slo_s = float(os.environ.get("BENCH_SCENARIOS_SLO_S", "10"))

    metrics.reset()
    _, _, signers = trusted_party_SSS_keygen(2, 3, params)
    revealed = list(range(2, ge.MSG_COUNT))
    root = tempfile.mkdtemp(prefix="bench-scenarios-")
    store = StateStore(root, replica_id="bench-scn")
    engine = ProtocolEngine(
        signers, params, 2,
        count_hidden=2, revealed_msg_indices=revealed,
        backend=backend_name, devices=2, max_batch=8,
        max_wait_ms=5.0, state_store=store,
    )
    # phase the diurnal curve so the run STARTS at the trough, peaks
    # mid-day, and returns to the trough — the elastic controller
    # should shrink at the edges and grow through the middle
    curve = DiurnalCurve(base_rate, peak_rate, duration)
    crowd = FlashCrowd(
        at_s=duration * 0.5, duration_s=duration * 0.12,
        multiplier=2.0, ramp_s=duration * 0.05,
    )
    report = ScenarioReport(slo_s=slo_s, flash_window=crowd.window())
    try:
        with engine:
            # one full warmup session outside the run: every program's
            # serving shape compiles here, not inside the SLO window
            from coconut_tpu.elgamal import elgamal_keygen
            from coconut_tpu.sss import rand_fr

            w_msgs = [rand_fr() for _ in range(ge.MSG_COUNT)]
            w_esk, w_epk = elgamal_keygen(params.ctx.sig, params.g)
            req, _ = engine.submit_prepare(w_msgs, w_epk).result(600.0)
            cred = engine.submit_mint(req, w_msgs, w_esk).result(600.0)
            proof, chal, rev = engine.submit_show_prove(
                cred, w_msgs
            ).result(600.0)
            assert engine.submit_show_verify(proof, rev, chal).result(600.0)

            elastic = ElasticController(
                engine,
                policy=ElasticPolicy(
                    min_executors=1, grow_after=2, shrink_after=3
                ),
            )
            mix = [
                (2.0, PetitionScenario(
                    engine, params, campaigns=4, resign_p=0.15,
                )),
                (2.0, EcashScenario(
                    engine, params, double_spend_p=0.15,
                )),
                (1.0, AccessScenario(
                    engine, params, session_range=(2, 3),
                )),
            ]
            driver = PopulationDriver(
                Population(128, n_tenants=8, seed=0x19),
                mix,
                RateSchedule(curve, [crowd]),
                duration,
                max_in_flight=64,
                seed=0x19,
                report=report,
                engine=engine,
                elastic=elastic,
                drain_timeout_s=120.0,
            )
            out = driver.run()
    finally:
        store.close()
        import shutil

        shutil.rmtree(root, ignore_errors=True)

    totals = out["totals"]
    # zero unattributed errors, zero dangling futures
    assert totals["failed"] == 0, (
        "unattributed scenario errors: %r" % (out["error_codes"],)
    )
    assert totals["cancelled"] == 0, "dangling futures after drain"
    assert totals["completed"] > 0, "no workflow completed"
    # every deliberate double-spend / re-sign is a TYPED rejection
    rejections = out["rejections"]
    rejected_n = sum(sum(r.values()) for r in rejections.values())
    labels = set()
    for per in rejections.values():
        labels.update(per)
    assert rejected_n > 0, (
        "adversarial fractions produced no rejection — detector dead?"
    )
    assert labels == {"double_spend"}, (
        "rejections carry unexpected labels: %r" % (rejections,)
    )
    # goodput tracks the diurnal curve: completions per second through
    # the mid-day peak beat the OPENING trough quarter (the closing
    # quarter is not comparable — the drain flushes mid-day backlog
    # into it, so completions bunch there regardless of arrival rate)
    good = out["availability"]["per_second_goodput"]
    day = good[: int(duration)]
    q = len(day) // 4
    mid = day[q : len(day) - q]
    opening = day[:q]
    mid_rate = sum(mid) / max(1, len(mid))
    trough_rate = sum(opening) / max(1, len(opening))
    assert mid_rate > trough_rate, (
        "goodput does not track the diurnal curve: peak-half %.2f/s "
        "vs opening trough %.2f/s" % (mid_rate, trough_rate)
    )
    # the elastic pool responded to the swing
    elastic_out = out["elastic"]
    pool_moved = (
        (elastic_out["grown"] or 0) + (elastic_out["shrunk"] or 0) > 0
    )
    assert pool_moved, (
        "elastic pool never changed size: %r" % (elastic_out,)
    )
    # p99 stays in SLO through the flash crowd (when the window saw
    # any completions at all)
    flash_p99 = out["slo"]["flash_p99_s"]
    if out["slo"]["flash_completed"]:
        assert flash_p99 is not None and flash_p99 <= slo_s, (
            "flash-crowd p99 %.2fs blew the %.1fs SLO" % (flash_p99, slo_s)
        )

    extras["scenarios"] = {
        "duration_s": duration,
        "base_rate": base_rate,
        "peak_rate": peak_rate,
        "slo_s": slo_s,
        "flash_window": crowd.window(),
        "goodput_peak_half_per_s": round(mid_rate, 3),
        "goodput_trough_per_s": round(trough_rate, 3),
        "report": out,
    }
    return out["goodput_per_s"] or 0.0


def bench_lifecycle(extras):
    """Warm-restart lane (--lifecycle, ISSUE 14): restart-to-first-SLO-
    compliant-response, cold vs warm. The compile wall is SIMULATED
    (a fused program's minutes-long cold compile scaled to
    BENCH_LIFECYCLE_COMPILE_S seconds) so the lane runs in CI
    seconds, but the lifecycle machinery is REAL: a LifecycleController
    drains the predecessor (manifest saved), the warm successor replays
    that manifest through engine.warm_shapes with persistent-cache hits,
    and readiness gates on the replay. Embeds the cold floor, both
    restart numbers, and their ratio under extras["lifecycle"]; asserts
    warm <= BENCH_LIFECYCLE_MAX_FRACTION * cold and that the warm
    successor never pays a full compile wall. Returns the speedup
    (cold / warm). BENCH_LIFECYCLE=0 skips."""
    import tempfile

    from coconut_tpu.engine.lifecycle import (
        LifecycleController,
        ShapeManifest,
    )

    compile_s = float(os.environ.get("BENCH_LIFECYCLE_COMPILE_S", "0.3"))
    n_shapes = int(os.environ.get("BENCH_LIFECYCLE_SHAPES", "3"))
    max_fraction = float(
        os.environ.get("BENCH_LIFECYCLE_MAX_FRACTION", "0.5")
    )
    #: cache-deserialize cost as a fraction of a full compile — JAX's
    #: persistent cache loads in seconds what XLA builds in minutes
    CACHE_HIT_FRACTION, RUN_S = 0.05, 0.002
    persistent_cache = {}  # the simulated jax_compilation_cache_dir

    class SimCompileEngine:
        """Every NEW shape pays the compile wall; a persistent-cache hit
        pays the deserialize fraction. warm_shapes is the manifest-replay
        seam, exactly like ExecutionEngine's."""

        def __init__(self, name, cache=None):
            self.name = name
            self.cache = cache  # None = no persistent cache wired
            self._compiled = set()
            self._shapes = set()
            self.full_walls = 0

        def shape_keys(self):
            return set(self._shapes)

        def _ensure(self, shape):
            if shape in self._compiled:
                return
            if self.cache is not None and shape in self.cache:
                time.sleep(compile_s * CACHE_HIT_FRACTION)
            else:
                time.sleep(compile_s)
                self.full_walls += 1
                if self.cache is not None:
                    self.cache[shape] = True
            self._compiled.add(shape)

        def warm_shapes(self, shapes):
            warmed = 0
            for prog, placement, shape in shapes:
                self._ensure(shape)
                self._shapes.add((prog, placement, shape))
                warmed += 1
            return warmed, 0

        def serve_one(self, shape):
            self._ensure(shape)
            time.sleep(RUN_S)
            self._shapes.add(("verify", "single", shape))

        def drain(self, timeout=None):
            return True

    shapes = [(2 ** i,) for i in range(n_shapes)]
    manifest_path = os.path.join(
        tempfile.mkdtemp(prefix="coconut-bench-lifecycle-"), "shapes.json"
    )

    def restart(name, cache, path):
        """One successor boot: controller boot (manifest replay when
        `path` names one) then first response at EVERY serving shape.
        Returns seconds from restart start to the last first-response —
        the restart-to-first-SLO-compliant-response number."""
        eng = SimCompileEngine(name, cache=cache)
        lc = LifecycleController(eng, manifest_path=path)
        t0 = time.monotonic()
        assert lc.boot() is not None and lc.ready()
        for s in shapes:
            eng.serve_one(s)
        return time.monotonic() - t0, eng

    # predecessor: pays the true cold floor, then drains + saves
    pred = SimCompileEngine("pred", cache=persistent_cache)
    pred_lc = LifecycleController(pred, manifest_path=manifest_path)
    pred_lc.boot()
    t0 = time.monotonic()
    for s in shapes:
        pred.serve_one(s)
    floor_s = time.monotonic() - t0
    assert pred_lc.begin_drain(timeout=30.0)
    manifest_shapes = len(ShapeManifest.load(manifest_path))
    assert manifest_shapes == n_shapes, (
        "predecessor manifest lost shapes: %d of %d"
        % (manifest_shapes, n_shapes)
    )

    # cold: no manifest, no cache — the pre-PR-14 restart experience
    cold_s, cold_eng = restart("cold", None, None)
    # warm: manifest replay + persistent-cache hits, readiness gated
    warm_s, warm_eng = restart("warm", persistent_cache, manifest_path)

    assert cold_eng.full_walls == n_shapes
    assert warm_eng.full_walls == 0, (
        "warm successor paid %d full compile walls" % warm_eng.full_walls
    )
    assert warm_s <= max_fraction * cold_s, (
        "warm restart is not cheap enough: %.3fs vs %.3fs cold "
        "(fraction %.2f > %.2f)"
        % (warm_s, cold_s, warm_s / cold_s, max_fraction)
    )
    extras["lifecycle"] = {
        "shapes": n_shapes,
        "simulated_compile_s": compile_s,
        "compile_plus_run_floor_s": round(floor_s, 4),
        "cold_restart_to_first_slo_s": round(cold_s, 4),
        "warm_restart_to_first_slo_s": round(warm_s, 4),
        "warm_over_cold": round(warm_s / cold_s, 4),
        "max_fraction": max_fraction,
        "manifest_shapes": manifest_shapes,
    }
    return cold_s / warm_s


def bench_keylife(ge, params, extras, backend_name):
    """Key-lifecycle lane (--keylife, ISSUE 15): goodput before / during /
    after a live t/n reshare. A 5-authority engine born from an ONLINE
    DKG serves closed-loop verify traffic; mid-run the lifecycle takes
    one proactive refresh AND one 3-of-5 -> 2-of-5 reshare on a side
    thread while the loadgen keeps driving pre-rollover credentials.
    Embeds the three goodput numbers, the during/before degradation
    ratio, and the after/before rollover ratio under extras["keylife"];
    asserts the during phase stayed NON-ZERO (rollover never blacked out
    serving) and that zero futures dropped across all three phases.
    Returns the after-rollover goodput. Knobs: BENCH_KEYLIFE_SECONDS
    (default 2), BENCH_KEYLIFE_MAX_BATCH (default 4),
    BENCH_KEYLIFE_CONCURRENCY (default 2*max_batch);
    BENCH_KEYLIFE=0 skips."""
    import threading

    from coconut_tpu import metrics
    from coconut_tpu.elgamal import elgamal_keygen
    from coconut_tpu.engine import ProtocolEngine
    from coconut_tpu.keylife import KeyLifecycleManager
    from coconut_tpu.serve import run_loadgen
    from coconut_tpu.sss import rand_fr

    seconds = float(os.environ.get("BENCH_KEYLIFE_SECONDS", "2"))
    max_batch = int(os.environ.get("BENCH_KEYLIFE_MAX_BATCH", "4"))
    concurrency = int(
        os.environ.get("BENCH_KEYLIFE_CONCURRENCY", str(2 * max_batch))
    )
    threshold, total = 3, 5

    mgr = KeyLifecycleManager(params, label=b"bench-keylife", window=3)
    ks1 = mgr.bootstrap(threshold, total)
    revealed = list(range(2, ge.MSG_COUNT))
    engine = ProtocolEngine(
        list(ks1.signers), params, threshold,
        count_hidden=2, revealed_msg_indices=revealed,
        vk=ks1.vk, backend=backend_name, max_batch=max_batch,
        keychain=mgr.registry,
    )
    mgr.attach(engine)

    class _VerifyFacade:
        """run_loadgen's verify surface (.submit) over the engine."""

        @staticmethod
        def submit(sig, messages, lane="interactive"):
            return engine.submit_verify(sig, messages, lane=lane)

    facade = _VerifyFacade()
    with engine:
        # pre-rollover credential pool, minted under epoch 1 — the
        # traffic the reshare must keep serving
        pool = []
        for _ in range(4 * max_batch):
            msgs = [rand_fr() for _ in range(ge.MSG_COUNT)]
            esk, epk = elgamal_keygen(params.ctx.sig, params.g)
            req, _ = engine.submit_prepare(msgs, epk).result(600.0)
            cred = engine.submit_mint(req, msgs, esk).result(600.0)
            pool.append((cred, msgs, True))
        assert all(c.epoch == 1 for c, _m, _e in pool)
        warm = [
            facade.submit(*pool[i % len(pool)][:2])
            for i in range(max_batch)
        ]
        for f in warm:
            f.result(timeout=600.0)

        def phase(duration):
            return run_loadgen(
                facade, pool, duration_s=duration,
                arrival="closed", concurrency=concurrency,
            )

        before = phase(seconds)
        rollover_err = []

        def rollover():
            try:
                ks1r = mgr.refresh()
                assert ks1r.vk.to_bytes(params.ctx) == ks1.vk.to_bytes(
                    params.ctx
                )
                mgr.reshare(threshold=2, total=total)
            except Exception as e:  # pragma: no cover - surfaced below
                rollover_err.append(e)

        t = threading.Thread(target=rollover, daemon=True)
        t.start()
        during = phase(max(seconds, 1.0))
        t.join(120.0)
        assert not t.is_alive(), "rollover thread hung under traffic"
        assert not rollover_err, "rollover failed: %r" % (rollover_err,)
        after = phase(seconds)
    for name, rep in (
        ("before", before), ("during", during), ("after", after)
    ):
        assert rep["dropped_futures"] == 0, (
            "keylife lane %s phase dropped futures: %r" % (name, rep)
        )
        assert rep["verdict_mismatches"] == 0, (
            "keylife lane %s phase verdict mismatch: %r" % (name, rep)
        )
    assert during["goodput_per_s"] > 0, (
        "reshare blacked out serving: %r" % (during,)
    )
    degradation = (
        round(during["goodput_per_s"] / before["goodput_per_s"], 4)
        if before["goodput_per_s"]
        else None
    )
    extras["keylife"] = {
        "authorities": total,
        "threshold_before": threshold,
        "threshold_after": 2,
        "max_batch": max_batch,
        "concurrency": concurrency,
        "seconds_per_phase": seconds,
        "goodput_per_s": {
            "before": before["goodput_per_s"],
            "during": during["goodput_per_s"],
            "after": after["goodput_per_s"],
        },
        "degradation_ratio": degradation,
        "rollover_ratio": (
            round(after["goodput_per_s"] / before["goodput_per_s"], 4)
            if before["goodput_per_s"]
            else None
        ),
        "refreshes": metrics.get_count("keylife_refreshes"),
        "reshares": metrics.get_count("keylife_reshares"),
    }
    return after["goodput_per_s"]


def bench_batchverify(ge, params, vk, sigs, msgs_list, extras,
                      backend_name):
    """Batched-pairing-verification lane (--batchverify, ISSUE 16):
    device time of the RLC-combined check (ONE multi-Miller product +
    ONE shared final exponentiation per batch) vs the exact per-lane
    path, for plain verify AND show-verify, at each batch width in
    BENCH_BATCHVERIFY_SIZES (default 64,256,1024 — widths above the
    fixture batch recycle fixture credentials). Embeds per-width
    timings, speedups, the smallest width where batched wins
    ("crossover_b"), and the soundness parameter under
    extras["batchverify"]; asserts every combined batch cost <= 2 final
    exponentiations (the "verify_final_exps" counter delta) while the
    exact path cost B, and that all-valid verdict vectors are
    bit-identical across modes. Knobs: BENCH_BATCHVERIFY_REPS
    (default 3); BENCH_BATCHVERIFY=0 skips. Returns the verify speedup
    at the widest batch."""
    from coconut_tpu import metrics, pok_sig, ps
    from coconut_tpu.backend import get_backend
    from coconut_tpu.batchverify import batch_lambda

    reps = int(os.environ.get("BENCH_BATCHVERIFY_REPS", "3"))
    sizes = sorted(
        int(x)
        for x in os.environ.get(
            "BENCH_BATCHVERIFY_SIZES", "64,256,1024"
        ).split(",")
        if x.strip()
    )
    backend = get_backend(backend_name)
    revealed = list(range(2, ge.MSG_COUNT))

    max_b = max(sizes)
    vsigs = [sigs[i % len(sigs)] for i in range(max_b)]
    vmsgs = [msgs_list[i % len(msgs_list)] for i in range(max_b)]
    proofs, challenges, revealed_list = pok_sig.batch_show(
        vsigs, vk, params, vmsgs, revealed, backend=backend
    )

    def fexp_delta(fn):
        base = metrics.get_count("verify_final_exps")
        out = fn()
        return metrics.get_count("verify_final_exps") - base, out

    points = []
    for B in sizes:
        def v_exact():
            return backend.batch_verify(
                vsigs[:B], vmsgs[:B], vk, params
            )

        def v_batched():
            return ps.batch_verify(
                vsigs[:B], vmsgs[:B], vk, params,
                backend=backend, mode="batched",
            )

        def s_exact():
            return ps.batch_show_verify(
                proofs[:B], vk, params, revealed_list[:B],
                challenges=challenges[:B], backend=backend,
                mode="exact",
            )

        def s_batched():
            return ps.batch_show_verify(
                proofs[:B], vk, params, revealed_list[:B],
                challenges=challenges[:B], backend=backend,
                mode="batched",
            )

        # warmup (jit compile), then pin the final-exp economics on one
        # counted call each: exact pays B, combined pays <= 2
        exact_fexp, exact_bits = fexp_delta(v_exact)
        combined_fexp, batched_bits = fexp_delta(v_batched)
        assert list(exact_bits) == list(batched_bits), (
            "verdict vectors diverged at B=%d" % B
        )
        assert all(batched_bits), "fixture batch must be all-valid"
        assert combined_fexp <= 2, (
            "combined batch cost %d final exps at B=%d (want <= 2)"
            % (combined_fexp, B)
        )
        show_fexp, show_batched_bits = fexp_delta(s_batched)
        assert show_fexp <= 2, (
            "combined show batch cost %d final exps at B=%d (want <= 2)"
            % (show_fexp, B)
        )
        assert list(show_batched_bits) == list(s_exact()), (
            "show verdict vectors diverged at B=%d" % B
        )

        t_vexact, _ = _timeit(v_exact, reps)
        t_vbatched, _ = _timeit(v_batched, reps)
        t_sexact, _ = _timeit(s_exact, reps)
        t_sbatched, _ = _timeit(s_batched, reps)
        points.append({
            "b": B,
            "verify_exact_s": round(t_vexact, 4),
            "verify_batched_s": round(t_vbatched, 4),
            "verify_speedup": round(t_vexact / t_vbatched, 3),
            "verify_exact_final_exps": exact_fexp,
            "verify_batched_final_exps": combined_fexp,
            "show_exact_s": round(t_sexact, 4),
            "show_batched_s": round(t_sbatched, 4),
            "show_speedup": round(t_sexact / t_sbatched, 3),
            "show_batched_final_exps": show_fexp,
        })

    crossover = next(
        (p["b"] for p in points if p["verify_speedup"] > 1.0), None
    )
    top = points[-1]
    extras["batchverify"] = {
        "lambda": batch_lambda(),
        "sizes": sizes,
        "points": points,
        "crossover_b": crossover,
        "verify_speedup_at_max_b": top["verify_speedup"],
        "show_speedup_at_max_b": top["show_speedup"],
        "batched_checks": metrics.get_count("verify_batched_checks"),
        "batched_fallbacks": metrics.get_count("verify_batched_fallbacks"),
    }
    return top["verify_speedup"]


def _bench_chaos_recovery(params, vk, pool, backend_name, mode, max_batch,
                          max_wait_ms):
    """Self-healing recovery datapoint (ISSUE 9): goodput before / during /
    after a scheduled mid-run fault pair (one executor-loop crash + one
    hung dispatch) against a pool with a fast watchdog and probation
    ladder. The number that matters is recovery_ratio = after/before: a
    pool that quarantines the culprits and re-admits them after a probe
    holds it near 1.0; a pool that bleeds capacity does not.
    BENCH_CHAOS=0 skips, BENCH_CHAOS_DEVICES / BENCH_CHAOS_SECONDS size
    the experiment."""
    from coconut_tpu import metrics
    from coconut_tpu.backend import get_backend
    from coconut_tpu.faults import ChaosSchedule
    from coconut_tpu.serve import CredentialService, run_loadgen
    from coconut_tpu.serve.health import HealthPolicy, Watchdog

    n_devices = int(os.environ.get("BENCH_CHAOS_DEVICES", "4"))
    seconds = float(os.environ.get("BENCH_CHAOS_SECONDS", "0.8"))
    concurrency = 2 * max_batch
    sched = ChaosSchedule()  # indices scheduled mid-run, below
    fb = sched.wrap(get_backend(backend_name))
    counters0 = {
        name: metrics.get_count(name)
        for name in (
            "serve_executor_crashes",
            "serve_watchdog_timeouts",
            "serve_quarantined",
            "serve_recovered",
            "serve_redistributed_batches",
        )
    }
    svc = CredentialService(
        fb,
        vk,
        params,
        mode=mode,
        max_batch=max_batch,
        max_wait_ms=max_wait_ms,
        max_depth=max(1024, 4 * max_batch * n_devices),
        devices=n_devices,
        watchdog=Watchdog(
            k=4.0, min_timeout_s=0.2, initial_timeout_s=120.0,
            max_timeout_s=120.0,
        ),
        watchdog_interval_s=0.05,
        health_policy=HealthPolicy(probe_after_s=0.3, probe_successes=1),
    )
    with svc:
        warm = [
            svc.submit(*pool[i % len(pool)][:2])
            for i in range(max_batch * n_devices)
        ]
        for f in warm:
            f.result(timeout=600.0)

        def phase(duration):
            return run_loadgen(
                svc, pool, duration_s=duration,
                arrival="closed", concurrency=concurrency,
            )

        before = phase(seconds)
        # schedule the faults at near-future dispatch indices (mirrored
        # onto the schedule object so describe() reports what actually ran)
        fb.crash_on = sched.crash_on = frozenset({fb.dispatches + 2})
        fb.hang_on = sched.hang_on = frozenset({fb.dispatches + 4})
        during = phase(max(seconds, 1.0))
        sched.release_hangs()
        time.sleep(0.4)  # one probation cooldown's room
        after = phase(seconds)
    for rep in (before, during, after):
        assert rep["dropped_futures"] == 0, (
            "chaos recovery dropped futures: %r" % (rep,)
        )
    ratio = (
        round(after["goodput_per_s"] / before["goodput_per_s"], 4)
        if before["goodput_per_s"]
        else None
    )
    return {
        "devices": n_devices,
        "seconds_per_phase": seconds,
        "schedule": sched.describe(),
        "goodput_per_s": {
            "before": before["goodput_per_s"],
            "during": during["goodput_per_s"],
            "after": after["goodput_per_s"],
        },
        "errors": {
            "before": before["errors"],
            "during": during["errors"],
            "after": after["errors"],
        },
        "recovery_ratio": ratio,
        "counters": {
            name: metrics.get_count(name) - start
            for name, start in sorted(counters0.items())
        },
    }


def _bench_serve_scaling(params, vk, pool, backend_name, mode, max_batch,
                         max_wait_ms):
    """BENCH_SERVE_DEVICES="1,2,4,8" device-count sweep (ISSUE 8 headline):
    one saturating closed-loop loadgen pass per dispatcher-pool size,
    reporting goodput, p99 latency, batch occupancy, per-device dispatch
    counts, and scaling efficiency (goodput_n / (n * goodput_1)). On the
    jax backend each executor pins to a real jax device (so 8 means the
    8-device mesh's chips); other backends get n unpinned worker
    executors. Each point drives 2*max_batch clients PER device so every
    pool size runs at ITS saturation, not the smallest pool's."""
    from coconut_tpu.serve import CredentialService, run_loadgen

    counts = [
        int(tok)
        for tok in os.environ["BENCH_SERVE_DEVICES"].replace(",", " ").split()
    ]
    seconds = float(
        os.environ.get(
            "BENCH_SERVE_SWEEP_SECONDS",
            os.environ.get("BENCH_SERVE_SECONDS", "2"),
        )
    )
    points = []
    base_goodput = None
    for n in counts:
        devices = n
        if backend_name == "jax":
            import jax

            devs = jax.devices()
            if len(devs) >= n:
                devices = list(devs[:n])
        svc = CredentialService(
            backend_name,
            vk,
            params,
            mode=mode,
            max_batch=max_batch,
            max_wait_ms=max_wait_ms,
            max_depth=max(1024, 4 * max_batch * n),
            devices=devices,
        )
        with svc:
            warm = [
                svc.submit(*pool[i % len(pool)][:2])
                for i in range(max_batch * n)
            ]
            for f in warm:
                f.result(timeout=600.0)
            report = run_loadgen(
                svc,
                pool,
                duration_s=seconds,
                arrival="closed",
                concurrency=2 * max_batch * n,
            )
        assert report["dropped_futures"] == 0, (
            "serve scaling sweep (devices=%d) dropped futures: %r"
            % (n, report)
        )
        goodput = report["goodput_per_s"]
        if base_goodput is None:
            base_goodput = goodput
        devices_seen = report["devices"] or {}
        points.append({
            "devices": n,
            "goodput_per_s": goodput,
            "dropped_futures": report["dropped_futures"],
            "p99_latency_s": report["latency_s"]["p99"],
            "mean_batch_occupancy": report["mean_batch_occupancy"],
            "devices_with_dispatches": len(devices_seen),
            "per_device_dispatches": {
                label: d.get("dispatches", 0)
                for label, d in sorted(devices_seen.items())
            },
            "scaling_efficiency": (
                round(goodput / (n * base_goodput), 4)
                if base_goodput
                else None
            ),
        })
    return {"seconds_per_point": seconds, "points": points}


def main():
    batch = int(os.environ.get("BENCH_BATCH", "1024"))
    # best-of-5: makes the best-of timing robust to run-to-run noise
    reps = int(os.environ.get("BENCH_REPS", "5"))
    backend_name = os.environ.get("BENCH_BACKEND", "jax")
    serve_flag = "--serve" in sys.argv[1:]
    # the online issuance lane shares the offline config-4 gate: if the
    # operator turned blind-sign benching off, the CLI flag stays off too
    issue_flag = (
        "--issue" in sys.argv[1:]
        and os.environ.get("BENCH_ISSUE", "1") == "1"
    )
    session_flag = (
        "--session" in sys.argv[1:]
        and os.environ.get("BENCH_SESSION", "1") == "1"
    )
    gateway_flag = (
        "--gateway" in sys.argv[1:]
        and os.environ.get("BENCH_GATEWAY", "1") == "1"
    )
    lifecycle_flag = (
        "--lifecycle" in sys.argv[1:]
        and os.environ.get("BENCH_LIFECYCLE", "1") == "1"
    )
    keylife_flag = (
        "--keylife" in sys.argv[1:]
        and os.environ.get("BENCH_KEYLIFE", "1") == "1"
    )
    batchverify_flag = (
        "--batchverify" in sys.argv[1:]
        and os.environ.get("BENCH_BATCHVERIFY", "1") == "1"
    )
    state_flag = (
        "--state" in sys.argv[1:]
        and os.environ.get("BENCH_STATE", "1") == "1"
    )
    hashmsm_flag = (
        "--hashmsm" in sys.argv[1:]
        and os.environ.get("BENCH_HASHMSM", "1") == "1"
    )
    scenarios_flag = (
        "--scenarios" in sys.argv[1:]
        and os.environ.get("BENCH_SCENARIOS", "1") == "1"
    )
    # BENCH_OFFLINE=0 (only meaningful with --serve/--issue) skips the
    # offline lanes so the CI online smokes don't pay for them
    offline = os.environ.get("BENCH_OFFLINE", "1") == "1" or not (
        serve_flag
        or issue_flag
        or session_flag
        or gateway_flag
        or lifecycle_flag
        or keylife_flag
        or batchverify_flag
        or state_flag
        or hashmsm_flag
        or scenarios_flag
    )

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import __graft_entry__ as ge

    t0 = time.time()
    params, sk, vk, sigs, msgs_list = ge._fixture(batch=batch)
    t_fixture = time.time() - t0

    extras = {
        "batch": batch,
        "backend": backend_name,
        "msg_count": ge.MSG_COUNT,
        "fixture_s": round(t_fixture, 3),
    }

    from coconut_tpu import metrics

    if offline:
        if backend_name == "python":
            value = bench_python(
                batch, ge, params, vk, sigs, msgs_list, extras
            )
        else:
            value = bench_jax(
                batch, reps, ge, params, sk, vk, sigs, msgs_list, extras
            )
        metric, unit = "aggregated_credential_verifies_per_sec", "verifies/sec"
    else:
        value = None

    if serve_flag:
        goodput = bench_serve(
            ge, params, vk, sigs, msgs_list, extras, backend_name
        )
        if value is None:
            value = goodput
            metric, unit = "serve_goodput_per_sec", "requests/sec"

    if issue_flag:
        minted_per_s = bench_issue(
            ge, params, vk, sigs, msgs_list, extras, backend_name
        )
        if value is None:
            value = minted_per_s
            metric, unit = "issue_credentials_per_sec", "credentials/sec"

    if session_flag:
        sessions_per_s = bench_session(ge, params, extras, backend_name)
        if value is None:
            value = sessions_per_s
            metric, unit = "session_sessions_per_sec", "sessions/sec"

    if gateway_flag:
        rpc_goodput = bench_gateway(
            ge, params, vk, sigs, msgs_list, extras, backend_name
        )
        if value is None:
            value = rpc_goodput
            metric, unit = "gateway_rpc_goodput_per_sec", "requests/sec"

    if lifecycle_flag:
        speedup = bench_lifecycle(extras)
        if value is None:
            value = speedup
            metric, unit = "lifecycle_warm_restart_speedup", "x"

    if keylife_flag:
        keylife_goodput = bench_keylife(ge, params, extras, backend_name)
        if value is None:
            value = keylife_goodput
            metric, unit = "keylife_rollover_goodput_per_sec", "requests/sec"

    if batchverify_flag:
        bv_speedup = bench_batchverify(
            ge, params, vk, sigs, msgs_list, extras, backend_name
        )
        if value is None:
            value = bv_speedup
            metric, unit = "batchverify_speedup_at_max_batch", "x"

    if state_flag:
        state_ratio = bench_state(ge, params, extras, backend_name)
        if value is None:
            value = state_ratio
            metric, unit = "state_goodput_ratio", "x"

    if hashmsm_flag:
        hash_speedup = bench_hashmsm(ge, params, extras, backend_name)
        if value is None:
            value = hash_speedup
            metric, unit = "hashmsm_device_hash_speedup", "x"

    if scenarios_flag:
        scn_goodput = bench_scenarios(ge, params, extras, backend_name)
        if value is None:
            value = scn_goodput
            metric, unit = "scenario_goodput_per_sec", "workflows/sec"

    extras["metrics"] = metrics.snapshot()
    # static-operand cache effectiveness, surfaced at top level so a
    # profiling round can grep them without digging into the snapshot
    extras["encode_cache_hits"] = metrics.get_count("encode_cache_hits")
    extras["encode_cache_misses"] = metrics.get_count("encode_cache_misses")
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(value, 2),
                "unit": unit,
                "vs_baseline": round(value / NORTH_STAR, 4),
                **extras,
            }
        )
    )


def bench_jax(batch, reps, ge, params, sk, vk, sigs, msgs_list, extras):
    import jax

    # persistent compile cache: the fused programs take minutes to build;
    # cache them across bench invocations (one shared definition — see
    # coconut_tpu/tpu/__init__.py)
    import coconut_tpu.tpu

    coconut_tpu.tpu.enable_compile_cache()
    import numpy as np

    from coconut_tpu import metrics
    from coconut_tpu.tpu.backend import JaxBackend, _fused_verify_kernel

    extras["device"] = str(jax.devices()[0])
    be = JaxBackend()

    # --- headline: attribute-grouped combined batch verify -----------------
    t0 = time.time()
    ok = be.batch_verify_grouped(sigs, msgs_list, vk, params)
    extras["grouped_compile_plus_run_s"] = round(time.time() - t0, 3)
    assert ok is True, "grouped verification wrong"
    t_grp, ok = _timeit(
        lambda: be.batch_verify_grouped(sigs, msgs_list, vk, params), reps
    )
    assert ok is True
    if os.environ.get("BENCH_PROFILE", "0") == "1":
        # device-side observability (VERDICT r3 item 9): one profiled rep
        # of the headline; the trace (viewable in xprof/tensorboard) breaks
        # kernel time down by the jax.named_scope annotations in
        # tpu/backend.py (comb_msm / grouped_* / miller / final_exp)
        trace_dir = os.environ.get("BENCH_PROFILE_DIR", "/tmp/coconut_trace")
        with jax.profiler.trace(trace_dir):
            be.batch_verify_grouped(sigs, msgs_list, vk, params)
        extras["profile_trace_dir"] = trace_dir
    value = batch / t_grp
    extras["grouped_s"] = round(t_grp, 4)
    metrics.count("verifies", batch * reps)  # headline (grouped) path only

    # steady-state (cache-hot, post-warmup) per-batch host encode for the
    # grouped path: what a stream actually pays per batch once the
    # static-operand cache holds the verkey tables — the ISSUE-3 axis
    # (the hot number is the Amdahl term that bounds multi-chip scaling)
    t_genc, _ = _timeit(
        lambda: be.encode_grouped_batch(sigs, msgs_list, vk, params), reps
    )
    extras["grouped_host_encode_hot_s"] = round(t_genc, 4)

    # soundness spot-check ON THE CHIP: one tampered credential must flip
    # the whole-batch boolean (same shapes -> no recompile)
    from coconut_tpu.signature import Signature as _Sig

    forged = list(sigs)
    forged[batch // 2] = _Sig(
        sigs[batch // 2].sigma_1,
        params.ctx.sig.mul(sigs[batch // 2].sigma_2, 2),
    )
    rejected = be.batch_verify_grouped(forged, msgs_list, vk, params) is False
    assert rejected, "grouped verify accepted a forged credential"
    extras["grouped_rejects_forgery"] = rejected

    # --- per-credential fused kernel (bit-per-credential path) -------------
    if os.environ.get("BENCH_PERCRED", "1") == "1":
        with metrics.timer("encode"):
            operands = be.encode_verify_batch(sigs, msgs_list, vk, params)
        extras["host_encode_s"] = round(
            metrics.snapshot()["timers_s"]["encode"], 3
        )
        # steady-state comparator: same encode with the static-operand
        # cache hot (comb tables + g_tilde cached; only signature points
        # and scalar digits are re-encoded)
        t_henc, _ = _timeit(
            lambda: be.encode_verify_batch(sigs, msgs_list, vk, params), reps
        )
        extras["host_encode_hot_s"] = round(t_henc, 4)
        sig_is_g1 = params.ctx.name == "G1"
        with metrics.timer("compile_plus_run"):
            bits = _fused_verify_kernel(sig_is_g1, *operands)
            bits.block_until_ready()
        extras["percred_compile_plus_run_s"] = round(
            metrics.snapshot()["timers_s"]["compile_plus_run"], 3
        )

        def run():
            # time through the host transfer (the [B] bool transfer
            # itself is sub-millisecond), so no early return of
            # block_until_ready can credit kernel time to "readback".
            with metrics.timer("kernel"):
                out = _fused_verify_kernel(sig_is_g1, *operands)
                return np.asarray(out)

        t_kernel, host_bits = _timeit(run, reps)
        assert bool(host_bits.all()), "verification bits wrong"
        extras["percred_kernel_s"] = round(t_kernel, 4)
        extras["percred_verifies_per_sec"] = round(batch / t_kernel, 2)

        # at-scale rejection ON THE CHIP for the per-credential path too:
        # the miscompiles seen on an earlier TPU runtime were
        # shape-dependent (B>=256, B=1024) — assert the full-width program
        # flips exactly the forged lane (same shapes -> no recompile)
        f_operands = be.encode_verify_batch(forged, msgs_list, vk, params)
        f_bits = np.asarray(_fused_verify_kernel(sig_is_g1, *f_operands))
        assert not f_bits[batch // 2] and bool(
            f_bits.sum() == batch - 1
        ), "per-credential kernel mis-flagged the forged lane"
        extras["percred_rejects_forgery"] = True

    if os.environ.get("BENCH_MULTIVK", "0") == "1":
        # multi-issuer verifier (VERDICT r4 weak #5): 8 verkeys round-robin
        # through the per-credential program. The per-verkey comb tables
        # must amortize behind the LRU cache — the datapoint is the
        # steady-state rate across verkey switches vs the single-verkey
        # rate above (a wholesale-clearing cache would rebuild tables,
        # host multiples + device doublings, on every switch).
        import random as _rnd

        _r = _rnd.Random(0x8151)
        nvk = 8
        vks = []
        for _ in range(nvk):
            # one issuer per fixture (own params/verkey/credentials);
            # identical shapes, so the compiled program is shared and the
            # only per-issuer cost is the comb-table build the LRU cache
            # amortizes
            p2, _, vk2, sigs2, ml2 = ge._fixture(
                batch=batch, seed=_r.randrange(1 << 30)
            )
            vks.append((p2, vk2, sigs2, ml2))
        sig_is_g1 = vks[0][0].ctx.name == "G1"
        # warm: one pass builds all 8 verkeys' comb tables
        for p2, vk2, sigs2, ml2 in vks:
            ops2 = be.encode_verify_batch(sigs2, ml2, vk2, p2)
            np.asarray(_fused_verify_kernel(sig_is_g1, *ops2))
        rounds = 2

        def timed_pass(issuers):
            t0 = time.time()
            for p2, vk2, sigs2, ml2 in issuers:
                ops2 = be.encode_verify_batch(sigs2, ml2, vk2, p2)
                bits2 = np.asarray(_fused_verify_kernel(sig_is_g1, *ops2))
                assert bool(bits2.all())
            return time.time() - t0

        dt = sum(timed_pass(vks) for _ in range(rounds))
        extras["multivk_verifies_per_sec"] = round(
            rounds * nvk * batch / dt, 2
        )
        # SAME-basis single-issuer comparator (encode included in the
        # timed region, unlike percred_verifies_per_sec which times a
        # pre-encoded kernel call): isolates what verkey ROTATION costs
        dt1 = sum(timed_pass(vks[:1]) for _ in range(rounds * nvk))
        extras["multivk_single_issuer_per_sec"] = round(
            rounds * nvk * batch / dt1, 2
        )
        extras["multivk_n"] = nvk

    if os.environ.get("BENCH_COMBINED", "0") == "1":
        # combined (small-exponents) batch verify: one bool per batch,
        # B+1 Miller pairs (superseded by grouped; kept for comparison)
        t0 = time.time()
        ok = be.batch_verify_combined(sigs, msgs_list, vk, params)
        extras["combined_compile_plus_run_s"] = round(time.time() - t0, 3)
        t_comb, ok = _timeit(
            lambda: be.batch_verify_combined(sigs, msgs_list, vk, params),
            reps,
        )
        assert ok is True
        extras["combined_s"] = round(t_comb, 4)
        extras["combined_verifies_per_sec"] = round(batch / t_comb, 2)

    # --- config 3: batched selective-disclosure prove + verify -------------
    if os.environ.get("BENCH_SHOW", "1") == "1":
        from coconut_tpu.pok_sig import batch_show

        t0 = time.time()
        proofs, chals, rmls = batch_show(
            sigs, vk, params, msgs_list, {2, 3, 4, 5}, backend=be
        )
        extras["show_prove_compile_plus_run_s"] = round(time.time() - t0, 3)
        t_prove, _ = _timeit(
            lambda: batch_show(
                sigs, vk, params, msgs_list, {2, 3, 4, 5}, backend=be
            ),
            reps,
        )
        extras["show_prove_per_sec"] = round(batch / t_prove, 2)
        extras["show_prove_s"] = round(t_prove, 4)
        t0 = time.time()
        bits = be.batch_show_verify(proofs, vk, params, rmls, chals)
        extras["show_compile_plus_run_s"] = round(time.time() - t0, 3)
        assert all(bits), "show-verify bits wrong"
        t_show, bits = _timeit(
            lambda: be.batch_show_verify(proofs, vk, params, rmls, chals),
            reps,
        )
        extras["show_verifies_per_sec"] = round(batch / t_show, 2)
        extras["show_s"] = round(t_show, 4)

        # the SECURE non-interactive path (VERDICT r3 item 5): recompute the
        # Fiat-Shamir challenge from each proof transcript inside the timed
        # region (ps.batch_show_verify challenges=None), so config 3 reports
        # what a real verifier pays, not the interactive-style cost above
        from coconut_tpu.ps import batch_show_verify as ps_batch_show_verify

        fs_bits = ps_batch_show_verify(
            proofs, vk, params, rmls, challenges=None, backend=be
        )
        assert all(fs_bits), "FS show-verify bits wrong"
        t_fs, _ = _timeit(
            lambda: ps_batch_show_verify(
                proofs, vk, params, rmls, challenges=None, backend=be
            ),
            reps,
        )
        extras["show_verify_fs_per_sec"] = round(batch / t_fs, 2)
        extras["show_fs_s"] = round(t_fs, 4)

    # --- config 4: threshold issuance (batched blind-sign MSMs) ------------
    if os.environ.get("BENCH_ISSUE", "1") == "1":
        from coconut_tpu.elgamal import elgamal_keygen
        from coconut_tpu.signature import (
            batch_blind_sign,
            batch_prepare_blind_sign,
        )

        # full-batch issuance: the small-distinct-MSM programs underfill
        # the VPU below ~1k lanes (256 -> 1024 lanes measured 157 -> 393
        # prepare/s, 658 -> 1262 blind-sign/s), so the honest batch shape
        # is the same 1024 the verify configs use
        n_req = min(batch, int(os.environ.get("BENCH_ISSUE_N", "1024")))
        # fixture (keygen) and first-call compile timed SEPARATELY so the
        # artifact shows which part of issuance is slow (VERDICT r3 weak 8)
        t0 = time.time()
        elg_sk, elg_pk = elgamal_keygen(params.ctx.sig, params.g)
        extras["issue_keygen_s"] = round(time.time() - t0, 3)
        t0 = time.time()
        out = batch_prepare_blind_sign(
            msgs_list[:n_req], 2, elg_pk, params, backend=be
        )
        reqs = [r for r, _ in out]
        extras["issue_prepare_compile_plus_run_s"] = round(time.time() - t0, 3)
        t_prep, _ = _timeit(
            lambda: batch_prepare_blind_sign(
                msgs_list[:n_req], 2, elg_pk, params, backend=be
            ),
            reps,
        )
        extras["issue_prepare_per_sec"] = round(n_req / t_prep, 2)
        t0 = time.time()
        blinded = batch_blind_sign(reqs, sk, params, backend=be)
        extras["issue_compile_plus_run_s"] = round(time.time() - t0, 3)
        from coconut_tpu.signature import BlindSignature

        want = BlindSignature.new(reqs[0], sk, params)
        assert (blinded[0].h, blinded[0].blinded) == (want.h, want.blinded), (
            "issuance output wrong"
        )
        t_issue, blinded = _timeit(
            lambda: batch_blind_sign(reqs, sk, params, backend=be), reps
        )
        extras["issue_per_sec"] = round(n_req / t_issue, 2)
        extras["issue_n"] = n_req
        extras["issue_s"] = round(t_issue, 4)

    # --- config 5: short streamed run (checkpointed, pipelined) ------------
    if os.environ.get("BENCH_STREAM", "1") == "1":
        import tempfile

        from coconut_tpu.stream import verify_stream

        n_batches = int(os.environ.get("BENCH_STREAM_BATCHES", "8"))
        with tempfile.TemporaryDirectory() as tmpdir:

            def stream(mode, name):
                wait0 = metrics.snapshot()["timers_s"].get("prefetch_wait", 0)
                t0 = time.time()
                state = verify_stream(
                    lambda i: (sigs, msgs_list),
                    n_batches,
                    vk,
                    params,
                    be,
                    state_path=os.path.join(tmpdir, name),
                    mode=mode,
                )
                dt = time.time() - t0
                # pipeline occupancy: fraction of the stream wall the main
                # thread was NOT starved waiting on the background encode
                # worker (1.0 = the prefetcher kept the device fed)
                wait = (
                    metrics.snapshot()["timers_s"].get("prefetch_wait", 0)
                    - wait0
                )
                occ = 1.0 - wait / dt if dt > 0 else None
                return state, dt, occ

            # grouped: ONE bool per batch — honest batch accounting
            state, dt, occ = stream("grouped", "grouped.json")
            assert state.batches_ok == n_batches and state.batches_failed == 0
            assert state.verified == n_batches * batch
            extras["stream_creds_per_sec"] = round(n_batches * batch / dt, 2)
            extras["stream_batches"] = n_batches
            extras["stream_mode"] = "grouped"
            if occ is not None:
                extras["stream_pipeline_occupancy"] = round(occ, 4)

            if os.environ.get("BENCH_PERCRED", "1") == "1":
                # sustained PER-CREDENTIAL rate (one bit per credential,
                # the reference's Signature::verify verdict semantics):
                # the same pipelined stream with the fused per-credential
                # program, which the percred section above already
                # compiled (same shapes) — this costs only run time.
                state, dt, occ = stream("per_credential", "percred.json")
                assert (
                    state.verified == n_batches * batch and state.failed == 0
                )
                extras["percred_stream_per_sec"] = round(
                    n_batches * batch / dt, 2
                )
                if occ is not None:
                    extras["percred_stream_occupancy"] = round(occ, 4)

    return value


if __name__ == "__main__":
    main()
