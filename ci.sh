#!/bin/sh -e
# One-command CI (VERDICT r2 item 9; the reference's analogue is
# .travis.yml:7-9, which runs `cargo test --release` under both
# group-assignment features).
#
#   ./ci.sh           default suite + sanitizer selftest
#   CI_HEAVY=1 ./ci.sh   also runs the multi-minute fused-kernel tests
#
# Group assignments: both SignatureG1 and SignatureG2 are exercised
# IN-SUITE (tests/test_protocol.py parametrizes the full lifecycle over
# SIGNATURES_IN_G1 and SIGNATURES_IN_G2), so one pytest run covers what the
# reference needed two feature builds for.
cd "$(dirname "$0")"

echo "== native: release build + sanitizer selftest =="
make -C native libccbls.so
make -C native selftest_asan
./native/selftest_asan

echo "== test suite (both group assignments in-suite) =="
python -m pytest tests/ -q

echo "== analysis lane (invariant lint suite + CI gate) =="
# the marker suite: each checker fires on its seeded-bad fixture, the
# runtime lock-order tracker catches a real ABBA interleaving, the
# dead-letter schema validator rejects malformed records
python -m pytest tests/test_analysis.py -m analysis -q
# the gate: lock-order / wire-contract / const-time / durability /
# metrics-doc over the tree; any finding not covered by an inline
# ``# lint: allow(...)`` pragma or analysis_baseline.json fails CI
python -m coconut_tpu.analysis --fail-on-new

echo "== fault-supervision lane (retry/fallback/bisection/checkpoints) =="
python -m pytest tests/test_faults.py -m faults -q
# dead-letter JSONL schema probe: run a tiny grouped stream with one forged
# credential and grep the bisection output for the documented keys
DLQ=$(mktemp -d)/dead.jsonl
DLQ_PATH="$DLQ" python - <<'EOF'
import os
from types import SimpleNamespace
from coconut_tpu.stream import verify_stream

def cred(ok=True):
    return SimpleNamespace(sigma_1=1, sigma_2=1, ok=ok)

def source(i):
    sigs = [cred(ok=not (i == 1 and j == 2)) for j in range(4)]
    return sigs, [[0]] * 4

class Grouped:
    def batch_verify_grouped(self, sigs, msgs, vk, params):
        return all(s.ok for s in sigs)

verify_stream(source, 3, None, None, Grouped(), mode="grouped",
              dead_letter_path=os.environ["DLQ_PATH"])
EOF
# structured schema-v4 validation (replaces the old grep chain, which
# passed on wrong types and torn lines): every line must parse, carry
# exactly the v4 key set with the right types/null-ability, and the
# bisected culprit must be batch 1 / credential 2
python -m coconut_tpu.analysis.schema "$DLQ" \
  --expect batch=1 --expect credential=2

echo "== serve lane (dynamic batching / admission control / loadgen) =="
# "not slow": the mesh-serve integration test already ran in the full
# suite above — re-tracing its multi-minute mesh program in this second
# process would double the lane's cost for no coverage
python -m pytest tests/test_serve.py -m "serve and not slow" -q
# 2-second loadgen smoke against the REAL service on the CPU (python)
# backend: closed loop at saturation, then assert the SLO report is sane —
# every accepted future resolved, batches actually coalesced, and the
# latency percentiles present. bench_serve itself asserts the invariants
# loudly; the JSON probe re-checks them from the artifact a human reads.
SERVE_JSON=$(mktemp -d)/serve.json
BENCH_OFFLINE=0 BENCH_BACKEND=python BENCH_BATCH=16 BENCH_CHAOS=0 \
  BENCH_SERVE_SECONDS=2 BENCH_SERVE_MAX_BATCH=4 JAX_PLATFORMS=cpu \
  python bench.py --serve > "$SERVE_JSON"
SERVE_JSON_PATH="$SERVE_JSON" python - <<'EOF'
import json, os
with open(os.environ["SERVE_JSON_PATH"]) as f:
    line = f.read().strip().splitlines()[-1]
report = json.loads(line)["serve"]
assert report["dropped_futures"] == 0, report
assert report["verdict_mismatches"] == 0, report
assert report["mean_batch_occupancy"] > 0.5, report
assert report["latency_s"]["p99"] is not None, report
assert report["completed"] > 0 and report["errors"] == 0, report
print("serve smoke: ok (goodput %.1f/s, occupancy %.2f, p99 %.0f ms)" % (
    report["goodput_per_s"], report["mean_batch_occupancy"],
    report["latency_s"]["p99"] * 1000.0))
EOF

# mesh-serve smoke (ISSUE 8): the same short real-service loadgen, now
# through the per-device dispatcher pool on the 8-device virtual CPU mesh,
# swept over pool sizes (BENCH_SERVE_DEVICES -> "serve"."scaling" in the
# BENCH JSON). The probe asserts from the artifact that scaling actually
# engaged: MORE THAN ONE device saw dispatches at the widest point, zero
# dropped futures at every point. (The jax mesh-sharded serve path itself
# is covered in-suite by tests/test_serve.py::test_mesh_serve_integration*
# on the same virtual mesh.)
MESH_SERVE_JSON=$(mktemp -d)/mesh_serve.json
BENCH_OFFLINE=0 BENCH_BACKEND=python BENCH_BATCH=16 BENCH_CHAOS=0 \
  BENCH_SERVE_SECONDS=1 BENCH_SERVE_MAX_BATCH=4 BENCH_TRACE_OVERHEAD=0 \
  BENCH_SERVE_DEVICES="1,8" BENCH_SERVE_SWEEP_SECONDS=0.5 \
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python bench.py --serve > "$MESH_SERVE_JSON"
MESH_SERVE_JSON_PATH="$MESH_SERVE_JSON" python - <<'EOF'
import json, os
with open(os.environ["MESH_SERVE_JSON_PATH"]) as f:
    line = f.read().strip().splitlines()[-1]
scaling = json.loads(line)["serve"]["scaling"]
points = {p["devices"]: p for p in scaling["points"]}
assert set(points) == {1, 8}, sorted(points)
for n, p in sorted(points.items()):
    assert p["goodput_per_s"] > 0, p
    assert p["dropped_futures"] == 0, p
    assert p["devices_with_dispatches"] >= 1, p
wide = points[8]
assert wide["devices_with_dispatches"] > 1, wide
assert all(v > 0 for v in wide["per_device_dispatches"].values()), wide
print("mesh-serve smoke: ok (%d devices dispatched at n=8, "
      "efficiency %.2f)" % (wide["devices_with_dispatches"],
                            wide["scaling_efficiency"]))
EOF

echo "== chaos lane (self-healing pool: crash containment / watchdog / brownout) =="
# the marker suite: breaker/watchdog/brownout units (tests/test_health.py),
# fake-clock crash/hang/quarantine/probation integration (test_serve.py),
# injection + rotation + crash-atomic checkpoint satellites (test_faults.py).
# COCONUT_LOCK_CHECK=1 runs the whole lane under the runtime lock-order
# tracker (analysis/lockcheck.py): any acquisition-order inversion
# recorded during a test fails that test
COCONUT_LOCK_CHECK=1 python -m pytest tests/ -m chaos -q
# end-to-end acceptance smoke (ISSUE 9): a real 8-executor stub-device
# service takes one injected executor crash AND one hung dispatch mid-run;
# the probe asserts every submitted future settled, the culprits were
# quarantined (crash + watchdog paths both fired), and goodput recovered
# to >= half the pre-fault level after the probation ladder re-admits
JAX_PLATFORMS=cpu python probes/probe_chaos.py
# chaos-recovery bench datapoint: goodput before/during/after a scheduled
# crash+hang pair, from the same JSON artifact a human reads
CHAOS_JSON=$(mktemp -d)/chaos.json
BENCH_OFFLINE=0 BENCH_BACKEND=python BENCH_BATCH=16 BENCH_TRACE_OVERHEAD=0 \
  BENCH_SERVE_SECONDS=0.5 BENCH_SERVE_MAX_BATCH=4 BENCH_CHAOS_SECONDS=0.5 \
  JAX_PLATFORMS=cpu python bench.py --serve > "$CHAOS_JSON"
CHAOS_JSON_PATH="$CHAOS_JSON" python - <<'PYEOF'
import json, os
with open(os.environ["CHAOS_JSON_PATH"]) as f:
    line = f.read().strip().splitlines()[-1]
cr = json.loads(line)["serve"]["chaos_recovery"]
assert cr["counters"]["serve_executor_crashes"] >= 1, cr
assert cr["counters"]["serve_quarantined"] >= 1, cr
assert all(v == 0 for v in cr["errors"].values()), cr
assert cr["recovery_ratio"] is not None and cr["recovery_ratio"] >= 0.5, cr
print("chaos bench smoke: ok (recovery ratio %.2f, %d quarantined, "
      "%d watchdog timeouts)" % (cr["recovery_ratio"],
                                 cr["counters"]["serve_quarantined"],
                                 cr["counters"]["serve_watchdog_timeouts"]))
PYEOF

echo "== issue lane (threshold issuance: quorum fan-out / hedging / attribution) =="
# the marker suite: fake-clock quorum/hedge/attribution mechanics plus the
# real-crypto first-t-bit-identical and crash+hang acceptance tests
python -m pytest tests/ -m issue -q
# end-to-end acceptance smoke (ISSUE 10): a real 5-authority t=3 pool
# takes one injected authority crash AND one hung sign on its first
# fan-out; the probe asserts every order minted, every minted credential
# verifies under the Lagrange-aggregated verkey, and the crashed
# authority was quarantined while the pool kept minting
JAX_PLATFORMS=cpu python probes/probe_issue.py
# issuance bench smoke: pure-issuance loadgen against the real service on
# the CPU backend, asserted from the JSON artifact a human reads
ISSUE_JSON=$(mktemp -d)/issue.json
BENCH_OFFLINE=0 BENCH_BACKEND=python BENCH_BATCH=16 \
  BENCH_ISSUE_SECONDS=1.5 BENCH_ISSUE_MAX_BATCH=4 JAX_PLATFORMS=cpu \
  python bench.py --issue > "$ISSUE_JSON"
ISSUE_JSON_PATH="$ISSUE_JSON" python - <<'EOF'
import json, os
with open(os.environ["ISSUE_JSON_PATH"]) as f:
    line = f.read().strip().splitlines()[-1]
report = json.loads(line)["issue"]
assert report["dropped_futures"] == 0, report
assert report["mint_mismatches"] == 0, report
assert report["errors"] == 0, report
assert report["minted"] > 0, report
assert report["quorum_unreachable"] == 0, report
assert report["quorum_wait_s"]["p95"] is not None, report
print("issue smoke: ok (%.1f credentials/s, quorum-wait p95 %.0f ms, "
      "hedge rate %s)" % (report["credentials_per_sec"],
                          report["quorum_wait_s"]["p95"] * 1000.0,
                          report["hedge_rate"]))
EOF

echo "== engine lane (unified fabric: five programs / one pool / session pipeline) =="
# the marker suite: typed retriable-error hierarchy, online/offline show
# parity through engine lanes (padding + ragged tails), mixed-program
# full-session pipeline, jit-shape-cache stability
python -m pytest tests/ -m engine -q
# end-to-end acceptance smoke (ISSUE 12): a real ProtocolEngine runs all
# FIVE phases over one 2-executor pool + 3-authority t=2 mint pool, takes
# one injected executor crash mid-workload; the probe asserts every
# future settled, the full sessions round-trip (mint -> verify -> show),
# the crash was contained+redistributed, and the per-program jit-shape
# counters stayed flat after warmup (no cross-program recompiles)
JAX_PLATFORMS=cpu python probes/probe_engine.py
# full-session bench smoke: closed-loop sessions (prepare -> mint ->
# show_prove -> show_verify) against the real engine on the CPU backend,
# asserted from the JSON artifact a human reads
SESSION_JSON=$(mktemp -d)/session.json
BENCH_OFFLINE=0 BENCH_BACKEND=python BENCH_BATCH=16 BENCH_CHAOS=0 \
  BENCH_SESSION_SECONDS=1.5 BENCH_SESSION_MAX_BATCH=4 JAX_PLATFORMS=cpu \
  python bench.py --session > "$SESSION_JSON"
SESSION_JSON_PATH="$SESSION_JSON" python - <<'EOF'
import json, os
with open(os.environ["SESSION_JSON_PATH"]) as f:
    line = f.read().strip().splitlines()[-1]
report = json.loads(line)["session"]
assert report["sessions_completed"] > 0, report
assert report["errors"] == 0, report
assert report["failed_shows"] == 0, report
assert report["jit_shapes_stable"], report
assert report["session_latency_s"]["p95"] is not None, report
print("session smoke: ok (%.1f sessions/s, p95 %.0f ms, jit shapes "
      "stable across %d programs)" % (
          report["sessions_per_s"],
          report["session_latency_s"]["p95"] * 1000.0,
          len(report["per_program"])))
EOF

echo "== gateway lane (wire-format RPC ingress / tenant admission / replica router) =="
# the marker suite: byte-exact wire golden vectors, strict-decode
# rejection, typed error envelopes round-tripped, fake-clock token
# buckets and gossip, consistent-hash affinity, loopback-fleet chaos
python -m pytest tests/ -m gateway -q
# end-to-end acceptance smoke (ISSUE 13): a REAL 3-replica fleet over
# loopback TCP sockets behind the router + gossip thread. The probe
# kills one replica mid-run and asserts: every in-flight future settles
# via retry on the survivors (zero dangling), the router demotes the
# dead replica, the over-quota tenant alone is refused, and the replica
# REJOINS via a fresh beacon after its serve loop restarts.
JAX_PLATFORMS=cpu python probes/probe_gateway.py
# RPC-tax bench smoke: the same warm CredentialService direct vs through
# the wire (real socket), asserted from the JSON artifact a human reads —
# the ISSUE 13 floor is RPC goodput >= 80% of direct
GATEWAY_JSON=$(mktemp -d)/gateway.json
BENCH_OFFLINE=0 BENCH_BACKEND=python BENCH_BATCH=16 BENCH_CHAOS=0 \
  BENCH_GATEWAY_SECONDS=2 BENCH_GATEWAY_MAX_BATCH=4 JAX_PLATFORMS=cpu \
  python bench.py --gateway > "$GATEWAY_JSON"
GATEWAY_JSON_PATH="$GATEWAY_JSON" python - <<'EOF'
import json, os
with open(os.environ["GATEWAY_JSON_PATH"]) as f:
    line = f.read().strip().splitlines()[-1]
report = json.loads(line)["gateway"]
assert report["goodput_ratio"] >= report["min_ratio"], report
for side in ("direct", "rpc"):
    assert report[side]["completed"] > 0, report
    assert report[side]["errors"] == 0, report
    assert report[side]["dropped_futures"] == 0, report
    assert report[side]["verdict_mismatches"] == 0, report
assert report["rpc"]["rpc_overhead_s"] is not None, report
print("gateway smoke: ok (rpc/direct goodput ratio %.2f, "
      "rpc overhead %.1f ms/req)" % (
          report["goodput_ratio"],
          report["rpc"]["rpc_overhead_s"] * 1000.0))
EOF

echo "== lifecycle lane (warm restarts / readiness gating / drain-and-handoff) =="
# the marker suite: shape-manifest canonicalization + corruption handling,
# WARMING->UP->DRAINING->CLOSED state machine on a fake clock, graceful
# drain refusals resubmitted on ring successors, elastic park/unpark
# hysteresis, and the deterministic loopback rolling-restart drill
python -m pytest tests/ -m lifecycle -q
# end-to-end acceptance smoke (ISSUE 14): a REAL 3-replica TCP fleet under
# continuous loadgen traffic has every replica restarted in sequence —
# graceful drain persists the shape manifest, the successor boots WARMING,
# replays it, and rejoins. The probe asserts zero dangling futures, zero
# non-retryable client errors, the gateway_placed_warming/draining audit
# counters at ZERO, and bounded restart-to-first-SLO per restart.
JAX_PLATFORMS=cpu python probes/probe_lifecycle.py
# warm-restart bench smoke: simulated compile walls behind the manifest +
# persistent-cache replay; asserted from the JSON artifact a human reads —
# the ISSUE 14 floor is warm restart-to-first-SLO at a small fraction of
# the cold compile_plus_run floor (both numbers embedded in the artifact).
# BENCH_LIFECYCLE=0 skips the lane (e.g. on boxes where the simulated
# compile sleeps make the wall too noisy to assert on).
if [ "${BENCH_LIFECYCLE:-1}" = "1" ]; then
  LIFECYCLE_JSON=$(mktemp -d)/lifecycle.json
  BENCH_OFFLINE=0 BENCH_BACKEND=python BENCH_BATCH=8 JAX_PLATFORMS=cpu \
    python bench.py --lifecycle > "$LIFECYCLE_JSON"
  LIFECYCLE_JSON_PATH="$LIFECYCLE_JSON" python - <<'EOF'
import json, os
with open(os.environ["LIFECYCLE_JSON_PATH"]) as f:
    line = f.read().strip().splitlines()[-1]
report = json.loads(line)["lifecycle"]
assert report["manifest_shapes"] == report["shapes"], report
assert report["cold_restart_to_first_slo_s"] >= report[
    "compile_plus_run_floor_s"], report
assert report["warm_restart_to_first_slo_s"] <= (
    report["max_fraction"] * report["cold_restart_to_first_slo_s"]), report
assert report["warm_over_cold"] <= report["max_fraction"], report
print("lifecycle bench smoke: ok (warm restart %.0f ms vs cold floor "
      "%.0f ms, warm/cold %.3f)" % (
          report["warm_restart_to_first_slo_s"] * 1000.0,
          report["compile_plus_run_floor_s"] * 1000.0,
          report["warm_over_cold"]))
EOF
else
  echo "lifecycle bench smoke: skipped (BENCH_LIFECYCLE=0)"
fi

echo "== keylife lane (online DKG / proactive refresh / epoch rollover) =="
# the marker suite: typed share-rejection paths, DKG complaint attribution
# + typed abort, no-master-secret enforcement, refresh same-verkey/all-
# shares-change, epoch registry window/pin mechanics, epoch-keyed wire +
# static-cache coexistence, and the deterministic rollover chaos drill
python -m pytest tests/ -m keylife -q
# end-to-end acceptance smoke (ISSUE 15): a REAL 5-authority fleet born
# from an online DKG (corrupt dealer named + excluded) serves full
# sessions over a TCP socket while the lifecycle takes one proactive
# refresh AND one 3-of-5 -> 2-of-5 reshare mid-traffic. The probe asserts
# zero dangling futures, zero terminal errors, every pre-rollover
# credential verifying post-rollover under its mint epoch, and the beacon
# epoch window advertising each transition.
JAX_PLATFORMS=cpu python probes/probe_epoch.py
# rollover bench smoke: goodput before/during/after a live reshare,
# asserted from the JSON artifact a human reads — the ISSUE 15 floor is a
# NON-ZERO during phase (the rollover never blacks out serving).
# BENCH_KEYLIFE=0 skips the lane.
if [ "${BENCH_KEYLIFE:-1}" = "1" ]; then
  KEYLIFE_JSON=$(mktemp -d)/keylife.json
  BENCH_OFFLINE=0 BENCH_BACKEND=python BENCH_BATCH=16 BENCH_CHAOS=0 \
    BENCH_KEYLIFE_SECONDS=1.5 BENCH_KEYLIFE_MAX_BATCH=4 JAX_PLATFORMS=cpu \
    python bench.py --keylife > "$KEYLIFE_JSON"
  KEYLIFE_JSON_PATH="$KEYLIFE_JSON" python - <<'EOF'
import json, os
with open(os.environ["KEYLIFE_JSON_PATH"]) as f:
    line = f.read().strip().splitlines()[-1]
report = json.loads(line)["keylife"]
assert report["goodput_per_s"]["during"] > 0, report
assert report["goodput_per_s"]["before"] > 0, report
assert report["goodput_per_s"]["after"] > 0, report
assert report["degradation_ratio"] is not None, report
assert report["refreshes"] == 1 and report["reshares"] == 1, report
print("keylife bench smoke: ok (goodput %.1f -> %.1f -> %.1f /s through "
      "refresh+reshare, degradation %.2f)" % (
          report["goodput_per_s"]["before"],
          report["goodput_per_s"]["during"],
          report["goodput_per_s"]["after"],
          report["degradation_ratio"]))
EOF
else
  echo "keylife bench smoke: skipped (BENCH_KEYLIFE=0)"
fi

echo "== batchverify lane (RLC combined pairing check / bisection fallback) =="
# the marker suite: deterministic combiner derivation (same transcript ->
# same exponents, cross-process), transcript domain separation (verkey /
# epoch / lane content), batched-vs-exact bit-identical verdicts, forged-
# lane attribution through the bisection ladder, the adversarial 100-draw
# soundness sweeps (B in {16,256}) and the cancellation-pair attack, plus
# the serve/engine "batched" program modes (pow2 jit-shape bucketing,
# COCONUT_BATCH_VERIFY default, keychain refusal)
python -m pytest tests/ -m batchverify -q
# end-to-end acceptance smoke (ISSUE 16): a REAL CredentialService in
# mode="batched" folds a 64-lane batch (one forged sigma_2) into ONE
# combined pairing check, bisects the failure down to the culprit lane,
# dead-letters it with program + lane index, and settles every survivor
# True — then proves the steady state: an all-valid batch is ONE combined
# check and ONE final exponentiation.
JAX_PLATFORMS=cpu python probes/probe_batchverify.py
# bench smoke: batched-vs-exact device time for verify AND show-verify,
# asserted from the JSON artifact a human reads — the ISSUE 16 floor is
# <= 2 final exponentiations per combined batch and a reported crossover.
# BENCH_BATCHVERIFY=0 skips the lane.
if [ "${BENCH_BATCHVERIFY:-1}" = "1" ]; then
  BATCHV_JSON=$(mktemp -d)/batchverify.json
  BENCH_OFFLINE=0 BENCH_BACKEND=python BENCH_BATCH=8 BENCH_CHAOS=0 \
    BENCH_BATCHVERIFY_SIZES=4,8 BENCH_BATCHVERIFY_REPS=1 JAX_PLATFORMS=cpu \
    python bench.py --batchverify > "$BATCHV_JSON"
  BATCHV_JSON_PATH="$BATCHV_JSON" python - <<'EOF'
import json, os
with open(os.environ["BATCHV_JSON_PATH"]) as f:
    line = f.read().strip().splitlines()[-1]
report = json.loads(line)["batchverify"]
assert report["points"], report
for p in report["points"]:
    assert p["verify_batched_final_exps"] <= 2, p
    assert p["show_batched_final_exps"] <= 2, p
assert report["batched_fallbacks"] == 0, report
assert "crossover_b" in report, report
print("batchverify bench smoke: ok (verify %.2fx, show %.2fx at B=%d, "
      "crossover_b=%s)" % (
          report["verify_speedup_at_max_b"],
          report["show_speedup_at_max_b"],
          report["points"][-1]["b"],
          report["crossover_b"]))
EOF
else
  echo "batchverify bench smoke: skipped (BENCH_BATCHVERIFY=0)"
fi

echo "== state lane (durable WAL / replicated nullifiers / kill-the-witness) =="
# the marker suite: WAL framing + torn-tail truncation (counted exactly
# once), the five-point crash enumeration (pre-append / mid-record /
# post-append-pre-fsync / mid-snapshot / mid-compaction -> prefix-
# consistent replay), snapshot+replay StateStore with LWW anti-entropy,
# nullifier derivation / device-vs-host probe parity / check-and-set
# commit, the typed DoubleSpendError through engine + wire, and the
# deterministic loopback kill-the-witness drill
python -m pytest tests/test_state.py -m state -q
# end-to-end acceptance smoke (ISSUE 17): a REAL 3-replica TCP fleet
# with per-replica WALs and beacon-driven anti-entropy — witness a show,
# SIGKILL-equivalent the witnessing replica, prove both survivors AND
# the WAL-replaying restarted witness still reject the replayed
# nullifier while a fresh re-randomized show stays accepted.
JAX_PLATFORMS=cpu python probes/probe_nullifier.py
# bench smoke: show-verify goodput bare vs WAL-backed nullifier set,
# asserted from the JSON artifact — the ISSUE 17 floor is >= 0.85x
# goodput with the group-commit-per-batch fsync policy visible as
# wal_fsyncs well under wal_appends. BENCH_STATE=0 skips the lane.
if [ "${BENCH_STATE:-1}" = "1" ]; then
  STATE_JSON=$(mktemp -d)/state.json
  BENCH_OFFLINE=0 BENCH_BACKEND=python BENCH_BATCH=8 BENCH_CHAOS=0 \
    BENCH_STATE_SHOWS=32 JAX_PLATFORMS=cpu \
    python bench.py --state > "$STATE_JSON"
  STATE_JSON_PATH="$STATE_JSON" python - <<'EOF'
import json, os
with open(os.environ["STATE_JSON_PATH"]) as f:
    line = f.read().strip().splitlines()[-1]
report = json.loads(line)["state"]
assert report["fsync_policy"] == "group_commit_per_batch", report
assert report["goodput_ratio"] >= report["min_ratio"], report
assert report["wal_fsyncs"] < report["wal_appends"], report
assert report["nullifier_commits"] == report["shows"], report
print("state bench smoke: ok (ratio %.2fx, %d commits in %d fsyncs)"
      % (report["goodput_ratio"], report["nullifier_commits"],
         report["wal_fsyncs"]))
EOF
else
  echo "state bench smoke: skipped (BENCH_STATE=0)"
fi

echo "== hashmsm lane (device hash-to-G1 / bucketed Pippenger MSM) =="
# the marker suite: SvdW map parity vs the spec and the native oracle
# (random messages, empty message, the 255-byte DST boundary, u-values
# driving each of the three x-candidates, the (u, p-u) identity-sum
# edge), bucketed-vs-Horner bit parity across window sizes / ragged B /
# zero scalars / GLV on/off, knob parsing, dispatch-counter routing,
# and the epoch-retirement nullifier compaction satellite
python -m pytest tests/ -m hashmsm -q
# end-to-end acceptance smokes: prepare with the device hash FORCED on
# (the probe asserts device_hash_batches moved and zero fallbacks), and
# the bucketed-vs-Horner micro-probe with every lane checked against
# the Python spec (small shapes — this is the CPU parity gate, the
# timing story lives on the real chip)
COCONUT_DEVICE_HASH=1 PROBE_PREPARE_B=8 JAX_PLATFORMS=cpu \
  python probes/probe_prepare.py
PROBE_MSM_WINDOWS=3 JAX_PLATFORMS=cpu python probes/probe_pippenger.py 4 6
# calibration mode (ISSUE 19 satellite): measured-vs-model crossover
# sweep on tiny shapes — prints per-shape verdicts and a
# COCONUT_MSM_WINDOW recommendation; exits nonzero on parity failure
PROBE_MSM_WINDOWS=3 PROBE_CALIB_B=2 PROBE_CALIB_KS=4,6 \
  JAX_PLATFORMS=cpu python probes/probe_pippenger.py --calibrate
# bench smoke: old-vs-new path goodput for the hash and MSM stages,
# parity + path selection asserted from the artifact's counters. On
# this CPU mesh there is NO timing floor (ISSUE 18 acceptance split:
# the "new path faster" assert binds on the device backend only).
# BENCH_HASHMSM=0 skips the lane.
if [ "${BENCH_HASHMSM:-1}" = "1" ]; then
  HASHMSM_JSON=$(mktemp -d)/hashmsm.json
  BENCH_OFFLINE=0 BENCH_BACKEND=python BENCH_BATCH=8 BENCH_CHAOS=0 \
    BENCH_HASHMSM_B=8 BENCH_HASHMSM_K=4 BENCH_HASHMSM_REPS=1 \
    JAX_PLATFORMS=cpu python bench.py --hashmsm > "$HASHMSM_JSON"
  HASHMSM_JSON_PATH="$HASHMSM_JSON" python - <<'EOF'
import json, os
with open(os.environ["HASHMSM_JSON_PATH"]) as f:
    line = f.read().strip().splitlines()[-1]
report = json.loads(line)["hashmsm"]
assert report["parity_ok"], report
assert report["device_hash_batches"] > 0, report
assert report["msm_bucketed_dispatches"] > 0, report
assert report["msm_horner_dispatches"] > 0, report
assert report["msm_bucket_window"] == report["window"], report
print("hashmsm bench smoke: ok (hash %s -> device x%s, msm horner -> "
      "bucketed w=%d x%s, floor_enforced=%s)" % (
          report["hash_old_path"], report["hash_speedup"],
          report["window"], report["msm_speedup"],
          report["timing_floor_enforced"]))
EOF
else
  echo "hashmsm bench smoke: skipped (BENCH_HASHMSM=0)"
fi

echo "== scenarios lane (application workflows / population traffic model) =="
# the marker suite: workflow state-machine runtime on a fake clock
# (retry taxonomy, deadlines, parked-retry resubmission, drain-cancel
# leaves no dangling frames), bit-stable seeded arrival streams
# (golden hash), Zipf tenanting + lazy population, report attribution,
# and the petition/e-cash/access flows end-to-end over loopback RPC
# with typed double-spend rejections
python -m pytest tests/ -m scenarios -q
# end-to-end acceptance smoke: a REAL 3-replica TCP fleet (per-replica
# WALs, anti-entropy, gossip-fed router) absorbing a mixed honest
# population through a flash crowd — zero failed, zero cancelled, zero
# rejections, availability timeline spanning the run
JAX_PLATFORMS=cpu python probes/probe_scenarios.py
# bench smoke: sustained mixed run on the local engine with the
# elastic controller in the loop and adversarial fractions ON — the
# artifact must show goodput tracking the diurnal curve, the pool
# resizing, p99 inside the SLO through the flash crowd, and every
# deliberate re-sign/double-spend as a typed rejection (asserted
# inside the lane itself). BENCH_SCENARIOS=0 skips the lane.
if [ "${BENCH_SCENARIOS:-1}" = "1" ]; then
  SCN_JSON=$(mktemp -d)/scenarios.json
  BENCH_OFFLINE=0 BENCH_BACKEND=python BENCH_BATCH=8 BENCH_CHAOS=0 \
    BENCH_SCENARIOS_S=40 JAX_PLATFORMS=cpu \
    python bench.py --scenarios > "$SCN_JSON"
  SCN_JSON_PATH="$SCN_JSON" python - <<'EOF'
import json, os
with open(os.environ["SCN_JSON_PATH"]) as f:
    line = f.read().strip().splitlines()[-1]
top = json.loads(line)
scn = top["scenarios"]
totals = scn["report"]["totals"]
assert totals["failed"] == 0 and totals["cancelled"] == 0, totals
assert totals["completed"] > 0 and totals["rejected_expected"] > 0, totals
print("scenarios bench smoke: ok (%.2f workflows/s, %d completed, "
      "%d typed rejections, peak %.2f/s vs trough %.2f/s)"
      % (top["value"], totals["completed"], totals["rejected_expected"],
         scn["goodput_peak_half_per_s"], scn["goodput_trough_per_s"]))
EOF
else
  echo "scenarios bench smoke: skipped (BENCH_SCENARIOS=0)"
fi

echo "== obs lane (request-scoped tracing / profiler bridge / flight recorder) =="
python -m pytest tests/test_obs.py -m obs -q
# end-to-end acceptance smoke on the REAL service (CPU, stub backend):
# one injected dispatch fault + one forged credential, tracing enabled.
# The forged request's span tree must show admission -> coalesce ->
# dispatch -> retry -> bisection -> dead-letter, and its trace_id must
# appear in the dead-letter JSONL line AND the flight record.
OBS_DIR=$(mktemp -d)
OBS_DLQ="$OBS_DIR/dead.jsonl" python - <<'EOF'
import os
from types import SimpleNamespace
from coconut_tpu.faults import DeadLetterLog, FaultyBackend
from coconut_tpu.obs import flight
from coconut_tpu.obs import trace as otrace
from coconut_tpu.retry import RetryPolicy
from coconut_tpu.serve.service import CredentialService

def cred(ok=True):
    return SimpleNamespace(sigma_1=1, sigma_2=1, ok=ok)

class Grouped:
    def batch_verify_grouped(self, sigs, msgs, vk, params):
        return all(s.sigma_1 is not None and s.ok for s in sigs)

otrace.enable()
dlq = os.environ["OBS_DLQ"]
svc = CredentialService(
    FaultyBackend(Grouped(), raise_on={0}), None, None, mode="grouped",
    max_batch=4, retry_policy=RetryPolicy(max_attempts=2, base_delay=0.0),
    dead_letter_path=dlq)
with svc:
    futs = [svc.submit(cred(ok=(i != 2)), [0], max_wait_ms=100.0)
            for i in range(4)]
    verdicts = [f.result(30.0) for f in futs]
assert verdicts == [True, True, False, True], verdicts
(rec,) = DeadLetterLog.read(dlq)
assert rec["schema"] == 4 and rec["trace_id"] == futs[2].trace_id, rec
assert rec["program"] == "verify", rec
tree = otrace.get_tracer().spans_for(futs[2].trace_id)
names = {s.name for s in tree}
assert names >= {"request", "queue_wait", "batch", "coalesce", "dispatch",
                 "device", "bisect", "demux"}, names
events = {e["name"] for s in tree for e in s.events}
assert {"retry", "attempt_failed", "split", "dead_letter"} <= events, events
(fl,) = flight.read(dlq)
assert fl["trace_id"] == futs[2].trace_id and fl["reason"] == "dead_letter"
print("obs smoke: ok (%d spans in the culprit's tree, trace %s)"
      % (len(tree), rec["trace_id"]))
EOF
test -f "$OBS_DIR/dead.jsonl.flight.jsonl"

echo "== encode-pipeline lane (prefetch worker / static cache / raw wire) =="
# lean by construction: only host-side / small-jit tests carry the
# `pipeline` marker (the kernel-materializing encode tests ride the
# default suite above, the sharded pad regression the heavy lane) — so
# this lane stays minutes, not the multi-minute-per-shape trace cost
python -m pytest tests/ -m pipeline -q
# per-stage encode micro-probe (bytes-framing vs digits vs tables): the
# profiling-round artifact for where the host encode wall actually is.
# Host-encode stages are platform-independent — pin CPU so the probe
# never pays a device comb build in the default lane.
JAX_PLATFORMS=cpu python probes/probe_encode.py
if [ "${CI_HEAVY:-0}" = "1" ]; then
  # Heavy lane in its OWN process: the at-scale B=1024 programs
  # accumulate ~25 GB of compiled XLA CPU state, and one combined
  # heavy+default+mesh process was observed segfaulting inside a later
  # sharded pjit execution (2026-08-01) while every lane passes in
  # isolation — bound the per-process executable cache by splitting.
  # Marker-based selection: file-agnostic, and the second process runs
  # ONLY the heavy tests.
  echo "== heavy lane (separate process) =="
  COCONUT_TEST_HEAVY=1 python -m pytest tests/ -m heavy -q
fi

echo "== driver probes =="
# Compile (not just import) the flagship entry program and check its
# bits, exactly as the driver's compile-check does. Budget ~5.5 min on
# this host: the cost is dominated by Python tracing + host comb-table
# build (the persistent cache only removes the XLA compile), so treat
# this as the entry probe's expected wall time, not a cache miss.
python -c "
import __graft_entry__ as ge
fn, a = ge.entry()
import jax
assert bool(jax.jit(fn)(*a).all())
"
# Run the multi-chip dryrun exactly as the driver does (8-device virtual CPU
# mesh). tests/test_shard.py compiled these exact programs above, so this is
# warm-seconds from the persistent cache — and it keeps the cache seeded so
# the driver's MULTICHIP probe never pays a cold compile (VERDICT r3 item 1).
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
  JAX_PLATFORMS=cpu \
  python -c "import __graft_entry__ as ge; ge.dryrun_multichip(8)"
echo "ci: ok"
