"""Test configuration.

Tests run on the CPU (JAX_PLATFORMS=cpu), on a virtual 8-device mesh; the
chip is reached only through `python chip_smoke.py` on the chip tool.
XLA_FLAGS must carry --xla_force_host_platform_device_count=8 before the
CPU client initializes, and the platform is pinned both in the
environment and through jax.config, so no test can pick up an
accelerator. tests/test_tpu_compile.py compiles for a DESCRIBED v5e chip
without running anything on it.
"""

import faulthandler
import os

import pytest

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

try:
    import jax

    jax.config.update("jax_platforms", "cpu")
    # persistent compile cache: the jax-backend differential tests compile
    # multi-minute XLA programs on the CPU mesh; cache them across runs
    # (one shared definition — see coconut_tpu/tpu/__init__.py)
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import coconut_tpu.tpu

    coconut_tpu.tpu.enable_compile_cache()
except ImportError:  # pragma: no cover - jax is baked into this image
    pass

try:
    from coconut_tpu.analysis import lockcheck as _lockcheck
except ImportError:  # pragma: no cover - analysis rides with the package
    _lockcheck = None


def pytest_configure(config):
    # Hang diagnosis: the driver's tier-1 run is killed at a hard wall
    # (timeout -k 10 870) with no stacks. Dump EVERY thread's traceback
    # shortly before that wall so a wedged run names its culprit (a
    # stuck Condition.wait, a hung dispatch) instead of dying silent.
    # COCONUT_TEST_DUMP_S=0 disables; exit=False — diagnose, don't kill.
    faulthandler.enable()
    try:
        _dump_s = float(os.environ.get("COCONUT_TEST_DUMP_S", "840"))
    except ValueError:
        _dump_s = 840.0
    if _dump_s > 0:
        faulthandler.dump_traceback_later(_dump_s, exit=False)

    # Runtime lock-order tracking (ISSUE 20): COCONUT_LOCK_CHECK=1
    # patches threading.Lock/RLock so every lock allocated by
    # coconut_tpu code records the global acquisition-order graph; the
    # autouse guard below fails any test that recorded an inversion.
    # Opt-in via env so the default tier-1 run is byte-identical.
    if _lockcheck is not None and _lockcheck.env_enabled():
        config._coconut_lock_tracker = _lockcheck.install()

    config.addinivalue_line(
        "markers",
        "heavy: multi-minute at-scale fused-kernel tests, run by ci.sh's "
        "separate heavy-lane process (COCONUT_TEST_HEAVY=1, -m heavy)",
    )
    config.addinivalue_line(
        "markers",
        "faults: fault-supervision suite (retry/fallback/bisection/"
        "checkpoint hardening), also run explicitly by ci.sh's fault lane",
    )
    config.addinivalue_line(
        "markers",
        "pipeline: encode-pipeline suite (verify_stream prefetch worker, "
        "static-operand cache, raw-wire Montgomery parity), also run "
        "explicitly by ci.sh's pipeline lane",
    )
    config.addinivalue_line(
        "markers",
        "serve: online serving layer suite (dynamic batching, deadline "
        "coalescing, admission control, demux/drain invariants, loadgen), "
        "also run explicitly by ci.sh's serve lane",
    )
    config.addinivalue_line(
        "markers",
        "obs: observability suite (request-scoped tracing, Chrome-trace/"
        "Perfetto export, flight recorder, percentile edge cases), also "
        "run explicitly by ci.sh's obs lane",
    )
    config.addinivalue_line(
        "markers",
        "chaos: self-healing pool suite (crash containment, hung-dispatch "
        "watchdog, quarantine/probation ladder, brownout shedding, chaos "
        "schedules), also run explicitly by ci.sh's chaos lane",
    )
    config.addinivalue_line(
        "markers",
        "issue: threshold-issuance suite (quorum fan-out, first-t-of-n "
        "aggregation, straggler hedging, corrupt-partial attribution), "
        "also run explicitly by ci.sh's issue lane",
    )
    config.addinivalue_line(
        "markers",
        "engine: unified execution-engine suite (program registration, "
        "cross-program placement, per-program jit-shape caches, typed "
        "error hierarchy, online/offline show parity, full-session "
        "pipeline), also run explicitly by ci.sh's engine lane",
    )
    config.addinivalue_line(
        "markers",
        "gateway: fleet-gateway suite (wire-format golden vectors, typed "
        "error envelopes, per-tenant admission, health gossip, consistent-"
        "hash routing, replica failover), also run explicitly by ci.sh's "
        "gateway lane",
    )
    config.addinivalue_line(
        "markers",
        "lifecycle: zero-downtime lifecycle suite (shape-manifest warm "
        "boot, WARMING/DRAINING readiness gating, drain-and-handoff, "
        "elastic pool sizing, rolling-restart drill), also run "
        "explicitly by ci.sh's lifecycle lane",
    )
    config.addinivalue_line(
        "markers",
        "keylife: dealerless key-lifecycle suite (online DKG with "
        "complaint attribution, proactive refresh, t/n reshare, epoch "
        "registry window/pinning, epoch-keyed wire + cache behavior, "
        "fake-clock rollover chaos drill), also run explicitly by "
        "ci.sh's keylife lane",
    )
    config.addinivalue_line(
        "markers",
        "batchverify: RLC combined-pairing batch verification suite "
        "(deterministic combiner derivation, pad-lane contract, "
        "adversarial soundness + bisection attribution, engine batched "
        "mode), also run explicitly by ci.sh's batchverify lane",
    )
    config.addinivalue_line(
        "markers",
        "state: durable state plane suite (WAL framing/torn-tail "
        "recovery, snapshot+replay StateStore, crash-point enumeration, "
        "anti-entropy replication, nullifier double-spend detection "
        "with the deterministic kill-the-witness drill), also run "
        "explicitly by ci.sh's state lane",
    )
    config.addinivalue_line(
        "markers",
        "hashmsm: device hash-to-curve + bucketed-MSM suite (SvdW map "
        "parity vs the spec/native oracle including adversarial vectors, "
        "Pippenger bucket schedule bit-parity across window sizes, GLV "
        "on/off, knob/counter routing), also run explicitly by ci.sh's "
        "hashmsm lane",
    )
    config.addinivalue_line(
        "markers",
        "scenarios: application-scenario suite (workflow state-machine "
        "runtime on a fake clock, bit-stable seeded arrival streams, "
        "petition/e-cash/access flows end-to-end over loopback RPC with "
        "typed double-spend rejections), also run explicitly by ci.sh's "
        "scenarios lane",
    )
    config.addinivalue_line(
        "markers",
        "analysis: invariant lint suite (static checkers' seeded-bad "
        "fixtures + clean-tree gate, runtime lock-order tracker, "
        "dead-letter schema validator), also run explicitly by ci.sh's "
        "analysis lane",
    )
    config.addinivalue_line(
        "markers",
        "slow: multi-minute tests (virtual-mesh program tracing/execution) "
        "excluded from the driver's bounded tier-1 run (-m 'not slow'); "
        "ci.sh's full-suite pass still runs them",
    )


def pytest_unconfigure(config):
    faulthandler.cancel_dump_traceback_later()
    tracker = getattr(config, "_coconut_lock_tracker", None)
    if tracker is not None and _lockcheck is not None:
        _lockcheck.uninstall()


@pytest.fixture(autouse=True)
def _lock_order_guard(request):
    """With COCONUT_LOCK_CHECK=1, fail any test during which coconut_tpu
    code acquired locks in an order that inverts a previously observed
    order (the two paths can deadlock under the right interleaving)."""
    tracker = getattr(request.config, "_coconut_lock_tracker", None)
    if tracker is None:
        yield
        return
    tracker.drain_inversions()  # don't blame this test for earlier ones
    yield
    inversions = tracker.drain_inversions()
    assert not inversions, (
        "lock acquisition-order inversion(s) recorded during this test "
        "(COCONUT_LOCK_CHECK): %r" % (inversions,)
    )
