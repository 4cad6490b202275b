"""probe_prepare.py: cProfile the warm batch_prepare_blind_sign, and
report the host-hash vs device-hash split (PR 18). When
COCONUT_DEVICE_HASH=1 the probe ASSERTS the device hash path actually
ran (device_hash_batches counter moved for every call).
PROBE_PREPARE_B overrides the batch size (default 1024)."""
import cProfile, os, pstats, sys, time
sys.path.insert(0, "/root/repo")
import coconut_tpu.tpu
coconut_tpu.tpu.enable_compile_cache()
import __graft_entry__ as ge
from coconut_tpu import metrics
from coconut_tpu.elgamal import elgamal_keygen
from coconut_tpu.signature import batch_prepare_blind_sign
from coconut_tpu.tpu.backend import JaxBackend

B = int(os.environ.get("PROBE_PREPARE_B", "1024"))
params, sk, vk, sigs, msgs_list = ge._fixture(batch=B)
be = JaxBackend()
esk, epk = elgamal_keygen(params.ctx.sig, params.g)
t0 = time.time()
batch_prepare_blind_sign(msgs_list, 2, epk, params, backend=be)
print("compile+run %.1fs" % (time.time() - t0))

hb0 = metrics.get_count("device_hash_batches")
hp0 = metrics.get_count("device_hash_points")
best = None
for _ in range(3):
    t0 = time.time()
    batch_prepare_blind_sign(msgs_list, 2, epk, params, backend=be)
    dt = time.time() - t0
    best = dt if best is None else min(best, dt)
print("warm best %.3fs -> %.0f req/s" % (best, B / best))

dev_batches = metrics.get_count("device_hash_batches") - hb0
dev_points = metrics.get_count("device_hash_points") - hp0
host_points = 3 * B - dev_points  # 3 warm runs of B hashes each
print(
    "hash split: device=%d host=%d (batches=%d) knob=%s"
    % (
        dev_points,
        host_points,
        dev_batches,
        os.environ.get("COCONUT_DEVICE_HASH", "<unset>"),
    )
)
if os.environ.get("COCONUT_DEVICE_HASH") == "1":
    assert be.device_hash_enabled(), "knob=1 but device hash disabled"
    assert dev_batches == 3 and dev_points == 3 * B, (
        "COCONUT_DEVICE_HASH=1 but the device path did not run: "
        "batches=%d points=%d" % (dev_batches, dev_points)
    )
    print("device-path assertion OK")

pr = cProfile.Profile(); pr.enable()
batch_prepare_blind_sign(msgs_list, 2, epk, params, backend=be)
pr.disable()
pstats.Stats(pr).sort_stats("cumulative").print_stats(22)
