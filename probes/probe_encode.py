"""probe_encode.py: per-stage host-encode timings (ISSUE-3 profiling aid).

Breaks the batch-encode wall into its stages so a profiling round can see
WHERE host time goes without instrumenting the backend:

  bytes-framing   fp_encode_raw_batch — to_bytes + frombuffer only (the
                  raw wire; Montgomery entry happens on device via
                  fp.to_mont)
  host-Montgomery fp_encode_batch — the bigint x*R%p + balance-carry path
                  the raw wire replaces
  digits          fr_digits_signed_np at the grouped 6-bit and comb
                  schedules
  tables          comb-table build, cold vs the static-operand/LRU caches
  full            encode_verify_batch / encode_grouped_batch, cold vs
                  cache-hot (the steady-state per-batch cost)

Host-only: no fused kernel runs (the one jitted program is the small comb
build). PROBE_BATCH overrides the 1024 default.
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import coconut_tpu.tpu

coconut_tpu.tpu.enable_compile_cache()
import __graft_entry__ as ge
from coconut_tpu.ops.fields import R
from coconut_tpu.tpu import limbs
from coconut_tpu.tpu.backend import (
    _COMB_CACHE,
    _STATIC_CACHE,
    JaxBackend,
    _comb_digits,
    _comb_tables,
)


def t(label, fn, reps=3):
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    print("%-34s %8.2f ms" % (label, best * 1e3))
    return best


batch = int(os.environ.get("PROBE_BATCH", "1024"))
params, sk, vk, sigs, msgs_list = ge._fixture(batch=batch)
be = JaxBackend()
ctx = params.ctx

coords = [s.sigma_1[0] for s in sigs] + [s.sigma_1[1] for s in sigs]
coords += [s.sigma_2[0] for s in sigs] + [s.sigma_2[1] for s in sigs]
print("batch=%d  (%d Fp coords per batch upload)" % (batch, len(coords)))

t("bytes-framing (raw wire)", lambda: limbs.fp_encode_raw_batch(coords))
t("host Montgomery (legacy wire)", lambda: limbs.fp_encode_batch(coords))

scalars = [[1] + [m % R for m in msgs] for msgs in msgs_list]
t("digits: comb schedule", lambda: _comb_digits(scalars))
flat = [m % R for msgs in msgs_list for m in msgs]
t(
    "digits: grouped 6-bit (one row)",
    lambda: limbs.fr_digits_signed_np(flat[:batch], nwin=43, window=6),
)

bases = tuple([vk.X_tilde] + list(vk.Y_tilde))


def cold_tables():
    _COMB_CACHE.clear()
    _comb_tables(ctx.other, ctx.name == "G1", bases)


t("tables: comb build (cold)", cold_tables, reps=2)
t("tables: comb build (LRU hit)", lambda: _comb_tables(ctx.other, ctx.name == "G1", bases))


def cold_verify_encode():
    _COMB_CACHE.clear()
    _STATIC_CACHE.clear()
    be.encode_verify_batch(sigs, msgs_list, vk, params)


t("full: encode_verify_batch (cold)", cold_verify_encode, reps=2)
t(
    "full: encode_verify_batch (hot)",
    lambda: be.encode_verify_batch(sigs, msgs_list, vk, params),
)
t(
    "full: encode_grouped_batch (hot)",
    lambda: be.encode_grouped_batch(sigs, msgs_list, vk, params),
)

from coconut_tpu import metrics

snap = metrics.snapshot()["counters"]
print(
    "encode_cache_hits=%d encode_cache_misses=%d"
    % (snap.get("encode_cache_hits", 0), snap.get("encode_cache_misses", 0))
)
