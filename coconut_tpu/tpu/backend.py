"""JaxBackend — the JAX/TPU CurveBackend implementation.

Routes the protocol hot paths (reference signature.rs:472-478 pairing check,
signature.rs:465/513 MSMs) through fused, jitted, batched limb kernels:

  host (python ints)
    -> limb encode (Montgomery)                      [limbs.py]
    -> one XLA program per batch shape:
         shared-base windowed MSM                    [curve.py]
         -> affine normalize (batched inversion)
         -> multi-Miller loop (scan over BLS bits)   [pairing.py]
         -> shared final exponentiation
         -> GT == 1 bits
    -> decode / bools

Results are bit-identical to the Python spec ops (enforced by
tests/test_backends.py and tests/test_tpu_backend.py): identical affine
coordinates for MSMs, identical booleans for pairing products, the spec's
`None`-identity conventions carried as validity masks.

Multi-chip: `shard_verify` shards the credential batch over a mesh axis with
`shard_map` (data parallelism — SURVEY.md §2.3) and all-gathers the bits.
"""

import functools
import os as _os

import jax
import jax.numpy as jnp
import numpy as np

from ..backend import CurveBackend
from ..obs import trace as otrace
from ..ops.curve import g1 as _sg1, g2 as _sg2
from ..ops.fields import R
from . import curve as cv
from . import on_tpu
from . import pairing as pr
from . import tower as tw
# Bit length of the small-exponents combiner scalars r_i (batch_verify_
# combined / _grouped sample secrets.randbits(_R_RAND_BITS)). The signed
# 5-bit recode of a (<2^128)-value occupies ceil(128/5) = 26 windows plus
# one carry window — everything above _R_NWIN is structurally zero, so the
# -sigma_2 MSM can run the short schedule.
_R_RAND_BITS = 128
_R_NWIN = -(-_R_RAND_BITS // 5) + 1  # 27

# The grouped verify's window schedule: signed 6-bit (43 windows, 33-entry
# on-device tables) — the fold adds dominate there and drop ~17% vs the
# 5-bit schedule; the comb/distinct paths keep 5-bit (17-entry host tables).
_G_WINDOW = 6
_G_NWIN = -(-255 // _G_WINDOW)  # 43
_G_RNWIN = -(-_R_RAND_BITS // _G_WINDOW) + 1  # 23


_SIGNED_NWIN = 52  # signed 5-bit windows covering the 255-bit Fr

# Comb (shared-base) schedule: signed 9-bit on the real chip — the comb has
# NO doublings, so fewer windows = strictly fewer fold adds (203 adds at
# k=7/29 windows, vs 224 at 8-bit, 301 at 6-bit, 364 at 5-bit); the larger
# tables (257 multiples/base, int16 digits) amortize behind the per-verkey
# cache. This is also why GLV buys the comb nothing: halving scalar bits
# doubles the base count at constant adds — the doubling-free schedule's
# lever is window size, harvested here directly. GLV is applied where
# doublings DO exist (msm_distinct_signed, see _msm_distinct).
#
# On CPU (the virtual-mesh correctness vehicle: tests, driver dryrun) the
# schedule stays 6-bit: the 257-entry on-device table build multiplies the
# already-dominant mesh execution/compile time there for zero correctness
# value (the 9-bit schedule itself is differentially tested at small
# shapes, and chip_smoke.py checks every lane of the full-width 9-bit
# programs on the chip). COCONUT_COMB_WINDOW overrides.


def _comb_window_default():
    w = _os.environ.get("COCONUT_COMB_WINDOW")
    if not w:
        return 9 if on_tpu() else 6
    w = int(w)
    # signed digit magnitudes ride in uint8 up to w=8 and int16 for w=9
    # (limbs.fr_digits_signed_np widens automatically — a uint8 cap
    # wrapped 256 -> 0 at w=9 and returned WRONG verify bits). w=10 stays
    # refused: an earlier TPU runtime mis-stacked the Fp2 comb-table
    # build's scan rows at E=513 entries (G1 at w=10 and both groups at
    # w=9 were bit-exact; CPU is bit-exact at every window), and no run
    # on the current chip has cleared it yet.
    if not 1 <= w <= 9:
        raise ValueError(
            "COCONUT_COMB_WINDOW=%d unsupported: comb windows are capped "
            "at 9 (the Fp2 table build at 513-entry tables is unverified "
            "on the chip; see _comb_window_default)" % w
        )
    return w


_C_SCHED = None


def _comb_schedule():
    """(window, nwin, entries) for the shared-base comb — 29/257 at the
    9-bit TPU default (int16 digits), 43/33 at the 6-bit CPU default.
    Chosen LAZILY on first use: `jax.default_backend()`
    initializes the platform client, and doing that at import time would
    both break callers that configure the platform after importing this
    module (multi-process TPU init ordering) and freeze the window choice
    before their config lands."""
    global _C_SCHED
    if _C_SCHED is None:
        w = _comb_window_default()
        _C_SCHED = (w, -(-255 // w), (1 << (w - 1)) + 1)
    return _C_SCHED

# GLV on distinct-base G1 MSMs (see _msm_distinct). Kill switch for callers
# that feed curve points outside the r-order subgroup.
_GLV_ENABLED = _os.environ.get("COCONUT_GLV", "1") == "1"


def _flag_or_tpu(name):
    """An explicit 0/1 environment override, else the platform default:
    on for the TPU, off on the CPU test mesh."""
    v = _os.environ.get(name)
    return on_tpu() if v is None else v == "1"


# Raw point wire (see _pts_f32 / tw.encode_raw_batch): ship 48 raw
# canonical bytes per Fp and enter the Montgomery domain on device. Like
# the comb window, decided LAZILY and per platform: on the real chip the
# host-side bigint Montgomery encode is the wall, on the CPU test mesh it
# would only force a recompile of every cached fused program (new operand
# dtypes) for zero correctness value — the conversion itself is
# differentially tested at the fp level. COCONUT_RAW_WIRE=0/1 overrides.
_RAW_WIRE = None


def _raw_wire_enabled():
    global _RAW_WIRE
    if _RAW_WIRE is None:
        _RAW_WIRE = _flag_or_tpu("COCONUT_RAW_WIRE")
    return _RAW_WIRE


# Device-resident hash-to-G1 (PR 18): run the CTH-v2 SvdW map +
# cofactor clear as one jitted program instead of per-message host
# hashing (the prepare phase's 1,024 serial native calls were its last
# named host wall). Same lazy per-platform default as the raw wire: on
# the real chip the device map wins; on the CPU test mesh it would only
# add compiles of a ~1k-mul program for zero correctness value (the map
# is differentially tested at small shapes). COCONUT_DEVICE_HASH=0/1
# overrides.
_DEVICE_HASH = None


def _device_hash_enabled():
    global _DEVICE_HASH
    if _DEVICE_HASH is None:
        _DEVICE_HASH = _flag_or_tpu("COCONUT_DEVICE_HASH")
    return _DEVICE_HASH


# Bucketed (Pippenger) distinct-MSM schedule (PR 18): window the
# scalars, scatter points into per-row buckets, fold with the
# running-sum trick (curve.msm_distinct_bucketed) — the table-free
# alternative to msm_distinct_signed's Horner schedule. Selection is a
# cost model per (effective base count, scalar bits), resolved with the
# same lazy per-platform pattern as _comb_window_default:
# COCONUT_MSM_WINDOW=w forces the bucketed path at window w (2..8),
# COCONUT_MSM_WINDOW=0 forces Horner, unset -> cost-model choice on the
# real chip and Horner on the CPU test mesh (where an extra schedule
# only multiplies compile time for zero correctness value — parity is
# asserted by the hashmsm test/bench lanes with the window forced).
_BUCKET_MODE = None


def _bucket_cost(k, nbits, w):
    # batch-width add-equivalents per row: nwin windows of (w doublings
    # ~0.75 add each, k scatter adds, 2*nb running-sum fold adds, 1
    # Horner add); NO table build
    nwin = -(-nbits // w) + 1
    return nwin * (0.75 * w + k + 2 * (1 << (w - 1)) + 1)


def _horner_cost(k, nbits):
    # msm_distinct_signed: 16 chained build adds at k lanes + nwin
    # windows of (5 doublings, k gathered adds)
    nwin = -(-nbits // 5) + 1
    return 16 * k + nwin * (0.75 * 5 + k)


def _bucket_window(k, nbits):
    """Bucketed-schedule window for an effective (post-GLV) per-row base
    count `k` and scalar width `nbits`, or None for the Horner path.
    The cost model's crossover sits around k ~ 64-128: below it the
    17-entry-table Horner schedule is strictly cheaper (the sigma-pair
    show MSM at k = 4 stays Horner unless forced), above it the bucket
    scatter amortizes the missing table build and the larger windows."""
    global _BUCKET_MODE
    if _BUCKET_MODE is None:
        v = _os.environ.get("COCONUT_MSM_WINDOW")
        if v is not None:
            w = int(v)
            if w == 0:
                _BUCKET_MODE = "off"
            elif not 2 <= w <= 8:
                raise ValueError(
                    "COCONUT_MSM_WINDOW=%d unsupported: bucketed windows "
                    "span 2..8 (uint8 digit magnitudes; 0 disables)" % w
                )
            else:
                _BUCKET_MODE = w
        else:
            _BUCKET_MODE = "auto" if on_tpu() else "off"
    if _BUCKET_MODE == "off" or k <= 0:
        return None
    if _BUCKET_MODE != "auto":
        return _BUCKET_MODE
    best = min(range(2, 9), key=lambda w: _bucket_cost(k, nbits, w))
    if _bucket_cost(k, nbits, best) < _horner_cost(k, nbits):
        return best
    return None


def _build_tables(spec_ops, bases, entries=16):
    """Host-side: per-base projective multiples 0..entries-1 as spec
    coordinate tuples (identity = (0, 1, 0), the complete-formula encoding).
    Incremental chain adds (row[d] = row[d-1] + b): one spec add per entry
    instead of a double-and-add ladder per entry. A `None` base (the
    sharded pad lanes from encode_verify_batch's pad_bases_to) encodes as
    an all-identity row explicitly — the complete formulas absorb identity
    entries, and the matching scalars are zero."""
    tables = []
    ident = (spec_ops.zero, spec_ops.one, spec_ops.zero)
    for b in bases:
        if b is None:
            tables.append([ident] * entries)
            continue
        row = [None]
        for _ in range(1, entries):
            row.append(spec_ops.add(row[-1], b) if row[-1] else b)
        enc = []
        for p in row:
            enc.append(ident if p is None else (p[0], p[1], spec_ops.one))
        tables.append(enc)
    # encode: [k][entries] of (X, Y, Z) -> pytree with leading [k, entries]
    flat = [e for row in tables for e in row]
    tree = tw.encode_batch(flat)
    k = len(bases)
    return jax.tree_util.tree_map(
        lambda t: t.reshape((k, entries) + t.shape[1:]), tree
    )


@functools.partial(jax.jit, static_argnums=(0,))
def _comb_build_kernel(field_is_fp2, tables_e):
    fl = cv.FP2 if field_is_fp2 else cv.FP
    window, nwin, _ = _comb_schedule()
    return cv.build_comb_tables(fl, tables_e, nwin, window)


# (is_fp2, base points) -> device comb tables. Bases are spec tuples of
# ints (hashable); the dominant user is the per-verkey fused verify, so a
# handful of entries live here per process — worth it: table build (host
# multiples + nwin x window device doublings) amortizes across every batch
# that reuses the verkey. LRU: a many-verkey workload (the realistic
# multi-issuer verifier rotating through its trust set) must evict ad-hoc
# base sets without throwing away the hot verkeys' tables — the previous
# wholesale clear() thrashed exactly the builds the cache exists to
# amortize (VERDICT r4 weak #5).
_COMB_CACHE = {}
_COMB_CACHE_MAX = 64


# An earlier TPU runtime corrupted the comb-build scan's stacked output
# above ~1.5k carry lanes (probed 2026-07-31: [nwin, k*E] scans were
# bit-exact at k*E <= 1028 — w9 k4 / w10 k2 — and corrupt at 1799/2052 —
# w9 k7, w10 k4; same bug family as the int8 einsum and fold-orientation
# workarounds in fp.py / curve.py). Chunk the BASE axis so every scan stays
# at or below the probed-good width; chunks are separate dispatches,
# amortized by the per-verkey cache like the build itself. chip_smoke.py's
# full-lane checks pass with the chunking in place (PR 21); whether the
# current chip still needs it is open until a run without it.
_BUILD_MAX_LANES = 1028


def _comb_tables(spec_ops, is_fp2, bases):
    # the window is part of the key: a schedule change mid-process (tests
    # monkeypatching _C_SCHED) must never serve tables built for another
    # window
    key = (_comb_schedule()[0], is_fp2, tuple(bases))
    wt = _COMB_CACHE.get(key)
    if wt is None:
        entries = _comb_schedule()[2]
        t_e = _build_tables(spec_ops, bases, entries=entries)
        kmax = max(1, _BUILD_MAX_LANES // entries)
        if len(bases) <= kmax:
            wt = _comb_build_kernel(is_fp2, t_e)
        else:
            chunks = [
                _comb_build_kernel(
                    is_fp2,
                    jax.tree_util.tree_map(
                        lambda t: t[off : off + kmax], t_e
                    ),
                )
                for off in range(0, len(bases), kmax)
            ]
            wt = jax.tree_util.tree_map(
                lambda *ts: jnp.concatenate(ts, axis=0), *chunks
            )
        while len(_COMB_CACHE) >= _COMB_CACHE_MAX:
            _COMB_CACHE.pop(next(iter(_COMB_CACHE)))  # dict = insertion order
        _COMB_CACHE[key] = wt
    else:
        # refresh recency: python dicts iterate in insertion order, so
        # move-to-end makes the eviction above least-recently-USED
        _COMB_CACHE.pop(key)
        _COMB_CACHE[key] = wt
    return wt


# Static-operand cache: the per-(verkey, params) invariant half of a batch
# encode — comb tables over [X_tilde] + Y_tilde, the grouped other-group
# point uploads, the g_tilde pairing constant. encode_verify_batch used to
# rebuild these every call even though they never change across a stream;
# with the cache the steady-state host encode reduces to signature points
# and scalar digits. Keyed by a verkey/params fingerprint (reusing the
# stream layer's run_fingerprint) + the comb window (tests monkeypatch the
# schedule mid-process) + a per-path tag, LRU'd with move-to-end recency
# exactly like _COMB_CACHE. Hit/miss counters: metrics
# encode_cache_hits / encode_cache_misses.
_STATIC_CACHE = {}
_STATIC_CACHE_MAX = 32


def _static_fingerprint(vk, params):
    """Digest identifying a (verkey, params) pair: the stream-layer run
    fingerprint (canonical verkey bytes under the params ctx) extended
    with the params generators — two params contexts sharing a verkey
    must never share cached operands (g_tilde differs)."""
    import hashlib

    from ..stream import run_fingerprint

    h = hashlib.sha256()
    h.update(run_fingerprint("encode", vk, params).encode())
    h.update(repr((params.ctx.name, params.g, params.g_tilde)).encode())
    return h.hexdigest()[:16]


def _static_operands(kind, vk, params, extra, build):
    from .. import metrics

    key = (kind, _static_fingerprint(vk, params), _comb_schedule()[0], extra)
    val = _STATIC_CACHE.get(key)
    if val is not None:
        _STATIC_CACHE.pop(key)
        _STATIC_CACHE[key] = val  # move-to-end: evictions stay LRU
        metrics.count("encode_cache_hits")
        return val
    metrics.count("encode_cache_misses")
    val = build()
    while len(_STATIC_CACHE) >= _STATIC_CACHE_MAX:
        _STATIC_CACHE.pop(next(iter(_STATIC_CACHE)))
    _STATIC_CACHE[key] = val
    return val


def _signed_digits(scalars_batch, nwin=_SIGNED_NWIN, window=5):
    """[B][k] ints -> (mag, sgn bool) [B, k, nwin] signed window digits
    (msb first). mag is uint8 for window <= 8, int16 for window >= 9
    (see limbs.fr_digits_signed_np). Default 5-bit/52 is the distinct-MSM
    Horner schedule; the comb paths pass _comb_schedule()'s window."""
    from .limbs import fr_digits_signed_np

    B = len(scalars_batch)
    k = len(scalars_batch[0]) if B else 0
    flat = [s for row in scalars_batch for s in row]
    mag, sgn = fr_digits_signed_np(flat, nwin=nwin, window=window)
    return (
        jnp.asarray(mag.reshape(B, k, nwin)),
        jnp.asarray(sgn.reshape(B, k, nwin)),
    )


def _comb_digits(scalars_batch):
    window, nwin, _ = _comb_schedule()
    return _signed_digits(scalars_batch, nwin=nwin, window=window)


def _pack_pt(x, y):
    """Compress the device->host result bytes 4.3x: affine outputs are
    LAZY combinations of normalized limbs — G1 coordinates come straight
    out of fp.mul (|v| <= 132, |value| < 0.66p), G2 coordinates are
    fp2_mul outputs, i.e. 2- and 3-term sums of normalized values
    (c1 = t2 - t0 - t1), so the bounds are |v| <= 396, |value| < 1.98p —
    inside fp.pack_canon48's contract, which carry-propagates on device
    to 48 exact base-256 digits of a canonical-width representative
    (48 B/Fp vs 208 B of f32 limbs; the r4 int16 packing was 104 B), so
    fewer result bytes cross the device-to-host link per point-returning
    program. fp_decode_batch inverts on dtype. COCONUT_DEBUG_PACK=1 checks
    the limb bound: the on-device callback only RECORDS a violation (an
    exception raised inside jax.debug.callback may be swallowed or
    deferred under jit) and limbs.fp_decode_batch asserts host-side at
    the decode boundary of the same readback."""
    if _os.environ.get("COCONUT_DEBUG_PACK") == "1":
        from .limbs import pack_debug_record

        for t in jax.tree_util.tree_leaves((x, y)):
            jax.debug.callback(pack_debug_record, jnp.max(jnp.abs(t)))
    from . import fp as _fp_mod

    f = _fp_mod.pack_canon48
    return jax.tree_util.tree_map(f, x), jax.tree_util.tree_map(f, y)


def _unpack_pt(x, y):
    """Inverse of _pack_pt for device-to-device consumers (the offset
    path): uint8 canonical digits back to f32 limb vectors (digits
    0..255 are valid LAZY limbs; the +2p offset is absorbed mod p by the
    downstream Montgomery arithmetic; limbs 48..51 restore as zeros)."""
    from .limbs import NLIMBS as _NL

    def f(t):
        ft = t.astype(jnp.float32)
        pad = jnp.zeros(ft.shape[:-1] + (_NL - ft.shape[-1],), jnp.float32)
        return jnp.concatenate([ft, pad], axis=-1)

    return jax.tree_util.tree_map(f, x), jax.tree_util.tree_map(f, y)


@functools.partial(jax.jit, static_argnums=(0,))
def _msm_affine_kernel(field_is_fp2, wtables, mag, sgn):
    fl = cv.FP2 if field_is_fp2 else cv.FP
    acc = cv.msm_shared_comb(fl, wtables, mag, sgn)
    x, y, inf = cv.to_affine(fl, acc)
    return (*_pack_pt(x, y), inf)


@jax.jit
def _pairing_kernel(px, py, qx, qy, valid):
    px, py, qx, qy = _pts_f32((px, py, qx, qy))
    return pr.pairing_product_is_one(px, py, qx, qy, valid)


@functools.partial(jax.jit, static_argnums=(0,))
def _msm_distinct_affine_kernel(field_is_fp2, x, y, inf, mag, sgn):
    fl = cv.FP2 if field_is_fp2 else cv.FP
    x, y = _pts_f32((x, y))
    acc = cv.msm_distinct_signed(fl, x, y, inf, mag, sgn)
    ax, ay, ainf = cv.to_affine(fl, acc)
    return (*_pack_pt(ax, ay), ainf)


@functools.partial(jax.jit, static_argnums=(0,))
def _msm_distinct_plus_offset_kernel(
    field_is_fp2, x, y, inf, mag, sgn, ox, oy, oinf
):
    """Distinct-base MSM with a per-lane affine offset added before the
    affine conversion: affine(offset_i + sum_j s_ij * P_ij). The offset
    is another device program's (int16-packed) affine output triple,
    consumed device-to-device — the prepare phase's c2 = pk^k + h^m
    assembly rides here instead of decoding pk^k and adding ~2B points
    on the host."""
    fl = cv.FP2 if field_is_fp2 else cv.FP
    x, y = _pts_f32((x, y))
    acc = cv.msm_distinct_signed(fl, x, y, inf, mag, sgn)
    ox, oy = _unpack_pt(ox, oy)
    off = cv.affine_to_jacobian(fl, ox, oy, oinf)
    ax, ay, ainf = cv.to_affine(fl, cv.jadd(fl, acc, off))
    return (*_pack_pt(ax, ay), ainf)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _msm_distinct_bucketed_kernel(field_is_fp2, window, x, y, inf, mag, sgn):
    """Bucketed-schedule twin of _msm_distinct_affine_kernel. `window`
    is a STATIC jit key (like field_is_fp2): the digit shapes [B, k,
    nwin] differ per window, and the schedule is chosen deterministically
    per (k, group) by _bucket_window, so each workload still compiles
    exactly one program — the engine's <ns>_jit_shapes counters stay
    flat after warmup."""
    fl = cv.FP2 if field_is_fp2 else cv.FP
    x, y = _pts_f32((x, y))
    acc = cv.msm_distinct_bucketed(fl, x, y, inf, mag, sgn, window)
    ax, ay, ainf = cv.to_affine(fl, acc)
    return (*_pack_pt(ax, ay), ainf)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _msm_distinct_bucketed_plus_offset_kernel(
    field_is_fp2, window, x, y, inf, mag, sgn, ox, oy, oinf
):
    """Bucketed-schedule twin of _msm_distinct_plus_offset_kernel, so
    the PR 3 prefetch/offset seams compose with the new schedule."""
    fl = cv.FP2 if field_is_fp2 else cv.FP
    x, y = _pts_f32((x, y))
    acc = cv.msm_distinct_bucketed(fl, x, y, inf, mag, sgn, window)
    ox, oy = _unpack_pt(ox, oy)
    off = cv.affine_to_jacobian(fl, ox, oy, oinf)
    ax, ay, ainf = cv.to_affine(fl, cv.jadd(fl, acc, off))
    return (*_pack_pt(ax, ay), ainf)


@jax.jit
def _hash_to_g1_kernel(u_digits, u_par):
    """Device half of CTH-v2 hash_to_g1 (PR 18): u_digits uint8
    [B, 2, 48] raw canonical digits of the two reduced field candidates
    per message (expand_message_xmd stays on host — cheap SHA-256),
    u_par bool [B, 2] the host-side sgn0(u) bits. One jitted program:
    Montgomery domain entry, the SvdW straight-line map on both
    candidates (stacked), the complete add, the static cofactor ladder,
    affine + packed readback. Bit-identical to ops.hashing.hash_to_g1
    (tests/test_hashmsm.py parity sweep, with the PR 3 native
    cc_hash_to_g1_batch as a second oracle)."""
    from . import fp as _fp_mod
    from ..ops.curve import G1_COFACTOR

    u = _fp_mod.to_mont(u_digits)  # [B, 2, L]
    x, y = cv.svdw_map_fp(u, u_par)
    pts = (x, y, cv.FP.ones(u_par.shape))
    p0 = jax.tree_util.tree_map(lambda t: t[:, 0], pts)
    p1 = jax.tree_util.tree_map(lambda t: t[:, 1], pts)
    q = cv.jadd(cv.FP, p0, p1)
    h = cv.scalar_mul_static(cv.FP, q, G1_COFACTOR)
    ax, ay, ainf = cv.to_affine(cv.FP, h)
    return (*_pack_pt(ax, ay), ainf)


@functools.partial(jax.jit, static_argnums=(0,))
def _msm_shared_many_kernel(field_is_fp2, jobs):
    """Several independent shared-base comb MSMs in ONE XLA program: one
    dispatch + one readback for a whole protocol phase (the issuance
    prepare step runs its commitment + two ElGamal MSMs here — the
    round-3 path paid per-MSM dispatch, VERDICT r3 item 4)."""
    fl = cv.FP2 if field_is_fp2 else cv.FP
    outs = []
    for wt, mag, sgn in jobs:
        x, y, inf = cv.to_affine(fl, cv.msm_shared_comb(fl, wt, mag, sgn))
        outs.append((*_pack_pt(x, y), inf))
    return tuple(outs)


def _pts_f32(tree):
    """Uploaded point operands enter the field arithmetic here, dispatched
    on dtype per leaf:

      - uint8 [..., 48]: RAW canonical base-256 digits from the raw wire
        (tw.encode_raw_batch — 48 B/Fp, no host Montgomery bigints).
        fp.to_mont pads to 52 limbs and multiplies by R^2 through the
        existing exact Montgomery kernel, entering the domain on device
        with bit-identical downstream results (raw digits are valid LAZY
        mul inputs: |v| <= 255, value < p, limbs 48..51 zero).
      - int16 [..., 52]: balanced Montgomery limbs (the legacy halved
        wire; exact integers |v| <= 132) — cast to f32, where XLA fuses
        the cast into the first consumer.
      - f32: device-resident operands and the CPU test path, unchanged.

    NOTE the uint8 MONTGOMERY canon48 digits of the device-to-device
    offset path never come through here — they go through _unpack_pt
    (no domain conversion), see _msm_distinct_plus_offset_kernel."""
    from . import fp as _fp_mod

    def conv(t):
        if t.dtype == jnp.uint8:
            return _fp_mod.to_mont(t)
        return t.astype(jnp.float32) if t.dtype != jnp.float32 else t

    return jax.tree_util.tree_map(conv, tree)


def verify_tail(sig_is_g1, acc, s1, s2n, gtx, gty, inf1, inf2):
    """Post-MSM half of the fused verify: normalize the accumulator and run
    the 2-pair pairing product. Split out so the sharded path (shard.py) can
    combine cross-device MSM partials before entering it.

    G1 assignment uses the specialized two-pair loop with pair 2's shared
    g_tilde ladder and a merged [B] accumulator (pr.miller_two_pairs_
    shared_q2); the G2 assignment keeps the generic pair-set loop (there
    the shared element g_tilde sits on the evaluation side already)."""
    s1, s2n, gtx, gty = _pts_f32((s1, s2n, gtx, gty))
    acc_fl = cv.FP2 if sig_is_g1 else cv.FP
    with jax.named_scope("affine_norm"):
        ax, ay, ainf = cv.to_affine(acc_fl, acc)

    if sig_is_g1:
        with jax.named_scope("miller_two_pairs"):
            f = pr.miller_two_pairs_shared_q2(
                s1[0],
                s1[1],
                ax,
                ay,
                ~inf1 & ~ainf,
                s2n[0],
                s2n[1],
                gtx,
                gty,
                ~inf2,
            )
        with jax.named_scope("final_exp"):
            fe = pr.final_exp(f)
        one = tw.fp12_is_one(fe)
        return one & ~inf1

    def stack2(a, b):
        return jax.tree_util.tree_map(
            lambda x, y: jnp.stack(
                jnp.broadcast_arrays(x, y), axis=max(x.ndim, y.ndim) - 1
            ),
            a,
            b,
        )

    px = stack2(ax, gtx)
    py = stack2(ay, gty)
    qx = stack2(s1[0], s2n[0])
    qy = stack2(s1[1], s2n[1])
    qinf = jnp.stack([inf1, inf2], axis=-1)
    pinf = jnp.stack([ainf, jnp.zeros_like(ainf)], axis=-1)
    valid = ~(pinf | qinf)
    one = pr.pairing_product_is_one(px, py, qx, qy, valid)
    return one & ~inf1


def fused_verify(sig_is_g1, wtables, mag, sgn, s1, s2n, gtx, gty, inf1, inf2):
    """Fused batch verify: comb MSM accumulator + 2-pair pairing product.

    sig_is_g1: signatures live in G1 (ctx "G1") — accumulator is in G2;
    otherwise roles flip. wtables: per-verkey comb window tables
    (cv.build_comb_tables); mag/sgn: signed 6-bit digits [B, k, 43];
    s1/s2n: sigma_1 and -sigma_2 coordinate pytrees [B]; gtx/gty: g_tilde
    affine coordinates pre-encoded as limb pytrees; inf1/inf2: identity
    masks for sigma_1 / sigma_2."""
    acc_fl = cv.FP2 if sig_is_g1 else cv.FP
    with jax.named_scope("comb_msm"):
        acc = cv.msm_shared_comb(acc_fl, wtables, mag, sgn)
    return verify_tail(sig_is_g1, acc, s1, s2n, gtx, gty, inf1, inf2)


_fused_verify_kernel = functools.partial(jax.jit, static_argnums=(0,))(
    fused_verify
)


def _tree_fold_fp12(f, n):
    """Product of a [n]-leading Fp12 pytree (n pow2) by pairwise halving —
    same rationale as cv.fold_points (~n-1 lane-muls instead of the
    fixed-width butterfly's n*log2(n)). Returns a [1]-leading pytree."""
    assert n & (n - 1) == 0
    while n > 1:
        half = n // 2
        lo = jax.tree_util.tree_map(lambda t: t[:half], f)
        hi = jax.tree_util.tree_map(lambda t: t[half:n], f)
        f = tw.fp12_mul(lo, hi)
        n = half
    return f


def fused_verify_combined(
    sig_is_g1, wtables, mag, sgn, s1, s2n, rmag, rsgn, gtx, gty, inf1, inf2
):
    """Probabilistic combined batch verify — ONE boolean for the whole batch.

    Standard small-exponents batch verification: with random 128-bit r_i,

      prod_i [ e(sigma_1_i, acc_i) * e(-sigma_2_i, g_tilde) ]^{r_i} == 1
      ==  prod_i e(r_i sigma_1_i, acc_i)  *  e(sum_i r_i (-sigma_2_i), g_tilde)

    so the batch costs B+1 Miller pairs and ONE shared final exponentiation
    instead of 2B pairs + B final exps (the per-credential kernel
    `fused_verify`). A forged credential escapes detection with probability
    2^-128. Identity masks must be rejected host-side (the kernel treats
    masked lanes as factor 1).

    B must be a power of two (host pads with valid=False lanes)."""
    s1, s2n, gtx, gty = _pts_f32((s1, s2n, gtx, gty))
    acc_fl = cv.FP2 if sig_is_g1 else cv.FP
    sig_fl = cv.FP if sig_is_g1 else cv.FP2
    B = inf1.shape[0]

    acc = cv.msm_shared_comb(acc_fl, wtables, mag, sgn)
    ax, ay, ainf = cv.to_affine(acc_fl, acc)

    def add_k1(pt):
        return jax.tree_util.tree_map(lambda t: t[:, None], pt)

    # r_i * sigma_1_i and r_i * (-sigma_2_i): k=1 signed distinct MSMs over
    # the short 27-window (128-bit r_i) schedule
    s1r = cv.msm_distinct_signed(
        sig_fl, add_k1(s1[0]), add_k1(s1[1]), inf1[:, None], rmag, rsgn
    )
    s2rn = cv.msm_distinct_signed(
        sig_fl, add_k1(s2n[0]), add_k1(s2n[1]), inf2[:, None], rmag, rsgn
    )
    # mask invalid lanes to the identity so they drop out of the sum
    dead = inf1 | inf2 | ainf
    s2rn = tuple(
        sig_fl.select(dead, i_, c)
        for i_, c in zip(cv.jinfinity(sig_fl, (B,)), s2rn)
    )
    s2sum = cv.fold_points(sig_fl, s2rn, B)
    sx, sy, sinf = cv.to_affine(sig_fl, s1r)
    zx, zy, zinf = cv.to_affine(sig_fl, s2sum)

    # B+1 miller pairs: (r_i sigma_1_i, acc_i) for each i, then
    # (sum_i r_i (-sigma_2_i), g_tilde) appended as one extra lane
    def cat(a, b):
        return jax.tree_util.tree_map(
            lambda x, y: jnp.concatenate([x, y[None]], axis=0), a, b
        )

    if sig_is_g1:
        px, py = cat(sx, zx), cat(sy, zy)
        qx, qy = cat(ax, gtx), cat(ay, gty)
    else:
        px, py = cat(ax, gtx), cat(ay, gty)
        qx, qy = cat(sx, zx), cat(sy, zy)
    valid = jnp.concatenate([~dead & ~sinf, ~zinf[None]], axis=0)
    # miller over a [B+1, 1] pair-set shape (npairs = 1: nothing to fold)
    f = pr.multi_miller_loop(
        jax.tree_util.tree_map(lambda t: t[:, None], px),
        jax.tree_util.tree_map(lambda t: t[:, None], py),
        jax.tree_util.tree_map(lambda t: t[:, None], qx),
        jax.tree_util.tree_map(lambda t: t[:, None], qy),
        valid[:, None],
    )  # -> [B+1] fp12
    head = jax.tree_util.tree_map(lambda t: t[:B], f)
    tail = jax.tree_util.tree_map(lambda t: t[B:], f)
    prod = tw.fp12_mul(_tree_fold_fp12(head, B), tail)
    ok = tw.fp12_is_one(pr.final_exp(prod))[0]
    # any dead lane (identity sigma or accumulator) fails the whole batch
    return ok & ~jnp.any(inf1 | inf2 | ainf)


_fused_verify_combined_kernel = functools.partial(
    jax.jit, static_argnums=(0,)
)(fused_verify_combined)


def _grouped_msms(fl, x, y, inf, mag, sgn):
    """M MSMs over the SAME [B] points: signed 6-bit window digits
    mag/sgn [M, B, nwin] (msb first, digit = (-1)^sgn * mag, mag <= 32)
    -> projective accumulators [M].

    Structure (this is the whole per-credential cost of the grouped verify
    — no OtherGroup arithmetic, no per-credential pairing):
      1. one on-device 33-entry table build (32 batched adds over [B]);
      2. ONE gather of all (msm, window, point) table entries [M, nwin, B]
         — the window axis rides in the lane dimension, so the fold runs
         at full width instead of once per window — with the sign applied
         as a Y-flip (free elementwise negate + lane select);
      3. fold over the B axis: ~B-1 lane-adds per (m, w) via fold_points;
      4. a Horner scan over the nwin window sums: 6 doublings + 1 add on
         [M] lanes per window."""
    with jax.named_scope("grouped_tables"):
        tables = cv.build_tables_device(
            fl, x, y, inf, entries=(1 << (_G_WINDOW - 1)) + 1
        )
    M, B, nwin = mag.shape
    dw = jnp.moveaxis(mag, 1, 2)  # [M, nwin, B]
    sw = jnp.moveaxis(sgn, 1, 2)

    def leaf(t):  # t: [B, 33, L...] -> [M, nwin, B, L...]
        tb = jnp.broadcast_to(t[None, None], (M, nwin) + t.shape)
        ix = dw[..., None].reshape(dw.shape + (1,) * (t.ndim - 1))
        return jnp.take_along_axis(tb, ix, axis=3)[:, :, :, 0]

    with jax.named_scope("grouped_gather_fold"):
        X, Y, Z = jax.tree_util.tree_map(leaf, tables)  # [M, nwin, B]
        Y = fl.select(sw, fl.neg(Y), Y)  # signed digit -> negated point
        S = cv.fold_points(fl, (X, Y, Z), B, axis_offset=2)  # [M, nwin]
    Sw = jax.tree_util.tree_map(lambda t: jnp.moveaxis(t, 1, 0), S)

    def body(acc, s):
        acc = jax.lax.fori_loop(
            0, _G_WINDOW, lambda _, a: cv.jdouble(fl, a), acc
        )
        return cv.jadd(fl, acc, s), None

    with jax.named_scope("grouped_horner"):
        acc, _ = jax.lax.scan(body, cv.jinfinity(fl, (M,)), Sw)
    return acc


def grouped_accumulators(sig_fl, s1, s2n, inf1, inf2, cmag, csgn, rmag, rsgn):
    """The per-credential half of the grouped verify: q+2 shared-point MSMs
    over the (local) credential batch -> projective accumulators [q+2].
    Split out so the dp-sharded path (shard.py) can combine cross-device
    partials (point sums commute) before the pairing tail."""
    s1, s2n = _pts_f32((s1, s2n))
    acc1 = _grouped_msms(sig_fl, s1[0], s1[1], inf1, cmag, csgn)  # [q+1]
    acc2 = _grouped_msms(sig_fl, s2n[0], s2n[1], inf2, rmag, rsgn)  # [1]
    return jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b], axis=0), acc1, acc2
    )


def grouped_tail(sig_is_g1, allacc, ox, oy, gtx, gty, any_dead):
    """Post-MSM half of the grouped verify: q+2 Miller pairs against the
    fixed other-group points, one shared final exponentiation, one bool."""
    ox, oy, gtx, gty = _pts_f32((ox, oy, gtx, gty))
    sig_fl = cv.FP if sig_is_g1 else cv.FP2
    px, py, pinf = cv.to_affine(sig_fl, allacc)  # [q+2] sig-group points

    qx = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b[None]], axis=0), ox, gtx
    )
    qy = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b[None]], axis=0), oy, gty
    )
    valid = ~pinf  # a zero accumulator contributes the factor 1
    npair = valid.shape[0]
    with jax.named_scope("grouped_miller"):
        f = _grouped_tail_miller(sig_is_g1, px, py, qx, qy, valid)
    # fold the q+2 miller values (pad to a power of two with ones)
    pow2 = 1 << (npair - 1).bit_length()
    if pow2 != npair:
        pad = tw.fp12_ones((pow2 - npair,))
        f = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0), f, pad
        )
    prod = _tree_fold_fp12(f, pow2)
    with jax.named_scope("final_exp"):
        fe = pr.final_exp(prod)
    ok = tw.fp12_is_one(fe)[0]
    return ok & ~any_dead


def _grouped_tail_miller(sig_is_g1, px, py, qx, qy, valid):
    if sig_is_g1:
        f = pr.multi_miller_loop(
            jax.tree_util.tree_map(lambda t: t[:, None], px),
            jax.tree_util.tree_map(lambda t: t[:, None], py),
            jax.tree_util.tree_map(lambda t: t[:, None], qx),
            jax.tree_util.tree_map(lambda t: t[:, None], qy),
            valid[:, None],
        )
    else:
        f = pr.multi_miller_loop(
            jax.tree_util.tree_map(lambda t: t[:, None], qx),
            jax.tree_util.tree_map(lambda t: t[:, None], qy),
            jax.tree_util.tree_map(lambda t: t[:, None], px),
            jax.tree_util.tree_map(lambda t: t[:, None], py),
            valid[:, None],
        )
    return f


def fused_verify_grouped(
    sig_is_g1, s1, s2n, inf1, inf2, cmag, csgn, rmag, rsgn, ox, oy, gtx, gty
):
    """Attribute-grouped combined batch verify — ONE boolean, q+2 pairs
    TOTAL regardless of batch size.

    The small-exponents combination regrouped by verkey component: with
    random 128-bit r_i and messages m_ij,

      prod_i [e(s1_i, X * prod_j Y_j^{m_ij}) * e(-s2_i, g)]^{r_i}
      = e(sum_i r_i s1_i, X)
        * prod_j e(sum_i (r_i m_ij) s1_i, Y_j)
        * e(sum_i r_i (-s2_i), g)

    so ALL G2/OtherGroup arithmetic disappears (X, Y_j, g are fixed affine
    inputs) and the per-credential work is q+2 shared-point G1 MSMs over the
    batch (_grouped_msms). Soundness 2^-128 per forged credential, as in
    fused_verify_combined.

    Shapes: s1/s2n coordinate pytrees [B]; cmag/csgn [q+1, B, 43] signed
    6-bit window digits (scalars r_i then r_i*m_ij mod r); rmag/rsgn
    [1, B, 23] (r_i for the -s2 sum — r_i are 128-bit so only the low 23
    msb-first windows can be nonzero); ox/oy [q+1] other-group affine (X
    then Y_j); gtx/gty other-group affine g. B power of two."""
    sig_fl = cv.FP if sig_is_g1 else cv.FP2
    # dead lanes: zero digits (host guarantees) -> identity contributions
    allacc = grouped_accumulators(
        sig_fl, s1, s2n, inf1, inf2, cmag, csgn, rmag, rsgn
    )
    return grouped_tail(
        sig_is_g1, allacc, ox, oy, gtx, gty, jnp.any(inf1 | inf2)
    )


_fused_verify_grouped_kernel = functools.partial(
    jax.jit, static_argnums=(0,)
)(fused_verify_grouped)


def fused_show_verify(
    sig_is_g1,
    vc_wtables,
    resp_mag,
    resp_sgn,
    jpt,
    jinf,
    cmag_j,
    csgn_j,
    commx,
    commy,
    comminf,
    acc_wtables,
    acc_mag,
    acc_sgn,
    s1,
    s2n,
    gtx,
    gty,
    inf1,
    inf2,
):
    """Batched PoKOfSignatureProof.verify (the Show/ShowVerify hot path,
    BASELINE config 3; reference surface pok_sig.rs:103-105).

    Two checks per proof, both on-device (cf. ps.PoKOfSignatureProof.verify
    and pok_vc.Proof.verify):

      1. Schnorr randomized-commitment equation over the OtherGroup:
           prod_k bases_k^{resp_ik} * J_i^{c_i} == t_i
         (bases = [g_tilde, hidden Y_tilde] shared across the batch ->
         shared-table MSM; the J_i^{c_i} term is a k=1 distinct MSM;
         t_i is the proof's commitment point, passed affine as commx/y).
      2. Pairing check with the re-randomized signature:
           e(sigma'_1i, J_i * X_tilde * prod_rev Y_tilde^m) * e(-sigma'_2i,
           g_tilde) == 1
         (shared-base MSM over [X_tilde, revealed Y_tilde] with scalars
         [1, m_rev..]; J_i joins by one Jacobian add).

    All proofs must share the same revealed-index set (the bench shape;
    ps.batch_show_verify falls back per-proof otherwise)."""
    jpt, commx, commy = _pts_f32((jpt, commx, commy))
    oth_fl = cv.FP2 if sig_is_g1 else cv.FP

    # -- Schnorr check ------------------------------------------------------
    vc = cv.msm_shared_comb(oth_fl, vc_wtables, resp_mag, resp_sgn)
    jterm = cv.msm_distinct_signed(
        oth_fl,
        jax.tree_util.tree_map(lambda t: t[:, None], jpt[0]),
        jax.tree_util.tree_map(lambda t: t[:, None], jpt[1]),
        jinf[:, None],
        cmag_j,
        csgn_j,
    )
    lhs = cv.jadd(oth_fl, vc, jterm)
    lx, ly, linf = cv.to_affine(oth_fl, lhs)
    schnorr_ok = (
        oth_fl.eq(lx, commx) & oth_fl.eq(ly, commy) & ~linf & ~comminf
    ) | (linf & comminf)

    # -- pairing check ------------------------------------------------------
    acc = cv.msm_shared_comb(oth_fl, acc_wtables, acc_mag, acc_sgn)
    jjac = cv.affine_to_jacobian(oth_fl, jpt[0], jpt[1], jinf)
    acc = cv.jadd(oth_fl, acc, jjac)
    pair_ok = verify_tail(sig_is_g1, acc, s1, s2n, gtx, gty, inf1, inf2)
    return schnorr_ok & pair_ok


_fused_show_verify_kernel = functools.partial(jax.jit, static_argnums=(0,))(
    fused_show_verify
)


def fused_show_verify_combined(
    sig_is_g1,
    vc_wtables,
    resp_mag,
    resp_sgn,
    jpt,
    jinf,
    cmag_j,
    csgn_j,
    commx,
    commy,
    comminf,
    acc_wtables,
    acc_mag,
    acc_sgn,
    s1,
    s2n,
    rmag,
    rsgn,
    gtx,
    gty,
    inf1,
    inf2,
):
    """RLC-combined batched show verify: per-lane Schnorr bits plus ONE
    pairing boolean for the whole batch.

    The Schnorr half is `fused_show_verify`'s verbatim (it is MSM-only —
    no pairing, nothing to combine); the pairing half folds the B
    per-lane checks e(sigma'_1i, acc_i) * e(-sigma'_2i, g_tilde) under
    the combiner exponents r_i exactly as `fused_verify_combined`:
    B+1 Miller pairs, ONE shared final exponentiation.

    Dead lanes (identity sigma' or accumulator) are masked OUT of the
    fold — they fail their own verdict (schnorr_ok & ~dead) without
    poisoning the batch pairing bool, matching the exact path where an
    identity sigma' fails only its lane. Returns
    (per-lane schnorr-and-liveness bits [B], batch pairing bool); the
    caller's lane verdict is bits_i & pair_ok, with ps-layer bisection
    re-deriving exponents per sub-batch to attribute pairing failures."""
    jpt, commx, commy = _pts_f32((jpt, commx, commy))
    s1, s2n, gtx, gty = _pts_f32((s1, s2n, gtx, gty))
    oth_fl = cv.FP2 if sig_is_g1 else cv.FP
    sig_fl = cv.FP if sig_is_g1 else cv.FP2
    B = inf1.shape[0]

    # -- Schnorr check (per lane, identical to fused_show_verify) -----------
    vc = cv.msm_shared_comb(oth_fl, vc_wtables, resp_mag, resp_sgn)
    jterm = cv.msm_distinct_signed(
        oth_fl,
        jax.tree_util.tree_map(lambda t: t[:, None], jpt[0]),
        jax.tree_util.tree_map(lambda t: t[:, None], jpt[1]),
        jinf[:, None],
        cmag_j,
        csgn_j,
    )
    lhs = cv.jadd(oth_fl, vc, jterm)
    lx, ly, linf = cv.to_affine(oth_fl, lhs)
    schnorr_ok = (
        oth_fl.eq(lx, commx) & oth_fl.eq(ly, commy) & ~linf & ~comminf
    ) | (linf & comminf)

    # -- combined pairing check (RLC fold, cf. fused_verify_combined) -------
    acc = cv.msm_shared_comb(oth_fl, acc_wtables, acc_mag, acc_sgn)
    jjac = cv.affine_to_jacobian(oth_fl, jpt[0], jpt[1], jinf)
    acc = cv.jadd(oth_fl, acc, jjac)
    ax, ay, ainf = cv.to_affine(oth_fl, acc)

    def add_k1(pt):
        return jax.tree_util.tree_map(lambda t: t[:, None], pt)

    s1r = cv.msm_distinct_signed(
        sig_fl, add_k1(s1[0]), add_k1(s1[1]), inf1[:, None], rmag, rsgn
    )
    s2rn = cv.msm_distinct_signed(
        sig_fl, add_k1(s2n[0]), add_k1(s2n[1]), inf2[:, None], rmag, rsgn
    )
    dead = inf1 | inf2 | ainf
    s2rn = tuple(
        sig_fl.select(dead, i_, c)
        for i_, c in zip(cv.jinfinity(sig_fl, (B,)), s2rn)
    )
    s2sum = cv.fold_points(sig_fl, s2rn, B)
    sx, sy, sinf = cv.to_affine(sig_fl, s1r)
    zx, zy, zinf = cv.to_affine(sig_fl, s2sum)

    def cat(a, b):
        return jax.tree_util.tree_map(
            lambda x, y: jnp.concatenate([x, y[None]], axis=0), a, b
        )

    if sig_is_g1:
        px, py = cat(sx, zx), cat(sy, zy)
        qx, qy = cat(ax, gtx), cat(ay, gty)
    else:
        px, py = cat(ax, gtx), cat(ay, gty)
        qx, qy = cat(sx, zx), cat(sy, zy)
    valid = jnp.concatenate([~dead & ~sinf, ~zinf[None]], axis=0)
    f = pr.multi_miller_loop(
        jax.tree_util.tree_map(lambda t: t[:, None], px),
        jax.tree_util.tree_map(lambda t: t[:, None], py),
        jax.tree_util.tree_map(lambda t: t[:, None], qx),
        jax.tree_util.tree_map(lambda t: t[:, None], qy),
        valid[:, None],
    )  # -> [B+1] fp12
    head = jax.tree_util.tree_map(lambda t: t[:B], f)
    tail = jax.tree_util.tree_map(lambda t: t[B:], f)
    prod = tw.fp12_mul(_tree_fold_fp12(head, B), tail)
    pair_ok = tw.fp12_is_one(pr.final_exp(prod))[0]
    return schnorr_ok & ~dead, pair_ok


_fused_show_verify_combined_kernel = functools.partial(
    jax.jit, static_argnums=(0,)
)(fused_show_verify_combined)


def _combiner_digits(rs):
    """Combiner exponents -> the short signed-5-bit digit schedule the
    combined kernels' k=1 distinct MSMs run ([B, 1, _R_NWIN]). Refuses
    exponents wider than _R_RAND_BITS — the schedule would silently drop
    their top windows."""
    for r in rs:
        if not 0 <= r < (1 << _R_RAND_BITS):
            raise ValueError(
                "combiner exponent exceeds %d bits" % _R_RAND_BITS
            )
    rmag, rsgn = _signed_digits([[r] for r in rs])
    # only the last _R_NWIN msb-first windows can be nonzero
    return (
        rmag[:, :, _SIGNED_NWIN - _R_NWIN :],
        rsgn[:, :, _SIGNED_NWIN - _R_NWIN :],
    )


class JaxBackend(CurveBackend):
    """Batched JAX/TPU backend (SURVEY.md §7 stage 6)."""

    name = "jax"

    # -- encoding helpers ----------------------------------------------------
    #
    # Point batches upload on one of two wires, chosen per platform by
    # _raw_wire_enabled():
    #
    #   raw (TPU default): 48 raw canonical uint8 digits per Fp — no host
    #   bigint Montgomery multiply, no balance-carry loop, and the upload
    #   halves AGAIN vs int16 (48 B vs 104 B). _pts_f32 enters the
    #   Montgomery domain at kernel entry via fp.to_mont.
    #
    #   int16 (CPU default): balanced Montgomery limbs, exact integers
    #   |v| <= 132, cast back to f32 at kernel entry. The cast to int16
    #   happens in NUMPY, before jnp.asarray commits the buffer.

    @staticmethod
    def _encode_g1_points(points):
        xs = [(0 if p is None else p[0]) for p in points]
        ys = [(0 if p is None else p[1]) for p in points]
        inf = jnp.asarray(np.array([p is None for p in points]))
        if _raw_wire_enabled():
            return (tw.encode_raw_batch(xs), tw.encode_raw_batch(ys)), inf
        return (
            tw.encode_batch(xs, dtype=np.int16),
            tw.encode_batch(ys, dtype=np.int16),
        ), inf

    @staticmethod
    def _encode_g2_points(points):
        zero2 = (0, 0)
        xs = [(zero2 if p is None else p[0]) for p in points]
        ys = [(zero2 if p is None else p[1]) for p in points]
        inf = jnp.asarray(np.array([p is None for p in points]))
        if _raw_wire_enabled():
            return (tw.encode_raw_batch(xs), tw.encode_raw_batch(ys)), inf
        return (
            tw.encode_batch(xs, dtype=np.int16),
            tw.encode_batch(ys, dtype=np.int16),
        ), inf

    # -- CurveBackend primitives --------------------------------------------

    def _msm_shared(self, spec_ops, is_fp2, bases, scalars_batch):
        # cached: the hot users (batch_show / batch_prepare_blind_sign /
        # issuance) call with FIXED base sets (verkey components, params
        # generators) — the 64-entry cap in _comb_tables guards ad-hoc sets
        wtables = _comb_tables(spec_ops, is_fp2, bases)
        mag, sgn = _comb_digits(scalars_batch)
        x, y, inf = _msm_affine_kernel(is_fp2, wtables, mag, sgn)
        xs = tw.decode_batch(x)
        ys = tw.decode_batch(y)
        infs = np.asarray(inf)
        return [
            None if i else (xv, yv) for xv, yv, i in zip(xs, ys, infs)
        ]

    def msm_g1_shared(self, bases, scalars_batch):
        return self._msm_shared(_sg1, False, bases, scalars_batch)

    def msm_g2_shared(self, bases, scalars_batch):
        return self._msm_shared(_sg2, True, bases, scalars_batch)

    def _msm_shared_many_dispatch(self, spec_ops, is_fp2, jobs):
        """Encode + launch the fused multi-MSM program; returns the device
        output handle WITHOUT blocking (jax dispatch is async). Pair with
        `msm_shared_many_wait` — protocol drivers overlap host work (e.g.
        the prepare step's hash-to-group loop, signature.rs:194-206 shape)
        with device execution this way."""
        operands = []
        for bases, scalars_batch in jobs:
            wt = _comb_tables(spec_ops, is_fp2, bases)
            mag, sgn = _comb_digits(scalars_batch)
            operands.append((wt, mag, sgn))
        return _msm_shared_many_kernel(is_fp2, tuple(operands))

    @staticmethod
    def msm_shared_many_wait(outs):
        """Block on a `_dispatch` handle and decode to spec points."""
        results = []
        for x, y, inf in outs:
            xs = tw.decode_batch(x)
            ys = tw.decode_batch(y)
            infs = np.asarray(inf)
            results.append(
                [None if i else (xv, yv) for xv, yv, i in zip(xs, ys, infs)]
            )
        return results

    def _msm_shared_many(self, spec_ops, is_fp2, jobs):
        """jobs: [(bases, scalars_batch)] -> list of per-job result lists,
        all jobs fused into one device program (one dispatch/readback)."""
        return self.msm_shared_many_wait(
            self._msm_shared_many_dispatch(spec_ops, is_fp2, jobs)
        )

    def msm_g1_shared_many(self, jobs):
        return self._msm_shared_many(_sg1, False, jobs)

    def msm_g2_shared_many(self, jobs):
        return self._msm_shared_many(_sg2, True, jobs)

    def msm_g1_shared_many_async(self, jobs):
        return self._msm_shared_many_dispatch(_sg1, False, jobs)

    def msm_g2_shared_many_async(self, jobs):
        return self._msm_shared_many_dispatch(_sg2, True, jobs)

    def _encode_distinct(self, is_fp2, points_batch, scalars_batch,
                         window=5):
        """Shared encode for the distinct-MSM kernels: GLV split (G1),
        limb encoding, signed-digit recode -> (x, y, inf, mag, sgn).
        `window` picks the digit width (5 = the Horner schedule's
        default; the bucketed schedule passes _bucket_window's choice);
        nwin follows as ceil(bits/window) + 1 carry window over the
        128-bit GLV halves or the full 255-bit Fr."""
        with otrace.span("encode"):
            B = len(points_batch)
            k = len(points_batch[0])
            if any(len(row) != k for row in points_batch):
                raise ValueError("ragged distinct-MSM batch")
            if not is_fp2 and _GLV_ENABLED:
                # GLV (tpu/glv.py): each 255-bit scalar splits into two
                # nonnegative <= 128-bit halves on (P, phi(P)) — the Horner
                # schedule's doubling chain halves (52 -> 27 windows) for the
                # same add count. G1 only (beta lives in Fp).
                #
                # PRECONDITION: points must lie in the r-order subgroup
                # (phi(P) = lambda*P holds only there; E(Fp) has cofactor
                # ~2^125). Every point that crosses the wire boundary is
                # subgroup-checked at deserialization (ops/serialize.py
                # g1_from_bytes/_from_compressed raise on non-r-torsion
                # input), so all protocol callers satisfy this; callers
                # feeding raw curve points from elsewhere must check
                # g1.in_subgroup first or set COCONUT_GLV=0.
                from . import glv

                points_batch = [
                    [q for p in row for q in (p, glv.phi(p))]
                    for row in points_batch
                ]
                scalars_batch = [
                    [h for s in row for h in glv.decompose(s)]
                    for row in scalars_batch
                ]
                k *= 2
                bits = glv.HALF_BITS
            else:
                bits = 255
            nwin = -(-bits // window) + 1  # 27 / 52 at the 5-bit default
            flat_pts = [p for row in points_batch for p in row]
            if is_fp2:
                (x, y), inf = self._encode_g2_points(flat_pts)
            else:
                (x, y), inf = self._encode_g1_points(flat_pts)
            reshape = lambda t: t.reshape((B, k) + t.shape[1:])
            x, y = jax.tree_util.tree_map(reshape, (x, y))
            inf = inf.reshape(B, k)
            mag, sgn = _signed_digits(scalars_batch, nwin=nwin, window=window)
            return x, y, inf, mag, sgn

    @staticmethod
    def _distinct_window(is_fp2, points_batch):
        """Bucketed-vs-Horner schedule choice for a distinct-MSM batch:
        None = Horner, else the bucketed window (_bucket_window's cost
        model over the post-GLV effective base count and scalar width)."""
        k0 = len(points_batch[0]) if points_batch else 0
        glv_on = not is_fp2 and _GLV_ENABLED
        from . import glv

        return _bucket_window(
            2 * k0 if glv_on else k0, glv.HALF_BITS if glv_on else 255
        )

    def _msm_distinct(self, is_fp2, points_batch, scalars_batch):
        from .. import metrics

        w = self._distinct_window(is_fp2, points_batch)
        if w is None:
            metrics.count("msm_horner_dispatches")
            return _msm_distinct_affine_kernel(
                is_fp2,
                *self._encode_distinct(is_fp2, points_batch, scalars_batch),
            )
        metrics.count("msm_bucketed_dispatches")
        metrics.set_gauge("msm_bucket_window", w)
        return _msm_distinct_bucketed_kernel(
            is_fp2,
            w,
            *self._encode_distinct(
                is_fp2, points_batch, scalars_batch, window=w
            ),
        )

    @staticmethod
    def msm_distinct_wait(handle):
        """Block on a `_distinct` dispatch handle and decode to spec points."""
        ax, ay, ainf = handle
        with otrace.span("decode"):
            xs = tw.decode_batch(ax)
            ys = tw.decode_batch(ay)
            infs = np.asarray(ainf)
            return [
                None if i else (xv, yv) for xv, yv, i in zip(xs, ys, infs)
            ]

    def msm_g1_distinct(self, points_batch, scalars_batch):
        return self.msm_distinct_wait(
            self._msm_distinct(False, points_batch, scalars_batch)
        )

    def msm_g2_distinct(self, points_batch, scalars_batch):
        return self.msm_distinct_wait(
            self._msm_distinct(True, points_batch, scalars_batch)
        )

    def msm_g1_distinct_async(self, points_batch, scalars_batch):
        return self._msm_distinct(False, points_batch, scalars_batch)

    def msm_g2_distinct_async(self, points_batch, scalars_batch):
        return self._msm_distinct(True, points_batch, scalars_batch)

    def _msm_distinct_plus_offset(
        self, is_fp2, points_batch, scalars_batch, offset_handle
    ):
        from .. import metrics

        ox, oy, oinf = offset_handle
        w = self._distinct_window(is_fp2, points_batch)
        if w is None:
            metrics.count("msm_horner_dispatches")
            return _msm_distinct_plus_offset_kernel(
                is_fp2,
                *self._encode_distinct(is_fp2, points_batch, scalars_batch),
                ox,
                oy,
                oinf,
            )
        metrics.count("msm_bucketed_dispatches")
        metrics.set_gauge("msm_bucket_window", w)
        return _msm_distinct_bucketed_plus_offset_kernel(
            is_fp2,
            w,
            *self._encode_distinct(
                is_fp2, points_batch, scalars_batch, window=w
            ),
            ox,
            oy,
            oinf,
        )

    def msm_g1_distinct_plus_offset_async(
        self, points_batch, scalars_batch, offset_handle
    ):
        """affine(offset_i + MSM_i) with `offset_handle` an affine device
        triple (x, y, inf) of shape [B] — e.g. one job's output from a
        `msm_g*_shared_many_async` dispatch, consumed without a host
        round trip. Settle with msm_distinct_wait."""
        return self._msm_distinct_plus_offset(
            False, points_batch, scalars_batch, offset_handle
        )

    def msm_g2_distinct_plus_offset_async(
        self, points_batch, scalars_batch, offset_handle
    ):
        return self._msm_distinct_plus_offset(
            True, points_batch, scalars_batch, offset_handle
        )

    # -- device hash-to-curve (PR 18) ---------------------------------------

    @staticmethod
    def device_hash_enabled():
        """Whether protocol callers should route batched hash-to-G1
        through this backend (the COCONUT_DEVICE_HASH knob; lazy
        per-platform default — see _device_hash_enabled)."""
        return _device_hash_enabled()

    def hash_to_g1_async(self, datas, dst=None):
        """Dispatch device-resident CTH-v2 hash_to_g1 over a batch of
        messages: expand_message_xmd runs on host (cheap SHA-256), the
        two reduced field candidates per message upload once as raw
        digits (48 B each, no host Montgomery bigints), and
        map(u0)+map(u1)+clear_cofactor executes as ONE jitted program.
        Returns a dispatch handle; settle with hash_to_g1_wait.
        Bit-identical to ops.hashing.hash_to_g1 and the native
        cc_hash_to_g1_batch oracle."""
        from .. import metrics
        from ..ops import hashing as _H
        from ..ops.fields import P as _P
        from .limbs import fp_encode_raw_batch

        dst = _H.DST_G1 if dst is None else dst
        us = []
        for m in datas:
            b = _H.expand_message_xmd(m, dst, 128)
            us.append(int.from_bytes(b[:64], "big") % _P)
            us.append(int.from_bytes(b[64:], "big") % _P)
        dig = fp_encode_raw_batch(us).reshape(len(datas), 2, -1)
        par = np.array([u & 1 for u in us], dtype=bool).reshape(
            len(datas), 2
        )
        metrics.count("device_hash_batches")
        metrics.count("device_hash_points", len(datas))
        return _hash_to_g1_kernel(jnp.asarray(dig), jnp.asarray(par))

    @staticmethod
    def hash_to_g1_wait(handle):
        """Block on a hash_to_g1_async handle and decode to spec affine
        points. Raises like the spec on the (~2^-255) identity output."""
        ax, ay, ainf = handle
        xs = tw.decode_batch(ax)
        ys = tw.decode_batch(ay)
        infs = np.asarray(ainf)
        if infs.any():
            raise ValueError(
                "hash_to_g1 hit the identity (probability ~2^-255)"
            )
        return list(zip(xs, ys))

    def hash_to_g1_batch(self, datas, dst=None):
        """Synchronous device hash-to-G1 (dispatch + wait)."""
        if not datas:
            return []
        return self.hash_to_g1_wait(self.hash_to_g1_async(datas, dst))

    def pairing_product_is_one(self, pairs_batch):
        B = len(pairs_batch)
        n = len(pairs_batch[0])
        if any(len(row) != n for row in pairs_batch):
            raise ValueError("ragged pairing batch")
        flat_p = [p for row in pairs_batch for p, _ in row]
        flat_q = [q for row in pairs_batch for _, q in row]
        (px, py), pinf = self._encode_g1_points(flat_p)
        (qx, qy), qinf = self._encode_g2_points(flat_q)
        reshape = lambda t: t.reshape((B, n) + t.shape[1:])
        px, py = jax.tree_util.tree_map(reshape, (px, py))
        qx, qy = jax.tree_util.tree_map(reshape, (qx, qy))
        valid = ~(pinf | qinf).reshape(B, n)
        bits = _pairing_kernel(px, py, qx, qy, valid)
        return [bool(b) for b in np.asarray(bits)]

    # -- fused hot path ------------------------------------------------------

    def encode_verify_batch(self, sigs, messages_list, vk, params, pad_bases_to=None):
        """Host-side encoding of a verify batch into the fused-kernel operand
        tuple (wtables, mag, sgn, s1, s2n, gtx, gty, inf1, inf2).

        pad_bases_to: pad the shared-base axis (with identity bases / zero
        scalars) up to this length — the sharded path needs the base count
        divisible by the MSM mesh axis."""
        ctx = params.ctx
        k = 1 + len(vk.Y_tilde)
        npad = max(0, (pad_bases_to or 0) - k)

        def build():
            bases = [vk.X_tilde] + list(vk.Y_tilde) + [None] * npad
            wtables = _comb_tables(ctx.other, ctx.name == "G1", bases)
            return (wtables,) + self._encode_gt(ctx, params)

        wtables, gtx, gty = _static_operands(
            "verify", vk, params, pad_bases_to, build
        )
        scalars = [
            [1] + [m % R for m in msgs] + [0] * npad
            for msgs in messages_list
        ]
        mag, sgn = _comb_digits(scalars)

        s1, inf1 = self._encode_sig_points(ctx, [s.sigma_1 for s in sigs])
        s2n, inf2 = self._encode_sig_points(
            ctx,
            [
                None if s.sigma_2 is None else ctx.sig.neg(s.sigma_2)
                for s in sigs
            ],
        )
        return (wtables, mag, sgn, s1, s2n, gtx, gty, inf1, inf2)

    def _encode_sig_points(self, ctx, pts):
        """Signature-group point batch for whichever group assignment
        `ctx` names — the per-batch (non-cacheable) half of the encode."""
        if ctx.name == "G1":
            return self._encode_g1_points(pts)
        return self._encode_g2_points(pts)

    def _encode_gt(self, ctx, params):
        """The g_tilde pairing constant (other-group generator) — invariant
        per params, so it rides the static-operand cache with the tables."""
        if ctx.name == "G1":
            return (
                tw.fp2_encode_const(params.g_tilde[0]),
                tw.fp2_encode_const(params.g_tilde[1]),
            )
        from .limbs import fp_encode

        return (
            jnp.asarray(fp_encode(params.g_tilde[0])),
            jnp.asarray(fp_encode(params.g_tilde[1])),
        )

    def _encode_sigs_and_gt(self, ctx, sig_pts_1, sig_pts_2n, params):
        """Signature-group point batches + the g_tilde constant, encoded for
        whichever group assignment `ctx` names. Shared by the per-credential,
        show-verify, and grouped paths."""
        s1, inf1 = self._encode_sig_points(ctx, sig_pts_1)
        s2n, inf2 = self._encode_sig_points(ctx, sig_pts_2n)
        gtx, gty = self._encode_gt(ctx, params)
        return s1, s2n, inf1, inf2, gtx, gty

    def batch_verify_async(self, sigs, messages_list, vk, params):
        """Pipelined variant of `batch_verify`: encodes and DISPATCHES the
        fused kernel (JAX dispatch is asynchronous), returning a zero-arg
        finalizer that blocks on the device result. The streaming driver
        (stream.verify_stream) overlaps the next batch's host encode with
        the current batch's device execution through this seam."""
        from .. import metrics

        with otrace.span("encode"):
            operands = self.encode_verify_batch(
                sigs, messages_list, vk, params
            )
        bits = _fused_verify_kernel(params.ctx.name == "G1", *operands)
        metrics.count("verify_final_exps", len(sigs))

        def finalize():
            return [bool(b) for b in np.asarray(bits)]

        return finalize

    def batch_verify_grouped_async(self, sigs, messages_list, vk, params):
        """Pipelined variant of `batch_verify_grouped` (ONE bool per batch):
        dispatches the grouped kernel and returns a zero-arg finalizer.
        Same input validation as the sync path (mismatched batches must
        raise, not truncate)."""
        B = len(sigs)
        self._validate_grouped_inputs(sigs, messages_list, vk)
        if B == 0:
            return lambda: True
        if any(s.sigma_1 is None or s.sigma_2 is None for s in sigs):
            return lambda: False
        with otrace.span("encode"):
            operands = self.encode_grouped_batch(
                sigs, messages_list, vk, params
            )
        ok = _fused_verify_grouped_kernel(params.ctx.name == "G1", *operands)
        return lambda: bool(ok)

    @staticmethod
    def _validate_grouped_inputs(sigs, messages_list, vk):
        B = len(sigs)
        q = len(vk.Y_tilde)
        if len(messages_list) != B:
            raise ValueError(
                "batch size mismatch: %d sigs, %d message vectors"
                % (B, len(messages_list))
            )
        for msgs in messages_list:
            if len(msgs) != q:
                raise ValueError(
                    "message vector length %d != msg_count %d"
                    % (len(msgs), q)
                )

    def batch_verify(self, sigs, messages_list, vk, params):
        """Fully-fused batched PS verification (the north-star path)."""
        from .. import metrics

        with metrics.timer("encode"):
            operands = self.encode_verify_batch(sigs, messages_list, vk, params)
            metrics.count(
                "transfer_bytes",
                sum(
                    t.size * t.dtype.itemsize
                    for t in jax.tree_util.tree_leaves(operands)
                    if hasattr(t, "size")
                ),
            )
        with metrics.timer("kernel"):
            bits = _fused_verify_kernel(params.ctx.name == "G1", *operands)
            bits.block_until_ready()
        with metrics.timer("readback"):
            out = [bool(b) for b in np.asarray(bits)]
        metrics.count("verifies", len(out))
        metrics.count("batches")
        # exact path: one final-exponentiation lane per credential
        metrics.count("verify_final_exps", len(out))
        return out

    def _combined_dispatch(self, sigs, messages_list, vk, params, rs, epoch):
        """Shared encode + dispatch for the combined verify (sync/async):
        derives deterministic combiner exponents when `rs` is None, pads
        the batch to a power of two, and returns the device bool handle.
        Callers must have rejected empty batches and identity sigmas."""
        from .. import metrics

        B = len(sigs)
        if rs is None:
            from ..batchverify import derive_combiners, verify_transcript

            rs = derive_combiners(
                verify_transcript(sigs, messages_list, vk, params,
                                  epoch=epoch),
                B,
            )
        elif len(rs) != B:
            raise ValueError(
                "combiner count mismatch: %d exponents, %d lanes"
                % (len(rs), B)
            )
        Bp = 1 << max(1, (B - 1).bit_length())
        pad = Bp - B
        if pad:
            sigs = list(sigs) + [sigs[0]] * pad
            messages_list = list(messages_list) + [messages_list[0]] * pad
            # pad lanes clone lane 0's (valid) relation; reusing r_0 keeps
            # lane 0's total exponent r_0 * (1 + pad) != 0 mod R — sound,
            # and a pure function of the same transcript
            rs = list(rs) + [rs[0]] * pad
        with otrace.span("encode"):
            operands = self.encode_verify_batch(
                sigs, messages_list, vk, params
            )
        wtables, mag, sgn, s1, s2n, gtx, gty, inf1, inf2 = operands
        rmag, rsgn = _combiner_digits(rs)
        ok = _fused_verify_combined_kernel(
            params.ctx.name == "G1",
            wtables,
            mag,
            sgn,
            s1,
            s2n,
            rmag,
            rsgn,
            gtx,
            gty,
            inf1,
            inf2,
        )
        # ONE shared final exponentiation per combined batch (vs B lanes
        # on the exact path) — the bench's <= 2-per-batch assert reads this
        metrics.count("verify_final_exps", 1)
        return ok

    def batch_verify_combined(
        self, sigs, messages_list, vk, params, rs=None, epoch=None
    ):
        """One boolean for the whole batch via small-exponents combination
        (see fused_verify_combined): ~half the Miller work and 1/B of the
        final-exponentiation work of `batch_verify`. Probabilistic: a forged
        credential passes with probability <= 2^-lambda over the combiner
        draw. `rs=None` derives the combiners deterministically from the
        domain-separated batch transcript (batchverify.derive_combiners —
        replayable, sound in the random-oracle model since the transcript
        commits to the batch before the exponents exist); pass explicit
        `rs` to pin exponents (tests). `epoch` joins the transcript's
        domain separation (PR 15 key epochs share verkey bytes)."""
        from .. import metrics

        metrics.count("verify_batched_checks")
        B = len(sigs)
        if B == 0:
            return True  # empty product is 1
        if any(s.sigma_1 is None or s.sigma_2 is None for s in sigs):
            return False
        return bool(
            self._combined_dispatch(sigs, messages_list, vk, params, rs, epoch)
        )

    def batch_verify_combined_async(
        self, sigs, messages_list, vk, params, rs=None, epoch=None
    ):
        """Pipelined variant of `batch_verify_combined` (ONE bool per
        batch): dispatches the combined kernel and returns a zero-arg
        finalizer — the stream/serve "batched" mode overlaps the next
        batch's host encode with this batch's device execution."""
        from .. import metrics

        metrics.count("verify_batched_checks")
        if len(sigs) == 0:
            return lambda: True
        if any(s.sigma_1 is None or s.sigma_2 is None for s in sigs):
            return lambda: False
        ok = self._combined_dispatch(sigs, messages_list, vk, params, rs, epoch)
        return lambda: bool(ok)

    def batch_show_verify_combined(
        self, proofs, vk, params, revealed_msgs_list, challenges, rs=None,
        epoch=None
    ):
        """RLC-combined batched show verify -> (per-lane Schnorr bits,
        ONE batch pairing bool). The Schnorr half stays per-lane (it is
        MSM-only); the B pairing checks fold under deterministic combiner
        exponents into B+1 Miller pairs + ONE final exponentiation
        (fused_show_verify_combined). A lane's verdict is
        bits[i] & pair_ok; on pair_ok=False the ps-layer bisects with
        fresh per-sub-batch exponents to attribute the culprit lanes.
        All proofs must share one revealed-index set (as
        `batch_show_verify`)."""
        from .. import metrics

        metrics.count("verify_batched_checks")
        B = len(proofs)
        if B == 0:
            return [], True
        if rs is None:
            from ..batchverify import derive_combiners, show_transcript

            rs = derive_combiners(
                show_transcript(proofs, vk, params, revealed_msgs_list,
                                challenges, epoch=epoch),
                B,
            )
        elif len(rs) != B:
            raise ValueError(
                "combiner count mismatch: %d exponents, %d lanes"
                % (len(rs), B)
            )
        Bp = 1 << max(1, (B - 1).bit_length())
        pad = Bp - B
        if pad:
            # clone-first padding, as the engine's assemble(): a cloned
            # lane reuses its original's challenge AND combiner exponent
            proofs = list(proofs) + [proofs[0]] * pad
            revealed_msgs_list = (
                list(revealed_msgs_list) + [revealed_msgs_list[0]] * pad
            )
            challenges = list(challenges) + [challenges[0]] * pad
            rs = list(rs) + [rs[0]] * pad
        operands = self.encode_show_verify_batch(
            proofs, vk, params, revealed_msgs_list, challenges
        )
        (
            vc_wtables, resp_mag, resp_sgn, jpt, jinf, cmag_j, csgn_j,
            commx, commy, comminf, acc_wtables, acc_mag, acc_sgn,
            s1, s2n, gtx, gty, inf1, inf2,
        ) = operands
        rmag, rsgn = _combiner_digits(rs)
        bits, pair_ok = _fused_show_verify_combined_kernel(
            params.ctx.name == "G1",
            vc_wtables,
            resp_mag,
            resp_sgn,
            jpt,
            jinf,
            cmag_j,
            csgn_j,
            commx,
            commy,
            comminf,
            acc_wtables,
            acc_mag,
            acc_sgn,
            s1,
            s2n,
            rmag,
            rsgn,
            gtx,
            gty,
            inf1,
            inf2,
        )
        metrics.count("verify_final_exps", 1)
        return (
            [bool(b) for b in np.asarray(bits)[:B]],
            bool(pair_ok),
        )

    def batch_show_verify(
        self, proofs, vk, params, revealed_msgs_list, challenges
    ):
        """Batched selective-disclosure proof verification (config 3).

        All proofs must share one revealed-index set; `ps.batch_show_verify`
        is the public API (it recomputes Fiat-Shamir challenges and falls
        back to the sequential path on ragged batches)."""
        from .. import metrics

        if len(proofs) == 0:
            return []
        operands = self.encode_show_verify_batch(
            proofs, vk, params, revealed_msgs_list, challenges
        )
        bits = _fused_show_verify_kernel(params.ctx.name == "G1", *operands)
        metrics.count("verify_final_exps", len(proofs))
        return [bool(b) for b in np.asarray(bits)]

    def encode_show_verify_batch(
        self, proofs, vk, params, revealed_msgs_list, challenges
    ):
        """Host-side encoding of a show-verify batch into the
        fused_show_verify operand tuple (everything after sig_is_g1).
        Split out so the dp-sharded path (tpu/shard.py) shares it."""
        ctx = params.ctx
        B = len(proofs)
        revealed = sorted(proofs[0].revealed_msg_indices)
        hidden = [
            i for i in range(len(vk.Y_tilde)) if i not in proofs[0].revealed_msg_indices
        ]
        oth = ctx.other
        is_g1_ctx = ctx.name == "G1"

        # static operands (Schnorr + pairing comb tables, g_tilde): one
        # cache entry per (vk, params, revealed-index set)
        def build():
            vc_bases = [params.g_tilde] + [vk.Y_tilde[i] for i in hidden]
            acc_bases = [vk.X_tilde] + [vk.Y_tilde[i] for i in revealed]
            return (
                _comb_tables(oth, is_g1_ctx, vc_bases),
                _comb_tables(oth, is_g1_ctx, acc_bases),
            ) + self._encode_gt(ctx, params)

        vc_wtables, acc_wtables, gtx, gty = _static_operands(
            "show", vk, params, tuple(revealed), build
        )

        # Schnorr operands
        resp_mag, resp_sgn = _comb_digits(
            [[r % R for r in p.proof_vc.responses] for p in proofs]
        )
        enc_other = (
            self._encode_g2_points if is_g1_ctx else self._encode_g1_points
        )
        (jx, jy), jinf = enc_other([p.J for p in proofs])
        cmag_j, csgn_j = _signed_digits([[c % R] for c in challenges])
        (commx, commy), comminf = enc_other([p.proof_vc.t for p in proofs])

        # pairing operands
        acc_mag, acc_sgn = _comb_digits(
            [
                [1] + [rm[i] % R for i in revealed]
                for rm in revealed_msgs_list
            ]
        )
        s1, inf1 = self._encode_sig_points(
            ctx, [p.sigma_prime_1 for p in proofs]
        )
        s2n, inf2 = self._encode_sig_points(
            ctx,
            [
                None if p.sigma_prime_2 is None else ctx.sig.neg(p.sigma_prime_2)
                for p in proofs
            ],
        )
        return (
            vc_wtables,
            resp_mag,
            resp_sgn,
            ((jx, jy)),
            jinf,
            cmag_j,
            csgn_j,
            commx,
            commy,
            comminf,
            acc_wtables,
            acc_mag,
            acc_sgn,
            s1,
            s2n,
            gtx,
            gty,
            inf1,
            inf2,
        )

    def batch_verify_grouped(self, sigs, messages_list, vk, params):
        """One boolean for the whole batch via the attribute-grouped
        combination (fused_verify_grouped): q+2 pairings total, all
        per-credential work in shared-point G1 MSMs. The fastest verify
        path; soundness 2^-128 per forged credential."""
        from .. import metrics

        B = len(sigs)
        self._validate_grouped_inputs(sigs, messages_list, vk)
        if B == 0:
            return True
        if any(s.sigma_1 is None or s.sigma_2 is None for s in sigs):
            return False
        operands = self.encode_grouped_batch(sigs, messages_list, vk, params)
        ok = _fused_verify_grouped_kernel(params.ctx.name == "G1", *operands)
        metrics.count("verify_final_exps", 1)
        return bool(ok)

    def encode_grouped_batch(
        self, sigs, messages_list, vk, params, pad_batch_to=None
    ):
        """Host-side encoding for the grouped verify kernel: pads the batch
        to a power of two (>= pad_batch_to if given — the sharded path needs
        the batch divisible by the mesh's dp extent), samples the combiner
        scalars, and recodes all scalar rows to the signed 6-bit/43-window
        schedule (_G_WINDOW/_G_NWIN).
        Returns the fused_verify_grouped operand tuple (everything after
        sig_is_g1). Callers must have rejected empty batches and identity
        sigmas already."""
        import secrets

        B = len(sigs)
        q = len(vk.Y_tilde)
        Bp = 1 << max(1, (B - 1).bit_length())
        if pad_batch_to is not None:
            while Bp < pad_batch_to:
                Bp *= 2
        pad = Bp - B
        if pad:
            sigs = list(sigs) + [sigs[0]] * pad
            messages_list = list(messages_list) + [messages_list[0]] * pad
        ctx = params.ctx
        rs = [secrets.randbits(_R_RAND_BITS) for _ in range(Bp)]
        rows = [rs] + [
            [r * (msgs[j] % R) % R for r, msgs in zip(rs, messages_list)]
            for j in range(q)
        ]
        from .limbs import fr_digits_signed_np

        recoded = [
            fr_digits_signed_np(row, nwin=_G_NWIN, window=_G_WINDOW)
            for row in rows
        ]
        cmag = jnp.asarray(np.stack([m for m, _ in recoded]))
        csgn = jnp.asarray(np.stack([s for _, s in recoded]))  # [q+1, Bp, 43]
        # r_i are _R_RAND_BITS-bit: only the last _G_RNWIN msb-first windows
        # of the r-row can be nonzero — slice so the -sigma_2 MSM runs a
        # short schedule. A real check (not assert: must survive python -O)
        # so a widened sampler can never silently drop top windows.
        nwin = cmag.shape[-1]
        if recoded[0][0][:, : nwin - _G_RNWIN].any():
            raise ValueError(
                "combiner scalar exceeds %d bits: top windows nonzero"
                % _R_RAND_BITS
            )
        rmag = cmag[:1, :, nwin - _G_RNWIN :]
        rsgn = csgn[:1, :, nwin - _G_RNWIN :]

        s1, inf1 = self._encode_sig_points(ctx, [s.sigma_1 for s in sigs])
        s2n, inf2 = self._encode_sig_points(
            ctx, [ctx.sig.neg(s.sigma_2) for s in sigs]
        )

        def build():
            others = [vk.X_tilde] + list(vk.Y_tilde)
            if ctx.name == "G1":
                ox = tw.encode_batch([p[0] for p in others])
                oy = tw.encode_batch([p[1] for p in others])
            else:
                from .limbs import fp_encode_batch

                ox = jnp.asarray(fp_encode_batch([p[0] for p in others]))
                oy = jnp.asarray(fp_encode_batch([p[1] for p in others]))
            return (ox, oy) + self._encode_gt(ctx, params)

        ox, oy, gtx, gty = _static_operands("grouped", vk, params, None, build)
        return (s1, s2n, inf1, inf2, cmag, csgn, rmag, rsgn, ox, oy, gtx, gty)

    def batch_verify_sharded(self, sigs, messages_list, vk, params, mesh, **kw):
        """Multi-chip variant: dp-sharded credentials, tp-sharded MSM bases
        over `mesh` (see tpu/shard.py)."""
        from . import shard

        return shard.batch_verify_sharded(
            self, sigs, messages_list, vk, params, mesh, **kw
        )

    def batch_verify_grouped_sharded(
        self, sigs, messages_list, vk, params, mesh, **kw
    ):
        """Multi-chip HEADLINE variant: the attribute-grouped one-bool
        verify with the credential batch dp-sharded over `mesh` and the
        MSM accumulators combined across devices (see tpu/shard.py)."""
        from . import shard

        return shard.batch_verify_grouped_sharded(
            self, sigs, messages_list, vk, params, mesh, **kw
        )
