"""Batched Fp (BLS12-381 base field) arithmetic on 52 lazy signed 8-bit
limbs in f32 — the third redesign of SURVEY.md §7 hard part (a).

Every function operates on arrays of shape [..., NLIMBS] (leading dims =
batch). Elements are in the Montgomery domain (R = 2^416) as SIGNED limb
vectors:

  value = sum_i limb_i * 256^i,  52 limbs, f32, |value| tracked by class.

R/p ~ 2^35 of headroom (52*8 = 416 bits vs the 381-bit p) buys LAZY
REDUCTION: between Montgomery multiplies nothing is ever normalized.

Two element classes, maintained by construction (import asserts pin every
bound the algebra relies on):

  NORMALIZED — mul outputs and encoded constants: |limbs| <= 132,
    |value| <= V_NORM = 4p. Tail domination then forces limbs 50 and 51 to
    be EXACTLY zero: |l51| <= (V_NORM + 132*(2^408-1)/255)/2^408 < 1, and
    an integer below 1 is 0 (same for l50). Two vacant top limbs make the
    carry passes inside `mul` value-exact: carries never fall off the top.

  LAZY — any +/-/small-constant combination of normalized values with
    total limb weight <= 2^17/132 (~992 terms; the heaviest real call site
    is the G2 complete-add b3 path at ~432 terms — t5 is a 9-term sum,
    the twist's b3 = 12(1+u) scales it 24x componentwise, and the next
    fp2_mul's Karatsuba a0+a1 doubles it):
    |limbs| <= L_LAZY = 2^17, |value| <= V_LAZY = 1024p, l50 = l51 = 0
    (sums of zeros stay zero).
    The VALUE bound relies on a tighter per-term bound than V_NORM: every
    value actually entering a lazy combination has |value| < p (mul
    outputs are < 0.66p, encoded constants are canonical < p), so even
    the maximal ~992-term combination stays below 992p < V_LAZY = 1024p.
    V_NORM = 4p is only the per-LIMB-shape class bound used by the carry
    vacancy argument above, never the per-term value entering sums.

Consequences:
  - add/sub/neg/mul_small are ELEMENTWISE f32 ops — one HLO instruction,
    no carry chains, no masked subtractions. This is where the previous
    (48-limb, eagerly-reduced) design spent most of its HLO size and VPU
    time: each add ran a 3-pass normalize + 3 masked-subtract rounds.
  - mul: two shift/round passes bring |limbs| <= 132 exactly (carries from
    l49 land in the vacant l50/l51), then one-shot Montgomery REDC with a
    signed m (|m| <= 0.64 R) — no nonnegativity fix-up term. Output value
    bound: V_LAZY^2/R + 0.64p < 0.66p.
  - schoolbook limb products run ON THE MXU: outer products (<= 132^2,
    exact f32) split into two byte planes hi = floor((t+128)/256) in
    [-69, 69] and lo = t - 256*hi in [-128, 127], each contracted against a
    static 0/1 band matrix as int8 x int8 -> int32 matmuls (native int8
    MXU peak is 2x bf16 on v5e; every sum of <= 52 terms is exact in both
    int32 and the bf16->f32 fallback, COCONUT_FP_INT8=0).
  - exact predicates COMPRESS first (one Montgomery mul by the encoded 1):
    the result is normalized with |value| < 0.66p < p, so value == 0 mod p
    iff value == 0 iff every limb is 0 (downward domination at |l| <= 132).

Kept bit-identical to the pure-Python spec (`coconut_tpu.ops.fields`) at
the decode boundary: limbs.fp_decode reduces the signed value mod p.
"""

import os

import numpy as np

import jax.numpy as jnp
from jax import lax

from ..ops.fields import P
from .limbs import MONT_R, NLIMBS, balanced_limbs

# --- bounds (exact integer arithmetic at import time) -----------------------

L_NORM = 132            # normalized limb bound
V_NORM = 4 * P          # normalized value bound
L_LAZY = 1 << 17        # lazy limb bound (mul-input cap)
V_LAZY = 1024 * P       # lazy value bound (mul-input cap)

_TAIL50 = L_NORM * ((256**50 - 1) // 255)
_TAIL51 = L_NORM * ((256**51 - 1) // 255)
# top-limb vacancy of normalized values: l50 = l51 = 0 exactly
assert V_NORM + _TAIL50 < 256**50
assert V_NORM + _TAIL51 < 256**51
# two passes on lazy limbs: pass1 <= 128 + ceil(L_LAZY/256) = 640;
# pass2 <= 128 + 3 = 131 <= L_NORM. Carries land in the vacant top limbs:
# pass1 puts <= 512 in l50, pass2 puts <= 2 in l51, carry out of l51 is 0.
_P1 = 128 + (L_LAZY + 128) // 256
assert 128 + (_P1 + 128) // 256 <= L_NORM
assert (_P1 + 128) // 256 < 128  # l51 stays far below a further carry
# byte planes exact in int8: |t| <= 132^2 => hi in [-69,69], lo in [-128,127]
assert L_NORM * L_NORM <= 127 * 256 + 127
# school coefficients: sums of <= 52 products, exact f32/int32
assert NLIMBS * L_NORM * L_NORM < 1 << 24
# REDC: |m| <= 0.64 R (m limbs <= 132 after 3 passes: 132*256/255/256 < 0.52,
# use 0.64 for slack); |out| <= V_LAZY^2/R + 0.64p < 0.66p < V_NORM
assert V_LAZY * V_LAZY // MONT_R + 64 * P // 100 + 1 < 2 * P // 3
# mul-internal coefficient bound (t + m*p): < 2^22, exact f32 adds
assert NLIMBS * L_NORM * L_NORM * 2 < 1 << 22

_BASE = 256.0
_INV_BASE = 1.0 / 256.0

_P_BAL_J = jnp.asarray(balanced_limbs(P), dtype=jnp.float32)
_NPRIME_J = jnp.asarray(
    balanced_limbs((-pow(P, -1, MONT_R)) % MONT_R, wrap=True),
    dtype=jnp.float32,
)
_ONE_M_J = jnp.asarray(balanced_limbs(MONT_R % P), dtype=jnp.float32)

# Static band matrix: BAND[i*NLIMBS + j, k] = 1 iff i + j == k.
_BAND_NP = np.zeros((NLIMBS * NLIMBS, 2 * NLIMBS - 1), dtype=np.float32)
for _i in range(NLIMBS):
    for _j in range(NLIMBS):
        _BAND_NP[_i * NLIMBS + _j, _i + _j] = 1.0
_BAND = jnp.asarray(_BAND_NP, dtype=jnp.bfloat16)
_BAND_I8 = jnp.asarray(_BAND_NP, dtype=jnp.int8)
_USE_INT8 = os.environ.get("COCONUT_FP_INT8", "1") == "1"


def _school(a, b, out_len):
    """Polynomial limb product c_k = sum_{i+j=k} a_i * b_j, truncated to
    out_len limbs. Inputs |a_i|,|b_j| <= 132 (see import asserts)."""
    outer = a[..., :, None] * b[..., None, :]
    lead = outer.shape[:-2]
    # Collapse ALL leading dims to one before the contraction: an earlier
    # TPU runtime miscompiled int8 dot_generals with multi-dim einsum
    # batches when several such contractions fused in one program
    # (observed as wrong results in exactly one column of a [B, 2, ...]
    # batch at B >= 256; a 2-D [N, x] @ [x, k] matmul was always correct).
    flat = outer.reshape((-1, NLIMBS * NLIMBS))
    if _USE_INT8:
        # byte-plane split in integer arithmetic (f32 products are exact
        # ints < 2^24; >> is an arithmetic shift, i.e. floor division)
        flat_i = flat.astype(jnp.int32)
        hi_i = (flat_i + 128) >> 8
        lo_i = flat_i - (hi_i << 8)
        acc_lo = jnp.dot(
            lo_i.astype(jnp.int8),
            _BAND_I8[:, :out_len],
            preferred_element_type=jnp.int32,
        )
        acc_hi = jnp.dot(
            hi_i.astype(jnp.int8),
            _BAND_I8[:, :out_len],
            preferred_element_type=jnp.int32,
        )
        out = (acc_lo + acc_hi * 256).astype(jnp.float32)
        return out.reshape(lead + (out_len,))
    hi = jnp.floor((flat + 128.0) * _INV_BASE)
    lo = flat - hi * _BASE
    acc_lo = jnp.dot(
        lo.astype(jnp.bfloat16),
        _BAND[:, :out_len],
        preferred_element_type=jnp.float32,
    )
    acc_hi = jnp.dot(
        hi.astype(jnp.bfloat16),
        _BAND[:, :out_len],
        preferred_element_type=jnp.float32,
    )
    return (acc_lo + acc_hi * _BASE).reshape(lead + (out_len,))


def _shift_up(hi):
    """Move per-limb carries one limb up (drops the top limb's carry —
    exact at every call site by the vacancy/zero-coefficient arguments in
    `mul`, or truncation mod 2^416 is intended)."""
    return jnp.concatenate([jnp.zeros_like(hi[..., :1]), hi[..., :-1]], axis=-1)


def _pass(t):
    """One shift/round carry pass: exact power-of-two scalings and integer
    adds below 2^24; |limb| drops ~256x toward the <= 132 band."""
    hi = jnp.round(t * _INV_BASE)
    lo = t - hi * _BASE
    return lo + _shift_up(hi)


def _norm(t, passes):
    for _ in range(passes):
        t = _pass(t)
    return t


def _ext(t, extra):
    return jnp.concatenate(
        [t, jnp.zeros(t.shape[:-1] + (extra,), dtype=jnp.float32)], axis=-1
    )


# --- public ops -------------------------------------------------------------


def zeros_like(a):
    return jnp.zeros_like(a)


def ones_mont(shape=()):
    return jnp.broadcast_to(_ONE_M_J, tuple(shape) + (NLIMBS,))


def add(a, b):
    return a + b


def sub(a, b):
    return a - b


def neg(a):
    return -a


def mul_small(a, k):
    """a * k for small static nonnegative k — elementwise (lazy)."""
    if k == 0:
        return jnp.zeros_like(a)
    if k == 1:
        return a
    return a * float(k)


def mul(a, b):
    """Montgomery product a * b * 2^-416 mod p. Inputs LAZY (|limbs| <=
    L_LAZY = 2^17, |value| <= V_LAZY = 1024p, top two limbs zero), output
    NORMALIZED (|limbs| <= 132, |value| < 0.66p).

    Signed one-shot REDC: t = a*b; m = (t mod 2^416)*N' mod 2^416 (signed,
    |m| <= 0.64 R); u = (t + m*p) / 2^416 — exact division, no
    nonnegativity term needed (values may be negative).

    On TPU the whole pipeline runs as one fused Pallas kernel
    (pallas_fp.py) so no intermediate ever touches HBM; the XLA
    formulation below is the CPU/fallback path (bit-identical)."""
    from . import pallas_fp

    if pallas_fp.enabled():
        return pallas_fp.mul(a, b)
    a1 = _norm(a, 2)  # |limbs| <= 132; carries land in vacant l50/l51
    b1 = _norm(b, 2)
    t = _school(a1, b1, 2 * NLIMBS - 1)  # |coeff| < 2^21
    tlo = _norm(t[..., :NLIMBS], 3)  # t mod 2^416 (truncation intended)
    m = _norm(_school(tlo, _NPRIME_J, NLIMBS), 3)  # signed, trunc mod 2^416
    w = t + _school(m, _P_BAL_J, 2 * NLIMBS - 1)  # = t + m*p, |coeff| < 2^22
    # Low half: value divisible by 2^416 and |coeffs| normalized => limbs
    # [0:52] end exactly zero; the carry into the high half sits in the
    # extension limbs (|carry| <= 2^14, fits 3 limbs).
    lo = _norm(_ext(w[..., :NLIMBS], 3), 3)
    hi = _ext(w[..., NLIMBS:], 1)  # 51 -> 52 limbs
    hi = hi.at[..., :3].add(lo[..., NLIMBS : NLIMBS + 3])
    # w's nonzero coefficients stop by index 102 (inputs have l50=l51~0),
    # so the high half's top limbs stay small: 3 passes normalize exactly.
    return _norm(hi, 3)


_R2_BAL_J = jnp.asarray(
    balanced_limbs(MONT_R * MONT_R % P), dtype=jnp.float32
)

# Raw canonical base-256 digits (0..255 per limb) are valid LAZY mul
# inputs: 255 <= L_LAZY, value < p <= V_LAZY, and p < 2^381 < 256^48 so a
# 48-byte value leaves limbs 48..51 exactly zero after padding.
assert 255 <= L_LAZY and P <= V_LAZY and P < 256**48


def to_mont(t):
    """Raw canonical limbs -> Montgomery domain, on device.

    `t` is uint8/float [..., 48 or 52] raw base-256 digits of a canonical
    Fp value (limbs.fp_encode_raw_batch). One Montgomery multiply by R^2
    gives x * R^2 * R^-1 = x * R mod p — the same value fp_encode computes
    with host bigints, via the existing exact mul kernel (XLA or Pallas),
    so downstream arithmetic is bit-identical to the host-encoded path."""
    if t.dtype != jnp.float32:
        t = t.astype(jnp.float32)
    if t.shape[-1] < NLIMBS:
        t = _ext(t, NLIMBS - t.shape[-1])
    return mul(t, _R2_BAL_J)


_ONE_RAW_J = jnp.zeros((NLIMBS,), jnp.float32).at[0].set(1.0)


def from_mont(t):
    """Montgomery limbs -> limbs whose VALUE is the standard-domain
    representative mod p: one Montgomery multiply by the raw integer 1
    (x*R * 1 * R^-1 = x). Output is mul-class (|value| < 0.66p). Needed
    wherever device logic must observe the standard-domain value itself
    — e.g. canon_parity as the SvdW map's sgn0, which is defined on the
    canonical integer, not its Montgomery image."""
    return mul(t, _ONE_RAW_J)


def sq(a):
    return mul(a, a)


def pow_static(a, e, window=4):
    """a^e for a static positive int exponent: 4-bit windowed scan.

    Per window: `window` squarings + ONE multiply by a table entry selected
    from the precomputed powers a^0..a^15 (gathered with a one-hot mask —
    cheap VPU selects vs a Montgomery mul). vs the bit-scan's
    square+multiply-every-bit this cuts ~2 muls/bit to ~1.25, which matters
    because `inv` (a^{p-2}, 381 bits) sits inside every to_affine and
    final_exp on full-batch shapes."""
    assert e > 0
    nw = (e.bit_length() + window - 1) // window
    digits = jnp.array(
        [(e >> (window * i)) & ((1 << window) - 1) for i in range(nw - 1, -1, -1)],
        dtype=jnp.int32,
    )
    # table a^0..a^(2^w - 1): leading axis 16, built with 14 muls + encode
    pows = [ones_mont(a.shape[:-1]), a]
    for _ in range(2, 1 << window):
        pows.append(mul(pows[-1], a))
    table = jnp.stack(jnp.broadcast_arrays(*pows), axis=0)  # [16, ..., N]

    def body(acc, d):
        for _ in range(window):
            acc = mul(acc, acc)
        entry = lax.dynamic_index_in_dim(table, d, axis=0, keepdims=False)
        return mul(acc, entry), None

    init = ones_mont(a.shape[:-1])
    acc, _ = lax.scan(body, init, digits)
    return acc


def inv(a):
    """a^{p-2}; returns 0 for input 0 (callers mask identities explicitly)."""
    return pow_static(a, P - 2)


# --- canonical byte packing (device-side readback compression) --------------

_TWO_P_DIGITS_NP = np.array(
    [((2 * P) >> (8 * i)) & 0xFF for i in range(NLIMBS)], dtype=np.float32
)
# 2p's top limbs: 2p < 2^382, so digits 48.. are zero — the 48-byte slice
# below is exact for any packed |value| < 2p
assert 2 * P < 1 << 383
CANON_BYTES = 48


def pack_canon48(t):
    """f32 [..., 52] lazy limbs with |value| < 2p and |limbs| <= ~400 ->
    uint8 [..., 48] base-256 digits of (value + 2p), a canonical-width
    representative of value mod p. This is the device half of the
    readback compression: 48 bytes per Fp instead of 104 (int16 x 52), so
    fewer result bytes cross the device-to-host link.

    Exactness: adding 2p's digits (<= 255) to limbs |v| <= ~400 keeps
    every limb in [-400, 655]; the full sequential carry scan (floor
    semantics) produces exact base-256 digits of the nonnegative value
    v + 2p in (0, 4p) subset [0, 2^383), whose digits 48..51 are zero and
    are dropped. Every intermediate is an exact small f32 integer. The
    host inverse is limbs.fp_decode_batch's uint8 path (value mod p after
    the Montgomery divide).

    Scan width: this scan carries a flat [lanes] f32 (no limb dim) and
    stacks [52, lanes] — a DIFFERENT shape family from the comb-build
    scans an earlier TPU runtime corrupted above ~1028 carry lanes
    (probes/README.md). Probed bit-exact on that runtime at 2,048 / 8,192
    / 65,536 lanes, all lanes checked, including negative-value lazy
    inputs (probes/probe_pack.py, 2026-08-01); re-run that probe if the
    scan structure here changes."""
    digsT = _canon_digits(t)
    digs = jnp.moveaxis(digsT, 0, -1)
    return digs[..., :CANON_BYTES].astype(jnp.uint8)


def _canon_digits(t):
    """Exact base-256 digits of (value + 2p), limb-major [52, ...] —
    the shared carry scan behind pack_canon48 and canon_parity. Same
    contract as pack_canon48: |value| < 2p, |limbs| <= ~400."""
    v = t + jnp.asarray(_TWO_P_DIGITS_NP)

    def step(c, d):
        s = d + c
        hi = jnp.floor(s * _INV_BASE)
        return hi, s - hi * _BASE

    vT = jnp.moveaxis(v, -1, 0)  # [52, ...]
    _, digsT = lax.scan(step, jnp.zeros(v.shape[:-1], v.dtype), vT)
    return digsT


def canon_parity(t):
    """sgn0 of t: the parity bit of the canonical representative of t
    mod p, on device — the SvdW map's y-sign test (ops/hashing.py:
    fp_sgn0(a) = a & 1 on the canonical value).

    Contract: NORMALIZED-class limbs with |value| < p (every fp.mul /
    pow_static output qualifies at |value| < 0.66p). Then w = value + 2p
    lies in (p, 3p), so the canonical value is w - 2p when w >= 2p and
    w - p otherwise; p is odd, so parity(canonical) = parity(w) flipped
    exactly when w < 2p. Both ingredients come from the same exact digit
    scan as pack_canon48: parity(w) is digit 0 mod 2, and w >= 2p is a
    lexicographic digit compare against 2p's digits (MS digit first;
    value-0 inputs hit w == 2p exactly and return 0, matching
    sgn0(0) = 0)."""
    digsT = _canon_digits(t)  # [52, ...] exact digits of value + 2p
    twop = jnp.asarray(_TWO_P_DIGITS_NP)
    cmp = jnp.zeros(digsT.shape[1:], digsT.dtype)
    for i in range(NLIMBS - 1, -1, -1):  # first nonzero diff from MSB wins
        d = jnp.sign(digsT[i] - twop[i])
        cmp = jnp.where(cmp != 0.0, cmp, d)
    ge2p = cmp >= 0.0
    par_w = jnp.mod(digsT[0], 2.0) != 0.0
    return jnp.where(ge2p, par_w, ~par_w)


# --- exact predicates (compress, then all-limbs-zero) -----------------------


def is_zero(a):
    """a == 0 mod p for any LAZY a: one Montgomery mul by the encoded 1
    compresses to a normalized value with |value| < p, which is 0 mod p
    iff it is 0 iff every limb is 0 (downward domination)."""
    c = mul(a, ones_mont(a.shape[:-1]))
    return jnp.all(c == 0.0, axis=-1)


def is_zero_many(vals):
    """[v, ...] -> [v == 0 mod p, ...] with ALL the compress-muls stacked
    into one MXU contraction (the tower predicates' batching lever)."""
    ones = ones_mont(vals[0].shape[:-1])
    outs = mul_stack(vals, [ones] * len(vals))
    return [jnp.all(o == 0.0, axis=-1) for o in outs]


def eq(a, b):
    return is_zero(a - b)


def select(mask, a, b):
    """mask [...] bool -> a where true else b (limb arrays)."""
    return jnp.where(mask[..., None], a, b)


# --- stacked-multiply helper (the tower's compile-size lever) ---------------


def mul_stack(lhs_list, rhs_list):
    """Stack S independent products into ONE mul: [(a, b), ...] with shared
    leading dims -> list of S products. Collapses tower/curve formulas'
    many base-field multiplies into a single MXU contraction."""
    L = jnp.stack(jnp.broadcast_arrays(*lhs_list), axis=-2)  # [..., S, N]
    Rv = jnp.stack(jnp.broadcast_arrays(*rhs_list), axis=-2)
    out = mul(L, Rv)
    return [out[..., i, :] for i in range(len(lhs_list))]
