"""Fused Pallas TPU kernel for the Montgomery multiply (fp.mul).

Why: the XLA formulation of `fp.mul` materializes the schoolbook outer
product (a 52x data expansion, [N, 2704] f32) plus byte planes in HBM for
every multiply — measured to make every kernel HBM-bound. This kernel
keeps the whole REDC pipeline (input carry passes, three limb-product
reductions, low-half carry extraction, output normalization) in VMEM: per
lane only 104 input + 52 output limbs cross HBM.

Layout: everything TRANSPOSED to [limbs, lanes] — the lane (batch) axis
sits in the 128-wide vector lanes, so every carry shift and coefficient
shift is a static concatenate on the sublane axis.

The limb product itself is a pure-VPU "comb": the [52, 52, TN] outer
product's rows are shift-aligned and summed in a pairwise tree, split into
low/high coefficient halves to avoid padding (every coefficient is a sum
of <= 52 products <= 132^2 — exact f32, no byte planes, no matmul). This
measured 52.5 ns/lane vs 92.2 for the int8-MXU band contraction and 351.5
for the XLA path: the band matmul's 95x MAC redundancy makes even the MXU
lose to straight VPU accumulation here. The MXU band path is kept behind
COCONUT_PALLAS_VPU=0 (int8 planes by default there; COCONUT_FP_INT8=0 for
bf16).

The arithmetic is the same proof-carrying pipeline as fp.mul (see fp.py's
import asserts): inputs LAZY (|limbs| <= 2^17, top two limbs vacant),
output NORMALIZED (|limbs| <= 132, |value| < 0.66p), results bit-identical
to the XLA path (differential-tested).

Enabled automatically when the default JAX backend is a TPU (CPU tests
keep the pure-XLA path), or forced via COCONUT_FP_PALLAS=1/0.
"""

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp

from . import fp as _fp
from .limbs import NLIMBS

TN = int(os.environ.get("COCONUT_PALLAS_TN", "256"))  # lanes per grid block
# int8 MXU planes by default; COCONUT_FP_INT8=0 (the documented knob) or
# COCONUT_PALLAS_I8=0 selects the bf16 fallback
_I8 = (
    os.environ.get("COCONUT_PALLAS_I8", os.environ.get("COCONUT_FP_INT8", "1"))
    == "1"
)

_OUT2 = 2 * NLIMBS - 1  # 103

# All Montgomery constants and the band structure are shared with fp.py so
# the two paths can never desynchronize (fp imports this module lazily
# inside mul, so there is no import cycle).
# numpy (host) constants only at module level — jnp.asarray here would
# create traced constants when this module is first imported inside a jit
# trace (fp.mul imports lazily), leaking tracers into the globals; the jnp
# conversion happens per call site (deduped per jit trace).
_BAND_T_NP = _fp._BAND_NP.T.copy()
_NPRIME_COL = np.asarray(_fp._NPRIME_J).reshape(NLIMBS, 1)
_P_COL = np.asarray(_fp._P_BAL_J).reshape(NLIMBS, 1)

_BASE = 256.0
_INV_BASE = 1.0 / 256.0


def _shift_up(h):
    """Carry shift on the sublane (limb) axis: drop top, prepend zero."""
    return jnp.concatenate([jnp.zeros_like(h[:1]), h[:-1]], axis=0)


def _pass(t):
    hi = jnp.round(t * _INV_BASE)
    lo = t - hi * _BASE
    return lo + _shift_up(hi)


def _norm(t, passes):
    for _ in range(passes):
        t = _pass(t)
    return t


def _ext(t, extra):
    return jnp.concatenate(
        [t, jnp.zeros((extra, t.shape[1]), dtype=t.dtype)], axis=0
    )


_VPU = os.environ.get("COCONUT_PALLAS_VPU", "1") == "1"
# Karatsuba on the FULL 52-limb products (the t = a*b and w = m*p steps).
# One level: 3x 26-limb schoolbooks (2,028 lane-mults) replace the 52x52
# outer product (2,704). Two levels (the default): each 26-schoolbook
# splits again into 3x 13-limb schoolbooks — 9x169 = 1,521 lane-mults —
# at the cost of deeper add-trees.
#
# Exactness proof (every f32 add of exact integers < 2^24 is exact, and
# the partial-sum ORDER below keeps every intermediate under 2^24):
#   level-2 operands: normalized halves |v| <= 132, L1-mid operands
#   (x0+x1) <= 264, their L2 halves' sums <= 528.
#   13-limb product coeff <= 13*528^2 = 3.63M; L2 z1 = mid - z0 - z2:
#   partial |mid - z0| <= 3.63M + 0.91M = 4.54M < 2^24.
#   Assembled 26-product coeff (z0 + z1 + z2 overlap) for M-bounded
#   operands <= 104*M^2: M=264 -> 7.25M < 2^24 (partials <= 6.35M).
#   L1 z1 = mid26 - z0_26 - z2_26: partial <= 7.25M + 3.63M = 10.9M
#   < 2^24. Final 103-coeff assembly partials <= 3.63M + 10.9M = 14.5M
#   < 2^24 = 16.8M; the finished coefficient is the TRUE product
#   coefficient <= 52*132^2 = 0.91M. The downstream 3-pass carry
#   extractions absorb the larger intermediate bound: pass-1 residual
#   <= 128 + round(14.5M/256) ~ 57k, pass 2 <= 128 + 224 = 352, pass 3
#   <= 128 + 2 <= 132 (the NORMALIZED class bound, as in fp.py).
# COCONUT_PALLAS_KARATSUBA: 0 = plain outer product, 1 = one level,
# 2 = two levels (default).


def _parse_karatsuba(raw, default=2):
    """Parse the COCONUT_PALLAS_KARATSUBA setting: unset/empty/garbage or
    a negative value falls back to the default (a typo'd env var must not
    crash import or silently pick a random depth); a level > 2 is an
    explicit error — the exactness proof above covers at most two levels,
    so deeper recursion would run UNPROVEN arithmetic."""
    if raw is None:
        return default
    raw = raw.strip()
    if not raw:
        return default
    try:
        level = int(raw)
    except ValueError:
        return default
    if level < 0:
        return default
    if level > 2:
        raise ValueError(
            "COCONUT_PALLAS_KARATSUBA=%d unsupported: the exactness proof "
            "covers at most two levels (use 0, 1, or 2)" % level
        )
    return level


_KARATSUBA = _parse_karatsuba(os.environ.get("COCONUT_PALLAS_KARATSUBA"))
_HALF = NLIMBS // 2  # 26


def _tree(terms):  # pairwise tree: log depth for VPU ILP
    while len(terms) > 1:
        nxt = [terms[k] + terms[k + 1] for k in range(0, len(terms) - 1, 2)]
        if len(terms) % 2:
            nxt.append(terms[-1])
        terms = nxt
    return terms[0]


def _school_comb(x, y, n, out_len):
    """n-limb VPU comb schoolbook: shift-align the [n, n, TN] outer
    product's rows and tree-sum them into 2n-1 coefficients. Row i
    contributes to coefficients [i, i+n): rows split into a low half
    t[0:n) and a high half t[n:2n-1) so no term pads to the full height.
    out_len < 2n-1 truncates AFTER the sum (dropped terms belong to
    limbs >= n and must not alias into the kept ones)."""
    tn = x.shape[1]
    outer = x[:, None, :] * y[None, :, :]  # [n, n, TN]
    lo_terms, hi_terms = [], []
    for i in range(n):
        row = outer[i]
        if i == 0:
            lo_terms.append(row)
            continue
        lo_terms.append(
            jnp.concatenate(
                [jnp.zeros((i, tn), x.dtype), row[: n - i]], axis=0
            )
        )
        hi_terms.append(
            jnp.concatenate(
                [row[n - i :], jnp.zeros((n - 1 - i, tn), x.dtype)]
                if i < n - 1
                else [row[n - i :]],
                axis=0,
            )
        )
    if out_len <= n:  # REDC's m-step: the high half is discarded
        return _tree(lo_terms)[:out_len]
    t = jnp.concatenate([_tree(lo_terms), _tree(hi_terms)], axis=0)
    return t[:out_len]


def _kara_full(x, y, n, levels):
    """Full [2n-1] coefficient product of n-limb operands via `levels` of
    Karatsuba recursion (0 = plain comb schoolbook). Requires n even at
    every recursion step; assembly order matches the exactness proof in
    the _KARATSUBA note (z0 + z1 first, then + z2)."""
    if levels <= 0 or n % 2:
        return _school_comb(x, y, n, 2 * n - 1)
    tn = x.shape[1]
    half = n // 2
    x0, x1 = x[:half], x[half:]
    y0, y1 = y[:half], y[half:]
    z0 = _kara_full(x0, y0, half, levels - 1)  # [2*half-1] coeffs 0..
    z2 = _kara_full(x1, y1, half, levels - 1)  # -> offset 2*half
    mid = _kara_full(x0 + x1, y0 + y1, half, levels - 1)
    z1 = mid - z0 - z2  # -> offset half
    out_len = 2 * n - 1
    zpad = lambda k: jnp.zeros((k, tn), x.dtype)
    return (
        jnp.concatenate([z0, zpad(out_len - (2 * half - 1))], axis=0)
        + jnp.concatenate(
            [zpad(half), z1, zpad(out_len - half - (2 * half - 1))], axis=0
        )
        + jnp.concatenate([zpad(2 * half), z2], axis=0)
    )


def _school_vpu(x, y, out_len, karatsuba=None):
    """The kernel's full limb product: plain comb schoolbook, or
    `karatsuba` levels of recursion on the full-width products (see the
    _KARATSUBA note). Module-level (pure jnp on [limbs, lanes] arrays) so
    CPU differential tests can execute the exact assembly the TPU kernel
    runs."""
    if karatsuba is None:
        karatsuba = _KARATSUBA
    if not (karatsuba and out_len == _OUT2):
        return _school_comb(x, y, NLIMBS, out_len)
    return _kara_full(x, y, NLIMBS, int(karatsuba))


def _mul_kernel(a_ref, b_ref, band_ref, np_ref, p_ref, out_ref):
    a = _norm(a_ref[:], 2)  # [52, TN], |limbs| <= 132
    b = _norm(b_ref[:], 2)

    def school(x, y, out_len):
        if _VPU:
            return _school_vpu(x, y, out_len)
        # outer[i, j, :] = x[i, :] * y[j, :] -> band-sum over i + j == k
        outer = x[:, None, :] * y[None, :, :]
        flat = outer.reshape(NLIMBS * NLIMBS, x.shape[1])
        band = band_ref[:out_len, :]
        if _I8:
            flat_i = flat.astype(jnp.int32)
            hi_i = (flat_i + 128) >> 8
            lo_i = flat_i - (hi_i << 8)
            acc_lo = jax.lax.dot_general(
                band.astype(jnp.int8),
                lo_i.astype(jnp.int8),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            acc_hi = jax.lax.dot_general(
                band.astype(jnp.int8),
                hi_i.astype(jnp.int8),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            return (acc_lo + acc_hi * 256).astype(jnp.float32)
        hi = jnp.floor((flat + 128.0) * _INV_BASE)
        lo = flat - hi * _BASE
        acc_lo = jax.lax.dot_general(
            band,
            lo.astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_hi = jax.lax.dot_general(
            band,
            hi.astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc_lo + acc_hi * _BASE

    t = school(a, b, _OUT2)  # [103, TN]
    tlo = _norm(t[:NLIMBS], 3)  # t mod 2^416 (truncation intended)
    nprime = jnp.broadcast_to(np_ref[:], a.shape)
    m = _norm(school(tlo, nprime, NLIMBS), 3)
    pcol = jnp.broadcast_to(p_ref[:], a.shape)
    w = t + school(m, pcol, _OUT2)  # = t + m*p
    lo52 = _norm(_ext(w[:NLIMBS], 3), 3)  # limbs 0..51 -> 0, carry above
    hi = _ext(w[NLIMBS:], 1)  # 51 -> 52 limbs
    hi = jnp.concatenate(
        [hi[:3] + lo52[NLIMBS : NLIMBS + 3], hi[3:]], axis=0
    )
    out_ref[:] = _norm(hi, 3)


@functools.partial(jax.jit, static_argnames=("nblocks", "interpret"))
def _mul_flat(at, bt, nblocks, interpret=False):
    """at, bt: f32 [52, nblocks*TN] transposed operands -> [52, n] product.
    interpret=True runs the kernel through the Pallas interpreter (any
    backend) — the CPU differential-test hook for this TPU-only path.

    Jitted so that a fused program traces the kernel body once per lane
    width: `pallas_call` re-traces its kernel on every call, and the
    per-credential verifier makes ~500 multiplies (~0.4 s of tracing
    each); nested jit caches the trace and lowers one shared function."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if _VPU:
        # band matrix unused by the VPU comb: ship a 1x1 dummy instead of
        # copying ~557 KB HBM->VMEM per launch
        band = jnp.zeros((1, 128), jnp.bfloat16)
        band_shape = (1, 128)
    else:
        band = jnp.asarray(_BAND_T_NP, dtype=jnp.bfloat16)
        band_shape = (_OUT2, NLIMBS * NLIMBS)
    return pl.pallas_call(
        _mul_kernel,
        out_shape=jax.ShapeDtypeStruct((NLIMBS, nblocks * TN), jnp.float32),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec(
                (NLIMBS, TN), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (NLIMBS, TN), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                band_shape,
                lambda i: (0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (NLIMBS, 1), lambda i: (0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (NLIMBS, 1), lambda i: (0, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (NLIMBS, TN), lambda i: (0, i), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(
        at,
        bt,
        band,
        jnp.asarray(_NPRIME_COL),
        jnp.asarray(_P_COL),
    )


_ENABLED = None


def enabled():
    """Pallas path active? auto: only on a real TPU backend."""
    global _ENABLED
    if _ENABLED is None:
        flag = os.environ.get("COCONUT_FP_PALLAS", "auto")
        if flag == "auto":
            from . import on_tpu

            _ENABLED = on_tpu()
        else:
            _ENABLED = flag == "1"
    return _ENABLED


def mul(a, b, interpret=False):
    """Drop-in fused replacement for fp.mul on TPU: same element classes,
    bit-identical results. Flattens leading dims, pads lanes to TN, runs
    the transposed Pallas kernel, restores shape. interpret=True executes
    the kernel via the Pallas interpreter on any backend (tests only)."""
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape).reshape(-1, NLIMBS)
    b = jnp.broadcast_to(b, shape).reshape(-1, NLIMBS)
    n = a.shape[0]
    nblocks = -(-n // TN)
    pad = nblocks * TN - n
    if pad:
        zpad = jnp.zeros((pad, NLIMBS), jnp.float32)
        a = jnp.concatenate([a, zpad], axis=0)
        b = jnp.concatenate([b, zpad], axis=0)
    out = _mul_flat(a.T, b.T, nblocks, interpret=interpret).T
    if pad:
        out = out[:n]
    return out.reshape(shape)
