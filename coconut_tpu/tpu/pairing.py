"""Batched optimal-ate pairing: multi-Miller loop + final exponentiation.

Blueprint: `ops.pairing.miller_loop_projective` / `final_exp_chain` — the
same homogeneous twist coordinates, line coefficients, and x-power chain, so
post-final-exp GT values decode bit-identical to the spec (the line scalings
lie in the Fp4 subfield and are killed by the final exponentiation; spec
pairing.py docstring).

Shapes: a "pair set" has G1 points [..., ] and twist points as Fp2 pytrees
with the same leading dims. The Miller loop and the final exponentiation's
x-power chain both follow the static |BLS_X| bit schedule with no select:
loops of the zero-bit step (doubling, squaring) between the 5 set bits
after the leading one, and the set-bit step (add, multiply) only at those
bits. Identity inputs are handled with validity masks exactly like the
spec's `None` convention (miller factor = 1).
"""

import jax.numpy as jnp
from jax import lax

from ..ops.fields import BLS_X
from . import tower as tw

# Segment decomposition of the static |BLS_X| bit schedule (msb first,
# leading bit dropped): |BLS_X| has only 5 set bits after the leading one,
# so instead of computing the set-bit step on every iteration and
# select-masking it away (~58 of 63 thrown away), the Miller loop and
# _pow_x_abs run loops of the zero-bit step between the STATIC set-bit
# positions and the set-bit step only at them. _SEG_ZEROS[i] = number of
# zero-bit steps before the i-th set bit; _TRAILING = zero-bit steps after
# the last set bit.
_SEG_ZEROS, _TRAILING = [], 0
for _b in [int(b) for b in bin(-BLS_X)[2:]][1:]:
    if _b:
        _SEG_ZEROS.append(_TRAILING)
        _TRAILING = 0
    else:
        _TRAILING += 1
# Squarings from one set bit to the next, the set bit's own included.
_SEG_SQUARES = [nz + 1 for nz in _SEG_ZEROS]


def _proj_double_step(T):
    """Mirror of ops.pairing.proj_double_step on Fp2 limb pytrees."""
    X, Y, Z = T
    A = tw.fp2_sq(X)
    B = tw.fp2_sq(Y)
    C = tw.fp2_sq(Z)
    D = tw.fp2_mul(tw.fp2_mul(X, B), Z)
    F = tw.fp2_sub(tw.fp2_mul_small(tw.fp2_sq(A), 9), tw.fp2_mul_small(D, 8))
    YZ = tw.fp2_mul(Y, Z)
    X3 = tw.fp2_mul(tw.fp2_mul_small(YZ, 2), F)
    Y3 = tw.fp2_sub(
        tw.fp2_mul(tw.fp2_mul_small(A, 3), tw.fp2_sub(tw.fp2_mul_small(D, 4), F)),
        tw.fp2_mul_small(tw.fp2_mul(tw.fp2_sq(B), C), 8),
    )
    t = tw.fp2_mul_small(YZ, 2)
    Z3 = tw.fp2_mul(tw.fp2_sq(t), t)
    lA = tw.fp2_sub(
        tw.fp2_mul(X, A), tw.fp2_mul_small(tw.fp2_mul_xi(tw.fp2_mul(Z, C)), 8)
    )
    lB = tw.fp2_neg(tw.fp2_mul_small(tw.fp2_mul(A, Z), 3))
    lC = tw.fp2_mul_small(tw.fp2_mul(Y, C), 2)
    return (X3, Y3, Z3), (lA, lB, lC)


def _proj_add_step(T, q):
    """Mirror of ops.pairing.proj_add_step; q = (x2, y2) affine twist."""
    X, Y, Z = T
    x2, y2 = q
    theta = tw.fp2_sub(Y, tw.fp2_mul(y2, Z))
    lam = tw.fp2_sub(X, tw.fp2_mul(x2, Z))
    lam2 = tw.fp2_sq(lam)
    lam3 = tw.fp2_mul(lam2, lam)
    H = tw.fp2_sub(
        tw.fp2_mul(tw.fp2_sq(theta), Z),
        tw.fp2_mul(lam2, tw.fp2_add(X, tw.fp2_mul(x2, Z))),
    )
    X3 = tw.fp2_mul(lam, H)
    Y3 = tw.fp2_sub(
        tw.fp2_mul(theta, tw.fp2_sub(tw.fp2_mul(lam2, X), H)),
        tw.fp2_mul(lam3, Y),
    )
    Z3 = tw.fp2_mul(lam3, Z)
    lA = tw.fp2_sub(tw.fp2_mul(theta, x2), tw.fp2_mul(lam, y2))
    lB = tw.fp2_neg(theta)
    lC = lam
    return (X3, Y3, Z3), (lA, lB, lC)


def _eval_line(line, px, py):
    """(lA, lB, lC) -> (lA, lB*px, lC*py): the sparse element for mul_line."""
    lA, lB, lC = line
    return (lA, tw.fp2_mul_fp(lB, px), tw.fp2_mul_fp(lC, py))


def multi_miller_loop(px, py, qx, qy, valid):
    """Product of Miller loops over the trailing "pairs" axis folded into the
    leading batch dims.

    px, py: Fp limb arrays [...]; qx, qy: Fp2 pytrees (affine twist);
    valid: bool [...] — False lanes contribute the factor 1 (the spec's
    `None` -> FP12_ONE convention).
    Returns an Fp12 pytree with the same leading dims [...].

    PAD-LANE CONTRACT (pinned by tests/test_ops.py's pad-lane
    regressions; the RLC batch verifier of PR 16 leans on it): a lane
    with valid=False contributes EXACTLY the GT identity to the product
    — every one of its line evaluations is masked to (1, 0, 0) inside
    _mask_line, so its point coordinates may be garbage (zeros,
    off-curve, aliased) without perturbing the other lanes. All-pad pair
    sets therefore fold to FP12_ONE, and ragged batches padded with
    valid=0 lanes return bit-identical products to their unpadded
    prefix, regardless of where the pad lanes sit (trailing or
    interleaved)."""
    shape = valid.shape
    T0 = (qx, qy, tw.fp2_ones(shape))
    f0 = tw.fp12_ones(shape)

    def dbl_body(carry, _):
        f, T = carry
        T, line = _proj_double_step(T)
        f = tw.mul_line(tw.fp12_sq(f), _eval_line(line, px, py))
        return (f, T), None

    carry = (f0, T0)
    for nz in _SEG_ZEROS:
        if nz:
            carry, _ = lax.scan(dbl_body, carry, None, length=nz)
        # the set-bit step, unrolled: double + add, no masks
        (carry, _) = dbl_body(carry, None)
        f, T = carry
        T, la = _proj_add_step(T, (qx, qy))
        f = tw.mul_line(f, _eval_line(la, px, py))
        carry = (f, T)
    if _TRAILING:
        carry, _ = lax.scan(dbl_body, carry, None, length=_TRAILING)
    f, _ = carry
    f = tw.fp12_conj(f)  # x < 0
    f = tw.fp12_select(valid, f, tw.fp12_ones(shape))
    # fold the pairs axis (last leading dim) by multiplication
    npairs = shape[-1]
    out = _index_fp12(f, 0)
    for i in range(1, npairs):
        out = tw.fp12_mul(out, _index_fp12(f, i))
    return out


def _index_fp12(f, i):
    import jax

    return jax.tree_util.tree_map(lambda t: t[..., i, :], f)


def _mask_line(line, valid):
    """Select the identity line (1, 0, 0) on invalid lanes so a dead pair
    contributes the factor 1 to the merged accumulator (the generic loop's
    post-hoc fp12 select, pushed down to the sparse element). This is the
    mechanism behind multi_miller_loop's pad-lane contract: masking every
    LINE (rather than the final fp12) keeps a valid=0 lane's garbage
    coordinates out of the product at every step, not just at the end."""
    lA, lB, lC = line
    one = tw.fp2_ones(valid.shape)
    zero = tw.fp2_zeros(valid.shape)
    return (
        tw.fp2_select(valid, lA, one),
        tw.fp2_select(valid, lB, zero),
        tw.fp2_select(valid, lC, zero),
    )


def miller_two_pairs_shared_q2(
    px1, py1, qx1, qy1, valid1, px2, py2, q2x, q2y, valid2
):
    """Miller product of exactly two pairs per credential with pair 2's
    TWIST point shared across the batch — the verify shape
    e(sigma_1_i, acc_i) * e(-sigma_2_i, g_tilde) in the G1 assignment.

    Two structural wins over the generic [B, 2] pair-set loop:
      - the fp12 accumulator is [B]-shaped (one per credential, both
        pairs' lines multiplied in per step) instead of [B, 2] + final
        fold — halving the dominant fp12_sq/mul_line work;
      - pair 2's T-ladder and line COEFFICIENTS run once at scalar shape
        (g_tilde is one point); only the two line evaluations at
        (px2_i, py2_i) are per-credential.
    Dead pairs contribute the factor 1 via line masking (_mask_line)."""
    shape = valid1.shape
    T1 = (qx1, qy1, tw.fp2_ones(shape))
    T2 = (q2x, q2y, tw.fp2_ones(()))
    f0 = tw.fp12_ones(shape)

    def fuse(f, l1, l2):
        f = tw.mul_line(f, _mask_line(_eval_line(l1, px1, py1), valid1))
        return tw.mul_line(f, _mask_line(_eval_line(l2, px2, py2), valid2))

    def dbl_body(carry, _):
        f, T1, T2 = carry
        T1, l1 = _proj_double_step(T1)
        T2, l2 = _proj_double_step(T2)
        f = fuse(tw.fp12_sq(f), l1, l2)
        return (f, T1, T2), None

    carry = (f0, T1, T2)
    for nz in _SEG_ZEROS:
        if nz:
            carry, _ = lax.scan(dbl_body, carry, None, length=nz)
        carry, _ = dbl_body(carry, None)
        f, T1, T2 = carry
        T1, l1 = _proj_add_step(T1, (qx1, qy1))
        T2, l2 = _proj_add_step(T2, (q2x, q2y))
        carry = (fuse(f, l1, l2), T1, T2)
    if _TRAILING:
        carry, _ = lax.scan(dbl_body, carry, None, length=_TRAILING)
    return tw.fp12_conj(carry[0])  # x < 0


def _pow_x_abs(m):
    """m^{|BLS_X|} in the cyclotomic subgroup, on the Miller loop's segment
    schedule: the leading bit is the initial acc = m, each zero bit one
    squaring and each of the 5 set bits a square-then-multiply by m — 63
    squarings and 5 multiplies, the same operations in the same order as a
    square-and-multiply over every bit. One scan step per set bit runs its
    _SEG_SQUARES[i] squarings (a loop of dynamic length) and its multiply,
    so the chain compiles to one squaring body, one multiply and the
    _TRAILING squarings however many set bits there are. (Unrolling the
    set-bit steps as the Miller loop does puts 25 multiplies and 25 loops
    into final_exp; on a TPU v5e that left ~53 ms of idle between ops in
    each 1,024-lane fused verify, and the program took a third longer to
    load from the compile cache.) Squarings use the Granger-Scott
    cyclotomic form (tw.fp12_cyclo_sq, 30 base lanes vs fp12_sq's 36) —
    sound because every value in the chain is a power of the cyclotomic
    input."""

    def sq(_, acc):
        return tw.fp12_cyclo_sq(acc)

    def set_bit(acc, n):
        return tw.fp12_mul(lax.fori_loop(0, n, sq, acc), m), None

    acc, _ = lax.scan(set_bit, m, jnp.array(_SEG_SQUARES, dtype=jnp.int32))
    return lax.fori_loop(0, _TRAILING, sq, acc)


def _pow_x_neg(m):
    """m^{BLS_X} (x negative): conj of m^{|x|}."""
    return tw.fp12_conj(_pow_x_abs(m))


def final_exp(f):
    """Mirror of ops.pairing.final_exp_chain (identical GT values)."""
    m = tw.fp12_mul(tw.fp12_conj(f), tw.fp12_inv(f))
    m = tw.fp12_mul(tw.fp12_frobenius2(m), m)
    t0 = tw.fp12_mul(_pow_x_neg(m), tw.fp12_conj(m))
    t1 = tw.fp12_mul(_pow_x_neg(t0), tw.fp12_conj(t0))
    t2 = tw.fp12_mul(_pow_x_neg(t1), tw.fp12_frobenius(t1))
    t3 = tw.fp12_mul(
        tw.fp12_mul(_pow_x_neg(_pow_x_neg(t2)), tw.fp12_frobenius2(t2)),
        tw.fp12_conj(t2),
    )
    return tw.fp12_mul(t3, tw.fp12_mul(tw.fp12_cyclo_sq(m), m))


def pairing_product_is_one(px, py, qx, qy, valid):
    """[..., npairs] pair sets -> bool [...]: prod e(P_i, Q_i) == 1.

    Inherits multi_miller_loop's pad-lane contract: valid=0 pairs are
    identity factors, so an all-pad set answers True (empty product) and
    pad lanes never change a batch's verdict — the invariant the PR-16
    combined verifier's clone-first power-of-two padding relies on."""
    f = multi_miller_loop(px, py, qx, qy, valid)
    return tw.fp12_is_one(final_exp(f))
