"""Extension-field tower Fp2/Fp6/Fp12 over the limb Fp — batched, as pytrees.

Mirrors `coconut_tpu.ops.fields` exactly (same tower construction
u^2 = -1, v^3 = xi = u+1, w^2 = v; same Karatsuba/complex formulas) so decoded
results are bit-identical to the spec. Elements are tuples of Fp limb arrays,
which makes every value a JAX pytree that flows through scan/jit unchanged.

Compile-size/MXU design: every tower multiply bottoms out in ONE stacked
base-field multiply (`fp.mul_stack`) — fp2_mul stacks its 3 Karatsuba
products, fp6_mul stacks its 6 fp2 products (-> 18 base lanes), fp12_mul its
3 fp6 products (-> 54 base lanes). One Fp12 multiply is therefore a single
[.., 54, 52] MXU contraction instead of 54 separate multiplies: ~50x fewer
HLO ops (XLA compile time) and far better systolic-array occupancy.

Also provides the sparse Fp12 x line multiplication for the Miller loop
(`mul_line`): lines have only the (w^0, w^2, w^3) components (see
`ops.pairing.line_to_fp12`), 15 Fp2 products stacked into one multiply.
"""

import jax
import jax.numpy as jnp

from ..ops import fields as F
from . import fp
from .limbs import NLIMBS, fp_encode

# --- codecs (host-side) -----------------------------------------------------


def encode_batch(elems, dtype=None):
    """List of same-structure spec elements (ints / nested tuples) ->
    pytree of Montgomery limb arrays with leading batch dim. dtype
    converts in NUMPY before the device transfer (int16 is the halved
    point-upload wire format — balanced limbs are exact |v| <= 132; the
    consuming kernels cast back to f32 at entry)."""
    first = elems[0]
    if isinstance(first, tuple):
        return tuple(
            encode_batch([e[i] for e in elems], dtype=dtype)
            for i in range(len(first))
        )
    from .limbs import fp_encode_batch

    arr = fp_encode_batch(elems)
    if dtype is not None:
        arr = arr.astype(dtype)
    return jnp.asarray(arr)


def encode_raw_batch(elems):
    """Raw-wire variant of encode_batch: pytree of np.uint8[n, 48] raw
    canonical base-256 digits, NOT in the Montgomery domain. The consuming
    kernels convert at entry via fp.to_mont (one on-device Montgomery
    multiply by R^2 — see backend._pts_f32), which keeps the host encode
    down to byte framing and the upload at 48 bytes per Fp."""
    first = elems[0]
    if isinstance(first, tuple):
        return tuple(
            encode_raw_batch([e[i] for e in elems]) for i in range(len(first))
        )
    from .limbs import fp_encode_raw_batch

    return jnp.asarray(fp_encode_raw_batch(elems))


def decode_batch(tree):
    """Inverse of encode_batch: pytree of limb arrays -> list of spec
    elements (canonical ints / nested tuples)."""
    if isinstance(tree, tuple):
        parts = [decode_batch(t) for t in tree]
        return [tuple(p[i] for p in parts) for i in range(len(parts[0]))]
    import numpy as np

    from .limbs import fp_decode_batch

    return fp_decode_batch(np.asarray(tree))


# --- stack/unstack helpers ---------------------------------------------------


def _bcast(elems):
    return jnp.broadcast_arrays(*elems)


def _stack2(elems):
    """[(c0, c1), ...] fp2s -> stacked fp2 with a new [S] axis before limbs."""
    return (
        jnp.stack(_bcast([e[0] for e in elems]), axis=-2),
        jnp.stack(_bcast([e[1] for e in elems]), axis=-2),
    )


def _unstack2(t, n):
    return [(t[0][..., i, :], t[1][..., i, :]) for i in range(n)]


def _stack6(elems):
    """[(c0, c1, c2), ...] fp6s -> stacked fp6 (components are stacked fp2s)."""
    return tuple(_stack2([e[i] for e in elems]) for i in range(3))


def _unstack6(t, n):
    parts = [_unstack2(t[i], n) for i in range(3)]
    return [(parts[0][i], parts[1][i], parts[2][i]) for i in range(n)]


# --- Fp2 --------------------------------------------------------------------


def fp2_encode_const(c):
    """Spec Fp2 (int pair) -> Montgomery limb constant pytree."""
    return (jnp.asarray(fp_encode(c[0])), jnp.asarray(fp_encode(c[1])))


def fp2_add(a, b):
    return (fp.add(a[0], b[0]), fp.add(a[1], b[1]))


def fp2_sub(a, b):
    return (fp.sub(a[0], b[0]), fp.sub(a[1], b[1]))


def fp2_neg(a):
    return (fp.neg(a[0]), fp.neg(a[1]))


def fp2_mul(a, b):
    # Karatsuba: one stacked mul of [a0*b0, a1*b1, (a0+a1)(b0+b1)]
    t0, t1, t2 = fp.mul_stack(
        [a[0], a[1], fp.add(a[0], a[1])],
        [b[0], b[1], fp.add(b[0], b[1])],
    )
    return (fp.sub(t0, t1), fp.sub(fp.sub(t2, t0), t1))


def fp2_sq(a):
    # (a0+a1)(a0-a1), 2*a0*a1 — one stacked mul
    t0, t1 = fp.mul_stack(
        [fp.add(a[0], a[1]), a[0]],
        [fp.sub(a[0], a[1]), a[1]],
    )
    return (t0, fp.add(t1, t1))


def fp2_mul_fp(a, s):
    t0, t1 = fp.mul_stack([a[0], a[1]], [s, s])
    return (t0, t1)


def fp2_mul_small(a, k):
    return (fp.mul_small(a[0], k), fp.mul_small(a[1], k))


def fp2_conj(a):
    return (a[0], fp.neg(a[1]))


def fp2_mul_xi(a):
    """x (u+1): (c0 - c1, c0 + c1)."""
    return (fp.sub(a[0], a[1]), fp.add(a[0], a[1]))


def fp2_inv(a):
    s0, s1 = fp.mul_stack([a[0], a[1]], [a[0], a[1]])
    ninv = fp.inv(fp.add(s0, s1))
    t0, t1 = fp.mul_stack([a[0], a[1]], [ninv, ninv])
    return (t0, fp.neg(t1))


def fp2_is_zero(a):
    z0, z1 = fp.is_zero_many([a[0], a[1]])
    return z0 & z1


def fp2_eq(a, b):
    z0, z1 = fp.is_zero_many([fp.sub(a[0], b[0]), fp.sub(a[1], b[1])])
    return z0 & z1


def fp2_select(mask, a, b):
    return (fp.select(mask, a[0], b[0]), fp.select(mask, a[1], b[1]))


def fp2_zeros(shape=()):
    z = jnp.zeros(tuple(shape) + (NLIMBS,), dtype=jnp.float32)
    return (z, z)


def fp2_ones(shape=()):
    return (
        fp.ones_mont(shape),
        jnp.zeros(tuple(shape) + (NLIMBS,), dtype=jnp.float32),
    )


# --- Fp6 --------------------------------------------------------------------


def fp6_add(a, b):
    return tuple(fp2_add(x, y) for x, y in zip(a, b))


def fp6_sub(a, b):
    return tuple(fp2_sub(x, y) for x, y in zip(a, b))


def fp6_neg(a):
    return tuple(fp2_neg(x) for x in a)


def fp6_mul(a, b):
    """Toom-style 6-product fp6 multiply, all products in ONE stacked
    fp2_mul (18 base lanes): t_i = a_i b_i, plus the three cross sums."""
    a0, a1, a2 = a
    b0, b1, b2 = b
    prods = fp2_mul(
        _stack2(
            [a0, a1, a2, fp2_add(a1, a2), fp2_add(a0, a1), fp2_add(a0, a2)]
        ),
        _stack2(
            [b0, b1, b2, fp2_add(b1, b2), fp2_add(b0, b1), fp2_add(b0, b2)]
        ),
    )
    t0, t1, t2, t12, t01, t02 = _unstack2(prods, 6)
    c0 = fp2_add(t0, fp2_mul_xi(fp2_sub(fp2_sub(t12, t1), t2)))
    c1 = fp2_add(fp2_sub(fp2_sub(t01, t0), t1), fp2_mul_xi(t2))
    c2 = fp2_add(fp2_sub(fp2_sub(t02, t0), t2), t1)
    return (c0, c1, c2)


def fp6_mul_by_v(a):
    return (fp2_mul_xi(a[2]), a[0], a[1])


def fp6_inv(a):
    a0, a1, a2 = a
    # six products in one stack: a0^2, a1*a2, a2^2, a0*a1, a1^2, a0*a2
    prods = fp2_mul(
        _stack2([a0, a1, a2, a0, a1, a0]), _stack2([a0, a2, a2, a1, a1, a2])
    )
    s00, s12, s22, s01, s11, s02 = _unstack2(prods, 6)
    c0 = fp2_sub(s00, fp2_mul_xi(s12))
    c1 = fp2_sub(fp2_mul_xi(s22), s01)
    c2 = fp2_sub(s11, s02)
    # t = xi*(a2 c1 + a1 c2) + a0 c0 — three products in one stack
    prods2 = fp2_mul(_stack2([a2, a1, a0]), _stack2([c1, c2, c0]))
    u1, u2, u0 = _unstack2(prods2, 3)
    t = fp2_add(fp2_mul_xi(fp2_add(u1, u2)), u0)
    tinv = fp2_inv(t)
    prods3 = fp2_mul(
        _stack2([c0, c1, c2]), _stack2([tinv, tinv, tinv])
    )
    r0, r1, r2 = _unstack2(prods3, 3)
    return (r0, r1, r2)


def fp6_select(mask, a, b):
    return tuple(fp2_select(mask, x, y) for x, y in zip(a, b))


def fp6_zeros(shape=()):
    z = fp2_zeros(shape)
    return (z, z, z)


def fp6_ones(shape=()):
    return (fp2_ones(shape), fp2_zeros(shape), fp2_zeros(shape))


# --- Fp12 -------------------------------------------------------------------


def fp12_mul(a, b):
    """Karatsuba over w: 3 fp6 products in ONE stacked fp6_mul (54 base
    lanes -> a single MXU contraction)."""
    a0, a1 = a
    b0, b1 = b
    prods = fp6_mul(
        _stack6([a0, a1, fp6_add(a0, a1)]),
        _stack6([b0, b1, fp6_add(b0, b1)]),
    )
    t0, t1, t2 = _unstack6(prods, 3)
    c0 = fp6_add(t0, fp6_mul_by_v(t1))
    c1 = fp6_sub(fp6_sub(t2, t0), t1)
    return (c0, c1)


def fp12_sq(a):
    a0, a1 = a
    # t = a0*a1 and s = (a0+a1)(a0 + v*a1) in one stacked fp6_mul
    prods = fp6_mul(
        _stack6([a0, fp6_add(a0, a1)]),
        _stack6([a1, fp6_add(a0, fp6_mul_by_v(a1))]),
    )
    t, s = _unstack6(prods, 2)
    c0 = fp6_sub(fp6_sub(s, t), fp6_mul_by_v(t))
    c1 = fp6_add(t, t)
    return (c0, c1)


def fp12_cyclo_sq(a):
    """Granger–Scott cyclotomic squaring — valid ONLY for elements of the
    cyclotomic subgroup G_{Phi12}(p) (everything after the easy part of the
    final exponentiation). For such elements the square decomposes into
    three Fp4 squarings over the pairs (z0,z1)=(c00,c11), (z2,z3)=
    (c10,c02), (z4,z5)=(c01,c12) with Fp4 = Fp2[s]/(s^2 - xi):

      fp4_sq(x, y) = (x^2 + xi y^2, 2xy)           [3 fp2 squarings]
      z0' = 3A0 - 2z0   z1' = 3B0 + 2z1            [A_i, B_i = fp4 parts]
      z4' = 3A1 - 2z4   z5' = 3B1 + 2z5
      z2' = 3 xi B2 + 2z2   z3' = 3A2 - 2z3

    Cost: 9 fp2 squarings (18 base products) + 12 compress muls, all in ONE
    stacked contraction = 30 base lanes, vs fp12_sq's 36 — and unlike
    fp12_sq the additive tail reuses the INPUT components, so each input
    component is compressed (one Montgomery mul by 1) to keep the lazy
    value/limb class bounded across unbounded squaring chains (the loops in
    pairing._pow_x_abs run up to 32 consecutive squarings with no
    intervening normalizing multiply):
      output limb weight <= 3*(3*132) + 2*132 = 1452 << L_LAZY = 2^17,
      output |value| <= 3*2p + 2*0.66p < 8p << V_LAZY = 1024p,
    a fixed point of the recursion (outputs are built only from fresh mul
    outputs and compressed inputs)."""
    (c00, c01, c02), (c10, c11, c12) = a
    pairs = [(c00, c11), (c10, c02), (c01, c12)]
    lhs, rhs = [], []
    for x, y in pairs:
        for e in (x, y, fp2_add(x, y)):
            # fp2_sq(e) = ((e0+e1)(e0-e1), 2 e0 e1): two base products
            lhs += [fp.add(e[0], e[1]), e[0]]
            rhs += [fp.sub(e[0], e[1]), e[1]]
    one = fp.ones_mont()
    for comp in (c00, c11, c10, c02, c01, c12):
        lhs += [comp[0], comp[1]]
        rhs += [one, one]
    prods = fp.mul_stack(lhs, rhs)
    sq = []  # the 9 fp2 squares, pair-major
    for i in range(9):
        sq.append((prods[2 * i], fp.add(prods[2 * i + 1], prods[2 * i + 1])))
    cc = []  # compressed input components, in the order fed above
    for j in range(6):
        cc.append((prods[18 + 2 * j], prods[18 + 2 * j + 1]))
    z0c, z1c, z2c, z3c, z4c, z5c = cc  # (c00, c11, c10, c02, c01, c12)

    def fp4_parts(i):
        tx, ty, ts = sq[3 * i], sq[3 * i + 1], sq[3 * i + 2]
        A = fp2_add(tx, fp2_mul_xi(ty))
        B = fp2_sub(fp2_sub(ts, tx), ty)
        return A, B

    A0, B0 = fp4_parts(0)
    A1, B1 = fp4_parts(1)
    A2, B2 = fp4_parts(2)

    def t3m2(t, z):  # 3t - 2z
        return fp2_sub(fp2_mul_small(t, 3), fp2_mul_small(z, 2))

    def t3p2(t, z):  # 3t + 2z
        return fp2_add(fp2_mul_small(t, 3), fp2_mul_small(z, 2))

    z0p = t3m2(A0, z0c)
    z1p = t3p2(B0, z1c)
    z4p = t3m2(A1, z4c)
    z5p = t3p2(B1, z5c)
    z2p = t3p2(fp2_mul_xi(B2), z2c)
    z3p = t3m2(A2, z3c)
    return ((z0p, z4p, z3p), (z2p, z1p, z5p))


def fp12_conj(a):
    return (a[0], fp6_neg(a[1]))


def fp12_inv(a):
    a0, a1 = a
    prods = fp6_mul(_stack6([a0, a1]), _stack6([a0, a1]))
    s0, s1 = _unstack6(prods, 2)
    t = fp6_sub(s0, fp6_mul_by_v(s1))
    tinv = fp6_inv(t)
    prods2 = fp6_mul(_stack6([a0, a1]), _stack6([tinv, tinv]))
    r0, r1 = _unstack6(prods2, 2)
    return (r0, fp6_neg(r1))


def mul_line(f, line):
    """f * (lA + lB w^2 + lC w^3) — the Miller-loop sparse product.

    The line element is s = (s0, s1) with s0 = (lA, lB, 0), s1 = (0, lC, 0)
    (cf. ops.pairing.line_to_fp12). 15 Fp2 products in ONE stacked mul:
    6 for f0*(lA,lB), 3 for f1*lC, 6 for (f0+f1)*(lA, lB+lC)."""
    lA, lB, lC = line
    f0, f1 = f
    g = fp6_add(f0, f1)
    lBC = fp2_add(lB, lC)
    lhs = _stack2(
        [
            f0[0], f0[2], f0[1], f0[0], f0[2], f0[1],  # mul_by_01(f0, lA, lB)
            f1[2], f1[0], f1[1],                        # mul_by_1(f1, lC)
            g[0], g[2], g[1], g[0], g[2], g[1],         # mul_by_01(g, lA, lBC)
        ]
    )
    rhs = _stack2(
        [
            lA, lB, lA, lB, lA, lB,
            lC, lC, lC,
            lA, lBC, lA, lBC, lA, lBC,
        ]
    )
    p = _unstack2(fp2_mul(lhs, rhs), 15)
    # mul_by_01 structure: c0 = a0*s0 + xi*(a2*s1); c1 = a1*s0 + a0*s1;
    # c2 = a2*s0 + a1*s1 — regroup the products accordingly:
    t0 = (
        fp2_add(p[0], fp2_mul_xi(p[1])),
        fp2_add(p[2], p[3]),
        fp2_add(p[4], p[5]),
    )
    t1 = (fp2_mul_xi(p[6]), p[7], p[8])
    mixed = (
        fp2_add(p[9], fp2_mul_xi(p[10])),
        fp2_add(p[11], p[12]),
        fp2_add(p[13], p[14]),
    )
    c0 = fp6_add(t0, fp6_mul_by_v(t1))
    c1 = fp6_sub(fp6_sub(mixed, t0), t1)
    return (c0, c1)


# Frobenius coefficients from the spec, as Montgomery constants.
_G1C = [fp2_encode_const(c) for c in F._GAMMA1]
_G2C = [fp2_encode_const(c) for c in F._GAMMA2]


def fp12_frobenius(a):
    a0, a1 = a
    prods = fp2_mul(
        _stack2(
            [
                fp2_conj(a0[1]),
                fp2_conj(a0[2]),
                fp2_conj(a1[0]),
                fp2_conj(a1[1]),
                fp2_conj(a1[2]),
            ]
        ),
        _stack2([_G1C[2], _G1C[4], _G1C[1], _G1C[3], _G1C[5]]),
    )
    m01, m02, m10, m11, m12 = _unstack2(prods, 5)
    return ((fp2_conj(a0[0]), m01, m02), (m10, m11, m12))


def fp12_frobenius2(a):
    a0, a1 = a
    prods = fp2_mul(
        _stack2([a0[1], a0[2], a1[0], a1[1], a1[2]]),
        _stack2([_G2C[2], _G2C[4], _G2C[1], _G2C[3], _G2C[5]]),
    )
    m01, m02, m10, m11, m12 = _unstack2(prods, 5)
    return ((a0[0], m01, m02), (m10, m11, m12))


def fp12_select(mask, a, b):
    return tuple(fp6_select(mask, x, y) for x, y in zip(a, b))


def fp12_ones(shape=()):
    return (fp6_ones(shape), fp6_zeros(shape))


def fp12_is_one(a):
    """Exact componentwise test against the Montgomery one (values are
    redundant — the compress-based predicates do the exact mod-p
    comparison), all 12 compress-muls stacked into one contraction."""
    comps = jax.tree_util.tree_leaves(a)  # 12 Fp components, c0.c0.c0 first
    diffs = [fp.sub(comps[0], fp.ones_mont(comps[0].shape[:-1]))] + comps[1:]
    zs = fp.is_zero_many(diffs)
    bits = zs[0]
    for z in zs[1:]:
        bits = bits & z
    return bits
