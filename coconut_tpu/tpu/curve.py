"""Batched G1/G2 complete projective arithmetic and windowed MSMs.

The TPU equivalent of the reference's `multi_scalar_mul_const_time/_var_time`
call sites (signature.rs:157,424,427,465,513,521), re-designed for XLA:
points are pytrees of limb arrays and the MSM loops run over a static
window schedule with per-batch-element table gathers.

Point formulas are the Renes-Costello-Batina (2015) COMPLETE projective
addition/doubling for short-Weierstrass curves with a = 0. BLS12-381's
E(Fp) and its twist E'(Fp2) both have odd group order, so the formulas are
valid for EVERY pair of inputs including the identity (0 : 1 : 0) — no
branch predicates, no select masks, no embedded doubling in the hot path
(the previous Jacobian implementation spent ~60% of its HLO and runtime on
that edge-case machinery). Each formula's independent field products are
stacked into single MXU contractions (fl.mul_many): 12 products in 3
stacked multiplies per addition, 9 in 3 per doubling.

b3 = 3b: 12 for G1 (b = 4), 12*(1+u) for the twist (b' = 4(1+u)) — free
elementwise small-scalings in the lazy fp representation.

Only affine outputs are compared bit-for-bit against the spec
(`ops.curve.CurveOps`) — projective representatives are not canonical.

Field genericity: each function takes `fl`, a field namespace (the `fp`
module for G1 or the Fp2 shim below for G2), mirroring the spec's CurveOps
being generic over the coordinate field.
"""

import jax
import jax.numpy as jnp

from . import fp
from . import tower as tw


class _Fp2Field:
    """Adapter giving the tower's Fp2 the same surface as the fp module."""

    add = staticmethod(tw.fp2_add)
    sub = staticmethod(tw.fp2_sub)
    mul = staticmethod(tw.fp2_mul)
    sq = staticmethod(tw.fp2_sq)
    neg = staticmethod(tw.fp2_neg)
    inv = staticmethod(tw.fp2_inv)
    is_zero = staticmethod(tw.fp2_is_zero)
    eq = staticmethod(tw.fp2_eq)
    select = staticmethod(tw.fp2_select)
    zeros = staticmethod(tw.fp2_zeros)
    ones = staticmethod(tw.fp2_ones)

    @staticmethod
    def mul_small(a, k):
        return tw.fp2_mul_small(a, k)

    @staticmethod
    def mul_many(lhs, rhs):
        """Stack independent Fp2 products into one base-field contraction."""
        prods = tw.fp2_mul(tw._stack2(lhs), tw._stack2(rhs))
        return tw._unstack2(prods, len(lhs))

    @staticmethod
    def b3(t):
        # 3b' = 12(1+u): t*(1+u) is (c0-c1, c0+c1); then scale by 12 — all
        # elementwise lazy ops
        return tw.fp2_mul_small(tw.fp2_mul_xi(t), 12)


class _FpField:
    add = staticmethod(fp.add)
    sub = staticmethod(fp.sub)
    mul = staticmethod(fp.mul)
    sq = staticmethod(fp.sq)
    neg = staticmethod(fp.neg)
    inv = staticmethod(fp.inv)
    is_zero = staticmethod(fp.is_zero)
    eq = staticmethod(fp.eq)
    select = staticmethod(fp.select)
    mul_small = staticmethod(fp.mul_small)
    mul_many = staticmethod(fp.mul_stack)

    @staticmethod
    def b3(t):
        return fp.mul_small(t, 12)  # 3b = 12 (b = 4)

    @staticmethod
    def zeros(shape=()):
        from .limbs import NLIMBS

        return jnp.zeros(tuple(shape) + (NLIMBS,), dtype=jnp.float32)

    ones = staticmethod(fp.ones_mont)


FP = _FpField
FP2 = _Fp2Field


def jinfinity(fl, shape=()):
    """The projective identity (0 : 1 : 0)."""
    return (fl.zeros(shape), fl.ones(shape), fl.zeros(shape))


def jadd(fl, p, q):
    """Complete projective addition (RCB 2015 Alg. 7, a = 0): 12 products
    in 3 stacked multiplies, valid for all curve points incl. identity."""
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    t0, t1, t2, m3, m4, m5 = fl.mul_many(
        [X1, Y1, Z1, fl.add(X1, Y1), fl.add(Y1, Z1), fl.add(X1, Z1)],
        [X2, Y2, Z2, fl.add(X2, Y2), fl.add(Y2, Z2), fl.add(X2, Z2)],
    )
    t3 = fl.sub(fl.sub(m3, t0), t1)  # X1Y2 + X2Y1
    t4 = fl.sub(fl.sub(m4, t1), t2)  # Y1Z2 + Y2Z1
    t5 = fl.sub(fl.sub(m5, t0), t2)  # X1Z2 + X2Z1
    b3t2 = fl.b3(t2)
    y3 = fl.b3(t5)
    t0_3 = fl.add(fl.add(t0, t0), t0)  # 3X1X2
    z3s = fl.add(t1, b3t2)
    t1m = fl.sub(t1, b3t2)
    x3a, t2c, y3b, t1d, t0e, z3f = fl.mul_many(
        [t4, t3, y3, t1m, t0_3, z3s],
        [y3, t1m, t0_3, z3s, t3, t4],
    )
    return (
        fl.sub(t2c, x3a),
        fl.add(t1d, y3b),
        fl.add(z3f, t0e),
    )


def jdouble(fl, p):
    """Complete projective doubling (RCB 2015 Alg. 9, a = 0): 9 products
    in 3 stacked multiplies."""
    X, Y, Z = p
    a_, b_, c_, xy = fl.mul_many([Y, Y, Z, X], [Y, Z, Z, Y])
    cb = fl.b3(c_)
    e8 = fl.mul_small(a_, 8)
    y3s = fl.add(a_, cb)
    t0m = fl.sub(a_, fl.mul_small(cb, 3))
    x3p, z3, y2m, x3m = fl.mul_many([cb, b_, t0m, t0m], [e8, e8, y3s, xy])
    return (fl.add(x3m, x3m), fl.add(x3p, y2m), z3)


def to_affine(fl, p):
    """Projective -> (x, y, is_infinity-mask). Uses one field inversion."""
    X, Y, Z = p
    zinv = fl.inv(Z)
    return fl.mul(X, zinv), fl.mul(Y, zinv), fl.is_zero(Z)


def affine_to_jacobian(fl, x, y, inf):
    """Affine pytree + infinity mask -> projective ((x,y,1) / (0,1,0))."""
    one = fl.ones(inf.shape)
    zero = fl.zeros(inf.shape)
    return (
        fl.select(inf, zero, x),
        fl.select(inf, one, y),
        fl.select(inf, zero, one),
    )


def build_tables_device(fl, x, y, inf, entries=16):
    """On-device per-point projective multiples 0..entries-1 for the
    windowed MSMs. x, y: affine coordinate pytrees [..., k]; inf: bool
    [..., k]. Returns a pytree with leaves [..., k, entries, limbs...].
    The chained complete adds run as a `lax.scan` so jadd is compiled
    ONCE; amortized over the whole [..., k] batch. entries=17 serves the
    signed 5-bit window schedule (digits in [-16, 16], negation is a
    Y-flip on the gathered entry)."""
    jac = affine_to_jacobian(fl, x, y, inf)

    def body(prev, _):
        return jadd(fl, prev, jac), prev  # emits entries 0..entries-1

    _, rows = jax.lax.scan(
        body, jinfinity(fl, inf.shape), None, length=entries
    )
    # rows leaves: [entries, ..., k, L] -> [..., k, entries, L]
    return jax.tree_util.tree_map(
        lambda t: jnp.moveaxis(t, 0, inf.ndim), rows
    )


def fold_points(fl, pts, n, axis_offset=0, chunk=16):
    """Sum a pytree of n (power of two) points along its (axis_offset)-th
    leading axis with ~n-1 lane-adds (the minimum): a lax.scan over
    chunk-size groups (jadd compiled ONCE at width n/chunk) followed by a
    pairwise-halving unroll over the n/chunk partial sums (log2(n/chunk)
    jadd shapes in HLO — small now that jadd is the complete-RCB form)."""
    assert n & (n - 1) == 0
    ax = axis_offset
    if n > chunk:
        g = n // chunk

        def split(t):
            s = t.shape
            return jnp.moveaxis(
                t.reshape(s[:ax] + (g, chunk) + s[ax + 1 :]), ax + 1, 0
            )

        xs = jax.tree_util.tree_map(split, pts)  # leaves [chunk, .. g ..]
        init = jax.tree_util.tree_map(lambda t: t[0], xs)
        rest = jax.tree_util.tree_map(lambda t: t[1:], xs)
        pts = jax.lax.scan(
            lambda c, x: (jadd(fl, c, x), None), init, rest
        )[0]
        n = g
    while n > 1:
        half = n // 2
        lo = jax.tree_util.tree_map(
            lambda t: jax.lax.slice_in_dim(t, 0, half, axis=ax), pts
        )
        hi = jax.tree_util.tree_map(
            lambda t: jax.lax.slice_in_dim(t, half, n, axis=ax), pts
        )
        pts = jadd(fl, lo, hi)
        n = half
    return jax.tree_util.tree_map(lambda t: jnp.take(t, 0, axis=ax), pts)


def fold_points_any(fl, pts, n, axis_offset=0):
    """Sum a pytree of n points (ANY n >= 1) along the (axis_offset)-th
    leading axis with n-1 lane-adds: static binary decomposition of n into
    power-of-two blocks, each folded by fold_points, partials chain-added."""
    ax = axis_offset
    if n == 1:
        return jax.tree_util.tree_map(lambda t: jnp.take(t, 0, axis=ax), pts)
    acc = None
    off = 0
    for bit in range(n.bit_length() - 1, -1, -1):
        blk = 1 << bit
        if not n & blk:
            continue
        part = jax.tree_util.tree_map(
            lambda t: jax.lax.slice_in_dim(t, off, off + blk, axis=ax), pts
        )
        folded = fold_points(fl, part, blk, axis_offset=ax)
        acc = folded if acc is None else jadd(fl, acc, folded)
        off += blk
    return acc


def build_comb_tables(fl, tables_e, nwin, window=5):
    """Fixed-base comb window tables for the shared-base MSM.

    tables_e: projective multiples 0..2^(window-1) as a pytree with leading
    [k, 2^(window-1)+1] (entry 0 = identity). Returns leading
    [k, nwin, entries] where entry (j, w, d) = d * (2^window)^(nwin-1-w) *
    base_j — i.e. the w-th MS-first signed window digit's contribution is a
    pure table lookup, so the MSM itself needs NO doublings. The scaling
    scan runs on the tiny [k, entries] shape (`window` doublings per
    window), so table build cost is negligible against the [B]-wide MSM;
    per-verkey tables are cached device-side by the backend."""

    def body(carry, _):
        nxt = carry
        for _ in range(window):
            nxt = jdouble(fl, nxt)
        return nxt, carry  # emit BEFORE scaling: row w = (2^window)^w * t

    _, rows = jax.lax.scan(body, tables_e, None, length=nwin)
    # rows: [nwin(lsb-first), k, E, L] -> msb-first, then [k, nwin, E, L]
    return jax.tree_util.tree_map(
        lambda t: jnp.moveaxis(jnp.flip(t, axis=0), 0, 1), rows
    )


def msm_shared_comb(fl, wtables, mag, sgn):
    """Fixed-base comb MSM over shared bases: gather one table entry per
    (credential, base, window) and fold — 0 doublings, k*nwin-1 lane-adds
    per credential, all at full [B] width (no sequential window scan).

    wtables: comb tables from build_comb_tables, leading [k, nwin, E];
    mag/sgn: signed window digits [B, k, nwin] (msb-first, digit =
    (-1)^sgn * mag, mag <= E-1 for E-entry tables; zero scalars ->
    all-zero digits). The backend uses the 6-bit/43-window schedule.
    Returns a projective accumulator pytree with leading [B].

    Layout: the fold runs over a LEADING (k*nwin) axis with the batch in
    the trailing lane axis — the same orientation as the grouped verify's
    _grouped_msms fold. (The transposed [B, k*nwin] orientation miscompiled
    on an earlier TPU runtime at B = 1024: the last batch row of the fold
    came back corrupted, data-independently, on every mul path — same
    bug family as the int8 einsum workaround in fp._school.)"""
    B, k, nwin = mag.shape
    jidx = jnp.arange(k)[:, None, None]
    widx = jnp.arange(nwin)[None, :, None]
    mag_t = jnp.transpose(mag, (1, 2, 0))  # [k, nwin, B]
    sgn_t = jnp.transpose(sgn, (1, 2, 0))

    def leaf(t):  # [k, nwin, E, L...] -> [k, nwin, B, L...]
        return t[jidx, widx, mag_t]

    X, Y, Z = (
        jax.tree_util.tree_map(leaf, wtables[0]),
        jax.tree_util.tree_map(leaf, wtables[1]),
        jax.tree_util.tree_map(leaf, wtables[2]),
    )
    Y = fl.select(sgn_t, fl.neg(Y), Y)
    flat = jax.tree_util.tree_map(
        lambda t: t.reshape((k * nwin, B) + t.shape[3:]), (X, Y, Z)
    )
    return fold_points_any(fl, flat, k * nwin, axis_offset=0)


def scalar_mul_static(fl, pt, k, window=4):
    """Projective point times a STATIC positive int scalar: windowed
    double-and-add, mirroring fp.pow_static's structure. The multiples
    table 0..2^window-1 is built by a lax.scan of chained complete adds
    (jadd compiled ONCE), then a scan over the static msb-first digit
    array runs `window` doublings + one gathered add per window. The
    dominant user is hash-to-G1's cofactor clear (G1_COFACTOR, 126 bits
    -> 32 windows); complete RCB formulas make this valid for FULL-curve
    points (the SvdW sum is not yet in the r-torsion subgroup)."""
    assert k > 0
    shape = jax.tree_util.tree_leaves(pt)[0].shape[:-1]
    nw = (k.bit_length() + window - 1) // window
    digits = jnp.array(
        [(k >> (window * i)) & ((1 << window) - 1) for i in range(nw - 1, -1, -1)],
        dtype=jnp.int32,
    )

    def tbody(prev, _):
        return jadd(fl, prev, pt), prev  # emits multiples 0..2^window-1

    _, rows = jax.lax.scan(
        tbody, jinfinity(fl, shape), None, length=1 << window
    )

    def body(acc, d):
        for _ in range(window):
            acc = jdouble(fl, acc)
        entry = jax.tree_util.tree_map(
            lambda t: jax.lax.dynamic_index_in_dim(
                t, d, axis=0, keepdims=False
            ),
            rows,
        )
        return jadd(fl, acc, entry), None

    acc, _ = jax.lax.scan(body, jinfinity(fl, shape), digits)
    return acc


# --- SvdW map (device half of CTH-v2 hash_to_g1) ----------------------------
#
# Montgomery-encoded constants for the Fp instantiation of the spec's
# straight-line Shallue-van de Woestijne map (ops/hashing._SVDW_FP — derived
# there at import from the curve equation alone; re-encoded here as balanced
# limb vectors). Resolved lazily: importing ops.hashing derives the Fp2
# constants too, which is pointless import-time work for non-hashing users.
_SVDW_MONT = None


def _svdw_mont():
    global _SVDW_MONT
    if _SVDW_MONT is None:
        from ..ops.hashing import _SVDW_FP
        from .limbs import MONT_R, balanced_limbs
        from .fp import P

        import numpy as _np

        def enc(v):
            # numpy, not jnp: the first resolve may happen INSIDE a jit
            # trace (the cached hash kernel), and arrays minted there
            # would be cached as leaked tracers
            return _np.asarray(
                balanced_limbs(v * MONT_R % P), dtype=_np.float32
            )

        Z, c1, c2, c3, c4 = _SVDW_FP
        _SVDW_MONT = (enc(Z), enc(c1), enc(c2), enc(c3), enc(c4), enc(4))
    return _SVDW_MONT


def svdw_map_fp(u, u_par):
    """Batched SvdW straight-line map for G1, bit-identical to the spec
    (ops/hashing._map_to_curve_svdw over _FpAdapter): u [..., L] field
    elements in Montgomery limbs, u_par [...] bool = host-side sgn0(u)
    (u is host-known — the expand_message_xmd output — so its parity
    ships as a bit instead of being recomputed on device). Returns
    affine (x, y) limb pytrees; the map NEVER outputs the identity or a
    y = 0 point (E(Fp) has odd order, so x^3 + 4 has no roots in Fp and
    the three-candidate select always lands on a curve point).

    Fixed op count, branchless selects — the property the CTH-v2 spec
    was designed around. The three candidate square roots run as ONE
    stacked pow_static over a [..., 3] axis (the map's dominant cost,
    ~480 Montgomery muls, same family as fp.inv)."""
    from . import fp as _f
    from ..ops.fields import P as _P

    Z, c1, c2, c3, c4, b4 = _svdw_mont()
    one = _f.ones_mont(u.shape[:-1])
    tv1 = _f.mul(_f.sq(u), c1)
    tv2 = _f.add(one, tv1)
    tv1m = _f.sub(one, tv1)
    tv3 = _f.inv(_f.mul(tv1m, tv2))  # inv0: fp.inv maps 0 -> 0
    tv4 = _f.mul(_f.mul(_f.mul(u, tv1m), tv3), c3)
    x1 = _f.sub(c2, tv4)
    x2 = _f.add(c2, tv4)
    t5 = _f.mul(_f.sq(tv2), tv3)
    x3 = _f.add(_f.mul(_f.sq(t5), c4), Z)
    xs = jnp.stack(jnp.broadcast_arrays(x1, x2, x3), axis=-2)  # [..., 3, L]
    gxs = _f.add(_f.mul(_f.sq(xs), xs), b4)  # g(x) = x^3 + 4
    ss = _f.pow_static(gxs, (_P + 1) // 4)  # candidate sqrt per x
    # is_square(gx) iff s^2 == gx (P = 3 mod 4); exactly the spec's
    # fp_sqrt-is-not-None test
    ok = _f.is_zero(_f.sub(_f.sq(ss), gxs))  # [..., 3]
    ok1, ok2 = ok[..., 0], ok[..., 1]
    x = _f.select(ok1, xs[..., 0, :], _f.select(ok2, xs[..., 1, :], xs[..., 2, :]))
    y = _f.select(ok1, ss[..., 0, :], _f.select(ok2, ss[..., 1, :], ss[..., 2, :]))
    # sgn0 is defined on the STANDARD-domain canonical value: leave the
    # Montgomery domain (one mul by raw 1) before the parity test
    flip = _f.canon_parity(_f.from_mont(y)) != u_par
    y = _f.select(flip, _f.neg(y), y)
    return x, y


def msm_distinct_bucketed(fl, x, y, inf, mag, sgn, window):
    """Bucketed (Pippenger) distinct-base MSM: the table-free schedule
    for FAT per-row base counts, where msm_distinct_signed's on-device
    17-entry table build (16 chained adds at [B*k] width) and per-window
    table gathers dominate.

    x, y, inf: affine points [..., k]; mag/sgn: [..., k, nwin] signed
    `window`-bit digits, msb first, magnitudes <= nb = 2^(window-1).
    Per window (Horner over windows, msb first): `window` doublings,
    then each of the k points is SCATTERED into its digit's bucket —
    gather the target bucket row (take_along_axis over the [..., nb]
    bucket axis), one complete add at batch width, one-hot writeback
    (cheap VPU selects, no extra field muls) — then the nb buckets fold
    with the running-sum trick (sum_b b*bucket_b in 2nb adds). Zero
    digits never scatter (the one-hot mask is all-false), so zero
    scalars and identity pad lanes cost nothing but the masked lanes.

    Cost per window ~ k + 2*nb batch-width adds + `window` doublings,
    with NO table build — vs the Horner schedule's 16k build adds +
    k adds/window; the backend's _bucket_window cost model picks the
    crossover (k ~ 64-128) and the window size. Returns a projective
    accumulator pytree with leading dims [...]."""
    nb = 1 << (window - 1)
    bshape = inf.shape[:-1]
    bdim = len(bshape)
    k = inf.shape[-1]
    jac = affine_to_jacobian(fl, x, y, inf)  # leaves [..., k, L]
    acc = jinfinity(fl, bshape)

    def win_body(acc, dw):
        mw, sw = dw  # each [..., k]
        acc = jax.lax.fori_loop(
            0, window, lambda _, a: jdouble(fl, a), acc
        )
        buckets = jinfinity(fl, bshape + (nb,))

        def scatter(j, bk):
            d = jnp.take(mw, j, axis=-1).astype(jnp.int32)  # [...], 0..nb
            sj = jnp.take(sw, j, axis=-1)
            px, py, pz = jax.tree_util.tree_map(
                lambda t: jnp.take(t, j, axis=bdim), jac
            )
            pj = (px, fl.select(sj, fl.neg(py), py), pz)
            idx = jnp.maximum(d - 1, 0)  # bucket index; d = 0 is masked

            def gather(t):  # [..., nb, L...] -> [..., L...] at idx
                ii = idx.reshape(idx.shape + (1,) * (t.ndim - idx.ndim))
                return jnp.squeeze(
                    jnp.take_along_axis(t, ii, axis=bdim), axis=bdim
                )

            cur = jax.tree_util.tree_map(gather, bk)
            new = jadd(fl, cur, pj)
            onehot = (jnp.arange(nb) == idx[..., None]) & (
                d[..., None] > 0
            )  # [..., nb]

            def put(bt, nt):
                oh = onehot.reshape(
                    onehot.shape + (1,) * (bt.ndim - onehot.ndim)
                )
                return jnp.where(oh, jnp.expand_dims(nt, axis=bdim), bt)

            return jax.tree_util.tree_map(put, bk, new)

        buckets = jax.lax.fori_loop(0, k, scatter, buckets)
        # running-sum fold, top bucket first: total = sum_b b * bucket_b
        rev = jax.tree_util.tree_map(
            lambda t: jnp.flip(jnp.moveaxis(t, bdim, 0), axis=0), buckets
        )

        def fold(carry, bslice):
            run, tot = carry
            run = jadd(fl, run, bslice)
            tot = jadd(fl, tot, run)
            return (run, tot), None

        (_, tot), _ = jax.lax.scan(
            fold, (jinfinity(fl, bshape), jinfinity(fl, bshape)), rev
        )
        return jadd(fl, acc, tot), None

    acc, _ = jax.lax.scan(
        win_body,
        acc,
        (jnp.moveaxis(mag, -1, 0), jnp.moveaxis(sgn, -1, 0)),
    )
    return acc


def msm_distinct_signed(fl, x, y, inf, mag, sgn):
    """Signed 5-bit windowed MSM over per-row bases (the issuance/show
    shape: per-credential points, so tables must be built on device).

    x, y, inf: affine points [..., k]; mag/sgn: [..., k, nwin] signed
    5-bit window digits, msb first (digit = (-1)^sgn * mag, mag <= 16).
    52-window Horner (5 doublings + k adds per window) vs the unsigned
    4-bit schedule's 64 windows. Returns a projective accumulator pytree
    with leading dims [...]."""
    tables = build_tables_device(fl, x, y, inf, entries=17)
    k = inf.shape[-1]
    acc = jinfinity(fl, inf.shape[:-1])

    def body(acc, dw):
        mw, sw = dw  # each [..., k]
        acc = jax.lax.fori_loop(0, 5, lambda _, a: jdouble(fl, a), acc)

        def add_base(j, a):
            idx = jnp.take(mw, j, axis=-1)  # [...]
            entry = jax.tree_util.tree_map(
                lambda t: jnp.squeeze(
                    jnp.take_along_axis(
                        jnp.take(t, j, axis=idx.ndim),
                        idx.reshape(idx.shape + (1,) * (t.ndim - idx.ndim - 1)),
                        axis=idx.ndim,
                    ),
                    axis=idx.ndim,
                ),
                tables,
            )
            sj = jnp.take(sw, j, axis=-1)
            ex, ey, ez = entry
            entry = (ex, fl.select(sj, fl.neg(ey), ey), ez)
            return jadd(fl, a, entry)

        acc = jax.lax.fori_loop(0, k, add_base, acc)
        return acc, None

    acc, _ = jax.lax.scan(
        body,
        acc,
        (jnp.moveaxis(mag, -1, 0), jnp.moveaxis(sgn, -1, 0)),
    )
    return acc


