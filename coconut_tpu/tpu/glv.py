"""GLV endomorphism acceleration for G1 distinct-base MSMs.

BLS12-381's E(Fp) carries the efficiently-computable endomorphism
phi(x, y) = (beta * x, y) with phi(P) = lambda * P, where beta is a cube
root of unity in Fp and lambda = z^2 - 1 (z the BLS parameter) is a cube
root of unity mod r (lambda^2 + lambda + 1 == 0 mod r; proved by the
import-time asserts below, and phi's eigenvalue is differentially tested
against the spec ops in tests/test_backends.py).

Because lambda ~ 2^127.1 and r ~ 2^254.9, the scalar decomposition needs
no lattice reduction: the plain Euclidean split

    k = k2 * lambda + k1,   k1 = k mod lambda < 2^128,
                            k2 = k div lambda < 2^128

is exact over the integers with both halves NONNEGATIVE, so

    k * P = k1 * P + k2 * phi(P)

turns one 255-bit scalar on one base into two <= 128-bit scalars on two
bases. For the Horner-style distinct-base MSM (curve.msm_distinct_signed:
5 doublings per window) this halves the doubling chain (52 -> 27 windows)
while keeping the add count — the win the grouped/comb schedules cannot
get from GLV (they have no doublings). phi itself costs one host-side Fp mul per base (beta * x).

Reference workload this accelerates: the issuance MSMs
(signature.rs:396-428) and the show prover's sigma re-randomization
(pok_sig.rs:85-95 surface), both routed through msm_g1_distinct.
"""

from ..ops.fields import P, R

# BLS parameter z and the G1 eigenvalue lambda = z^2 - 1 (see module doc).
Z = -0xD201000000010000
LAMBDA = (Z * Z - 1) % R
# The cube root of unity in Fp matching phi(P) = lambda * P on G1 (the
# OTHER root pairs with lambda^2; checked by tests/test_backends.py).
BETA = 0x1A0111EA397FE699EC02408663D4DE85AA0D857D89759AD4897D29650FB85F9B409427EB4F49FFFD8BFD00000000AAAC

# lambda is a primitive cube root of unity mod r, beta one in Fp
assert (LAMBDA * LAMBDA + LAMBDA + 1) % R == 0
assert BETA != 1 and pow(BETA, 3, P) == 1

# Window budget for the decomposed halves: both are < 2^128, so ceil(128/5)
# signed 5-bit windows plus one carry window cover them (the same bound the
# 128-bit combiner scalars use, backend._R_NWIN).
HALF_BITS = 128
NWIN_5 = -(-HALF_BITS // 5) + 1  # 27

assert LAMBDA.bit_length() == 128
assert (R - 1) // LAMBDA < 1 << HALF_BITS


def decompose(k):
    """k (mod r) -> (k1, k2) with k = k1 + k2 * lambda, both in [0, 2^128)."""
    # lint: allow(const-time, CONSTTIME.md §1 host caveat - CPython big-int
    # divmod cost tracks bit length; accepted on the host recode path)
    k = int(k) % R
    return k % LAMBDA, k // LAMBDA


def phi(pt):
    """The endomorphism on a spec G1 point tuple (None = identity)."""
    if pt is None:
        return None
    return (pt[0] * BETA % P, pt[1])
