"""The plain reference: Coconut's verification relations over BLS12-381 G1,
written out with Python integers and nothing else.

It imports nothing of the system under test and takes nothing it made
beyond the answers being judged. A credential (sigma_1, sigma_2) on the
attributes m_1..m_q is valid under the master key (x, y_1..y_q) exactly
when sigma_1 is a point of G1 other than the identity and

    sigma_2 == (x + sum_j y_j * m_j) * sigma_1.

That is the pairing check e(sigma_1, X * prod Y_j^m_j) == e(sigma_2, g)
read with the secret key in hand, so no pairing is needed. A show proof
randomised with t has sigma'_2 == (x + sum_j y_j * m_j + t) * sigma'_1,
where the revealed m_j are the values the verifier was given.

Points are affine (x, y) tuples of ints, None for the identity: the same
plain data any BLS12-381 implementation can print.
"""

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
B = 4  # y^2 = x^3 + 4


def on_curve(pt):
    if pt is None:
        return True
    x, y = pt
    return 0 <= x < P and 0 <= y < P and (y * y - x * x * x - B) % P == 0


def _jac_double(X, Y, Z):
    if Z == 0 or Y == 0:
        return 0, 1, 0
    A = X * X % P
    Bq = Y * Y % P
    C = Bq * Bq % P
    D = 2 * ((X + Bq) * (X + Bq) - A - C) % P
    E = 3 * A % P
    X3 = (E * E - 2 * D) % P
    Y3 = (E * (D - X3) - 8 * C) % P
    Z3 = 2 * Y * Z % P
    return X3, Y3, Z3


def _jac_add_affine(X1, Y1, Z1, x2, y2):
    if Z1 == 0:
        return x2, y2, 1
    Z1Z1 = Z1 * Z1 % P
    U2 = x2 * Z1Z1 % P
    S2 = y2 * Z1 % P * Z1Z1 % P
    H = (U2 - X1) % P
    rr = (S2 - Y1) % P
    if H == 0:
        if rr == 0:
            return _jac_double(X1, Y1, Z1)
        return 0, 1, 0
    HH = H * H % P
    HHH = H * HH % P
    V = X1 * HH % P
    X3 = (rr * rr - HHH - 2 * V) % P
    Y3 = (rr * (V - X3) - Y1 * HHH) % P
    Z3 = Z1 * H % P
    return X3, Y3, Z3


def _to_affine(X, Y, Z):
    if Z == 0:
        return None
    zi = pow(Z, P - 2, P)
    zi2 = zi * zi % P
    return X * zi2 % P, Y * zi2 % P * zi % P


def mul(pt, k):
    """k * pt by double-and-add (k reduced mod R; pt in the subgroup)."""
    k %= R
    if pt is None or k == 0:
        return None
    x, y = pt
    X, Y, Z = 0, 1, 0
    for bit in bin(k)[2:]:
        X, Y, Z = _jac_double(X, Y, Z)
        if bit == "1":
            X, Y, Z = _jac_add_affine(X, Y, Z, x, y)
    return _to_affine(X, Y, Z)


def in_subgroup(pt):
    """pt on the curve and of order R (or the identity)."""
    if not on_curve(pt):
        return False
    if pt is None:
        return True
    x, y = pt
    X, Y, Z = 0, 1, 0
    for bit in bin(R)[2:]:
        X, Y, Z = _jac_double(X, Y, Z)
        if bit == "1":
            X, Y, Z = _jac_add_affine(X, Y, Z, x, y)
    return Z == 0


def exponent(x, ys, messages, extra=0):
    """x + sum_j y_j * m_j (+ extra), mod R."""
    return (x + sum(y * m for y, m in zip(ys, messages)) + extra) % R


def credential_valid(sigma_1, sigma_2, x, ys, messages):
    """Whether (sigma_1, sigma_2) is a valid credential on `messages`."""
    if sigma_1 is None or not on_curve(sigma_1) or not on_curve(sigma_2):
        return False
    return mul(sigma_1, exponent(x, ys, messages)) == sigma_2


def show_valid(sigma_1p, sigma_2p, x, ys, messages, t):
    """Whether a show randomised with t proves `messages`, the hidden ones
    as the holder knows them and the revealed ones as the verifier was
    given them."""
    if sigma_1p is None or not on_curve(sigma_1p) or not on_curve(sigma_2p):
        return False
    return mul(sigma_1p, exponent(x, ys, messages, t)) == sigma_2p
