"""CppBackend — ctypes bridge to the native C++ core (native/ccbls.cpp).

SURVEY.md §7 stage 1's Python-visible face: the same `CurveBackend` seam the
JAX backend implements, routed through the batch C ABI of `libccbls.so`.
The native library is the framework's CPU baseline and the
const-time issuance path (reference const-time MSM call sites
signature.rs:157,424-428): `ct=True` selects the masked-lookup schedule,
which accumulates through the COMPLETE Renes-Costello-Batina projective
formulas (the same branch-free formulas as the TPU kernels) over
branchless masked field normalization — no secret-dependent branch,
formula path, or memory access anywhere in the schedule.

Wire codec (must match ccbls.cpp): Fp = 48B LE canonical; affine G1 = x||y
(96B), G2 = x.c0||x.c1||y.c0||y.c1 (192B); infinity = all-zero bytes
(0^3+4 != 0 so the encoding is unambiguous); scalars = 32B LE canonical Fr.

Built from the committed source on every first load (`make -C native
libccbls.so`: make's mtime rule rebuilds a library older than ccbls.cpp
and is a no-op otherwise); `CCBLS_SO` loads a prebuilt library as is.
"""

import ctypes
import fcntl
import os
import subprocess

from .backend import CurveBackend, register_backend
from .ops.fields import R

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
_SO_PATH = os.environ.get("CCBLS_SO", os.path.join(_NATIVE_DIR, "libccbls.so"))

_lib = None


def _build():
    # serialized across processes (pytest-xdist workers load at once):
    # the lock on the committed Makefile keeps a second process from
    # loading a library the first is still writing
    with open(os.path.join(_NATIVE_DIR, "Makefile")) as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        subprocess.run(
            ["make", "-C", _NATIVE_DIR, "libccbls.so"],
            check=True,
            capture_output=True,
        )


def load():
    """Build (when stale) and load, then selftest, the native library."""
    global _lib
    if _lib is not None:
        return _lib
    if "CCBLS_SO" not in os.environ:
        _build()
    lib = ctypes.CDLL(_SO_PATH)
    lib.cc_selftest.restype = ctypes.c_int
    rc = lib.cc_selftest()
    if rc != 0:
        raise RuntimeError("ccbls selftest failed: %d" % rc)
    for name, argt in [
        ("cc_msm_g1", [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_int]),
        ("cc_msm_g2", [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_int]),
        ("cc_pairing_product_is_one", [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_char_p]),
        ("cc_g1_mul", [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p]),
    ]:
        fn = getattr(lib, name)
        fn.argtypes = argt
        fn.restype = None
    for name, argt in [
        ("cc_msm_pippenger_g1", [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p]),
        ("cc_msm_pippenger_g2", [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p]),
    ]:
        fn = getattr(lib, name)
        fn.argtypes = argt
        fn.restype = None
    lib.cc_fr_lagrange_basis_at_0.argtypes = [
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_int,
        ctypes.c_uint32,
        ctypes.c_char_p,
    ]
    lib.cc_fr_lagrange_basis_at_0.restype = ctypes.c_int
    lib.cc_fr_poly_eval.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int,
        ctypes.c_uint32,
        ctypes.c_char_p,
    ]
    lib.cc_fr_poly_eval.restype = None
    lib.cc_fr_reconstruct.argtypes = [
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_char_p,
        ctypes.c_int,
        ctypes.c_char_p,
    ]
    lib.cc_fr_reconstruct.restype = ctypes.c_int
    lib.cc_fr_random.argtypes = [ctypes.c_char_p]
    lib.cc_fr_random.restype = ctypes.c_int
    lib.cc_pedersen_deal_from_coeffs.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p,
    ]
    lib.cc_pedersen_deal_from_coeffs.restype = None
    lib.cc_pedersen_deal.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p,
    ]
    lib.cc_pedersen_deal.restype = ctypes.c_int
    lib.cc_pedersen_verify_share.argtypes = [
        ctypes.c_int, ctypes.c_uint32, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
    ]
    lib.cc_pedersen_verify_share.restype = ctypes.c_int
    lib.cc_dvss_new.argtypes = [
        ctypes.c_uint32, ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
        ctypes.c_char_p,
    ]
    lib.cc_dvss_new.restype = ctypes.c_void_p
    lib.cc_dvss_deal.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
    ]
    lib.cc_dvss_deal.restype = None
    lib.cc_dvss_receive.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p,
    ]
    lib.cc_dvss_receive.restype = ctypes.c_int
    lib.cc_dvss_finalize.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
    ]
    lib.cc_dvss_finalize.restype = ctypes.c_int
    lib.cc_dvss_free.argtypes = [ctypes.c_void_p]
    lib.cc_dvss_free.restype = None
    for name in ("cc_hash_to_fr", "cc_hash_to_g1", "cc_hash_to_g2"):
        fn = getattr(lib, name)
        fn.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int,
            ctypes.c_char_p,
            ctypes.c_int,
            ctypes.c_char_p,
        ]
        fn.restype = ctypes.c_int
    try:
        # batched entry point; absent from a stale .so built before it
        # existed (hash_to_g1_batch then falls back to the per-msg calls)
        lib.cc_hash_to_g1_batch.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int),
            ctypes.c_int,
            ctypes.c_char_p,
            ctypes.c_int,
            ctypes.c_char_p,
        ]
        lib.cc_hash_to_g1_batch.restype = ctypes.c_int
    except AttributeError:
        pass
    _lib = lib
    return lib


# --- hashing (native CTH-v2: the amcl `from_msg_hash` replacement — C++
# side of spec ops/hashing.py; reference call sites signature.rs:23-29,
# 205, 598) -------------------------------------------------------------


def hash_to_fr(msg, dst=None):
    """Native hash-to-Fr, bit-identical to ops.hashing.hash_to_fr."""
    from .ops.hashing import DST_FR

    dst = DST_FR if dst is None else dst
    lib = load()
    out = ctypes.create_string_buffer(32)
    rc = lib.cc_hash_to_fr(msg, len(msg), dst, len(dst), out)
    if rc != 0:
        raise ValueError("cc_hash_to_fr failed: %d" % rc)
    return int.from_bytes(out.raw, "little")


def hash_to_g1(msg, dst=None):
    """Native hash-to-G1, bit-identical to ops.hashing.hash_to_g1."""
    from .ops.hashing import DST_G1

    dst = DST_G1 if dst is None else dst
    lib = load()
    out = ctypes.create_string_buffer(96)
    rc = lib.cc_hash_to_g1(msg, len(msg), dst, len(dst), out)
    if rc != 0:
        raise ValueError("cc_hash_to_g1 failed: %d" % rc)
    return _g1_parse(out.raw)


def hash_to_g1_batch(msgs, dst=None):
    """Batched native hash-to-G1: N messages in ONE FFI call (the per-call
    ctypes overhead across 1,024 serial hashes was a visible slice of the
    prepare phase's host wall). Bit-identical to [hash_to_g1(m) for m in
    msgs]; falls back to exactly that loop on a stale .so without the
    batched symbol."""
    from .ops.hashing import DST_G1

    dst = DST_G1 if dst is None else dst
    msgs = list(msgs)
    lib = load()
    if not hasattr(lib, "cc_hash_to_g1_batch"):
        return [hash_to_g1(m, dst) for m in msgs]
    n = len(msgs)
    if n == 0:
        return []
    lens = (ctypes.c_int * n)(*[len(m) for m in msgs])
    out = ctypes.create_string_buffer(96 * n)
    rc = lib.cc_hash_to_g1_batch(b"".join(msgs), lens, n, dst, len(dst), out)
    if rc != 0:
        raise ValueError("cc_hash_to_g1_batch failed at msg %d" % (rc - 1))
    raw = out.raw
    return [_g1_parse(raw[i * 96 : (i + 1) * 96]) for i in range(n)]


def hash_to_g2(msg, dst=None):
    """Native hash-to-G2, bit-identical to ops.hashing.hash_to_g2."""
    from .ops.hashing import DST_G2

    dst = DST_G2 if dst is None else dst
    lib = load()
    out = ctypes.create_string_buffer(192)
    rc = lib.cc_hash_to_g2(msg, len(msg), dst, len(dst), out)
    if rc != 0:
        raise ValueError("cc_hash_to_g2 failed: %d" % rc)
    return _g2_parse(out.raw)


# --- Pippenger single-MSM (reference multi_scalar_mul_var_time surface,
# signature.rs:513,521: large-t Verkey.aggregate and any big-MSM workload) --

# Below this size the windowed row schedule beats the bucket combine; the
# crossover was measured on the build box.
PIPPENGER_MIN = 96


def msm_g1_single(points, scalars, force_pippenger=False):
    """One var-time MSM over n distinct G1 points through the native core:
    Pippenger buckets for n >= PIPPENGER_MIN, the windowed row schedule
    below it. Returns a spec point tuple (None = identity)."""
    n = len(points)
    if n == 0:
        return None
    lib = load()
    if n < PIPPENGER_MIN and not force_pippenger:
        return CppBackend().msm_g1_distinct([list(points)], [list(scalars)])[0]
    pts = b"".join(_g1_bytes(p) for p in points)
    ss = b"".join((int(s) % R).to_bytes(32, "little") for s in scalars)
    out = ctypes.create_string_buffer(96)
    lib.cc_msm_pippenger_g1(pts, ss, n, out)
    return _g1_parse(out.raw)


def msm_g2_single(points, scalars, force_pippenger=False):
    """G2 variant of msm_g1_single."""
    n = len(points)
    if n == 0:
        return None
    lib = load()
    if n < PIPPENGER_MIN and not force_pippenger:
        return CppBackend().msm_g2_distinct([list(points)], [list(scalars)])[0]
    pts = b"".join(_g2_bytes(p) for p in points)
    ss = b"".join((int(s) % R).to_bytes(32, "little") for s in scalars)
    out = ctypes.create_string_buffer(192)
    lib.cc_msm_pippenger_g2(pts, ss, n, out)
    return _g2_parse(out.raw)


# --- native sss (secret_sharing crate surface: Polynomial/Lagrange/Shamir,
# reference keygen.rs:58,248, signature.rs:460,502) --------------------------


def _id_u32(v, what="signer id"):
    """The C ABI carries ids/eval points as uint32 — reject anything that
    would silently wrap (sss.py accepts arbitrary ints; callers with wider
    ids must use the Python module)."""
    from .errors import GeneralError

    v = int(v)
    if not 0 <= v < 1 << 32:
        raise GeneralError(
            "%s %d outside the native uint32 range; use coconut_tpu.sss"
            % (what, v)
        )
    return v


def lagrange_basis_at_0(ids, my_id):
    """Native l_{my_id}(0) over `ids`, bit-identical to
    sss.lagrange_basis_at_0 (same GeneralError contract)."""
    from .errors import GeneralError

    lib = load()
    ids = sorted({_id_u32(i) for i in ids})
    arr = (ctypes.c_uint32 * len(ids))(*ids)
    out = ctypes.create_string_buffer(32)
    rc = lib.cc_fr_lagrange_basis_at_0(arr, len(ids), _id_u32(my_id), out)
    if rc == 1:
        raise GeneralError("id %d not in interpolation set" % my_id)
    if rc:
        raise GeneralError("signer ids must be nonzero (1-based)")
    return int.from_bytes(out.raw, "little")


def poly_eval(coeffs, x):
    """Native Horner evaluation in Fr (the Shamir share map)."""
    lib = load()
    cb = b"".join((int(c) % R).to_bytes(32, "little") for c in coeffs)
    out = ctypes.create_string_buffer(32)
    lib.cc_fr_poly_eval(cb, len(coeffs), _id_u32(x, "eval point"), out)
    return int.from_bytes(out.raw, "little")


def reconstruct_secret(threshold, shares):
    """Native Lagrange interpolation at 0, same semantics (and GeneralError
    contract) as sss.reconstruct_secret (first `threshold` shares by id)."""
    from .errors import GeneralError

    if len(shares) < threshold:
        raise GeneralError(
            "need %d shares to reconstruct, got %d" % (threshold, len(shares))
        )
    lib = load()
    use = sorted(shares.items())[:threshold]
    ids = (ctypes.c_uint32 * threshold)(
        *[_id_u32(i) for i, _ in use]
    )
    sb = b"".join((int(s) % R).to_bytes(32, "little") for _, s in use)
    out = ctypes.create_string_buffer(32)
    rc = lib.cc_fr_reconstruct(ids, sb, threshold, out)
    if rc:
        raise GeneralError("invalid share ids")
    return int.from_bytes(out.raw, "little")


# --- native Pedersen VSS / DVSS (finishes the secret_sharing rebuild
# target: reference keygen.rs:74-205; differential tests vs sss.py in
# tests/test_backends.py) ----------------------------------------------------


def rand_fr():
    """Native uniform Fr from OS entropy (FieldElement::random surface)."""
    lib = load()
    out = ctypes.create_string_buffer(32)
    if lib.cc_fr_random(out):
        raise RuntimeError("native entropy source failed")
    return int.from_bytes(out.raw, "little")


def pedersen_deal_from_coeffs(threshold, total, g, h, f_coeffs, g_coeffs):
    """Native Pedersen deal from given polynomial coefficients: returns
    (comm_coeffs {j: point}, s_shares {id: int}, t_shares {id: int}).
    Bit-identical to the sss.py math on the same coefficients."""
    from .errors import GeneralError

    if not 0 < threshold <= total:
        raise GeneralError(
            "invalid threshold %d for total %d" % (threshold, total)
        )
    if len(f_coeffs) != threshold or len(g_coeffs) != threshold:
        raise GeneralError(
            "need %d coefficients per polynomial, got %d and %d"
            % (threshold, len(f_coeffs), len(g_coeffs))
        )
    lib = load()
    fc = b"".join(_scalar_bytes(c) for c in f_coeffs)
    gc = b"".join(_scalar_bytes(c) for c in g_coeffs)
    comms = ctypes.create_string_buffer(96 * threshold)
    ss = ctypes.create_string_buffer(32 * total)
    ts = ctypes.create_string_buffer(32 * total)
    lib.cc_pedersen_deal_from_coeffs(
        threshold, total, _g1_bytes(g), _g1_bytes(h), fc, gc, comms, ss, ts
    )
    comm_coeffs = {
        j: _g1_parse(comms.raw[j * 96 : (j + 1) * 96])
        for j in range(threshold)
    }
    s_shares = {
        i: int.from_bytes(ss.raw[(i - 1) * 32 : i * 32], "little")
        for i in range(1, total + 1)
    }
    t_shares = {
        i: int.from_bytes(ts.raw[(i - 1) * 32 : i * 32], "little")
        for i in range(1, total + 1)
    }
    return comm_coeffs, s_shares, t_shares


def pedersen_deal(threshold, total, g, h):
    """Native PedersenVSS::deal (keygen.rs:93-94): fresh random polynomials
    from native entropy. Returns (secret, blind_secret, comm_coeffs,
    s_shares, t_shares) — the sss.PedersenVSS.deal tuple."""
    from .errors import GeneralError

    if not 0 < threshold <= total:
        raise GeneralError(
            "invalid threshold %d for total %d" % (threshold, total)
        )
    lib = load()
    fc = ctypes.create_string_buffer(32 * threshold)
    gc = ctypes.create_string_buffer(32 * threshold)
    comms = ctypes.create_string_buffer(96 * threshold)
    ss = ctypes.create_string_buffer(32 * total)
    ts = ctypes.create_string_buffer(32 * total)
    if lib.cc_pedersen_deal(
        threshold, total, _g1_bytes(g), _g1_bytes(h), fc, gc, comms, ss, ts
    ):
        raise RuntimeError("native entropy source failed")
    comm_coeffs = {
        j: _g1_parse(comms.raw[j * 96 : (j + 1) * 96])
        for j in range(threshold)
    }
    s_shares = {
        i: int.from_bytes(ss.raw[(i - 1) * 32 : i * 32], "little")
        for i in range(1, total + 1)
    }
    t_shares = {
        i: int.from_bytes(ts.raw[(i - 1) * 32 : i * 32], "little")
        for i in range(1, total + 1)
    }
    secret = int.from_bytes(fc.raw[:32], "little")
    blind = int.from_bytes(gc.raw[:32], "little")
    return secret, blind, comm_coeffs, s_shares, t_shares


def pedersen_verify_share(threshold, share_id, share, comm_coeffs, g, h):
    """Native PedersenVSS::verify_share (keygen.rs:334-351)."""
    lib = load()
    s, t = share
    comms = b"".join(
        _g1_bytes(comm_coeffs[j]) for j in range(threshold)
    )
    return bool(
        lib.cc_pedersen_verify_share(
            threshold,
            _id_u32(share_id),
            _scalar_bytes(s),
            _scalar_bytes(t),
            comms,
            _g1_bytes(g),
            _g1_bytes(h),
        )
    )


class DvssParticipant:
    """Native DVSS participant (reference PedersenDVSSParticipant surface,
    keygen.rs:136-162): the dealing, share verification, and combining run
    in C++; the protocol driver stays host-side like the reference's.

    Mirrors sss.PedersenDVSSParticipant's attribute surface so the two are
    interchangeable in the keygen drivers and differential tests."""

    def __init__(self, participant_id, threshold, total, g, h):
        from .errors import GeneralError

        lib = load()
        self._lib = lib
        self.id = _id_u32(participant_id)
        self.threshold = threshold
        self.total = total
        self._h = lib.cc_dvss_new(
            self.id, threshold, total, _g1_bytes(g), _g1_bytes(h)
        )
        if not self._h:
            raise GeneralError(
                "invalid DVSS parameters id=%d t=%d n=%d"
                % (participant_id, threshold, total)
            )
        comms = ctypes.create_string_buffer(96 * threshold)
        ss = ctypes.create_string_buffer(32 * total)
        ts = ctypes.create_string_buffer(32 * total)
        lib.cc_dvss_deal(self._h, comms, ss, ts)
        self.comm_coeffs = {
            j: _g1_parse(comms.raw[j * 96 : (j + 1) * 96])
            for j in range(threshold)
        }
        self.s_shares = {
            i: int.from_bytes(ss.raw[(i - 1) * 32 : i * 32], "little")
            for i in range(1, total + 1)
        }
        self.t_shares = {
            i: int.from_bytes(ts.raw[(i - 1) * 32 : i * 32], "little")
            for i in range(1, total + 1)
        }
        self.secret_share = None
        self.t_secret_share = None
        self.final_comm_coeffs = None

    def received_share(self, from_id, comm_coeffs, share, threshold=None,
                       total=None, g=None, h=None):
        """Verify and store a share of `from_id`'s secret (the extra args
        of the sss.py surface are carried by the native handle)."""
        from .errors import GeneralError

        s, t = share
        comms = b"".join(
            _g1_bytes(comm_coeffs[j]) for j in range(self.threshold)
        )
        rc = self._lib.cc_dvss_receive(
            self._h,
            _id_u32(from_id),
            comms,
            _scalar_bytes(s),
            _scalar_bytes(t),
        )
        if rc == 1:
            raise GeneralError(
                "participant %d received its own share" % self.id
            )
        if rc == 2:
            raise GeneralError("participant id %d out of range" % from_id)
        if rc == 3:
            raise GeneralError(
                "participant %d already has a share from %d"
                % (self.id, from_id)
            )
        if rc:
            raise GeneralError(
                "share from participant %d failed verification at %d"
                % (from_id, self.id)
            )

    def compute_final_comm_coeffs_and_shares(self, threshold=None,
                                             total=None, g=None, h=None):
        from .errors import GeneralError

        s32 = ctypes.create_string_buffer(32)
        t32 = ctypes.create_string_buffer(32)
        comms = ctypes.create_string_buffer(96 * self.threshold)
        rc = self._lib.cc_dvss_finalize(self._h, s32, t32, comms)
        if rc:
            raise GeneralError(
                "participant %d is missing pairwise shares" % self.id
            )
        self.secret_share = int.from_bytes(s32.raw, "little")
        self.t_secret_share = int.from_bytes(t32.raw, "little")
        self.final_comm_coeffs = {
            j: _g1_parse(comms.raw[j * 96 : (j + 1) * 96])
            for j in range(self.threshold)
        }

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.cc_dvss_free(h)
            self._h = None


def share_secret_dvss(threshold, total, g, h):
    """Native-participant version of sss.share_secret_dvss: the full
    dealerless 3-round protocol simulated in-process (keygen.rs:126-165)."""
    participants = [
        DvssParticipant(i, threshold, total, g, h)
        for i in range(1, total + 1)
    ]
    for recv in participants:
        for sender in participants:
            if sender.id == recv.id:
                continue
            recv.received_share(
                sender.id,
                sender.comm_coeffs,
                (sender.s_shares[recv.id], sender.t_shares[recv.id]),
            )
    for p in participants:
        p.compute_final_comm_coeffs_and_shares()
    return participants


def derive_params(msg_count, label):
    """Params derivation entirely through the native core (the reference's
    Params::new, signature.rs:22-32, with amcl's from_msg_hash replaced by
    cc_hash_to_g1/g2): returns (g, g_tilde, h list) as spec point tuples
    for the default SIGNATURES_IN_G1 assignment."""
    g = hash_to_g1(bytes(label) + b" : g")
    g_tilde = hash_to_g2(bytes(label) + b" : g_tilde")
    hs = [
        hash_to_g1(bytes(label) + (" : y%d" % i).encode())
        for i in range(msg_count)
    ]
    return g, g_tilde, hs


# --- codec (ints <-> the C ABI byte layout) ---------------------------------


def _fp_bytes(x):
    return int(x).to_bytes(48, "little")


def _g1_bytes(p):
    if p is None:
        return b"\x00" * 96
    return _fp_bytes(p[0]) + _fp_bytes(p[1])


def _g2_bytes(p):
    if p is None:
        return b"\x00" * 192
    (x0, x1), (y0, y1) = p
    return _fp_bytes(x0) + _fp_bytes(x1) + _fp_bytes(y0) + _fp_bytes(y1)


def _g1_parse(b):
    if not any(b):
        return None
    return (
        int.from_bytes(b[:48], "little"),
        int.from_bytes(b[48:96], "little"),
    )


def _g2_parse(b):
    if not any(b):
        return None
    vals = [int.from_bytes(b[i * 48 : (i + 1) * 48], "little") for i in range(4)]
    return ((vals[0], vals[1]), (vals[2], vals[3]))


def _scalar_bytes(s):
    return (int(s) % R).to_bytes(32, "little")


class CppBackend(CurveBackend):
    """Native C++ batched backend (the CPU baseline)."""

    name = "cpp"

    def __init__(self, ct=False):
        self._lib = load()
        self._ct = 1 if ct else 0

    def msm_g1_shared(self, bases, scalars_batch):
        k = len(bases)
        B = len(scalars_batch)
        bb = b"".join(_g1_bytes(p) for p in bases)
        sb = b"".join(
            _scalar_bytes(s) for row in scalars_batch for s in row
        )
        out = ctypes.create_string_buffer(96 * B)
        self._lib.cc_msm_g1(bb, sb, k, B, out, self._ct)
        return [_g1_parse(out.raw[i * 96 : (i + 1) * 96]) for i in range(B)]

    def msm_g2_shared(self, bases, scalars_batch):
        k = len(bases)
        B = len(scalars_batch)
        bb = b"".join(_g2_bytes(p) for p in bases)
        sb = b"".join(
            _scalar_bytes(s) for row in scalars_batch for s in row
        )
        out = ctypes.create_string_buffer(192 * B)
        self._lib.cc_msm_g2(bb, sb, k, B, out, self._ct)
        return [_g2_parse(out.raw[i * 192 : (i + 1) * 192]) for i in range(B)]

    def msm_g1_distinct(self, points_batch, scalars_batch):
        # per-row bases: each row is a size-k shared-base MSM with B=1
        return [
            self.msm_g1_shared(pts, [row])[0]
            for pts, row in zip(points_batch, scalars_batch)
        ]

    def msm_g2_distinct(self, points_batch, scalars_batch):
        return [
            self.msm_g2_shared(pts, [row])[0]
            for pts, row in zip(points_batch, scalars_batch)
        ]

    def pairing_product_is_one(self, pairs_batch):
        B = len(pairs_batch)
        n = len(pairs_batch[0]) if B else 0
        if any(len(row) != n for row in pairs_batch):
            raise ValueError("ragged pairing batch")
        pb = b"".join(_g1_bytes(p) for row in pairs_batch for p, _ in row)
        qb = b"".join(_g2_bytes(q) for row in pairs_batch for _, q in row)
        out = ctypes.create_string_buffer(B)
        self._lib.cc_pairing_product_is_one(pb, qb, n, B, out)
        return [bool(out.raw[i]) for i in range(B)]


def available():
    """True if the native backend can load (build tools + source present)."""
    try:
        load()
        return True
    except Exception:
        return False


register_backend("cpp", CppBackend)
