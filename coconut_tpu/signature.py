"""The Coconut credential protocol: blind signature requests with proofs of
knowledge, blind signing, unblinding, threshold aggregation, verification.

Rebuilds the reference's signature.rs (the L3 protocol layer, SURVEY.md §1)
semantics-for-semantics on top of this framework's own PS / pok_vc / sss
layers. Differences from the reference are rebuild improvements, each noted
at the definition site: typed errors instead of asserts, Fiat-Shamir
recomputation support, canonical serialization on every wire struct.
"""

from .elgamal import elgamal_encrypt
from .errors import (
    DeserializationError,
    GeneralError,
    UnequalNoOfBasesExponents,
    UnsupportedNoOfMessages,
)
from .ops import serialize as ser
from .ops.fields import R
from .ops.hashing import hash_to_fr
from .pok_vc import Proof, ProverCommitting
from .ps import ps_verify
from .sss import lagrange_basis_at_0, rand_fr


def _validate_share_ids(pairs, threshold):
    """The (signer_id, value) subset an aggregation will interpolate over
    must hold `threshold` DISTINCT, in-range (positive integer) share
    indices: a repeated id would skew its Lagrange weight silently, and an
    id <= 0 has no Shamir evaluation point (sss.lagrange_basis_at_0 treats
    0 as the secret itself). Raises GeneralError NAMING the offending ids
    so an operator can see which authority double-reported or mislabeled
    its share. Returns the validated id set."""
    ids = [i for i, _ in pairs]
    bad = sorted({i for i in ids if not isinstance(i, int) or i < 1})
    if bad:
        raise GeneralError(
            "out-of-range signer ids in aggregation set: %r "
            "(share indices are 1-based positive integers)" % (bad,)
        )
    seen, dup = set(), set()
    for i in ids:
        if i in seen:
            dup.add(i)
        seen.add(i)
    if dup:
        raise GeneralError(
            "duplicate signer ids in aggregation set: %r "
            "(a repeated id would skew its Lagrange weight)"
            % (sorted(dup),)
        )
    if len(seen) != threshold:
        raise GeneralError(
            "aggregation subset holds %d distinct signer ids, need %d"
            % (len(seen), threshold)
        )
    return seen


class Sigkey:
    """Signer secret key: x, y_1..y_q (signature.rs:39-43)."""

    def __init__(self, x, y):
        self.x = x
        self.y = list(y)


class Verkey:
    """Verification key: X_tilde, Y_tilde_1..q in OtherGroup
    (signature.rs:45-49)."""

    def __init__(self, X_tilde, Y_tilde):
        self.X_tilde = X_tilde
        self.Y_tilde = list(Y_tilde)

    @staticmethod
    def aggregate(threshold, keys, ctx=None):
        """Lagrange-weighted aggregation over any `threshold` subset of
        (signer_id, Verkey) pairs — "AggKey" (signature.rs:481-527). Supports
        id gaps and differing subsets from the signing set
        (tests signature.rs:711-822)."""
        from .params import DEFAULT_CTX

        ctx = ctx or DEFAULT_CTX
        if len(keys) < threshold:
            raise GeneralError(
                "need at least %d verkeys, got %d" % (threshold, len(keys))
            )
        q = len(keys[0][1].Y_tilde)
        for _, vk in keys[1:]:
            if len(vk.Y_tilde) != q:
                raise UnsupportedNoOfMessages(q, len(vk.Y_tilde))
        use = keys[:threshold]
        ids = _validate_share_ids(use, threshold)
        ls = {i: lagrange_basis_at_0(ids, i) for i in ids}
        ops = ctx.other
        X_tilde = ops.msm([vk.X_tilde for i, vk in use], [ls[i] for i, _ in use])
        Y_tilde = [
            ops.msm([vk.Y_tilde[j] for i, vk in use], [ls[i] for i, _ in use])
            for j in range(q)
        ]
        return Verkey(X_tilde, Y_tilde)

    def to_bytes(self, ctx):
        out = [ctx.other_to_bytes(self.X_tilde)]
        out.extend(ctx.other_to_bytes(y) for y in self.Y_tilde)
        return b"".join(out)

    @classmethod
    def from_bytes(cls, b, ctx):
        n = ctx.other_nbytes
        if len(b) < 2 * n or len(b) % n:
            raise DeserializationError("malformed Verkey encoding")
        parts = [ctx.other_from_bytes(b[o : o + n]) for o in range(0, len(b), n)]
        return cls(parts[0], parts[1:])

    def __eq__(self, other):
        return (
            isinstance(other, Verkey)
            and self.X_tilde == other.X_tilde
            and self.Y_tilde == other.Y_tilde
        )


class Signature:
    """An (unblinded or aggregated) credential in PS form (signature.rs:66-71)."""

    def __init__(self, sigma_1, sigma_2):
        self.sigma_1 = sigma_1
        self.sigma_2 = sigma_2

    @staticmethod
    def aggregate(threshold, sigs, ctx=None):
        """Lagrange interpolation in the exponent over any `threshold` subset
        of (signer_id, Signature) — "AggCred" (signature.rs:446-470). All
        partial signatures share the same sigma_1 = h (signature.rs:452)."""
        from .params import DEFAULT_CTX

        ctx = ctx or DEFAULT_CTX
        if len(sigs) < threshold:
            raise GeneralError(
                "need at least %d signatures, got %d" % (threshold, len(sigs))
            )
        use = sigs[:threshold]
        ids = _validate_share_ids(use, threshold)
        sigma_1 = use[0][1].sigma_1
        for _, s in use[1:]:
            if s.sigma_1 != sigma_1:
                raise GeneralError(
                    "partial signatures disagree on sigma_1 (different requests?)"
                )
        bases = [s.sigma_2 for _, s in use]
        exps = [lagrange_basis_at_0(ids, i) for i, _ in use]
        return Signature(sigma_1, ctx.sig.msm(bases, exps))

    def verify(self, messages, vk, params):
        """Verify a per-signer or aggregated credential (signature.rs:472-478);
        delegates to the PS layer, the TPU-batched hot path."""
        return ps_verify(self, messages, vk, params)

    def to_bytes(self, ctx):
        return ctx.sig_to_bytes(self.sigma_1) + ctx.sig_to_bytes(self.sigma_2)

    @classmethod
    def from_bytes(cls, b, ctx):
        n = ctx.sig_nbytes
        if len(b) != 2 * n:
            raise DeserializationError("malformed Signature encoding")
        return cls(ctx.sig_from_bytes(b[:n]), ctx.sig_from_bytes(b[n:]))

    def __eq__(self, other):
        return (
            isinstance(other, Signature)
            and self.sigma_1 == other.sigma_1
            and self.sigma_2 == other.sigma_2
        )


class SignatureRequest:
    """User-side "PrepareBlindSign" output (signature.rs:51-57,124-207):
    commitment to hidden messages, ElGamal ciphertexts of h^{m_i}, and the
    known (revealed-to-signer) messages."""

    def __init__(self, known_messages, commitment, ciphertexts):
        self.known_messages = list(known_messages)
        self.commitment = commitment
        self.ciphertexts = list(ciphertexts)
        self._h_cache = None

    def get_h(self, ctx):
        """The request's anti-malleability generator, computed once and cached
        (the reference recomputes it at every use site — XXX notes at
        signature.rs:245,360)."""
        if self._h_cache is None:
            self._h_cache = self.compute_h(
                self.commitment, self.known_messages, ctx
            )
        return self._h_cache

    @classmethod
    def new(cls, messages, count_hidden, elgamal_pk, params):
        """Returns (request, randomness) where randomness = [r, k_1..k_hidden]
        feeds the PoK (signature.rs:127-192)."""
        if len(messages) < count_hidden:
            raise GeneralError(
                "count_hidden %d exceeds message count %d"
                % (count_hidden, len(messages))
            )
        if len(messages) != params.msg_count():
            raise UnsupportedNoOfMessages(params.msg_count(), len(messages))
        ops = params.ctx.sig
        randomness = []
        bases = list(params.h[:count_hidden]) + [params.g]
        r = rand_fr()
        exps = list(messages[:count_hidden]) + [r]
        commitment = ops.msm(bases, exps)
        randomness.append(r)
        known_messages = list(messages[count_hidden:])
        ciphertexts = []
        h = None
        if count_hidden > 0:
            h = cls.compute_h(commitment, known_messages, params.ctx)
            for m in messages[:count_hidden]:
                c1, c2, k = elgamal_encrypt(
                    ops, params.g, elgamal_pk, ops.mul(h, m)
                )
                randomness.append(k)
                ciphertexts.append((c1, c2))
        req = cls(known_messages, commitment, ciphertexts)
        req._h_cache = h
        return req, randomness

    @staticmethod
    def compute_h(commitment, known_messages, ctx):
        """Anti-malleability per-request generator
        h = Hash2Group(commitment || known messages) (signature.rs:197-206)."""
        data = ctx.sig_to_bytes(commitment) + b"".join(
            ser.fr_to_bytes(m) for m in known_messages
        )
        return ctx.hash_to_sig(data)

    def to_bytes(self, ctx):
        out = [
            len(self.known_messages).to_bytes(4, "big"),
            len(self.ciphertexts).to_bytes(4, "big"),
        ]
        out.extend(ser.fr_to_bytes(m) for m in self.known_messages)
        out.append(ctx.sig_to_bytes(self.commitment))
        for c1, c2 in self.ciphertexts:
            out.append(ctx.sig_to_bytes(c1))
            out.append(ctx.sig_to_bytes(c2))
        return b"".join(out)

    @classmethod
    def from_bytes(cls, b, ctx):
        if len(b) < 8:
            raise DeserializationError("malformed SignatureRequest encoding")
        n_known = int.from_bytes(b[:4], "big")
        n_ct = int.from_bytes(b[4:8], "big")
        n = ctx.sig_nbytes
        expect = 8 + 32 * n_known + n + 2 * n * n_ct
        if len(b) != expect:
            raise DeserializationError("malformed SignatureRequest encoding")
        o = 8
        known = []
        for _ in range(n_known):
            known.append(ser.fr_from_bytes(b[o : o + 32]))
            o += 32
        commitment = ctx.sig_from_bytes(b[o : o + n])
        o += n
        cts = []
        for _ in range(n_ct):
            c1 = ctx.sig_from_bytes(b[o : o + n])
            c2 = ctx.sig_from_bytes(b[o + n : o + 2 * n])
            cts.append((c1, c2))
            o += 2 * n
        return cls(known, commitment, cts)


def _statement_bytes(sig_req, elgamal_pk, ctx):
    """Statement binding for the issuance PoK's Fiat-Shamir transcript:
    the full request (commitment, known messages, ciphertexts) and the
    ElGamal public key."""
    return sig_req.to_bytes(ctx) + ctx.sig_to_bytes(elgamal_pk)


class SignatureRequestPoK:
    """Commitment phase of the request PoK (signature.rs:106-113,209-269):
    one Schnorr sub-proof for the ElGamal sk, one for the commitment opening,
    two per ciphertext — with shared blindings linking each hidden message
    across the commitment and its ciphertext."""

    def __init__(self, pok_vc_elgamal_sk, pok_vc_commitment, pok_vc_ciphertext,
                 statement):
        self.pok_vc_elgamal_sk = pok_vc_elgamal_sk
        self.pok_vc_commitment = pok_vc_commitment
        self.pok_vc_ciphertext = list(pok_vc_ciphertext)
        self.statement = statement

    @classmethod
    def init(cls, sig_req, elgamal_pk, params):
        ctx = params.ctx
        ops = ctx.sig
        statement = _statement_bytes(sig_req, elgamal_pk, ctx)
        if len(sig_req.known_messages) + len(sig_req.ciphertexts) != len(
            params.h
        ):
            raise UnsupportedNoOfMessages(
                len(params.h),
                len(sig_req.known_messages) + len(sig_req.ciphertexts),
            )
        # (a) knowledge of ElGamal secret key (signature.rs:227-229)
        committing_sk = ProverCommitting(ops, ctx.sig_to_bytes)
        committing_sk.commit(params.g, None)
        committed_sk = committing_sk.finish()
        # (b) knowledge of hidden messages + r in the commitment, with saved
        # blindings reused per ciphertext (signature.rs:232-242)
        committing_comm = ProverCommitting(ops, ctx.sig_to_bytes)
        hidden_msg_blindings = []
        for h_i in params.h[: len(sig_req.ciphertexts)]:
            b = rand_fr()
            committing_comm.commit(h_i, b)
            hidden_msg_blindings.append(b)
        committing_comm.commit(params.g, None)
        committed_comm = committing_comm.finish()
        # (c) two sub-proofs per ciphertext, sharing blinding i
        # (signature.rs:244-259)
        ciphertext_commts = []
        if sig_req.ciphertexts:
            h = sig_req.get_h(ctx)
            for i in range(len(sig_req.ciphertexts)):
                committing_1 = ProverCommitting(ops, ctx.sig_to_bytes)
                committing_1.commit(params.g, None)
                committing_2 = ProverCommitting(ops, ctx.sig_to_bytes)
                committing_2.commit(elgamal_pk, None)
                committing_2.commit(h, hidden_msg_blindings[i])
                ciphertext_commts.append(
                    (committing_1.finish(), committing_2.finish())
                )
        return cls(committed_sk, committed_comm, ciphertext_commts, statement)

    def to_bytes(self):
        """Fiat-Shamir transcript bytes. Extends the reference's transcript
        (signature.rs:271-280) by binding the *statement* — the request bytes
        and the ElGamal public key — closing the weak-Fiat-Shamir gap where
        ciphertexts were absent from the challenge and the ciphertext
        sub-proofs were forgeable non-interactively."""
        out = [self.statement,
               self.pok_vc_elgamal_sk.to_bytes(), self.pok_vc_commitment.to_bytes()]
        for p1, p2 in self.pok_vc_ciphertext:
            out.append(p1.to_bytes())
            out.append(p2.to_bytes())
        return b"".join(out)

    def gen_proof(self, hidden_messages, randomness, elgamal_sk, challenge):
        """Response phase (signature.rs:282-320). `randomness` is the vector
        returned by SignatureRequest.new: [r, k_1..k_hidden]."""
        if len(self.pok_vc_ciphertext) != len(hidden_messages):
            raise UnequalNoOfBasesExponents(
                len(self.pok_vc_ciphertext), len(hidden_messages)
            )
        if len(randomness) != len(self.pok_vc_ciphertext) + 1:
            raise UnequalNoOfBasesExponents(
                len(self.pok_vc_ciphertext) + 1, len(randomness)
            )
        proof_elgamal_sk = self.pok_vc_elgamal_sk.gen_proof(
            challenge, [elgamal_sk]
        )
        secrets_commitment = list(hidden_messages) + [randomness[0]]
        proof_commitment = self.pok_vc_commitment.gen_proof(
            challenge, secrets_commitment
        )
        proof_ciphertexts = []
        for i, (p1, p2) in enumerate(self.pok_vc_ciphertext):
            proof_1 = p1.gen_proof(challenge, [randomness[i + 1]])
            proof_2 = p2.gen_proof(
                challenge, [randomness[i + 1], hidden_messages[i]]
            )
            proof_ciphertexts.append((proof_1, proof_2))
        return SignatureRequestProof(
            proof_elgamal_sk, proof_commitment, proof_ciphertexts
        )


class SignatureRequestProof:
    """Response phase of the request PoK (signature.rs:117-122,323-378)."""

    def __init__(self, proof_elgamal_sk, proof_commitment, proof_ciphertexts):
        self.proof_elgamal_sk = proof_elgamal_sk
        self.proof_commitment = proof_commitment
        self.proof_ciphertexts = list(proof_ciphertexts)

    def verify(self, sig_req, elgamal_pk, challenge, params):
        """Signer-side verification before blind signing (signature.rs:324-377):
        checks the response-equality linkage between the commitment sub-proof
        and each ciphertext sub-proof, then each Schnorr relation."""
        ctx = params.ctx
        ops = ctx.sig
        # attacker-controlled input: every malformed shape is a clean False,
        # never an exception (contrast reference asserts, signature.rs:331-335)
        if len(self.proof_ciphertexts) != len(sig_req.ciphertexts):
            return False
        if len(self.proof_commitment.responses) != len(self.proof_ciphertexts) + 1:
            return False
        if len(self.proof_elgamal_sk.responses) != 1:
            return False
        if not self.proof_elgamal_sk.verify(
            ops, [params.g], elgamal_pk, challenge
        ):
            return False
        bases = list(params.h[: len(sig_req.ciphertexts)]) + [params.g]
        if not self.proof_commitment.verify(
            ops, bases, sig_req.commitment, challenge
        ):
            return False
        h = sig_req.get_h(ctx)
        ct_bases = [elgamal_pk, h]
        for i, (proof_1, proof_2) in enumerate(self.proof_ciphertexts):
            # malformed sub-proof shapes are a clean rejection, not a crash
            if len(proof_1.responses) != 1 or len(proof_2.responses) != 2:
                return False
            # hidden message response must match the commitment sub-proof's
            # (signature.rs:363-367)
            if proof_2.responses[1] != self.proof_commitment.responses[i]:
                return False
            if not proof_1.verify(
                ops, [params.g], sig_req.ciphertexts[i][0], challenge
            ):
                return False
            if not proof_2.verify(
                ops, ct_bases, sig_req.ciphertexts[i][1], challenge
            ):
                return False
        return True

    def to_bytes(self, ctx):
        """Canonical wire encoding (the struct sent user -> signer)."""
        out = [
            self.proof_elgamal_sk.to_bytes(ctx.sig_to_bytes),
            self.proof_commitment.to_bytes(ctx.sig_to_bytes),
            len(self.proof_ciphertexts).to_bytes(4, "big"),
        ]
        for p1, p2 in self.proof_ciphertexts:
            out.append(p1.to_bytes(ctx.sig_to_bytes))
            out.append(p2.to_bytes(ctx.sig_to_bytes))
        return b"".join(out)

    @classmethod
    def from_bytes(cls, b, ctx):
        p_sk, o = Proof.read_from(b, 0, ctx.sig_from_bytes, ctx.sig_nbytes)
        p_comm, o = Proof.read_from(b, o, ctx.sig_from_bytes, ctx.sig_nbytes)
        if len(b) < o + 4:
            raise DeserializationError("malformed SignatureRequestProof")
        n_ct = int.from_bytes(b[o : o + 4], "big")
        o += 4
        cts = []
        for _ in range(n_ct):
            p1, o = Proof.read_from(b, o, ctx.sig_from_bytes, ctx.sig_nbytes)
            p2, o = Proof.read_from(b, o, ctx.sig_from_bytes, ctx.sig_nbytes)
            cts.append((p1, p2))
        if o != len(b):
            raise DeserializationError("trailing bytes in SignatureRequestProof")
        return cls(p_sk, p_comm, cts)

    def to_bytes_for_challenge(self, sig_req, elgamal_pk, params):
        """Reconstruct the prover's transcript bytes (matching
        SignatureRequestPoK.to_bytes) so Fiat-Shamir verifiers recompute the
        challenge — rebuild addition."""
        ctx = params.ctx
        out = [
            _statement_bytes(sig_req, elgamal_pk, ctx),
            self.proof_elgamal_sk.to_bytes_with_bases(
                ctx.sig_to_bytes, [params.g]
            ),
            self.proof_commitment.to_bytes_with_bases(
                ctx.sig_to_bytes,
                list(params.h[: len(sig_req.ciphertexts)]) + [params.g],
            ),
        ]
        if self.proof_ciphertexts:
            h = sig_req.get_h(ctx)
            for p1, p2 in self.proof_ciphertexts:
                out.append(
                    p1.to_bytes_with_bases(ctx.sig_to_bytes, [params.g])
                )
                out.append(
                    p2.to_bytes_with_bases(ctx.sig_to_bytes, [elgamal_pk, h])
                )
        return b"".join(out)


class BlindSignature:
    """Signer-side "BlindSign" and user-side "Unblind"
    (signature.rs:59-64,380-443). The signer does NOT re-verify the request
    PoK here — callers must check SignatureRequestProof first, as the
    reference's tests do (signature.rs:613-616)."""

    def __init__(self, h, blinded):
        self.h = h
        self.blinded = blinded

    @classmethod
    def new(cls, sig_request, sigkey, params):
        hidden_count = len(sig_request.ciphertexts)
        if hidden_count + len(sig_request.known_messages) != len(sigkey.y):
            raise UnsupportedNoOfMessages(
                len(sigkey.y),
                hidden_count + len(sig_request.known_messages),
            )
        ctx = params.ctx
        ops = ctx.sig
        h = sig_request.get_h(ctx)
        c1_bases, c1_exps = [], []
        c2_bases, c2_exps = [], []
        for i, (a, b) in enumerate(sig_request.ciphertexts):
            c1_bases.append(a)
            c1_exps.append(sigkey.y[i])
            c2_bases.append(b)
            c2_exps.append(sigkey.y[i])
        exp = sigkey.x
        for i, m in enumerate(sig_request.known_messages):
            exp = (exp + sigkey.y[hidden_count + i] * m) % R
        c2_bases.append(h)
        c2_exps.append(exp)
        c_tilde_1 = ops.msm(c1_bases, c1_exps)
        c_tilde_2 = ops.msm(c2_bases, c2_exps)
        return cls(h, (c_tilde_1, c_tilde_2))

    def unblind(self, elgamal_sk, ctx):
        """sigma_2 = c_tilde_2 - c_tilde_1^sk (signature.rs:436-443)."""
        ops = ctx.sig
        a_sk = ops.mul(self.blinded[0], elgamal_sk)
        return Signature(self.h, ops.sub(self.blinded[1], a_sk))

    def to_bytes(self, ctx):
        return (
            ctx.sig_to_bytes(self.h)
            + ctx.sig_to_bytes(self.blinded[0])
            + ctx.sig_to_bytes(self.blinded[1])
        )

    @classmethod
    def from_bytes(cls, b, ctx):
        n = ctx.sig_nbytes
        if len(b) != 3 * n:
            raise DeserializationError("malformed BlindSignature encoding")
        return cls(
            ctx.sig_from_bytes(b[:n]),
            (ctx.sig_from_bytes(b[n : 2 * n]), ctx.sig_from_bytes(b[2 * n :])),
        )


def batch_prepare_blind_sign(messages_list, count_hidden, elgamal_pk, params,
                             backend=None):
    """User-side PrepareBlindSign over a batch (VERDICT r2 item 4): the same
    per-request output as `SignatureRequest.new` (signature.rs:124-207) with
    the commitment MSMs, ElGamal scalar mults, and h^{m} terms each batched
    through one backend MSM call. The per-request generator h is derived
    through the native C++ hash-to-group when available (bit-identical to
    the spec; tests/vectors/hashing.json).

    `elgamal_pk` is either ONE ElGamal public key shared by the whole
    batch, or a list of B per-request keys (the engine's prepare lane
    coalesces unrelated users into one batch, so each request encrypts
    under its own key; per-request keys route the pk^k terms through the
    distinct-base MSM instead of the shared comb).

    Returns [(request, randomness)] — randomness = [r, k_1..k_hidden] per
    request, exactly as the sequential path."""
    from .backend import get_backend

    B = len(messages_list)
    if B == 0:
        return []
    # per-request keys arrive as a Python LIST (affine points themselves
    # are tuples, so tuple cannot mean per-request here)
    pk_list = None
    if isinstance(elgamal_pk, list):
        pk_list = list(elgamal_pk)
        if len(pk_list) != B:
            raise GeneralError(
                "elgamal_pk list length %d != batch size %d"
                % (len(pk_list), B)
            )
    if backend is None:
        backend = get_backend("python")
    elif isinstance(backend, str):
        backend = get_backend(backend)
    ctx = params.ctx
    ops = ctx.sig
    q = params.msg_count()
    for msgs in messages_list:
        if len(msgs) != q:
            raise UnsupportedNoOfMessages(q, len(msgs))
        if len(msgs) < count_hidden:
            raise GeneralError(
                "count_hidden %d exceeds message count %d"
                % (count_hidden, len(msgs))
            )
    msm_shared = (
        backend.msm_g1_shared if ctx.name == "G1" else backend.msm_g2_shared
    )
    msm_distinct = (
        backend.msm_g1_distinct
        if ctx.name == "G1"
        else backend.msm_g2_distinct
    )

    # commitments: shared bases [h_0..h_hidden-1, g], per-request scalars
    rs = [rand_fr() for _ in range(B)]
    commit_bases = list(params.h[:count_hidden]) + [params.g]
    commit_rows = [
        list(m[:count_hidden]) + [r] for m, r in zip(messages_list, rs)
    ]
    known_lists = [list(m[count_hidden:]) for m in messages_list]
    ks = [[rand_fr() for _ in range(count_hidden)] for _ in range(B)]
    flat_k = [[k] for row in ks for k in row]

    if count_hidden == 0:
        commitments = msm_shared(commit_bases, commit_rows)
        return [
            (SignatureRequest(k, c, []), [r])
            for k, c, r in zip(known_lists, commitments, rs)
        ]

    # The phase's device work is three shared-base comb MSM jobs
    # (commitments, ElGamal g^k, ElGamal pk^k) plus one distinct-base MSM
    # (h_i^{m_ij}) that DEPENDS on the commitments through the per-request
    # hash h = H(commitment || known) (the reference's anti-malleability
    # generator, signature.rs:194-206). With an async-capable backend the
    # schedule hides the host hash loop and result decodes behind device
    # execution: dispatch commitments, dispatch the (independent) ElGamal
    # jobs behind them, block only on commitments, hash while the device
    # runs the ElGamal program, dispatch h^m, then decode the ElGamal
    # results while h^m executes (VERDICT r3 item 4).
    from .backend import async_distinct_api, async_shared_many_api

    grp = "g1" if ctx.name == "G1" else "g2"
    many_api = async_shared_many_api(backend, grp)
    distinct_api = async_distinct_api(backend, grp)
    many = getattr(backend, "msm_%s_shared_many" % grp, None)
    elg_handle = None
    if pk_list is not None:
        # per-request keys: pk is a distinct base per lane, so the
        # shared-comb ElGamal program does not apply — take the
        # synchronous path with pk^k through the distinct-base MSM
        commitments = msm_shared(commit_bases, commit_rows)
        gk = msm_shared([params.g], flat_k)
        pkk = msm_distinct(
            [[pk_list[i]] for i in range(B) for _ in range(count_hidden)],
            flat_k,
        )
    elif many_api is not None:
        many_dispatch, many_wait = many_api
        commit_handle = many_dispatch([(commit_bases, commit_rows)])
        elg_handle = many_dispatch(
            [([params.g], flat_k), ([elgamal_pk], flat_k)]
        )
        (commitments,) = many_wait(commit_handle)
    elif many is not None:
        commitments, gk, pkk = many(
            [
                (commit_bases, commit_rows),
                ([params.g], flat_k),
                ([elgamal_pk], flat_k),
            ]
        )
    else:
        commitments = msm_shared(commit_bases, commit_rows)
        gk = msm_shared([params.g], flat_k)
        pkk = msm_shared([elgamal_pk], flat_k)

    # per-request anti-malleability generator h (hash of public data);
    # the native core is ~2 orders faster than the Python spec here.
    # On the async path this loop overlaps the ElGamal device program.
    from . import native as _native

    hash_native = ctx.name == "G1" and _native.available()
    hash_device = (
        ctx.name == "G1"
        and getattr(backend, "hash_to_g1_batch", None) is not None
        and getattr(backend, "device_hash_enabled", None) is not None
        and backend.device_hash_enabled()
    )
    datas = [
        ctx.sig_to_bytes(c) + b"".join(ser.fr_to_bytes(m) for m in known)
        for c, known in zip(commitments, known_lists)
    ]
    if hash_device:
        # the SvdW map + cofactor clear run as one jitted device program;
        # only the cheap expand_message_xmd stays on host (the 1,024
        # serial host hashes were the prepare phase's wall). A device
        # failure propagates like any other dispatch failure.
        hs = backend.hash_to_g1_batch(datas)
    elif hash_native:
        # one FFI round trip for the whole batch
        hs = _native.hash_to_g1_batch(datas)
    else:
        hs = [ctx.hash_to_sig(d) for d in datas]

    # the per-request h^{m_ij} terms need h, which needs the commitment
    # hash — an unavoidable host round trip between the two programs
    hm_points = [[h] for h in hs for _ in range(count_hidden)]
    hm_scalars = [
        [m % R] for msgs in messages_list for m in msgs[:count_hidden]
    ]
    from .backend import async_distinct_plus_offset_api

    offset_api = async_distinct_plus_offset_api(backend, grp)
    c2s = None
    if elg_handle is not None and offset_api is not None:
        # c2 = pk^k + h^m assembled ON DEVICE: the ElGamal program's pk^k
        # output triple feeds the h^m MSM program as a per-lane offset
        # (device-to-device), replacing the host decode of pk^k plus
        # B*hidden host point-adds
        offset_dispatch, offset_wait = offset_api
        c2_handle = offset_dispatch(hm_points, hm_scalars, elg_handle[1])
        (gk,) = many_wait((elg_handle[0],))
        c2s = offset_wait(c2_handle)
    elif elg_handle is not None and distinct_api is not None:
        distinct_dispatch, distinct_wait = distinct_api
        hm_handle = distinct_dispatch(hm_points, hm_scalars)
        gk, pkk = many_wait(elg_handle)
        hm = distinct_wait(hm_handle)
    else:
        if elg_handle is not None:
            gk, pkk = many_wait(elg_handle)
        hm = msm_distinct(hm_points, hm_scalars)
    out = []
    for i, (msgs, known, c, h, r) in enumerate(
        zip(messages_list, known_lists, commitments, hs, rs)
    ):
        cts = []
        for j in range(count_hidden):
            f = i * count_hidden + j
            c2 = c2s[f] if c2s is not None else ops.add(pkk[f], hm[f])
            cts.append((gk[f], c2))
        req = SignatureRequest(known, c, cts)
        req._h_cache = h
        out.append((req, [r] + ks[i]))
    return out


def batch_blind_sign(sig_requests, sigkey, params, backend=None):
    """Signer-side BlindSign over a batch of requests (BASELINE config 4).

    Same math as `BlindSignature.new` per request (reference
    signature.rs:396-428: c_tilde_1 = prod a_i^{y_i},
    c_tilde_2 = prod b_i^{y_i} * h^{x + sum y_j m_j}), but the two MSMs of
    every request run as ONE batched distinct-base MSM each through the
    backend — the bases (ciphertext points, h) differ per request, so this
    uses the `msm_*_distinct` primitive, not the shared-table path.

    All requests must have the same hidden/known message split. Callers must
    have verified each request's PoK first (signature.rs:613-616).
    Returns [B] BlindSignature.

    Timing discipline: the scalars here are the signer's long-term secrets
    (the reference runs these MSMs const-time, signature.rs:424-428). The
    JAX device path is a static XLA schedule whose execution time is
    measured independent of secret digit values (CONSTTIME.md: 3% median
    spread across digit-extreme keys, inside the run-to-run noise);
    its residual caveat is host-side big-int encode work with
    bit-length-correlated sub-ms timing. Pass backend="cpp_ct" for the
    native masked-lookup schedule when host-resident attackers with
    sub-ms timing oracles are in scope; the Python spec path is a
    variable-time development vehicle only."""
    from .backend import get_backend

    if not sig_requests:
        return []
    if backend is None:
        backend = get_backend("python")
    elif isinstance(backend, str):
        backend = get_backend(backend)
    ctx = params.ctx
    hidden_count = len(sig_requests[0].ciphertexts)
    for req in sig_requests:
        if len(req.ciphertexts) != hidden_count or len(
            req.known_messages
        ) != len(sigkey.y) - hidden_count:
            raise UnsupportedNoOfMessages(
                len(sigkey.y),
                len(req.ciphertexts) + len(req.known_messages),
            )
    from .backend import async_distinct_api

    hs = [req.get_h(ctx) for req in sig_requests]
    g1 = ctx.name == "G1"
    msm = backend.msm_g1_distinct if g1 else backend.msm_g2_distinct
    c2_points, c2_scalars = [], []
    for req, h in zip(sig_requests, hs):
        exp = sigkey.x
        for i, m in enumerate(req.known_messages):
            exp = (exp + sigkey.y[hidden_count + i] * m) % R
        c2_points.append([b for _, b in req.ciphertexts] + [h])
        c2_scalars.append(list(sigkey.y[:hidden_count]) + [exp])
    B = len(sig_requests)
    fused = async_distinct_api(backend, "g1" if g1 else "g2")
    if fused is not None:
        # ONE fused distinct-base MSM for both c_tilde_1 and c_tilde_2: the
        # c_tilde_1 rows (k = hidden) pad with an identity base / zero
        # scalar to the c_tilde_2 width (k = hidden + 1) and stack into a
        # [2B, hidden+1] batch — one device dispatch + readback instead of
        # two (the round-3 issuance path was dispatch-bound, VERDICT r3
        # item 4). Only the single-dispatch device backend gains from the
        # stacking; per-row backends would pay the dummy column for nothing.
        points = [
            [a for a, _ in req.ciphertexts] + [None] for req in sig_requests
        ] + c2_points
        scalars = [
            list(sigkey.y[:hidden_count]) + [0] for _ in sig_requests
        ] + c2_scalars
        fused_dispatch, fused_wait = fused
        out = fused_wait(fused_dispatch(points, scalars))
        c1s, c2s = out[:B], out[B:]
    elif hidden_count == 0:
        c1s = [None] * B  # no ciphertexts -> c_tilde_1 is the identity
        c2s = msm(c2_points, c2_scalars)
    else:
        c1s = msm(
            [[a for a, _ in req.ciphertexts] for req in sig_requests],
            [list(sigkey.y[:hidden_count])] * B,
        )
        c2s = msm(c2_points, c2_scalars)
    return [
        BlindSignature(h, (c1, c2)) for h, c1, c2 in zip(hs, c1s, c2s)
    ]


def batch_unblind(blind_sigs, elgamal_sk, ctx, backend=None):
    """User-side Unblind over a batch: sigma_2 = c_tilde_2 - c_tilde_1^sk
    (signature.rs:436-443), the scalar muls batched as a k=1 distinct MSM.

    `elgamal_sk` is either ONE secret shared by every blind signature (the
    original single-user batch) or a LIST aligned with `blind_sigs` — the
    threshold-issuance service unblinds many users' partials in one call,
    each under its own ElGamal secret (coconut_tpu/issue/quorum.py)."""
    from .backend import get_backend

    if not blind_sigs:
        return []
    if backend is None:
        backend = get_backend("python")
    elif isinstance(backend, str):
        backend = get_backend(backend)
    if isinstance(elgamal_sk, (list, tuple)):
        if len(elgamal_sk) != len(blind_sigs):
            raise GeneralError(
                "per-signature elgamal_sk list length %d != %d blind "
                "signatures" % (len(elgamal_sk), len(blind_sigs))
            )
        sk_rows = [[sk] for sk in elgamal_sk]
    else:
        sk_rows = [[elgamal_sk]] * len(blind_sigs)
    msm = (
        backend.msm_g1_distinct
        if ctx.name == "G1"
        else backend.msm_g2_distinct
    )
    a_sks = msm(
        [[bs.blinded[0]] for bs in blind_sigs],
        sk_rows,
    )
    ops = ctx.sig
    return [
        Signature(bs.h, ops.sub(bs.blinded[1], a_sk))
        for bs, a_sk in zip(blind_sigs, a_sks)
    ]


def batch_aggregate(threshold, partials_list, ctx=None, backend=None):
    """Lagrange-aggregate MANY requests' partial-signature subsets in one
    batched distinct-base MSM (the threshold-issuance hot path,
    coconut_tpu/issue/quorum.py).

    partials_list: one entry per request, each a list of
    (signer_id, Signature) pairs — the same shape `Signature.aggregate`
    takes; every entry is validated the same way (>= threshold partials,
    distinct in-range ids, shared sigma_1) and aggregated over its FIRST
    `threshold` pairs. Where `Signature.aggregate` runs one [t]-point MSM
    per credential, this runs ONE [B, t] distinct MSM through the backend,
    so minting a coalesced batch costs one dispatch. Bit-identical to the
    sequential path (tests/test_issue.py pins the parity)."""
    from .backend import get_backend

    if not partials_list:
        return []
    from .params import DEFAULT_CTX

    ctx = ctx or DEFAULT_CTX
    if backend is None:
        backend = get_backend("python")
    elif isinstance(backend, str):
        backend = get_backend(backend)
    sigma_1s, rows_bases, rows_exps = [], [], []
    for sigs in partials_list:
        if len(sigs) < threshold:
            raise GeneralError(
                "need at least %d signatures, got %d" % (threshold, len(sigs))
            )
        use = sigs[:threshold]
        ids = _validate_share_ids(use, threshold)
        sigma_1 = use[0][1].sigma_1
        for _, s in use[1:]:
            if s.sigma_1 != sigma_1:
                raise GeneralError(
                    "partial signatures disagree on sigma_1 (different requests?)"
                )
        sigma_1s.append(sigma_1)
        rows_bases.append([s.sigma_2 for _, s in use])
        rows_exps.append([lagrange_basis_at_0(ids, i) for i, _ in use])
    msm = (
        backend.msm_g1_distinct
        if ctx.name == "G1"
        else backend.msm_g2_distinct
    )
    sigma_2s = msm(rows_bases, rows_exps)
    return [Signature(s1, s2) for s1, s2 in zip(sigma_1s, sigma_2s)]


def fiat_shamir_challenge(transcript_bytes):
    """The challenge convention used at every reference call site
    (signature.rs:598, pok_sig.rs:94): hash the PoK transcript to Fr."""
    return hash_to_fr(transcript_bytes)
