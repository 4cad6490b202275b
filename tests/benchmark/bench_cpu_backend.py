"""A CPU stand-in for the device backend, on the native core, so the
benchmark's drivers run end to end in a test without a chip; and backends
with one fault planted in the timed path."""

from coconut_tpu.backend import get_backend
from coconut_tpu.native import CppBackend


class NativeBackend(CppBackend):
    """The native core with a batched show verify (the two checks of
    PoKOfSignatureProof.verify per lane: the Schnorr relation on J and
    the pairing product)."""

    def batch_show_verify(self, proofs, vk, params, revealed_list, challenges):
        ctx = params.ctx
        revealed = sorted(proofs[0].revealed_msg_indices)
        hidden = [i for i in range(len(vk.Y_tilde)) if i not in revealed]
        bases = [params.g_tilde] + [vk.Y_tilde[i] for i in hidden]
        lhs = self.msm_g2_distinct(
            [bases + [p.J] for p in proofs],
            [list(p.proof_vc.responses) + [c]
             for p, c in zip(proofs, challenges)],
        )
        accs = self.msm_g2_distinct(
            [[p.J, vk.X_tilde] + [vk.Y_tilde[i] for i in revealed]
             for p in proofs],
            [[1, 1] + [rm[i] for i in revealed] for rm in revealed_list],
        )
        ok = self.pairing_product_is_one(
            [
                [(p.sigma_prime_1, acc),
                 (ctx.sig.neg(p.sigma_prime_2), params.g_tilde)]
                for p, acc in zip(proofs, accs)
            ]
        )
        return [
            p.sigma_prime_1 is not None and l == p.proof_vc.t and bool(o)
            for p, l, o in zip(proofs, lhs, ok)
        ]


class FlipOneVerdict(NativeBackend):
    """An answer altered where it is produced: the first lane's verdict of
    every batch is inverted."""

    def batch_verify(self, *a):
        bits = super().batch_verify(*a)
        return [not bits[0]] + bits[1:]

    def batch_show_verify(self, *a):
        bits = super().batch_show_verify(*a)
        return [not bits[0]] + bits[1:]


class HalfBatch(NativeBackend):
    """Half of the batch left out: only the first half is verified, the
    rest reads True."""

    def batch_verify(self, sigs, msgs, vk, params):
        h = len(sigs) // 2
        return super().batch_verify(sigs[:h], msgs[:h], vk, params) + [True] * (
            len(sigs) - h
        )

    def batch_show_verify(self, proofs, vk, params, revealed, challenges):
        h = len(proofs) // 2
        return super().batch_show_verify(
            proofs[:h], vk, params, revealed[:h], challenges[:h]
        ) + [True] * (len(proofs) - h)


class AlteredAggregate(NativeBackend):
    """A minted credential altered where it is produced: every Lagrange
    aggregate's sigma_2 is doubled. The verify-before-release gate stops
    it, so the mints fail."""

    def msm_g1_distinct(self, points_batch, scalars_batch):
        out = super().msm_g1_distinct(points_batch, scalars_batch)
        if out and len(points_batch[0]) == 3:  # the [B, t] aggregate MSM
            cpp = get_backend("cpp")
            out = [cpp.msm_g1_shared([p], [[2]])[0] for p in out]
        return out


class UngatedAlteredAggregate(AlteredAggregate):
    """The same, with the verify-before-release gate passing everything:
    the altered credentials are released and only the reference sees
    them."""

    def batch_verify(self, sigs, *a):
        return [True] * len(sigs)


class UngatedAlteredOneLane(NativeBackend):
    """One lane of every Lagrange aggregate altered (sigma_2 doubled at
    lane 1) and the release gate passing everything: one credential in
    each batch is wrong."""

    def msm_g1_distinct(self, points_batch, scalars_batch):
        out = super().msm_g1_distinct(points_batch, scalars_batch)
        if len(out) > 1 and len(points_batch[0]) == 3:
            out = list(out)
            out[1] = get_backend("cpp").msm_g1_shared([out[1]], [[2]])[0]
        return out

    def batch_verify(self, sigs, *a):
        return [True] * len(sigs)


class FailingShowVerify(NativeBackend):
    """After its first two batches every show verify raises, so the
    window's requests fail instead of answering."""

    calls = 0

    def batch_show_verify(self, *a):
        self.calls += 1
        if self.calls > 2:
            raise RuntimeError("planted show-verify failure")
        return super().batch_show_verify(*a)
