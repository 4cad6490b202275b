"""Multi-chip sharded batch verification over a (dp, tp) device mesh.

The TPU-native answer to SURVEY.md §2.3's parallelism table:

  - **dp** (data parallelism): the credential batch is sharded over the mesh's
    ``dp`` axis — each device verifies its slice independently. This is the
    primary axis; the workload (one pairing check per credential, reference
    signature.rs:472-478) is embarrassingly data-parallel.
  - **tp** (tensor parallelism / sharded MSM): the shared-base MSM inside each
    verification (the X̃·∏Ỹⱼ^{mⱼ} accumulator, SURVEY.md §3.4) is sharded
    over the ``tp`` axis by *base index*: each device computes a partial MSM
    over its subset of bases, partials are combined with an
    ``all_gather`` + Jacobian-add tree inside ``shard_map`` (point addition is
    not a ring sum, so ``psum`` does not apply — the combine rides the same
    ICI links), and every device then runs the pairing tail on its dp-slice.

Collectives ride ICI via XLA (`all_gather` over the tp axis); nothing here
depends on device count — the same program runs on a v5e-8 mesh or the
8-device virtual CPU mesh the tests use (conftest.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from . import backend as bk
from . import curve as cv


_PROGRAM_CACHE = {}


def require_axes(mesh, *axes):
    """Check that `mesh` names every axis in `axes`, with a clear error up
    front instead of a bare KeyError from mesh.shape['tp'] deep inside the
    first batch's dispatch."""
    missing = [a for a in axes if a not in mesh.shape]
    if missing:
        raise ValueError(
            "mesh is missing axis(es) %s: it has %s; build the mesh with "
            "shard.default_mesh() or Mesh(devices, ('dp', 'tp'))"
            % (
                ", ".join(repr(a) for a in missing),
                tuple(mesh.shape) or "no axes",
            )
        )


def _shard_map(local, mesh, in_specs, out_specs):
    """shard_map with check_vma=False: the scans initialize carries from
    replicated constants that become mesh-varying inside the loop — sound,
    since every sharded program's outputs are asserted bit-identical to
    the spec path, but rejected by the static vma check."""
    return shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def make_sharded_verify(mesh, sig_is_g1, batch_axis="dp", msm_axis="tp"):
    """Build the jitted shard_map'd fused-verify program for `mesh`.

    Operands are the same tuple `JaxBackend.encode_verify_batch` produces,
    with the base axis padded to a multiple of the tp axis size and the batch
    divisible by the dp axis size. Returns bits [B] (fully replicated gather
    of the dp shards).

    Programs are memoized per (mesh, flavor, axes): a fresh closure + jit
    per call would defeat jit's function-identity cache and re-pay the
    multi-minute fused compile on every batch of a streamed run."""
    key = (mesh, sig_is_g1, batch_axis, msm_axis)
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached
    ntp = mesh.shape[msm_axis]
    acc_fl = cv.FP2 if sig_is_g1 else cv.FP

    def local(wtables, mag, sgn, s1, s2n, gtx, gty, inf1, inf2):
        # wtables: leading [k/ntp, nwin, 17]; mag/sgn: [B/ndp, k/ntp, nwin]
        acc = cv.msm_shared_comb(acc_fl, wtables, mag, sgn)
        if ntp > 1:
            parts = jax.lax.all_gather(acc, msm_axis)  # leaves [ntp, ...]

            def take(i):
                return jax.tree_util.tree_map(lambda t: t[i], parts)

            acc = take(0)
            for i in range(1, ntp):
                acc = cv.jadd(acc_fl, acc, take(i))
        return bk.verify_tail(sig_is_g1, acc, s1, s2n, gtx, gty, inf1, inf2)

    in_specs = (
        P(msm_axis),  # comb tables: bases sharded
        P(batch_axis, msm_axis),  # mag: batch x bases
        P(batch_axis, msm_axis),  # sgn
        P(batch_axis),  # s1
        P(batch_axis),  # s2n
        P(),  # gtx (replicated constant)
        P(),  # gty
        P(batch_axis),  # inf1
        P(batch_axis),  # inf2
    )
    # check_vma=False (via _shard_map): the Miller/MSM scans initialize
    # carries from replicated constants (identity points, GT one) that
    # become mesh-varying inside the loop — sound here (outputs are
    # asserted bit-identical to the spec path), but the static vma type
    # check rejects it.
    jitted = jax.jit(_shard_map(local, mesh, in_specs, P(batch_axis)))
    _PROGRAM_CACHE[key] = jitted
    return jitted


def make_sharded_grouped_verify(mesh, sig_is_g1, batch_axis="dp"):
    """The HEADLINE program, sharded: dp-shard the credential batch of the
    attribute-grouped one-bool verify (backend.fused_verify_grouped).

    Each device runs the q+2 shared-point grouped MSMs on its credential
    slice; the projective accumulators (point sums — order-independent,
    the complete RCB formulas are exact) are combined across the dp axis
    with an all_gather + Jacobian-add tree, and every device then runs the
    identical q+2-pair pairing tail, returning the replicated batch bool.
    The identity-sigma death flag is psum-reduced so ANY device's dead lane
    fails the whole batch, exactly like the single-chip kernel."""
    key = ("grouped", mesh, sig_is_g1, batch_axis)
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached
    ndp = mesh.shape[batch_axis]
    sig_fl = cv.FP if sig_is_g1 else cv.FP2

    def local(s1, s2n, inf1, inf2, cmag, csgn, rmag, rsgn, ox, oy, gtx, gty):
        allacc = bk.grouped_accumulators(
            sig_fl, s1, s2n, inf1, inf2, cmag, csgn, rmag, rsgn
        )
        if ndp > 1:
            parts = jax.lax.all_gather(allacc, batch_axis)  # leaves [ndp, ..]

            def take(i):
                return jax.tree_util.tree_map(lambda t: t[i], parts)

            allacc = take(0)
            for i in range(1, ndp):
                allacc = cv.jadd(sig_fl, allacc, take(i))
        dead = jnp.any(inf1 | inf2).astype(jnp.int32)
        any_dead = jax.lax.psum(dead, batch_axis) > 0
        return bk.grouped_tail(sig_is_g1, allacc, ox, oy, gtx, gty, any_dead)

    in_specs = (
        P(batch_axis),  # s1 (coordinate pytree, leading [B])
        P(batch_axis),  # s2n
        P(batch_axis),  # inf1
        P(batch_axis),  # inf2
        P(None, batch_axis),  # cmag [q+1, B, nwin]
        P(None, batch_axis),  # csgn
        P(None, batch_axis),  # rmag [1, B, nwin_r]
        P(None, batch_axis),  # rsgn
        P(),  # ox (replicated verkey points)
        P(),  # oy
        P(),  # gtx
        P(),  # gty
    )
    jitted = jax.jit(_shard_map(local, mesh, in_specs, P()))
    _PROGRAM_CACHE[key] = jitted
    return jitted


def batch_verify_grouped_sharded(
    backend, sigs, messages_list, vk, params, mesh, batch_axis="dp",
    pad_batch_to=None,
):
    """dp-sharded attribute-grouped batch verify on a mesh: ONE bool for
    the whole batch, same semantics (and 2^-128 soundness) as
    `JaxBackend.batch_verify_grouped`. The batch is padded to a power of
    two divisible by the dp extent (pad_batch_to, default 2x the dp
    extent; the dryrun passes ndp for the one-lane-per-device minimum);
    per-device slices stay powers of two (fold_points requires it)."""
    require_axes(mesh, batch_axis)
    ndp = mesh.shape[batch_axis]
    if ndp & (ndp - 1):
        raise ValueError("dp extent %d must be a power of two" % ndp)
    if len(sigs) == 0:
        return True
    if any(s.sigma_1 is None or s.sigma_2 is None for s in sigs):
        return False
    operands = backend.encode_grouped_batch(
        sigs, messages_list, vk, params,
        pad_batch_to=2 * ndp if pad_batch_to is None else pad_batch_to,
    )
    fn = make_sharded_grouped_verify(
        mesh, params.ctx.name == "G1", batch_axis
    )
    return bool(fn(*operands))


def batch_verify_grouped_sharded_async(
    backend, sigs, messages_list, vk, params, mesh, batch_axis="dp",
    pad_batch_to=None,
):
    """Pipelined variant of `batch_verify_grouped_sharded`: dispatches the
    sharded grouped program (JAX dispatch is asynchronous) and returns a
    zero-arg finalizer, so `stream.verify_stream` can overlap batch i+1's
    host encode with batch i's mesh execution — config 5 on a mesh."""
    require_axes(mesh, batch_axis)
    ndp = mesh.shape[batch_axis]
    if ndp & (ndp - 1):
        raise ValueError("dp extent %d must be a power of two" % ndp)
    if len(sigs) == 0:
        return lambda: True
    if any(s.sigma_1 is None or s.sigma_2 is None for s in sigs):
        return lambda: False
    operands = backend.encode_grouped_batch(
        sigs, messages_list, vk, params,
        pad_batch_to=2 * ndp if pad_batch_to is None else pad_batch_to,
    )
    fn = make_sharded_grouped_verify(
        mesh, params.ctx.name == "G1", batch_axis
    )
    ok = fn(*operands)
    return lambda: bool(ok)


def make_sharded_show_verify(mesh, sig_is_g1, batch_axis="dp"):
    """dp-sharded batched show-verify (config 3 on a mesh): each device runs
    the fused Schnorr + pairing checks (backend.fused_show_verify) on its
    slice of proofs; bits are per-proof, so no cross-device combine is
    needed — the output stays dp-sharded and gathers on readback."""
    key = ("show", mesh, sig_is_g1, batch_axis)
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached

    def local(*ops):
        return bk.fused_show_verify(sig_is_g1, *ops)

    dp = P(batch_axis)
    in_specs = (
        P(),  # vc_wtables (shared Schnorr bases, replicated)
        dp,  # resp_mag [B, k, nwin]
        dp,  # resp_sgn
        dp,  # jpt (J coordinate pytree, leading [B])
        dp,  # jinf
        dp,  # cmag_j [B, 1, nwin]
        dp,  # csgn_j
        dp,  # commx
        dp,  # commy
        dp,  # comminf
        P(),  # acc_wtables (replicated)
        dp,  # acc_mag
        dp,  # acc_sgn
        dp,  # s1
        dp,  # s2n
        P(),  # gtx
        P(),  # gty
        dp,  # inf1
        dp,  # inf2
    )
    jitted = jax.jit(_shard_map(local, mesh, in_specs, P(batch_axis)))
    _PROGRAM_CACHE[key] = jitted
    return jitted


def batch_show_verify_sharded(
    backend, proofs, vk, params, revealed_msgs_list, challenges, mesh,
    batch_axis="dp",
):
    """dp-sharded batched PoKOfSignatureProof.verify on a mesh: [B] bools,
    bit-identical to `JaxBackend.batch_show_verify` (reference surface
    pok_sig.rs:103-105). The proof batch must divide the dp extent."""
    require_axes(mesh, batch_axis)
    ndp = mesh.shape[batch_axis]
    if len(proofs) % ndp:
        raise ValueError(
            "batch size %d not divisible by %s=%d"
            % (len(proofs), batch_axis, ndp)
        )
    operands = backend.encode_show_verify_batch(
        proofs, vk, params, revealed_msgs_list, challenges
    )
    fn = make_sharded_show_verify(
        mesh, params.ctx.name == "G1", batch_axis
    )
    bits = fn(*operands)
    return [bool(b) for b in np.asarray(bits)]


def pad_to_multiple(k, n):
    return ((k + n - 1) // n) * n


class _IdentityLane:
    """Identity-signature pad lane (`sigma_1 is None`): verifies False by
    the reference rule (signature.rs:472-478) and encodes as the point at
    infinity, so a pad lane can never flip a real lane's verdict — the
    same identity-lane convention serve/batcher.PAD_CREDENTIAL and
    `encode_verify_batch(pad_bases_to=...)` use."""

    __slots__ = ()
    sigma_1 = None
    sigma_2 = None


PAD_LANE = _IdentityLane()


def batch_verify_sharded_async(
    backend, sigs, messages_list, vk, params, mesh, batch_axis="dp",
    msm_axis="tp",
):
    """Pipelined variant of `batch_verify_sharded` ([B] bools, the
    reference's per-credential verdict semantics, signature.rs:472-478):
    dispatches the sharded fused program and returns a zero-arg finalizer
    so `stream.verify_stream(mode='per_credential', mesh=...)` can keep
    the mesh busy across the readback round trip.

    The final batch of a stream rarely divides the dp extent; it is padded
    with IDENTITY lanes up to the next multiple (ADVICE r5 #1 — matching
    the grouped mesh path's identity-lane encode convention rather than
    duplicating a real credential) and the verdict bits are sliced back to
    the true length, so callers never see the padding (identity lanes
    verify False; verdicts are per-lane, so pad lanes cannot affect real
    ones)."""
    require_axes(mesh, batch_axis, msm_axis)
    ndp = mesh.shape[batch_axis]
    ntp = mesh.shape[msm_axis]  # the sharded program requires both axes
    B = len(sigs)
    if B == 0:
        return lambda: []
    pad = (-B) % ndp
    if pad:
        sigs = list(sigs) + [PAD_LANE] * pad
        messages_list = list(messages_list) + [messages_list[-1]] * pad
    k = 1 + len(vk.Y_tilde)
    operands = backend.encode_verify_batch(
        sigs, messages_list, vk, params, pad_bases_to=pad_to_multiple(k, ntp)
    )
    fn = make_sharded_verify(mesh, params.ctx.name == "G1", batch_axis, msm_axis)
    bits = fn(*operands)
    return lambda: [bool(b) for b in np.asarray(bits)[:B]]


# --- sharded issuance (config 4 on a mesh) ----------------------------------


def make_sharded_distinct(mesh, is_fp2, with_offset, batch_axis="dp"):
    """dp-sharded distinct-base MSM program (the issuance/show shape:
    per-credential bases, on-device tables — backend's
    _msm_distinct_affine_kernel / _msm_distinct_plus_offset_kernel).
    Every operand leads with the batch axis, so the spec is a plain dp
    shard per leaf; outputs stay dp-sharded and gather on readback."""
    key = ("distinct", mesh, is_fp2, with_offset, batch_axis)
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached
    fl = cv.FP2 if is_fp2 else cv.FP

    def local(x, y, inf, mag, sgn, *offset):
        x, y = bk._pts_f32((x, y))
        acc = cv.msm_distinct_signed(fl, x, y, inf, mag, sgn)
        if offset:
            ox, oy, oinf = offset
            ox, oy = bk._unpack_pt(ox, oy)
            off = cv.affine_to_jacobian(fl, ox, oy, oinf)
            acc = cv.jadd(fl, acc, off)
        ax, ay, ainf = cv.to_affine(fl, acc)
        return (*bk._pack_pt(ax, ay), ainf)

    dp = P(batch_axis)
    nargs = 8 if with_offset else 5
    jitted = jax.jit(
        _shard_map(local, mesh, (dp,) * nargs, (dp, dp, dp))
    )
    _PROGRAM_CACHE[key] = jitted
    return jitted


def make_sharded_shared_many(mesh, is_fp2, njobs, batch_axis="dp"):
    """dp-sharded multi-job shared-base comb MSM (the prepare phase's
    fused program, backend._msm_shared_many_kernel): comb tables are
    replicated (fixed bases), digit arrays shard over the batch axis."""
    key = ("shared_many", mesh, is_fp2, njobs, batch_axis)
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached
    fl = cv.FP2 if is_fp2 else cv.FP
    dp = P(batch_axis)

    def local(jobs):
        outs = []
        for wt, mag, sgn in jobs:
            x, y, inf = cv.to_affine(fl, cv.msm_shared_comb(fl, wt, mag, sgn))
            outs.append((*bk._pack_pt(x, y), inf))
        return tuple(outs)

    in_specs = (tuple((P(), dp, dp) for _ in range(njobs)),)
    out_specs = tuple((dp, dp, dp) for _ in range(njobs))
    jitted = jax.jit(_shard_map(local, mesh, in_specs, out_specs))
    _PROGRAM_CACHE[key] = jitted
    return jitted


class ShardedIssuanceBackend(bk.JaxBackend):
    """JaxBackend with the issuance-shape MSM programs dp-sharded over a
    mesh, so the protocol drivers — `signature.batch_prepare_blind_sign`,
    `signature.batch_blind_sign`, `signature.batch_unblind`,
    `pok_sig.batch_show` — run unchanged with each device computing its
    slice of the credential batch (config 4 multi-chip; reference surface
    signature.rs:124-207, 380-433). Verify-side entry points inherit the
    sharded variants' superclass behavior (single-device); use the
    dedicated `batch_verify_*_sharded` drivers for those.

    Batch sizes must divide the dp extent (the prepare driver's row
    counts are B and B*hidden, so B must be a multiple of ndp and the
    hidden count is unconstrained)."""

    name = "jax_sharded_issuance"

    def __init__(self, mesh, batch_axis="dp"):
        require_axes(mesh, batch_axis)
        self.mesh = mesh
        self.batch_axis = batch_axis

    def _check_rows(self, n):
        ndp = self.mesh.shape[self.batch_axis]
        if n % ndp:
            raise ValueError(
                "row count %d not divisible by %s=%d"
                % (n, self.batch_axis, ndp)
            )

    def _msm_distinct(self, is_fp2, points_batch, scalars_batch):
        ops = self._encode_distinct(is_fp2, points_batch, scalars_batch)
        self._check_rows(ops[2].shape[0])
        fn = make_sharded_distinct(self.mesh, is_fp2, False, self.batch_axis)
        return fn(*ops)

    def _msm_distinct_plus_offset(
        self, is_fp2, points_batch, scalars_batch, offset_handle
    ):
        ops = self._encode_distinct(is_fp2, points_batch, scalars_batch)
        self._check_rows(ops[2].shape[0])
        fn = make_sharded_distinct(self.mesh, is_fp2, True, self.batch_axis)
        return fn(*ops, *offset_handle)

    def _msm_shared_many_dispatch(self, spec_ops, is_fp2, jobs):
        operands = []
        for bases, scalars_batch in jobs:
            wt = bk._comb_tables(spec_ops, is_fp2, bases)
            mag, sgn = bk._comb_digits(scalars_batch)
            self._check_rows(mag.shape[0])
            operands.append((wt, mag, sgn))
        fn = make_sharded_shared_many(
            self.mesh, is_fp2, len(jobs), self.batch_axis
        )
        return fn(tuple(operands))


def batch_verify_sharded(
    backend, sigs, messages_list, vk, params, mesh, batch_axis="dp", msm_axis="tp"
):
    """Data+tensor-parallel batch verify on a mesh: [B] bools, bit-identical
    to `JaxBackend.batch_verify` / the Python spec path."""
    return batch_verify_sharded_async(
        backend, sigs, messages_list, vk, params, mesh, batch_axis, msm_axis
    )()


def default_mesh(ndp=None, ntp=1, devices=None):
    """A (dp, tp) mesh over the available devices (dp fills what tp leaves)."""
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices)
    if ndp is None:
        ndp = n // ntp
    if ndp * ntp != n:
        raise ValueError("mesh %dx%d != %d devices" % (ndp, ntp, n))
    arr = np.array(devices).reshape(ndp, ntp)
    return Mesh(arr, ("dp", "tp"))
