"""Device hash-to-curve + bucketed MSM suite (PR 18).

Two kernels move the prepare hash and the show-prove sigma MSM onto
device schedules, and both are pure
re-schedules of already-proven math — so every test here is a BIT
parity test against an independent oracle, never a statistical one:

  - device `hash_to_g1` (SvdW straight-line map + cofactor clear as
    one jitted program) vs the Python spec in ops/hashing.py and,
    when built, the native `cc_hash_to_g1_batch` FFI core from PR 3;
  - the bucketed Pippenger MSM schedule vs the existing signed-Horner
    distinct-base kernels, across window sizes, ragged batch sizes,
    zero scalars, and GLV on/off.

Adversarial hash vectors: empty message, the 255-byte DST boundary
(expand_message_xmd's long-DST hashing kicks in above 255), u-values
driving each of the three SvdW x-candidates, and the identity-sum
edge via the map's oddness (map(p-u) = -map(u), so u1 = p - u0 sums
to infinity and must raise, exactly like the spec)."""

import random

import pytest

from coconut_tpu.ops import hashing as spec_hashing
from coconut_tpu.ops.curve import G1_GEN, G2_GEN, g1, g2
from coconut_tpu.ops.fields import P, R, fp_sqrt

pytestmark = pytest.mark.hashmsm


@pytest.fixture(scope="module")
def jax_backend():
    from coconut_tpu.backend import get_backend

    return get_backend("jax")


@pytest.fixture()
def device_hash_on(monkeypatch):
    import coconut_tpu.tpu.backend as tb

    monkeypatch.setattr(tb, "_DEVICE_HASH", True)


def _force_window(monkeypatch, w):
    """Pin the bucket-schedule knob: an int forces that window for
    every distinct-base MSM, 'off' forces the legacy Horner path."""
    import coconut_tpu.tpu.backend as tb

    monkeypatch.setattr(tb, "_BUCKET_MODE", w)


# ---------------------------------------------------------------------------
# device hash-to-G1 parity
# ---------------------------------------------------------------------------


class TestDeviceHashParity:
    def test_random_messages_vs_spec(self, jax_backend, device_hash_on):
        rng = random.Random(0xC0C0)
        msgs = [
            bytes(rng.randrange(256) for _ in range(rng.randrange(0, 96)))
            for _ in range(17)
        ]
        got = jax_backend.hash_to_g1_batch(msgs)
        for m, p in zip(msgs, got):
            assert p == spec_hashing.hash_to_g1(m)

    def test_native_oracle(self, jax_backend, device_hash_on):
        from coconut_tpu import native

        if not native.available():
            pytest.skip("native core not built")
        msgs = [b"oracle-%d" % i for i in range(9)]
        assert jax_backend.hash_to_g1_batch(msgs) == list(
            native.hash_to_g1_batch(msgs)
        )

    def test_empty_message_and_empty_batch(
        self, jax_backend, device_hash_on
    ):
        assert jax_backend.hash_to_g1_batch([]) == []
        (p,) = jax_backend.hash_to_g1_batch([b""])
        assert p == spec_hashing.hash_to_g1(b"")

    def test_dst_boundary_255(self, jax_backend, device_hash_on):
        # expand_message_xmd switches to the hashed-DST form above 255
        # bytes; 255 is the last direct-encoding length
        for dst in (bytes(range(255)), b"\xff" * 255, b"x"):
            msgs = [b"", b"dst-edge", b"A" * 130]
            got = jax_backend.hash_to_g1_batch(msgs, dst=dst)
            for m, p in zip(msgs, got):
                assert p == spec_hashing.hash_to_g1(m, dst=dst)

    def test_counters_and_path_selection(
        self, jax_backend, device_hash_on
    ):
        from coconut_tpu import metrics

        b0 = metrics.get_count("device_hash_batches")
        p0 = metrics.get_count("device_hash_points")
        jax_backend.hash_to_g1_batch([b"a", b"b", b"c"])
        assert metrics.get_count("device_hash_batches") == b0 + 1
        assert metrics.get_count("device_hash_points") == p0 + 3


def _u_for_candidate(which):
    """Search out a field element whose SvdW map accepts exactly
    x-candidate `which` (1-based), replaying the spec's own
    straight-line candidates and square tests."""
    F = spec_hashing._FpAdapter
    Z, c1, c2, c3, c4 = spec_hashing._SVDW_FP
    one = F.embed(1)
    rng = random.Random(0x5D + which)

    def g(x):
        return F.add(F.mul(F.sq(x), x), F.embed(F.B))

    while True:
        u = rng.randrange(1, P)
        tv1 = F.mul(F.sq(u), c1)
        tv2 = F.add(one, tv1)
        tv1 = F.sub(one, tv1)
        tv3 = F.inv0(F.mul(tv1, tv2))
        tv4 = F.mul(F.mul(F.mul(u, tv1), tv3), c3)
        x1 = F.sub(c2, tv4)
        x2 = F.add(c2, tv4)
        x3 = F.add(F.mul(F.sq(F.mul(F.sq(tv2), tv3)), c4), Z)
        sq = [fp_sqrt(g(x)) is not None for x in (x1, x2, x3)]
        if which == 1 and sq[0]:
            return u
        if which == 2 and not sq[0] and sq[1]:
            return u
        if which == 3 and not sq[0] and not sq[1]:
            # the SvdW construction guarantees x3 works here
            assert sq[2]
            return u


class TestSvdwCandidates:
    """Drive the device map through each of the three x-candidate
    accept branches and the identity edge, below the message layer."""

    @pytest.fixture(scope="class")
    def kernel(self):
        import jax.numpy as jnp

        from coconut_tpu.tpu import backend as tb
        from coconut_tpu.tpu.limbs import fp_encode_raw_batch

        def run(u_pairs):
            import numpy as np

            flat = [u for pair in u_pairs for u in pair]
            dig = fp_encode_raw_batch(flat).reshape(len(u_pairs), 2, -1)
            par = np.array(
                [u & 1 for u in flat], dtype=bool
            ).reshape(len(u_pairs), 2)
            handle = tb._hash_to_g1_kernel(
                jnp.asarray(dig), jnp.asarray(par)
            )
            return tb.JaxBackend.hash_to_g1_wait(handle)

        return run

    def _spec_point(self, u0, u1):
        F = spec_hashing._FpAdapter
        consts = spec_hashing._SVDW_FP
        q0 = spec_hashing._map_to_curve_svdw(F, consts, u0)
        q1 = spec_hashing._map_to_curve_svdw(F, consts, u1)
        from coconut_tpu.ops.curve import G1_COFACTOR

        return g1.mul(g1.add(q0, q1), G1_COFACTOR)

    @pytest.mark.parametrize("cand", [1, 2, 3])
    def test_each_candidate(self, kernel, cand):
        u = _u_for_candidate(cand)
        v = _u_for_candidate((cand % 3) + 1)
        got = kernel([(u, v)])
        assert got[0] == self._spec_point(u, v)

    def test_identity_sum_raises(self, kernel):
        # for a candidate-3 u (both gx1, gx2 non-square) the map is odd
        # in u — negating u keeps x3 (it depends only on u^2) and flips
        # the y sign — so the pair (u, p-u) sums to the identity, which
        # must be refused exactly like the spec's ~2^-255 edge
        u = _u_for_candidate(3)
        with pytest.raises(ValueError):
            kernel([(u, P - u)])


# ---------------------------------------------------------------------------
# bucketed Pippenger MSM parity
# ---------------------------------------------------------------------------


def _rand_rows(grp, gen, B, k, rng, zero_lane=False):
    pts = [
        [grp.mul(gen, rng.randrange(1, R)) for _ in range(k)]
        for _ in range(B)
    ]
    scs = [[rng.randrange(R) for _ in range(k)] for _ in range(B)]
    if zero_lane:
        scs[0][0] = 0
    return pts, scs


class TestBucketedMsmParity:
    # the full window sweep / ragged-shape / GLV-off / G2 lanes each
    # compile a fresh XLA program per (B, k, window) shape — minutes on
    # the CPU mesh, so they ride the hashmsm CI lane (-m hashmsm) and
    # stay out of the bounded tier-1 run; all_zero + dispatch_counters
    # below keep a fast bucketed-path representative in tier-1
    @pytest.mark.slow
    @pytest.mark.parametrize("window", [2, 3, 5, 8])
    def test_g1_windows_vs_horner(
        self, jax_backend, monkeypatch, window
    ):
        rng = random.Random(900 + window)
        pts, scs = _rand_rows(g1, G1_GEN, 3, 6, rng, zero_lane=True)
        _force_window(monkeypatch, "off")
        ref = jax_backend.msm_g1_distinct(pts, scs)
        _force_window(monkeypatch, window)
        assert jax_backend.msm_g1_distinct(pts, scs) == ref
        assert ref == [grp_msm(g1, p, s) for p, s in zip(pts, scs)]

    @pytest.mark.slow
    @pytest.mark.parametrize("B,k", [(1, 4), (3, 1), (5, 7)])
    def test_g1_ragged_shapes(self, jax_backend, monkeypatch, B, k):
        rng = random.Random(1000 + 10 * B + k)
        pts, scs = _rand_rows(g1, G1_GEN, B, k, rng)
        _force_window(monkeypatch, 4)
        got = jax_backend.msm_g1_distinct(pts, scs)
        assert got == [grp_msm(g1, p, s) for p, s in zip(pts, scs)]

    @pytest.mark.slow
    def test_g1_glv_off(self, jax_backend, monkeypatch):
        import coconut_tpu.tpu.backend as tb

        rng = random.Random(77)
        pts, scs = _rand_rows(g1, G1_GEN, 2, 5, rng, zero_lane=True)
        monkeypatch.setattr(tb, "_GLV_ENABLED", False)
        _force_window(monkeypatch, 5)
        got = jax_backend.msm_g1_distinct(pts, scs)
        assert got == [grp_msm(g1, p, s) for p, s in zip(pts, scs)]

    @pytest.mark.slow
    def test_g2(self, jax_backend, monkeypatch):
        rng = random.Random(78)
        pts, scs = _rand_rows(g2, G2_GEN, 2, 3, rng, zero_lane=True)
        _force_window(monkeypatch, "off")
        ref = jax_backend.msm_g2_distinct(pts, scs)
        _force_window(monkeypatch, 3)
        assert jax_backend.msm_g2_distinct(pts, scs) == ref
        assert ref == [grp_msm(g2, p, s) for p, s in zip(pts, scs)]

    def test_all_zero_scalars(self, jax_backend, monkeypatch):
        pts = [[G1_GEN, g1.double(G1_GEN)]]
        scs = [[0, 0]]
        _force_window(monkeypatch, 3)
        assert jax_backend.msm_g1_distinct(pts, scs) == [None]

    def test_dispatch_counters(self, jax_backend, monkeypatch):
        from coconut_tpu import metrics

        rng = random.Random(79)
        pts, scs = _rand_rows(g1, G1_GEN, 1, 3, rng)
        _force_window(monkeypatch, 5)
        b0 = metrics.get_count("msm_bucketed_dispatches")
        jax_backend.msm_g1_distinct(pts, scs)
        assert metrics.get_count("msm_bucketed_dispatches") == b0 + 1
        assert metrics.get_gauge("msm_bucket_window") == 5
        _force_window(monkeypatch, "off")
        h0 = metrics.get_count("msm_horner_dispatches")
        jax_backend.msm_g1_distinct(pts, scs)
        assert metrics.get_count("msm_horner_dispatches") == h0 + 1


def grp_msm(grp, pts, scs):
    return grp.msm(pts, scs)


class TestWindowSelection:
    """The lazy knob: COCONUT_MSM_WINDOW forces, 'auto' consults the
    cost model, CPU defaults to the legacy Horner schedule."""

    def test_forced_window_parses(self, monkeypatch):
        import coconut_tpu.tpu.backend as tb

        monkeypatch.setattr(tb, "_BUCKET_MODE", None)
        monkeypatch.setenv("COCONUT_MSM_WINDOW", "6")
        assert tb._bucket_window(100, 255) == 6
        monkeypatch.setattr(tb, "_BUCKET_MODE", None)
        monkeypatch.setenv("COCONUT_MSM_WINDOW", "0")
        assert tb._bucket_window(100, 255) is None

    def test_bad_window_rejected(self, monkeypatch):
        import coconut_tpu.tpu.backend as tb

        monkeypatch.setattr(tb, "_BUCKET_MODE", None)
        monkeypatch.setenv("COCONUT_MSM_WINDOW", "17")
        with pytest.raises(ValueError):
            tb._bucket_window(100, 255)
        monkeypatch.setattr(tb, "_BUCKET_MODE", None)

    def test_auto_prefers_buckets_only_at_scale(self, monkeypatch):
        import coconut_tpu.tpu.backend as tb

        monkeypatch.setattr(tb, "_BUCKET_MODE", "auto")
        # the show prover's post-GLV sigma pair is k=4: Horner wins
        assert tb._bucket_window(4, 128) is None
        # at prepare/batch-verify scale the bucket schedule wins
        assert tb._bucket_window(512, 255) is not None


# ---------------------------------------------------------------------------
# epoch retirement drops the nullifier keyspace (satellite)
# ---------------------------------------------------------------------------


@pytest.mark.state
class TestRetirementCompaction:
    def test_retired_epoch_refused_before_probe(self, tmp_path):
        import collections

        from coconut_tpu import metrics
        from coconut_tpu.errors import EpochRetiredError
        from coconut_tpu.keylife.epoch import EpochRegistry
        from coconut_tpu.state.nullifier import (
            NullifierGuard,
            keyspace_of,
        )
        from coconut_tpu.state.store import StateStore

        store = StateStore(str(tmp_path))
        guard = NullifierGuard(store, use_device=False)
        reg = EpochRegistry(window=1, store=store)
        reg.add_retire_hook(guard.retire_epoch)

        probes = []
        real_probe = guard.probe

        def spying_probe(*a, **kw):
            probes.append(a)
            return real_probe(*a, **kw)

        guard.probe = spying_probe

        KS = collections.namedtuple("KS", "epoch gen key vk")
        reg.register(KS(1, 0, "k1", "vk1"))
        reg.activate(1)
        digest = "ab" * 32
        assert guard.commit([digest], epochs=[1]) == [True]
        assert store.seen(keyspace_of(1), digest)

        n0 = metrics.get_count("state_nullifiers_compacted")
        reg.register(KS(2, 0, "k2", "vk2"))
        reg.activate(2)  # window=1: epoch 1 retires NOW

        # the keyspace is gone wholesale and the counter moved
        assert keyspace_of(1) not in store.keyspaces()
        assert not store.seen(keyspace_of(1), digest)
        assert (
            metrics.get_count("state_nullifiers_compacted") == n0 + 1
        )

        # a retired-epoch show is refused at resolve time — BEFORE any
        # membership probe could touch the (now absent) keyspace
        probes.clear()
        with pytest.raises(EpochRetiredError):
            reg.resolve(1)
        assert probes == []

        # the WAL was compacted underneath: a fresh store over the same
        # root must not resurrect the dropped keyspace
        store.close()
        store2 = StateStore(str(tmp_path))
        assert keyspace_of(1) not in store2.keyspaces()
        assert store2.seen("epoch", "1")  # journal survives
        store2.close()
