"""chip_smoke.py — the Coconut main path on a TPU chip, end to end.

    python chip_smoke.py             # one chip: issue, verify, show, serve
    python chip_smoke.py --chips 4   # four chips: the dp/tp-sharded paths

One process drives the chip through the entry points a user calls, at the
shape in BASELINE.json: 1024 credentials, q=6 attributes, a 3-of-5
threshold, 2 hidden / 4 revealed, signatures in G1. Keys are random and
the users' messages come from --seed. Every phase checks every lane of
its result against a reference independent of the JAX code: the native
core (native/ccbls.cpp, built from the committed source) or the Python
spec path. Any failed check exits non-zero. Without a TPU the script
exits non-zero at its first phase: it never falls back to the CPU.

Earlier lines report each phase's compile-plus-first-run and warm-run
seconds and the XLA compile seconds of every program it built; the last
line is {"ok": true, "device": {"platform", "kind", "count"}}.
"""

import argparse
import json
import os
import random
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

B = 1024  # credentials per batch (BASELINE.json configs 2-4)
Q = 6  # attributes per credential
THRESHOLD, TOTAL = 3, 5
HIDDEN = 2  # attributes hidden from the signers at issuance
REVEALED = [2, 3, 4, 5]  # attributes disclosed at show
TIMEOUT_S = 900.0  # bound on any one future of the serve phases


def log(msg):
    print(msg, flush=True)


def require(cond, msg):
    if not cond:
        raise RuntimeError("check failed: " + msg)


class Compiles:
    """XLA compile seconds per program, from JAX's own monitoring events
    (persistent-cache hits land here too, as short compiles)."""

    def __init__(self):
        import jax

        self.pending = []

        def listener(name, secs, fun_name="?", **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.pending.append((fun_name, secs))

        jax.monitoring.register_event_duration_secs_listener(listener)

    def report(self, phase):
        for fun_name, secs in self.pending:
            log("compile %s %s %.3fs" % (phase, fun_name, secs))
        total = sum(s for _, s in self.pending)
        self.pending = []
        return total


class Phase:
    """Times one phase's first (compiling) run and its warm rerun."""

    def __init__(self, name, compiles):
        self.name, self.compiles = name, compiles

    def first(self, fn):
        t0 = time.perf_counter()
        out = fn()
        self.first_s = time.perf_counter() - t0
        return out

    def warm(self, fn):
        t0 = time.perf_counter()
        out = fn()
        self.warm_s = time.perf_counter() - t0
        return out

    def done(self):
        compile_s = self.compiles.report(self.name)
        log(
            "phase %s compile_plus_first_run_s=%.3f warm_run_s=%s "
            "xla_compile_s=%.3f"
            % (
                self.name,
                self.first_s,
                "%.3f" % self.warm_s if hasattr(self, "warm_s") else "n/a",
                compile_s,
            )
        )


# -- phases ------------------------------------------------------------------


def phase_device(chips):
    """The chip, and the TPU value of every lazily chosen kernel path."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    require(
        d0.platform == "tpu",
        "no TPU: JAX's first device is %r (platform %s)" % (d0, d0.platform),
    )
    require(
        len(devices) >= chips,
        "--chips %d needs %d TPU devices, JAX sees %d"
        % (chips, chips, len(devices)),
    )
    import coconut_tpu.tpu as ctpu
    from coconut_tpu.tpu import backend as tb
    from coconut_tpu.tpu import pallas_fp

    log("compile_cache_dir %s" % ctpu.enable_compile_cache())
    tb._bucket_window(1, 255)  # resolves the bucket mode
    choices = {
        "pallas_fp.enabled": (pallas_fp.enabled(), True),
        "comb_schedule": (tb._comb_schedule(), (9, 29, 257)),
        "raw_wire": (tb._raw_wire_enabled(), True),
        "device_hash": (tb._device_hash_enabled(), True),
        "bucket_mode": (tb._BUCKET_MODE, "auto"),
    }
    for name, (got, want) in choices.items():
        log("choice %s=%r" % (name, got))
        require(got == want, "%s resolved to %r, not %r" % (name, got, want))
    log(
        "device platform=%s kind=%s count=%d"
        % (d0.platform, d0.device_kind, len(devices))
    )
    return {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": len(devices),
    }


def phase_kernel(compiles):
    """The Pallas Montgomery multiply alone: 256 products vs big ints."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from coconut_tpu.ops.fields import P
    from coconut_tpu.tpu import limbs, pallas_fp

    rng = random.Random(0xF00D)
    xs = [rng.randrange(P) for _ in range(256)]
    ys = [rng.randrange(P) for _ in range(256)]
    a = jnp.asarray(limbs.fp_encode_batch(xs))
    b = jnp.asarray(limbs.fp_encode_batch(ys))
    mul = jax.jit(pallas_fp.mul)
    ph = Phase("kernel", compiles)
    out = ph.first(lambda: np.asarray(mul(a, b)))
    ph.warm(lambda: np.asarray(mul(a, b)))
    require(
        limbs.fp_decode_batch(out) == [x * y % P for x, y in zip(xs, ys)],
        "Pallas fp.mul products differ from x*y mod p",
    )
    ph.done()


def _users(rng, n, params):
    """n users: message vectors and per-user ElGamal key pairs (the public
    keys computed natively — set-up, not the path under test)."""
    from coconut_tpu.backend import get_backend
    from coconut_tpu.ops.fields import R

    msgs = [[rng.randrange(R) for _ in range(Q)] for _ in range(n)]
    esks = [rng.randrange(1, R) for _ in range(n)]
    epks = get_backend("cpp").msm_g1_shared([params.g], [[s] for s in esks])
    return msgs, esks, epks


def phase_issue(compiles, be, params, signers, rng):
    """Threshold issuance for B users: prepare (device hash), blind-sign
    by 3 of the 5 signers, unblind, aggregate."""
    from coconut_tpu import native
    from coconut_tpu.ops import serialize as ser
    from coconut_tpu.signature import (
        BlindSignature,
        Signature,
        batch_aggregate,
        batch_blind_sign,
        batch_prepare_blind_sign,
        batch_unblind,
    )

    ctx = params.ctx
    msgs, esks, epks = _users(rng, B, params)

    def prepare():
        return batch_prepare_blind_sign(
            msgs, HIDDEN, epks, params, backend=be
        )

    ph = Phase("issue_prepare", compiles)
    prepared = ph.first(prepare)
    ph.warm(prepare)
    reqs = [r for r, _ in prepared]
    datas = [
        ctx.sig_to_bytes(r.commitment)
        + b"".join(ser.fr_to_bytes(m) for m in r.known_messages)
        for r in reqs
    ]
    want_h = native.hash_to_g1_batch(datas)
    got_h = [r.get_h(ctx) for r in reqs]
    require(
        got_h == list(want_h),
        "device hash-to-G1 differs from native cc_hash_to_g1 on %d lanes"
        % sum(g != w for g, w in zip(got_h, want_h)),
    )
    ph.done()

    quorum = signers[:THRESHOLD]
    ph = Phase("issue_blind_sign", compiles)
    blinds = {quorum[0].id: ph.first(
        lambda: batch_blind_sign(reqs, quorum[0].sigkey, params, backend=be)
    )}
    for s in quorum[1:]:
        blinds[s.id] = ph.warm(
            lambda s=s: batch_blind_sign(reqs, s.sigkey, params, backend=be)
        )
    for i in (0, B // 2, B - 1):
        for s in quorum:
            want = BlindSignature.new(reqs[i], s.sigkey, params)
            got = blinds[s.id][i]
            require(
                (got.h, got.blinded) == (want.h, want.blinded),
                "blind signature of lane %d by signer %d differs from "
                "BlindSignature.new" % (i, s.id),
            )
    ph.done()

    # unblind + Lagrange-aggregate exactly as the engine's mint does
    # (issue/quorum.CryptoMinter): one flattened unblind, one [B, t] MSM
    ph = Phase("issue_aggregate", compiles)
    flat = [blinds[s.id][i] for i in range(B) for s in quorum]
    flat_sks = [esks[i] for i in range(B) for _ in quorum]

    def mint():
        partials = batch_unblind(flat, flat_sks, ctx, backend=be)
        rows = [
            [(s.id, partials[i * THRESHOLD + j]) for j, s in enumerate(quorum)]
            for i in range(B)
        ]
        return batch_aggregate(THRESHOLD, rows, ctx=ctx, backend=be)

    creds = ph.first(mint)
    ph.warm(mint)
    for i in (0, B // 2, B - 1):
        want = Signature.aggregate(
            THRESHOLD,
            [
                (s.id, blinds[s.id][i].unblind(esks[i], ctx))
                for s in quorum
            ],
            ctx=ctx,
        )
        require(
            (creds[i].sigma_1, creds[i].sigma_2)
            == (want.sigma_1, want.sigma_2),
            "aggregated credential %d differs from Signature.aggregate" % i,
        )
    ph.done()
    return creds, msgs


def _forge(creds, params, lane):
    from coconut_tpu.signature import Signature

    forged = list(creds)
    s = creds[lane]
    forged[lane] = Signature(s.sigma_1, params.ctx.sig.mul(s.sigma_2, 2))
    return forged


def phase_verify(compiles, be, params, vk, creds, msgs):
    """Grouped, per-credential and RLC-batched verify of the B issued
    credentials, one of them forged in the second batch."""
    from coconut_tpu import ps
    from coconut_tpu.backend import get_backend

    lane = B // 2 + 3
    forged = _forge(creds, params, lane)

    ph = Phase("verify_grouped", compiles)
    ok = ph.first(lambda: be.batch_verify_grouped(creds, msgs, vk, params))
    bad = ph.warm(lambda: be.batch_verify_grouped(forged, msgs, vk, params))
    require(ok is True, "grouped verify rejected the clean batch")
    require(bad is False, "grouped verify accepted the forged batch")
    ph.done()

    want = ps.batch_verify(
        forged, msgs, vk, params, backend=get_backend("cpp")
    )
    require(
        want.count(False) == 1 and not want[lane],
        "native reference does not reject exactly the forged lane",
    )
    ph = Phase("verify_per_credential", compiles)
    bits = ph.first(lambda: be.batch_verify(forged, msgs, vk, params))
    exact = ph.warm(lambda: be.batch_verify(creds, msgs, vk, params))
    require(
        bits == want,
        "per-credential bits differ from native on lanes %s"
        % [i for i, (g, w) in enumerate(zip(bits, want)) if g != w][:16],
    )
    require(all(exact), "per-credential verify rejected a clean credential")
    ph.done()

    # mode="batched" (PR 16) on the clean batch: one combined check,
    # bit-identical to mode="exact". The forged batch is rejected by the
    # same 1024-lane combined program; the bisection ladder that would
    # attribute it compiles one program per halving and is left to the
    # CPU tests.
    ph = Phase("verify_batched", compiles)
    batched = ph.first(
        lambda: ps.batch_verify(
            creds, msgs, vk, params, backend=be, mode="batched"
        )
    )
    rejected = ph.warm(
        lambda: be.batch_verify_combined(forged, msgs, vk, params)
    )
    require(batched == exact, "mode='batched' bits differ from mode='exact'")
    require(rejected is False, "combined check accepted the forged batch")
    ph.done()
    return forged, bits


def native_show_verify(proofs, vk, params, revealed_list, challenges):
    """PoKOfSignatureProof.verify's two checks on the native core: the
    Schnorr relation on J and the pairing product, per lane."""
    from coconut_tpu.backend import get_backend

    cpp = get_backend("cpp")
    ctx = params.ctx
    hidden = [i for i in range(Q) if i not in REVEALED]
    bases = [params.g_tilde] + [vk.Y_tilde[i] for i in hidden]
    lhs = cpp.msm_g2_distinct(
        [bases + [p.J] for p in proofs],
        [list(p.proof_vc.responses) + [c] for p, c in zip(proofs, challenges)],
    )
    accs = cpp.msm_g2_distinct(
        [[p.J, vk.X_tilde] + [vk.Y_tilde[i] for i in REVEALED] for p in proofs],
        [[1, 1] + [rm[i] for i in REVEALED] for rm in revealed_list],
    )
    pair_ok = cpp.pairing_product_is_one(
        [
            [
                (p.sigma_prime_1, acc),
                (ctx.sig.neg(p.sigma_prime_2), params.g_tilde),
            ]
            for p, acc in zip(proofs, accs)
        ]
    )
    return [
        p.sigma_prime_1 is not None and l == p.proof_vc.t and bool(ok)
        for p, l, ok in zip(proofs, lhs, pair_ok)
    ]


def phase_show(compiles, be, params, vk, creds, msgs):
    """Selective disclosure: show_prove then show_verify of B credentials,
    one revealed value tampered."""
    from coconut_tpu import ps
    from coconut_tpu.ops.fields import R
    from coconut_tpu.pok_sig import batch_show
    from coconut_tpu.signature import fiat_shamir_challenge

    ph = Phase("show_prove", compiles)
    proofs, chals, revealed = ph.first(
        lambda: batch_show(creds, vk, params, msgs, set(REVEALED), backend=be)
    )
    ph.warm(
        lambda: batch_show(creds, vk, params, msgs, set(REVEALED), backend=be)
    )
    ph.done()

    lane = B // 4 + 1
    tampered = [dict(rm) for rm in revealed]
    tampered[lane][REVEALED[0]] = (tampered[lane][REVEALED[0]] + 1) % R
    fs = [fiat_shamir_challenge(p.to_bytes_for_challenge(vk, params))
          for p in proofs]
    require(fs == list(chals), "prover challenges differ from Fiat-Shamir")
    want = native_show_verify(proofs, vk, params, tampered, fs)
    for i in (0, lane):  # the native reference against the spec path
        require(
            want[i] == proofs[i].verify(vk, params, tampered[i], fs[i]),
            "native show reference differs from the spec on lane %d" % i,
        )
    require(
        want.count(False) == 1 and not want[lane],
        "native show reference does not reject exactly the tampered lane",
    )
    ph = Phase("show_verify", compiles)
    bits = ph.first(
        lambda: ps.batch_show_verify(proofs, vk, params, tampered, backend=be)
    )
    clean = ph.warm(
        lambda: ps.batch_show_verify(proofs, vk, params, revealed, backend=be)
    )
    require(
        list(bits) == want,
        "show-verify bits differ from native on lanes %s"
        % [i for i, (g, w) in enumerate(zip(bits, want)) if g != w][:16],
    )
    require(all(clean), "show-verify rejected an untampered proof")
    ph.done()


def _settle(futures, what):
    out = [f.result(timeout=TIMEOUT_S) for f in futures]
    log("serve %s settled=%d" % (what, len(out)))
    return out


def _jit_shapes():
    from coconut_tpu import metrics

    return {
        k: v
        for k, v in metrics.snapshot()["counters"].items()
        if k.endswith("_jit_shapes")
    }


def phase_serve(compiles, be, params, signers, vk, forged, msgs, bits, rng):
    """The ProtocolEngine answering B full sessions (prepare -> mint ->
    verify -> show_prove -> show_verify), then a verify burst that fills
    one batch. max_batch=B, so every program reuses the shapes the
    offline phases compiled."""
    from coconut_tpu.engine import ProtocolEngine

    s_msgs, esks, epks = _users(rng, B, params)
    engine = ProtocolEngine(
        signers,
        params,
        THRESHOLD,
        count_hidden=HIDDEN,
        revealed_msg_indices=REVEALED,
        vk=vk,
        backend=be,
        max_batch=B,
        # a full batch is submitted at once: the long wait makes the
        # unpadded mint lane coalesce all B orders into one batch
        max_wait_ms=10_000.0,
        max_depth=4 * B,
        showv_mode="exact",
    ).start()
    ph = Phase("serve_sessions", compiles)
    try:
        def sessions():
            prepared = _settle(
                [engine.submit_prepare(m, pk) for m, pk in zip(s_msgs, epks)],
                "prepare",
            )
            creds = _settle(
                [
                    engine.submit_mint(req, m, sk)
                    for (req, _), m, sk in zip(prepared, s_msgs, esks)
                ],
                "mint",
            )
            verdicts = _settle(
                [engine.submit_verify(c, m) for c, m in zip(creds, s_msgs)],
                "verify",
            )
            shown = _settle(
                [
                    engine.submit_show_prove(c, m)
                    for c, m in zip(creds, s_msgs)
                ],
                "show_prove",
            )
            show_ok = _settle(
                [
                    engine.submit_show_verify(p, rv, c)
                    for p, c, rv in shown
                ],
                "show_verify",
            )
            return verdicts, show_ok

        verdicts, show_ok = ph.first(sessions)
        require(all(verdicts), "served verify rejected a minted credential")
        require(all(show_ok), "served show-verify rejected a proof")
        warm = _jit_shapes()
        log("serve jit_shapes after warm-up %s" % json.dumps(warm))
        burst = ph.warm(
            lambda: _settle(
                [engine.submit_verify(c, m) for c, m in zip(forged, msgs)],
                "verify burst",
            )
        )
        require(
            burst == bits,
            "served verify burst differs from the offline per-credential bits",
        )
        require(
            _jit_shapes() == warm,
            "jit shapes grew after warm-up: %s" % json.dumps(_jit_shapes()),
        )
    finally:
        drained = engine.drain(timeout=TIMEOUT_S)
    require(drained, "engine drain did not finish")
    log("serve drained=True")
    ph.done()


def phase_sharded(compiles, be, params, vk, creds, msgs):
    """--chips 4: the dp-sharded grouped verify (dp=4), the (dp, tp)
    sharded per-credential verify (2x2), and a CredentialService whose
    four executors each serve on their own chip, against the native
    reference; placement read from the result arrays."""
    import jax
    import numpy as np

    from coconut_tpu import ps
    from coconut_tpu.backend import get_backend
    from coconut_tpu.serve.service import CredentialService
    from coconut_tpu.tpu import backend as tb
    from coconut_tpu.tpu import shard

    devices = jax.devices()[:4]
    want_devs = set(devices)
    lane = B // 2 + 3
    forged = _forge(creds, params, lane)
    want = ps.batch_verify(
        forged, msgs, vk, params, backend=get_backend("cpp")
    )
    require(
        want.count(False) == 1 and not want[lane],
        "native reference does not reject exactly the forged lane",
    )

    # one CredentialService executor per chip: record where each
    # per-credential program's result lives
    placed = []
    kernel = tb._fused_verify_kernel

    def recording_kernel(*args):
        out = kernel(*args)
        placed.append(frozenset(out.devices()))
        return out

    tb._fused_verify_kernel = recording_kernel
    service = CredentialService(
        be,
        vk,
        params,
        mode="per_credential",
        max_batch=B,
        max_wait_ms=10_000.0,
        max_depth=8 * B,
        devices=devices,
    ).start()
    ph = Phase("sharded", compiles)
    try:
        # four full batches, submitted before the sharded programs run so
        # the executors compile on their chips while the mesh programs do
        futures = [
            service.submit(s, m) for _ in devices for s, m in zip(forged, msgs)
        ]
        # the one-device grouped reference compiles alongside as well
        one_dev = ThreadPoolExecutor(1).submit(
            lambda: [
                be.batch_verify_grouped(sigs, msgs, vk, params)
                for sigs in (creds, forged)
            ]
        )

        def mesh_programs():
            mesh = shard.default_mesh(ndp=2, ntp=2, devices=devices)
            k = 1 + len(vk.Y_tilde)
            ops = be.encode_verify_batch(
                forged, msgs, vk, params,
                pad_bases_to=shard.pad_to_multiple(k, 2),
            )
            arr = shard.make_sharded_verify(mesh, True)(*ops)
            require(
                set(arr.devices()) == want_devs,
                "sharded per-credential bits live on %s" % arr.devices(),
            )
            dp_bits = [bool(b) for b in np.asarray(arr)]
            gmesh = shard.default_mesh(ndp=4, ntp=1, devices=devices)
            grouped = []
            for sigs in (creds, forged):
                g_ops = be.encode_grouped_batch(
                    sigs, msgs, vk, params, pad_batch_to=8
                )
                ok = shard.make_sharded_grouped_verify(gmesh, True)(*g_ops)
                require(
                    set(ok.devices()) == want_devs,
                    "sharded grouped verdict lives on %s" % ok.devices(),
                )
                grouped.append(bool(ok))
            rerun = lambda: np.asarray(  # noqa: E731
                shard.make_sharded_verify(mesh, True)(*ops)
            )
            return dp_bits, grouped, rerun

        dp_bits, grouped, rerun = ph.first(mesh_programs)
        ph.warm(rerun)
        one_dev = one_dev.result(timeout=TIMEOUT_S)
        require(
            dp_bits == want,
            "dp x tp sharded bits differ from native on lanes %s"
            % [i for i, (g, w) in enumerate(zip(dp_bits, want)) if g != w],
        )
        require(
            grouped == one_dev == [True, False],
            "dp-sharded grouped verdicts %s, one-device %s, want "
            "[True, False]" % (grouped, one_dev),
        )
        served = _settle(futures, "verify x4")
        for i in range(len(devices)):
            require(
                served[i * B : (i + 1) * B] == want,
                "served batch %d differs from native" % i,
            )
        from coconut_tpu import metrics

        per_exec = {
            str(i): metrics.get_count("serve_dev%d_dispatches" % i)
            for i in range(len(devices))
        }
        log("sharded executor dispatches %s" % json.dumps(per_exec))
        require(all(per_exec.values()), "an executor received no dispatch")
        seen = set().union(*placed) if placed else set()
        log("sharded executor result devices %s" % sorted(map(str, seen)))
        require(
            all(len(p) == 1 for p in placed) and seen == want_devs,
            "executor results were not one per chip on every chip: %s"
            % [sorted(map(str, p)) for p in placed],
        )
    finally:
        drained = service.drain(timeout=TIMEOUT_S)
        tb._fused_verify_kernel = kernel
    require(drained, "service drain did not finish")
    ph.done()


def run(chips, seed):
    device = phase_device(chips)
    compiles = Compiles()

    from coconut_tpu.keygen import trusted_party_SSS_keygen
    from coconut_tpu.params import Params
    from coconut_tpu.signature import Verkey
    from coconut_tpu.tpu.backend import JaxBackend

    rng = random.Random(seed)
    params = Params.new(Q, b"chip_smoke")
    _, _, signers = trusted_party_SSS_keygen(THRESHOLD, TOTAL, params)
    vk = Verkey.aggregate(
        THRESHOLD,
        [(s.id, s.verkey) for s in signers[:THRESHOLD]],
        ctx=params.ctx,
    )
    be = JaxBackend()

    if chips == 4:
        import __graft_entry__ as ge

        fx_params, _, fx_vk, fx_creds, fx_msgs = ge._fixture(B, seed=seed)
        phase_sharded(compiles, be, fx_params, fx_vk, fx_creds, fx_msgs)
        return device

    phase_kernel(compiles)
    creds, msgs = phase_issue(compiles, be, params, signers, rng)
    forged, bits = phase_verify(compiles, be, params, vk, creds, msgs)
    phase_show(compiles, be, params, vk, creds, msgs)
    phase_serve(compiles, be, params, signers, vk, forged, msgs, bits, rng)
    return device


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: the main path on one chip; 4: only the sharded paths",
    )
    ap.add_argument("--seed", type=int, default=0xC0C0)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        device = run(args.chips, args.seed)
    except BaseException:
        traceback.print_exc()
        sys.stderr.flush()
        # engine/executor threads must not keep a failed run alive
        os._exit(1)
    log("total_s=%.3f" % (time.perf_counter() - t0))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
