"""One deployment's keys and inputs, made from the seed.

Everything random here comes from one `random.Random(seed)`, so a seed
gives the same keys, attributes and tampered lanes on every run. Group
elements are made in bulk on the native core (several threads, each a
batch multi-scalar multiplication), never with the JAX code under test.
The master key stays with the benchmark: the plain reference judges the
system's answers with it.
"""

from concurrent.futures import ThreadPoolExecutor

from . import reference as ref

THREADS = 8


def rng_fr(rng, lo=0):
    return rng.randrange(lo, ref.R)


def _native():
    from coconut_tpu.backend import get_backend

    return get_backend("cpp")


def _parallel(fn, rows, chunk=512):
    """fn(rows[i:i+chunk]) over THREADS threads, concatenated in order
    (the native calls release the GIL)."""
    parts = [rows[i : i + chunk] for i in range(0, len(rows), chunk)]
    with ThreadPoolExecutor(THREADS) as pool:
        out = []
        for res in pool.map(fn, parts):
            out.extend(res)
    return out


def g1_fixed(base, scalars):
    """[k * base for k in scalars] on the native core."""
    be = _native()
    return _parallel(
        lambda part: be.msm_g1_shared([base], [[k % ref.R] for k in part]),
        list(scalars),
    )


def g2_fixed(base, scalars):
    be = _native()
    return _parallel(
        lambda part: be.msm_g2_shared([base], [[k % ref.R] for k in part]),
        list(scalars),
        chunk=64,
    )


class Deployment:
    """Params, a t-of-n trusted-dealer key set and its aggregate verkey.

    x, ys: the master key; signers: keygen.Signer per authority, their
    shares evaluated from a seeded polynomial of degree t - 1."""

    def __init__(self, cfg, rng):
        from coconut_tpu.keygen import Signer
        from coconut_tpu.params import Params
        from coconut_tpu.signature import Sigkey, Verkey

        if cfg["signature_group"] != "G1":
            raise ValueError("only signatures in G1 are built")
        self.cfg = cfg
        q, t, n = cfg["attributes"], cfg["threshold"], cfg["authorities"]
        self.q, self.t, self.n = q, t, n
        self.params = Params.new(q, cfg["params_label"].encode())
        polys = [
            [rng_fr(rng) for _ in range(t)] for _ in range(q + 1)
        ]  # polys[0] shares x, polys[1 + j] shares y_j
        self.x = polys[0][0]
        self.ys = [p[0] for p in polys[1:]]

        def share(poly, i):
            return sum(c * pow(i, e, ref.R) for e, c in enumerate(poly)) % ref.R

        shares = [[share(p, i) for p in polys] for i in range(1, n + 1)]
        g2 = g2_fixed(
            self.params.g_tilde,
            [self.x] + self.ys + [s for row in shares for s in row],
        )
        self.vk = Verkey(g2[0], g2[1 : q + 1])
        self.signers = []
        for i, row in enumerate(shares):
            pts = g2[q + 1 + i * (q + 1) : q + 1 + (i + 1) * (q + 1)]
            self.signers.append(
                Signer(i + 1, Sigkey(row[0], row[1:]), Verkey(pts[0], pts[1:]))
            )

    def exponent(self, messages, extra=0):
        return ref.exponent(self.x, self.ys, messages, extra)


def credential_pool(dep, rng, n_batches, batch, tampered_per_batch):
    """n_batches batches of `batch` credentials under dep's verkey.

    A credential is (h, s * h) with h = r * g for a seeded r and
    s = x + sum_j y_j m_j. In each batch `tampered_per_batch` seeded lanes
    carry sigma_2 doubled, so the correct verdict is False exactly there.
    Returns [(sigs, messages_list, expected_bits)]."""
    from coconut_tpu.signature import Signature

    rows = []
    for _ in range(n_batches * batch):
        msgs = [rng_fr(rng) for _ in range(dep.q)]
        rows.append((rng_fr(rng, 1), msgs))
    bad = [
        set(rng.sample(range(batch), tampered_per_batch))
        for _ in range(n_batches)
    ]
    scal = []
    for k, (r, msgs) in enumerate(rows):
        mult = 2 if (k % batch) in bad[k // batch] else 1
        scal += [r, r * dep.exponent(msgs) * mult]
    pts = g1_fixed(dep.params.g, scal)
    pool = []
    for b in range(n_batches):
        lo = b * batch
        sigs = [
            Signature(pts[2 * k], pts[2 * k + 1]) for k in range(lo, lo + batch)
        ]
        msgs = [rows[k][1] for k in range(lo, lo + batch)]
        want = [j not in bad[b] for j in range(batch)]
        pool.append((sigs, msgs, want))
    return pool


def reference_disagreements(dep, pool, rng, sample):
    """Lanes where the plain reference's verdict differs from the pool's
    expected bit: every tampered lane plus `sample` lanes drawn from the
    seed. Returns (disagreements, lanes_checked)."""
    lanes = {
        (b, j)
        for b, (_, _, want) in enumerate(pool)
        for j, ok in enumerate(want)
        if not ok
    }
    batch = len(pool[0][0])
    want = min(len(pool) * batch, sample + len(lanes))
    while len(lanes) < want:
        lanes.add((rng.randrange(len(pool)), rng.randrange(batch)))
    bad = 0
    for b, j in sorted(lanes):
        sigs, msgs, want = pool[b]
        s = sigs[j]
        got = ref.credential_valid(s.sigma_1, s.sigma_2, dep.x, dep.ys, msgs[j])
        bad += got != want[j]
    return bad, len(lanes)


def show_pool(dep, rng, size, tampered_every):
    """`size` show proofs of credentials under dep's verkey, the
    configuration's hidden attributes hidden and the rest revealed.

    The proofs are made here, on the native core, from the seed: sigma' =
    (r' * sigma_1, r' * (sigma_2 + t * sigma_1)), J = t * g~ + sum_hidden
    m_j * Y~_j, a Schnorr proof of (t, hidden m) with a Fiat-Shamir
    challenge over the program's transcript. One request in
    `tampered_every` (at seeded positions) gives the verifier its first
    revealed value plus one, so the correct verdict is False there.
    Returns [(proof, revealed_msgs, expected_bit, claimed_messages, t)]."""
    from coconut_tpu.pok_vc import Proof
    from coconut_tpu.ps import PoKOfSignatureProof
    from coconut_tpu.signature import fiat_shamir_challenge

    revealed = list(dep.cfg["revealed_at_show"])
    hidden = [j for j in range(dep.q) if j not in revealed]
    params, vk = dep.params, dep.vk
    bases = [params.g_tilde] + [vk.Y_tilde[j] for j in hidden]
    rows = []
    for _ in range(size):
        msgs = [rng_fr(rng) for _ in range(dep.q)]
        r, rp, t = rng_fr(rng, 1), rng_fr(rng, 1), rng_fr(rng)
        blind = [rng_fr(rng) for _ in bases]
        rows.append((msgs, r, rp, t, blind))
    n_bad = max(1, size // tampered_every)
    bad = set(rng.sample(range(size), n_bad))
    g1 = g1_fixed(
        params.g,
        [
            k
            for msgs, r, rp, t, _ in rows
            for k in (r * rp, r * rp % ref.R * dep.exponent(msgs, t))
        ],
    )
    be = _native()
    secrets = [[t] + [msgs[j] for j in hidden] for msgs, _, _, t, _ in rows]
    js = _parallel(lambda part: be.msm_g2_shared(bases, part), secrets, 64)
    ts = _parallel(
        lambda part: be.msm_g2_shared(bases, part), [b for *_, b in rows], 64
    )
    pool = []
    for i, (msgs, _, _, t, blind) in enumerate(rows):
        p = PoKOfSignatureProof(
            g1[2 * i], g1[2 * i + 1], js[i], Proof(ts[i], [0] * len(bases)),
            set(revealed),
        )
        c = fiat_shamir_challenge(p.to_bytes_for_challenge(vk, params))
        p.proof_vc.responses = [
            (b - c * s) % ref.R for b, s in zip(blind, secrets[i])
        ]
        claimed = list(msgs)
        if i in bad:
            claimed[revealed[0]] = (claimed[revealed[0]] + 1) % ref.R
        shown = {j: claimed[j] for j in revealed}
        pool.append((p, shown, i not in bad, claimed, t))
    return pool


def show_disagreements(dep, pool, rng, sample):
    """Pool entries where the plain reference's verdict differs from the
    expected bit: every tampered one plus `sample` drawn from the seed."""
    idx = {i for i, e in enumerate(pool) if not e[2]}
    want = min(len(pool), sample + len(idx))
    while len(idx) < want:
        idx.add(rng.randrange(len(pool)))
    bad = 0
    for i in sorted(idx):
        p, _, want, claimed, t = pool[i]
        got = ref.show_valid(
            p.sigma_prime_1, p.sigma_prime_2, dep.x, dep.ys, claimed, t
        )
        bad += got != want
    return bad, len(idx)
