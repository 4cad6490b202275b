"""closed_issue: an issuer minting credentials in bulk, as a closed loop of
issuance chains through `ProtocolEngine.submit_prepare` then `submit_mint`
(every authority blind-signs, the first t partials are unblinded,
Lagrange-aggregated and verified before release).

Traffic parameters: chains outstanding at all times; denominations the
public attribute is drawn from (the hidden one is a fresh serial number);
max_wait_ms, the engine's coalescing deadline; warm_mints released before
the window; reference_sample released credentials, besides one whole
batch, judged by the plain reference after it.

Each chain owns an ElGamal key made from the seed and issues one
credential after another: its next prepare is submitted from the previous
mint future's done callback, not from a thread per chain. With chains a
multiple of max_batch the prepare and mint batches run full.

A turn of the loop is `chains` releases, one per chain. With more chains
than one batch the batches of a turn release close together and the turn
then waits on the next round of prepares, so the gaps between batch
releases alternate short and long. The window opens at the release of the
warm_mints-th credential (a whole number of turns) and closes at the
release that completes the first whole number of further turns once
`seconds` have passed, so it holds as many long gaps as short ones:
`mints_per_s` is the credentials released in it over its length.

The reference judges one whole batch of the window (max_batch consecutive
releases from a batch boundary drawn from the seed, so every lane of a
batch is judged) and reference_sample more drawn from the rest.

Controls: "threshold_below_quorum" runs the mint with t - 1 partials, so
every credential aggregates from too few shares.
"""

import random
import threading
import time

from .. import deploy
from .. import reference as ref
from ..harness import log
from ..stats import percentile

CONTROLS = ("threshold_below_quorum",)
CLOSE_WAIT_S = 60  # longest wait for the batch that closes the window


def run(run):
    from coconut_tpu.engine import ProtocolEngine

    cfg, tr = run.cfg, run.traffic
    if run.control is not None and run.control not in CONTROLS:
        raise ValueError("unknown control %r" % run.control)
    rng = random.Random(run.seed)
    dep = deploy.Deployment(cfg, rng)
    run.mark("keys")
    n = tr["chains"]
    esks = [deploy.rng_fr(rng, 1) for _ in range(n)]
    epks = deploy.g1_fixed(dep.params.g, esks)
    denominations = tr["denominations"]
    hidden = cfg["hidden_at_issue"]
    run.mark("pool")

    # each chain draws its coins from its own generator, so a seed gives
    # the same coins whatever order the callbacks run in
    chain_rngs = [random.Random("%d/%d" % (run.seed, c)) for c in range(n)]

    def coin(c):
        # hidden attributes first: the serial number, then the value
        r = chain_rngs[c]
        msgs = [deploy.rng_fr(r) for _ in range(hidden)]
        return msgs + [r.choice(denominations) for _ in range(dep.q - hidden)]

    be = run.backend()
    threshold = dep.t - 1 if run.control else dep.t
    engine = ProtocolEngine(
        dep.signers, dep.params, threshold,
        count_hidden=hidden,
        revealed_msg_indices=cfg["revealed_at_show"],
        vk=dep.vk, backend=be, max_batch=cfg["max_batch"],
        max_wait_ms=tr["max_wait_ms"],
        max_depth=2 * n,
    )
    lock = threading.Lock()
    released = []  # (time, credential, messages)
    failures = []  # (time, exception)
    stop = threading.Event()

    def start_chain(c):
        if stop.is_set():
            return
        msgs = coin(c)
        try:
            fut = engine.submit_prepare(msgs, epks[c])
        except Exception as e:
            return failed(c, e)
        fut.add_done_callback(lambda f: prepared(c, msgs, f))

    def prepared(c, msgs, fut):
        try:
            req, _ = fut.result(timeout=0)
            mint = engine.submit_mint(req, msgs, esks[c])
        except Exception as e:
            return failed(c, e)
        mint.add_done_callback(lambda f: minted(c, msgs, f))

    def minted(c, msgs, fut):
        try:
            cred = fut.result(timeout=0)
        except Exception as e:
            return failed(c, e)
        t = time.perf_counter()
        with lock:
            released.append((t, cred, msgs))
        start_chain(c)

    def failed(c, e):
        with lock:
            failures.append((time.perf_counter(), e))
        start_chain(c)

    def count():
        with lock:
            return len(released)

    batch = cfg["max_batch"]
    k0 = tr["warm_mints"]
    if k0 < n or k0 % n:
        raise ValueError("warm_mints has to be a whole number of turns")
    engine.start()
    try:
        for c in range(n):
            start_chain(c)
        # warm-up ends after warm_mints releases; a program whose mints
        # all fail goes on to the window too, where the checks count them
        while count() < k0 and len(failures) <= n:
            time.sleep(0.01)
        if failures:
            log("warm-up failures=%d first=%r" % (len(failures), failures[0][1]))
        run.mark("warm")
        with run.window():
            with lock:
                t_s = released[k0 - 1][0] if len(released) >= k0 else run.window_start
            time.sleep(max(0.0, t_s + run.seconds - time.perf_counter()))
            # none released in the window: nothing to wait for
            give_up = time.perf_counter() + (CLOSE_WAIT_S if count() > k0 else 0)
            while time.perf_counter() < give_up:
                with lock:
                    # the first whole turn of the loop past `seconds`
                    k = len(released)
                    whole = k0 + (k - k0) // n * n
                    if whole > k0 and released[whole - 1][0] >= t_s + run.seconds:
                        t_e = released[whole - 1][0]
                        break
                time.sleep(0.01)
            else:
                t_e = time.perf_counter()
            run.close()
        stop.set()
    finally:
        stop.set()
        try:
            engine.shutdown(drain=False, timeout=5.0)
        except Exception as e:
            # SigningAuthority.join can race abandon() after a quarantine
            # (its _thread goes None between the join and is_alive)
            log("engine shutdown raised %r" % (e,))

    with lock:
        inside = [r for r in released if t_s < r[0] <= t_e]
        fails = [f for f in failures if t_s < f[0] <= t_e]
    run.attempted = len(inside) + len(fails)
    run.failed = len(fails)
    run.e2e["mints_per_s"] = len(inside) / (t_e - t_s)
    run.counts["batches"] = len(inside) / cfg["max_batch"]
    run.counts["engine_ns"] = "prep"
    waits = run.hist.get("issue_quorum_wait_s")
    ends = [t_s] + [inside[i - 1][0] for i in range(batch, len(inside) + 1, batch)]
    log("window mints=%d failed=%d window_s=%.3f quorum_wait_p50_ms=%s "
        "batch_periods_s=%s"
        % (len(inside), len(fails), t_e - t_s,
           waits and 1e3 * percentile(waits, 50),
           ",".join("%.4f" % (b - a) for a, b in zip(ends, ends[1:]))))

    pick = random.Random(run.seed ^ 0x5EED)
    lo = batch * pick.randrange(max(1, len(inside) // batch))
    rest = inside[:lo] + inside[lo + batch:]
    sample = inside[lo:lo + batch] + pick.sample(
        rest, min(tr["reference_sample"], len(rest))
    )
    wrong = 0
    for _, cred, msgs in sample:
        wrong += not (
            ref.in_subgroup(cred.sigma_1)
            and ref.credential_valid(
                cred.sigma_1, cred.sigma_2, dep.x, dep.ys, msgs
            )
        )
    h_repeats = len(inside) - len({str(r[1].sigma_1) for r in inside})
    log("reference credentials_checked=%d" % len(sample))
    run.check("no_credentials_in_window", 0 if inside else 1, 0)
    run.check("failed_mints", len(fails), 0)
    run.check("invalid_credentials", wrong, 0)
    run.check("repeated_sigma_1", h_repeats, 0)
