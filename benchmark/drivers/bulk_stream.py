"""bulk_stream: a ledger verifier streaming batches of credentials through
`stream.verify_stream(mode="per_credential")`, checkpointing each batch.

Traffic parameters: pool_batches distinct batches of the configuration's
max_batch credentials, cycled; tampered_per_batch lanes of each carry
sigma_2 doubled; warm_batches go through a stream of their own before the
window; reference_sample lanes (besides every tampered one) are judged by
the plain reference after it.

The window is one stream, started at the window's start and closed by
the first batch that settles once `seconds` have passed; `verdicts_per_s`
counts the lanes whose verdicts the window delivered, that batch's
included, over the window's length as it ran, so the rate is not rounded
to whole batches. Every such verdict is compared with the lane's
expected bit.

Controls: "one_bool_per_batch" streams the program's RLC-combined mode
(one verdict per batch) and hands that verdict to every lane.
"""

import os
import random
import shutil

from .. import deploy
from ..harness import log, scratch_dir

CONTROLS = {"one_bool_per_batch": "batched"}


class WindowClosed(Exception):
    pass


def run(run):
    from coconut_tpu.stream import verify_stream

    cfg, tr = run.cfg, run.traffic
    rng = random.Random(run.seed)
    dep = deploy.Deployment(cfg, rng)
    run.mark("keys")
    batch = cfg["max_batch"]
    pool = deploy.credential_pool(
        dep, rng, tr["pool_batches"], batch, tr["tampered_per_batch"]
    )
    run.mark("pool")
    if run.control is not None and run.control not in CONTROLS:
        raise ValueError("unknown control %r" % run.control)
    mode = CONTROLS.get(run.control, "per_credential")

    be = run.backend()
    if hasattr(be, "encode_verify_batch"):
        run.wrap(be, "encode_verify_batch", "encode")
    dispatch_name = {
        "per_credential": "batch_verify_async",
        "batched": "batch_verify_combined_async",
    }[mode]
    if hasattr(be, dispatch_name):
        _wrap_dispatch(run, be, dispatch_name)

    def source(i):
        sigs, msgs, _ = pool[i % len(pool)]
        return sigs, msgs

    mismatches = [0]
    lanes = [0]
    batches = [0]

    def lane_bits(result, n):
        return list(result) if isinstance(result, list) else [bool(result)] * n

    def judge(i, result):
        want = pool[i % len(pool)][2]
        bits = lane_bits(result, len(want))
        mismatches[0] += sum(b != w for b, w in zip(bits, want))
        mismatches[0] += abs(len(bits) - len(want))
        return len(bits)

    tmp = scratch_dir()
    try:
        verify_stream(
            source, tr["warm_batches"], dep.vk, dep.params, be,
            state_path=os.path.join(tmp, "warm.ckpt"), mode=mode,
            on_batch=judge,
        )
        warm_mismatches, mismatches[0] = mismatches[0], 0
        run.mark("warm")

        def on_batch(i, result):
            lanes[0] += judge(i, result)
            batches[0] += 1
            if run.remaining() <= 0:
                run.close()
                raise WindowClosed

        with run.window():
            try:
                verify_stream(
                    source, 1 << 40, dep.vk, dep.params, be,
                    state_path=os.path.join(tmp, "window.ckpt"), mode=mode,
                    on_batch=on_batch,
                )
            except WindowClosed:
                pass
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    run.attempted = lanes[0]
    run.failed = 0
    run.e2e["verdicts_per_s"] = lanes[0] / run.window_s
    run.counts["batches"] = batches[0]
    log("window batches=%d lanes=%d window_s=%.3f"
        % (batches[0], lanes[0], run.window_s))

    disagree, checked = deploy.reference_disagreements(
        dep, pool, random.Random(run.seed ^ 0x5EED), tr["reference_sample"]
    )
    log("reference lanes_checked=%d" % checked)
    run.check("no_verdicts_in_window", 0 if lanes[0] else 1, 0)
    run.check("warm_verdict_mismatches", warm_mismatches, 0)
    run.check("verdict_mismatches", mismatches[0], 0)
    run.check("reference_disagreements", disagree, 0)


def _wrap_dispatch(run, be, name):
    """Span "dispatch" around encode + launch, span "readback" around the
    wait for each batch's verdicts."""
    fn = getattr(be, name)

    def dispatch(*a, **kw):
        with run.span("dispatch"):
            finalize = fn(*a, **kw)

        def readback():
            with run.span("readback"):
                return finalize()

        return readback

    setattr(be, name, dispatch)
