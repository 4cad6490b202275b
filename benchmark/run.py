"""Run one benchmark cell and print its result as the last line.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
BENCHMARK.json. The run refuses any device other than a TPU, builds keys
and inputs from --seed, warms the cell's own shapes, measures for
--seconds, checks what the window produced against the plain reference
and prints one JSON object: correct, attempted, failed, metrics, device,
(with --trace 1) breakdown, and last the checks, each number beside its
limit. The checks are also the last lines on standard error.

--control <name> runs a control instead of the system as configured: a
path that breaks one guarantee the configuration states, which the checks
must find. The benchmark's own runs never pass it.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from . import spec  # noqa: E402
from . import trace as trace_mod  # noqa: E402
from .harness import Run, log  # noqa: E402


class NoChip(RuntimeError):
    pass


def device_info(chips):
    """JAX's devices, refused unless they are `chips` or more TPUs."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise NoChip(
            "no TPU: JAX's first device is %r (platform %s)" % (d0, d0.platform)
        )
    if len(devices) < chips:
        raise NoChip("the cell needs %d TPUs, JAX sees %d" % (chips, len(devices)))
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def enable_cache():
    """The program's own persistent compile cache (JAX_COMPILATION_CACHE_DIR
    or <checkout>/.jax_cache), keeping every program however fast it
    compiled, so that only a cell's first run in a checkout compiles."""
    import jax

    from coconut_tpu.tpu import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


def resolved_choices():
    """The algorithm choices the program resolved for this device, so a
    PR that changes a default is seen in the run's log."""
    from coconut_tpu.tpu import backend as tb
    from coconut_tpu.tpu import pallas_fp

    tb._bucket_window(1, 255)
    return {
        "pallas_fp": pallas_fp.enabled(),
        "comb_schedule": tb._comb_schedule(),
        "raw_wire": tb._raw_wire_enabled(),
        "device_hash": tb._device_hash_enabled(),
        "bucket_mode": tb._BUCKET_MODE,
    }


def run_cell(bench, name, seed, seconds, traced, control=None,
             device=None, backend_factory=None, overrides=None,
             traffic_overrides=None, t0=None):
    """Drive one cell and return (result dict, Run). `device` is the
    device dict; tests pass one together with a CPU backend_factory and
    small `overrides` of the configuration and the traffic mix."""
    cell = spec.cell(bench, name)
    cfg = dict(spec.config(bench, cell["config"]))
    cfg.update(overrides or {})
    traffic = dict(spec.traffic(cell["traffic"]))
    traffic.update(traffic_overrides or {})
    run = Run(cell, cfg, traffic, seed, seconds, traced,
              T0 if t0 is None else t0, control=control,
              backend_factory=backend_factory)
    run.listen()
    spec.driver(traffic["kind"]).run(run)
    if run.setup_s is None:
        raise RuntimeError("the driver never opened its window")

    device = dict(device)
    device["memory_peak_bytes"] = run.memory_peak
    metrics = {}
    if traced:
        red = run.reduced or {"busy_s": 0.0, "window_s": run.seconds}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        for m in spec.per_layer(bench, name):
            value = spec.reader(m["name"]).read(m["name"], run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec.end_to_end(bench, name):
            value = run.setup_s if m["name"] == "setup_s" else run.e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": run.correct(),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if traced and run.reduced is not None:
        result["breakdown"] = trace_mod.breakdown(run.reduced)
    result["checks"] = {
        n: {"value": v, "limit": lim} for n, v, lim in run.checks
    }
    return result, run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)
    bench = spec.load()
    cell = spec.cell(bench, args.workload)
    try:
        device = device_info(cell["chips"])
    except NoChip as e:
        log("refused: %s" % e)
        return 2
    log("device platform=%s kind=%s count=%d"
        % (device["platform"], device["kind"], device["count"]))
    log("compile_cache_dir %s" % enable_cache())
    log("choices %s" % json.dumps(resolved_choices(), sort_keys=True))
    result, run = run_cell(
        bench, args.workload, args.seed, args.seconds, bool(args.trace),
        control=args.control, device=device,
    )
    log("setup_s=%r window_compiles=%d" % (run.setup_s, run.window_compiles))
    run.report_checks()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # engine and prefetch worker threads are daemons; end without waiting
    # on them once the result is out
    os._exit(code)
