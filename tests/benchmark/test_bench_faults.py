"""Each cell driven end to end on the CPU, with the native core in the
device backend's place and the chip check skipped: sound, it comes out
correct; with a fault planted in the timed path, or run as its control,
it comes out not correct."""

import pytest

from bench_cpu_backend import (
    AlteredAggregate,
    FailingShowVerify,
    FlipOneVerdict,
    HalfBatch,
    NativeBackend,
    UngatedAlteredAggregate,
    UngatedAlteredOneLane,
)
from benchmark import run as bench_run
from benchmark import spec

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2**33 + 11  # above 32 signed bits, as the driver's seeds are
SHOWV = "showv.q6-3of5.open"


def bench():
    """BENCHMARK.json, plus a show-verify cell for the open_show driver,
    which no committed cell drives yet."""
    b = spec.load()
    b["workloads"].append({
        "name": SHOWV, "config": "coconut-q6-3of5", "traffic": "open_show",
        "chips": 1, "why": "the open_show driver at test size",
    })
    b["end_to_end"].append({
        "name": "latency_p95_ms", "unit": "ms", "better": "lower",
        "bound": 0.25, "source": "host_clock", "workloads": [SHOWV],
    })
    return b


SMALL = {
    "verify.q6-3of5.bulk": (
        {"max_batch": 8},
        {"pool_batches": 2, "tampered_per_batch": 4, "warm_batches": 1,
         "reference_sample": 4},
    ),
    SHOWV: (
        {"max_batch": 8},
        {"rate_per_s": 16, "pool_size": 32, "tampered_every": 2,
         "warm_batches": 1, "max_wait_ms": 50, "drain_s": 20,
         "reference_sample": 4},
    ),
    # no sample beyond the one whole batch, which alone has to find
    # every planted fault
    "mint.q2-3of5.closed": (
        {"max_batch": 4},
        {"chains": 8, "warm_mints": 8, "max_wait_ms": 2000,
         "reference_sample": 0},
    ),
}


def drive(cell, backend=NativeBackend, control=None, seconds=1):
    cfg_over, traffic_over = SMALL[cell]
    result, run = bench_run.run_cell(
        bench(), cell, SEED, seconds, False, control=control,
        device=CPU, backend_factory=backend, overrides=cfg_over,
        traffic_overrides=traffic_over,
    )
    return result, run


def assert_line(result):
    assert list(result)[:5] == [
        "correct", "attempted", "failed", "metrics", "device"
    ]
    assert list(result)[-1] == "checks"
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(cell):
    result, run = drive(cell)
    assert_line(result)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0
    for m in spec.end_to_end(bench(), cell):
        assert result["metrics"][m["name"]]["value"] > 0


FAULTS = [
    ("verify.q6-3of5.bulk", FlipOneVerdict),
    ("verify.q6-3of5.bulk", HalfBatch),
    (SHOWV, FlipOneVerdict),
    (SHOWV, HalfBatch),
    (SHOWV, FailingShowVerify),
    ("mint.q2-3of5.closed", AlteredAggregate),
    ("mint.q2-3of5.closed", UngatedAlteredAggregate),
    ("mint.q2-3of5.closed", UngatedAlteredOneLane),
]


@pytest.mark.parametrize(
    "cell,fault", FAULTS, ids=["%s-%s" % (c, f.__name__) for c, f in FAULTS]
)
def test_fault_in_timed_path_is_not_correct(cell, fault):
    result, _ = drive(cell, backend=fault)
    assert_line(result)
    assert not result["correct"], result["checks"]


CONTROLS = [
    ("verify.q6-3of5.bulk", "one_bool_per_batch"),
    (SHOWV, "one_bool_per_batch"),
    ("mint.q2-3of5.closed", "threshold_below_quorum"),
]


@pytest.mark.parametrize("cell,control", CONTROLS)
def test_control_is_not_correct(cell, control):
    result, _ = drive(cell, control=control)
    assert not result["correct"], result["checks"]
