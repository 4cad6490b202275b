"""Ahead-of-time compiles for a DESCRIBED v5e chip (no chip attached).

The TPU's compiler refuses what the Pallas interpreter accepts: a slice
not aligned to the tiling, more VMEM than a kernel may use. These tests
lower and compile the Pallas Montgomery multiply, at the lane widths the
1024-lane fused programs feed it, and one Fp12 multiply on that kernel,
for a v5e:2x2 topology described in-process. Nothing runs; results on
the chip are chip_smoke.py's job.

The topology is described inside a module-scoped fixture (never at
import): only one process may load the TPU library, and the worker that
runs this file keeps it until it exits. The persistent compile cache is
off around the compiles — an entry written for a described chip cannot
be read back without one.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from coconut_tpu.tpu import pallas_fp
from coconut_tpu.tpu import tower as tw
from coconut_tpu.tpu.limbs import NLIMBS

# Lane widths of pallas_fp.mul inside the 1024-lane per-credential
# verifier (traced with the TPU settings): one Fp lane per credential,
# the Fp2 pair, the three-way stack, and the two widest fused stacks.
LANES = (1024, 2048, 3072, 18432, 46080)


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled.as_text()


@pytest.mark.parametrize("lanes", LANES)
def test_pallas_mul_compiles_for_v5e(one_chip, lanes):
    x = jax.ShapeDtypeStruct((lanes, NLIMBS), jnp.float32, sharding=one_chip)
    text = _compile(pallas_fp.mul, x, x)
    assert "tpu_custom_call" in text


def test_fp12_mul_compiles_on_pallas_for_v5e(one_chip, monkeypatch):
    monkeypatch.setattr(pallas_fp, "_ENABLED", True)
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: tw.fp12_ones((1024,))),
    )
    text = _compile(tw.fp12_mul, shapes, shapes)
    assert "tpu_custom_call" in text
