"""JAX/TPU execution layer.

Everything under this package is the TPU-native equivalent of the reference's
`amcl_wrapper` curve layer (SURVEY.md §2.2) re-designed for XLA: 381-bit base
field elements are decomposed into 52 x 8-bit lazy signed limbs in float32,
limb products run as bf16 matmuls with exact f32 accumulation ON THE MXU
(see tpu/limbs.py for why this representation), every operation is natively
batched over leading array dimensions, control flow is `lax.scan` over the
static BLS parameter bits, and the whole credential-verification hot path
(reference signature.rs:472-478) compiles to one fused XLA program per batch
shape. No 64-bit lane support is required — everything is f32/bf16/int32.
"""

import functools
import os as _os

#: The checkout's own cache directory (listed in .gitignore), used when
#: JAX_COMPILATION_CACHE_DIR does not name one.
DEFAULT_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache():
    """Turn on JAX's persistent compile cache — the one definition every
    entry point (tests/conftest.py, bench.py, chip_smoke.py,
    __graft_entry__, engine.lifecycle) goes through.

    When JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and no
    directory is set here; otherwise the cache lives in the checkout's
    `.jax_cache`. Returns the directory in use."""
    import jax

    env_dir = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 5.0)
    return env_dir or DEFAULT_CACHE_DIR


@functools.cache
def on_tpu():
    """Whether JAX's default backend is a TPU: the platform default of
    every TPU-only choice (Pallas multiply, 9-bit comb, raw wire, device
    hash, bucketed MSM). Asked once; a backend that fails to initialise
    raises here instead of silently selecting the CPU choices."""
    import jax

    return jax.default_backend() == "tpu"
