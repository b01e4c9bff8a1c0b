"""Test bootstrap.

Tests run JAX on the CPU with 8 virtual devices, so multi-chip sharding
(openr_tpu/parallel) is exercised without TPU hardware; the chip is only
ever driven by chip_smoke.py (one process owns it). Both settings are
plain environment variables, set before jax is first imported, so the
daemons that tests spawn as child processes inherit them.
"""

import asyncio
import functools
import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
# hermetic tests: never load persistent-cache AOT artifacts compiled for
# a different backend/machine-feature set (ops/xla_cache.py)
os.environ["OPENR_TPU_XLA_CACHE"] = "off"
# same for the serialized-executable cache: a developer's fleet-wide
# $OPENR_TPU_AOT_CACHE opt-in must not leak entries into (or out of)
# the suite; tests that exercise it configure a tmp dir explicitly
os.environ["OPENR_TPU_AOT_CACHE"] = "off"


def run_async(fn):
    """Decorator: run an async test in a fresh event loop
    (no pytest-asyncio in the image)."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        return asyncio.run(asyncio.wait_for(fn(*args, **kwargs), timeout=60))

    return wrapper


@pytest.fixture
def fresh_xla_cache_state():
    """enable_compilation_cache is first-call-wins and mutates jax's
    config; isolate both (the conftest keeps the cache off)."""
    import jax

    import openr_tpu.ops.xla_cache as xc

    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    old_applied = xc._applied
    old_cfg = {k: getattr(jax.config, k) for k in keys}
    old_aot = xc.get_aot().dir
    xc._applied = None
    yield xc
    xc._applied = old_applied
    # the prewarm tool turns the AOT store on ('auto'): left on, it
    # answers whichever test file this worker runs next
    xc.configure_aot(old_aot or "off")
    for k, v in old_cfg.items():
        jax.config.update(k, v)
