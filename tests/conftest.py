"""Test config: force CPU with a virtual 8-device mesh.

The suite is a CPU suite wherever it runs: JAX_PLATFORMS=cpu is set before
jax is imported, and the jax.config update below makes the pin hold even if
something imported jax first. XLA_FLAGS is read at backend init, so setting
it here is still in time. The chip is reached only by `python chip_smoke.py`
through the chip tool, never by pytest.

Multi-chip sharding tests then run against the 8 virtual CPU devices; the
driver separately dry-runs the multi-chip path via
__graft_entry__.dryrun_multichip.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# CI sets JAX_COMPILATION_CACHE_DIR and caches that directory across runs
# (.github/workflows/ci.yml). JAX reads the variable itself; this only
# zeroes the size/time gates so the small scoring programs persist too.
# Unset, the suite keeps no persistent cache (compile-count gates stay
# exact) — the checkout default is for entry points, not for pytest.
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    from foremast_tpu.engine.pipeline import enable_compile_cache

    enable_compile_cache()
