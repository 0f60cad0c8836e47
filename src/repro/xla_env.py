"""Process-level XLA environment knobs.

Import-safe WITHOUT jax: these must run before jax initialises its
backend (device count is locked on first init), so every entry point
that needs virtual host devices calls ``ensure_host_devices`` at the
very top, before any jax-importing module.

Used by tests/conftest.py, benchmarks/run.py and
benchmarks/lut_infer_bench.py (4 devices for the sharded serving
path).  launch/dryrun.py keeps its own 512-device setup — it
deliberately owns the whole subprocess environment.

``use_compile_cache`` places JAX's persistent compilation cache for the
entry points (chip_smoke.py, launch/serve.py, launch/worker.py and the
benchmark scripts); tests never call it.
"""
from __future__ import annotations

import os

# <checkout>/.jax_cache: a FIXED path (git-ignored), since the cache
# key includes it — a temp, pid or time-based directory never hits
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_host_devices(n: int = 4) -> None:
    """Append ``--xla_force_host_platform_device_count=n`` to XLA_FLAGS
    unless a count is already set (never override an explicit choice).
    Only affects the host (CPU) platform — harmless on TPU.  A no-op
    if jax is already initialised, so call it before importing jax."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and no directory is set here; otherwise the cache goes to
    the checkout's ``.jax_cache``.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
