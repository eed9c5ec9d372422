"""Which device the window path runs on, and where its compiles are kept.

The device executors are written for a TPU.  Nothing here may let a run
carry on quietly on the CPU backend: the default device is the TPU or an
error, unless the caller chose a platform through JAX's own
``JAX_PLATFORMS`` (the test suite does, tests/conftest.py).
"""

from __future__ import annotations

import os

import jax

#: fixed, derived from the package's location (the path is part of the
#: persistent cache's key, so a directory that moves never hits)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def default_devices():
    """``jax.devices()``, refused when JAX fell back to a non-TPU backend
    on its own.  A platform named in ``JAX_PLATFORMS`` is the caller's
    explicit choice and is honoured."""
    devs = jax.devices()
    if devs[0].platform != "tpu" and not os.environ.get("JAX_PLATFORMS"):
        raise RuntimeError(
            f"no TPU: JAX's default backend is {devs[0].platform!r} "
            f"({devs[0].device_kind}, {len(devs)} device(s)) and "
            "JAX_PLATFORMS is not set; the device window path does not "
            "fall back to it — set JAX_PLATFORMS to run there on purpose")
    return devs


def default_device():
    """Device 0 of :func:`default_devices`."""
    return default_devices()[0]


def require_tpu():
    """The default device, which must be a TPU whatever ``JAX_PLATFORMS``
    says (bench.py, chip_smoke.py: a figure named ``*_tpu`` comes from a
    chip or not at all)."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"needs a TPU, found platform {devs[0].platform!r} "
            f"({devs[0].device_kind}, {len(devs)} device(s))")
    return devs[0]


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` of the default backend as JAX
    reports it — every CLI result carries this."""
    devs = default_devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Called by every entry point before its first compile (never at package
    import).  ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside
    and JAX reads it itself, so no directory is set in code then; otherwise
    the cache lives at ``<checkout>/.jax_cache``.  The step executables
    compile in 0.3-1.7 s each, under JAX's default 1 s storage threshold,
    so both thresholds are dropped."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def cli_start() -> dict:
    """What every CLI ``main`` does before any work: place the compile
    cache, then fail — here, not after a warmup — when there is no TPU and
    ``JAX_PLATFORMS`` names nothing else.  Returns :func:`device_info`."""
    enable_compile_cache()
    return device_info()
