"""Who may hold the accelerator, and where JAX keeps compiled programs.

A chip belongs to one process at a time: the first process to
initialise a JAX backend holds it until it exits. In a deployment that
process is the volume server, the only role that runs the codec; the
master, filer, gateways and shell must never initialise a backend, or
the volume server that needs the chip fails or hangs.
"""
from __future__ import annotations

import os
import sys

# the in-tree persistent compile cache used when the environment does
# not place one: a fixed path, because the directory is part of the
# cache key and a moving one never hits
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def cpu_forced() -> bool:
    """True when JAX_PLATFORMS=cpu asked for the CPU on purpose (the
    tests and CPU rehearsals): device codecs then run on the CPU
    backend instead of refusing."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def require_accelerator(backend: str) -> None:
    """Raise unless JAX's default device is an accelerator or the CPU
    was forced. An explicit device codec that silently ran on the CPU
    would report CPU speed under a device name."""
    if cpu_forced():
        return
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        raise RuntimeError(
            f"ec backend {backend!r} needs an accelerator, but JAX "
            f"found only {dev.platform} devices; set JAX_PLATFORMS=cpu "
            "to run it on the CPU on purpose")


def backends_initialized() -> bool:
    """Whether this process already initialised a JAX backend (and so
    may hold the chip). Importing jax initialises nothing; asking for
    devices does."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point
    (cli.main, chip_smoke.py; never the tests). The directory
    is JAX_COMPILATION_CACHE_DIR when set — JAX reads it itself — and
    otherwise the fixed in-tree CACHE_DIR. Every compile is kept: the
    codec kernels compile in well under JAX's default one-second floor
    and would otherwise never be cached. Returns the directory."""
    import jax

    path = os.environ.get(_CACHE_ENV, "").strip()
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
