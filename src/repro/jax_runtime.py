"""Process-level JAX runtime settings shared by every entry point.

* **Compile cache.**  JAX's persistent compilation cache lives in
  ``JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise at one
  fixed, git-ignored path inside the checkout (``.jax_cache/``).  The
  directory is part of what makes a cached program findable again, so
  it never depends on a temporary name, a pid or a time.  The sweep
  workers, the fabric's cache shipping and ``chip_smoke.py`` all
  resolve it here.  It is on by default on an accelerator; on the CPU
  only when ``JAX_COMPILATION_CACHE_DIR`` is set.
* **One process per chip.**  A process that has initialised JAX on an
  accelerator holds the device; a child process that needs it then
  fails or hangs.  :func:`on_accelerator` is what the sweep and the
  fabric worker consult before spawning process pools.
"""
from __future__ import annotations

import os
import pathlib

#: the checkout root (this file is ``<root>/src/repro/jax_runtime.py``)
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"


def compile_cache_dir() -> str:
    """The persistent compilation cache directory for this process."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(DEFAULT_CACHE_DIR))


def enable_compile_cache() -> str | None:
    """Point JAX's persistent cache at :func:`compile_cache_dir` and
    cache every program, however quick its compile (idempotent; call it
    before the first compile).  Returns the directory, or ``None`` when
    the cache stays off: on a CPU backend it is used only where
    ``JAX_COMPILATION_CACHE_DIR`` asks for it, because XLA:CPU reloads
    log a host-feature mismatch error for every cached program."""
    import jax
    if not (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or on_accelerator()):
        return None
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def on_accelerator() -> bool:
    """True when JAX's default backend is not the host CPU."""
    import jax
    return jax.default_backend() != "cpu"
