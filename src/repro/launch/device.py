"""Device bring-up shared by every entry point.

* :func:`enable_compile_cache` — JAX's persistent compilation cache, turned
  on before the first compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
  the cache (JAX reads it itself; nothing else is set here).  Otherwise the
  cache lives at :data:`CACHE_DIR`, a fixed path inside the checkout: the
  path is part of what a later process must find again, so it is never
  derived from a temporary name, a process id or the time.
* :func:`take_devices` — the first ``n`` visible devices, or an error that
  names the platform.  Forcing host devices through ``XLA_FLAGS`` is advice
  for the CPU only; on an accelerator too few devices is a fact about the
  machine.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import List

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def take_devices(n: int, what: str) -> List:
    """The first ``n`` devices of the default backend for ``what`` (a
    short description of the run that needs them)."""
    devs = jax.devices()
    if len(devs) >= n:
        return devs[:n]
    platform = devs[0].platform
    hint = (f"; set XLA_FLAGS=--xla_force_host_platform_device_count={n}"
            if platform == "cpu" else "")
    raise RuntimeError(f"{what} needs {n} devices but {len(devs)} "
                       f"{platform} device(s) are visible{hint}")
