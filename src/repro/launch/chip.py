"""Start-up for runs that must be on a TPU: the compile cache and the device.

``setup_compile_cache`` runs before anything compiles.  A process started
with ``JAX_COMPILATION_CACHE_DIR`` keeps its cache there (JAX reads the
variable itself, so no other directory is set in code); otherwise the
cache sits at the fixed, git-ignored ``<repo>/.jax_cache``.  The path is
part of each entry's key, so it never depends on a temporary name, PID or
time.

``require_tpu`` is the guard against a run that silently lands on the CPU:
the kernels and the planner's sort pick CPU code paths whenever the backend
is not a TPU, and such a run would otherwise look healthy.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def require_tpu(n: int = 1) -> list:
    """The first ``n`` TPU devices; raises RuntimeError when JAX sees fewer."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX's default backend is {devs[0].platform!r} "
            f"({len(devs)} device(s))"
        )
    if len(devs) < n:
        raise RuntimeError(f"need {n} TPU devices, JAX sees {len(devs)}")
    return devs[:n]
