"""What a run executes on, and where its compiled programs are kept.

Helpers for the entry points (``repro.launch.serve``, ``benchmarks.run``
and the repository's ``chip_smoke.py``). Nothing here touches JAX on
import, and tests do not call :func:`use_compile_cache`: it changes
process-wide JAX configuration.
"""
from __future__ import annotations

import os
import pathlib

#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR``
#: is not set: ``.jax_cache`` at the repository root (gitignored). The
#: directory is part of the cache key, so it is fixed — never a temporary
#: name, a pid or the time.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at a fixed place.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other path; otherwise the cache goes to
    :data:`CACHE_DIR`. Call it once, from an entry point, before the
    first compilation.

    Returns:
        The cache directory in force.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def device_info() -> dict:
    """The devices this process runs on, as JAX reports them.

    Returns:
        ``{"platform", "kind", "count"}`` of ``jax.devices()`` — the
        first device's platform and ``device_kind``, and how many there
        are.
    """
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def device_line() -> str:
    """:func:`device_info` as one human-readable line."""
    d = device_info()
    return f"platform={d['platform']} kind={d['kind']} count={d['count']}"
