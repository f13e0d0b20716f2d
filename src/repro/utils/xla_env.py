"""Process-level XLA setup shared by the entry points.

``force_host_device_count`` is the "N host devices" dance of the CPU
multi-device harness (tests/conftest.py keeps its own pre-import copy
because it must run before anything under ``repro`` loads).  It does not
choose the platform: a caller that wants the CPU harness sets
``JAX_PLATFORMS=cpu`` itself, so on an accelerator host nothing here hides
the device.

``enable_compile_cache`` turns on JAX's persistent compilation cache for
the launchers, the examples and ``chip_smoke.py``.
"""

from __future__ import annotations

import os
import pathlib

_COUNT_FLAG = "--xla_force_host_platform_device_count"

#: where the compile cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: a fixed path (the directory is part of the cache key, so a temporary or
#: per-process directory would never hit), listed in ``.gitignore``
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def force_host_device_count(n: int) -> None:
    """Point XLA's CPU backend at ``n`` host devices.

    Must run before the first jax BACKEND INIT (the first device use) in the
    process — merely having imported jax is fine. Strips any pre-existing
    count flag so this one wins regardless of XLA's duplicate-flag
    precedence; a no-op on an already-initialized backend.
    """
    rest = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith(_COUNT_FLAG)
    )
    os.environ["XLA_FLAGS"] = (rest + f" {_COUNT_FLAG}={int(n)}").strip()


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself, and
    no other directory is set here); otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
