"""Process-environment knobs that must be set before the first jax import.

Deliberately imports nothing heavy (``repro`` is a namespace package, so
``import repro.envflags`` pulls no jax): tests/conftest.py,
benchmarks/run.py and the examples call ``force_virtual_devices`` first
thing, before any module that imports jax; ``chip_smoke.py``,
benchmarks/run.py and the examples call ``use_compile_cache`` the same
way.
"""
from __future__ import annotations

import os

_COUNT_FLAG = "--xla_force_host_platform_device_count"
_CACHE_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def force_virtual_devices(n: int = 8, override: bool = False) -> None:
    """Expose ``n`` virtual CPU devices via ``XLA_FLAGS``.

    Appends to operator-set flags instead of clobbering them. An existing
    device-count flag wins unless ``override=True`` (which replaces only
    that flag and keeps the rest). Has no effect on processes that
    already imported jax — call this before the first jax import.
    """
    cur = os.environ.get("XLA_FLAGS", "")
    if _COUNT_FLAG in cur:
        if not override:
            return
        cur = " ".join(p for p in cur.split() if not p.startswith(_COUNT_FLAG))
    os.environ["XLA_FLAGS"] = f"{cur} {_COUNT_FLAG}={n}".strip()


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process.

    Sets ``JAX_COMPILATION_CACHE_DIR`` to ``<checkout>/.jax_cache`` unless
    it is already set: a directory chosen from outside wins, and no other
    directory is set in code. A fixed path matters because the path is
    part of the cache key. JAX reads the variable when it is imported, so
    call this before the first jax import. Returns the directory in use.
    """
    return os.environ.setdefault(_CACHE_VAR,
                                 os.path.join(_CHECKOUT, ".jax_cache"))
