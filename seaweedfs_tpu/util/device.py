"""What this process computes on, read once and never guessed.

The device planes (EC codec, index probes) are written for a TPU. Whether
one is attached is a fact of the process, not something to recover from:
`platform()` asks JAX once, and an error from JAX is an error — no caller
turns it into "no device, serve from the host".
"""

from __future__ import annotations

import functools
import os
import sys
from typing import Optional

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


@functools.lru_cache(maxsize=None)
def describe() -> dict:
    """{"platform", "device_kind", "count"} as JAX reports them."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }


def platform() -> str:
    return describe()["platform"]


def on_tpu() -> bool:
    return platform() == "tpu"


def require_chip(**requested: str) -> None:
    """`-storageBackend tpu` and `-batchLookup device|arena` are requests
    for the chip. On any other backend refuse to start — unless
    JAX_PLATFORMS itself says `cpu`, which is how tests, rehearsals and the
    chipless children of a one-chip cluster say so outright."""
    if on_tpu() or os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        return
    asked = " ".join(f"-{flag} {value}" for flag, value in requested.items())
    raise RuntimeError(
        f"{asked} asks for a TPU, but this process's JAX backend is "
        f"{platform()!r} (JAX_PLATFORMS="
        f"{os.environ.get('JAX_PLATFORMS', '')!r}). Attach the chip, or set "
        "JAX_PLATFORMS=cpu to run the device planes on the host on purpose."
    )


def setup_compile_cache() -> Optional[str]:
    """Persistent compile cache, placeable from outside. Where
    JAX_COMPILATION_CACHE_DIR is set that directory is used and none is
    set in code; otherwise `<checkout>/.jax_cache` — a fixed path (the path
    is part of the cache key, so a directory that moves never hits). The
    thresholds drop to 0 so the sub-second GF kernels are kept too.
    Call before the first compile; returns the directory.

    A process told outright to run on the CPU (JAX_PLATFORMS=cpu: tests,
    rehearsals, the chipless children of a cluster) is left alone and
    gets None: it compiles in milliseconds, and XLA:CPU's loader logs a
    page of machine-feature warnings on every cache hit."""
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        return None
    path = os.environ.get(_CACHE_ENV)
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        _configure("jax_compilation_cache_dir", path)
    _configure("jax_persistent_cache_min_compile_time_secs", 0)
    _configure("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _configure(name: str, value) -> None:
    # JAX reads its config from the environment when it is imported (2 s:
    # the CLI's masters, filers and shells never pay it), so before that
    # the environment IS the config — and children inherit it
    if "jax" in sys.modules:
        sys.modules["jax"].config.update(name, value)
    else:
        os.environ[name.upper()] = str(value)


_watching = False


def watch_compiles() -> None:
    """Count this process's XLA compiles into /metrics (seconds, compiles,
    persistent-cache hits and misses) — what a chip run spends before its
    first answer, seen from outside the process."""
    global _watching
    if _watching:
        return
    _watching = True
    from jax import monitoring

    from . import metrics

    def on_duration(event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            metrics.JAX_COMPILES.inc()
            metrics.JAX_COMPILE_SECONDS.inc(seconds)

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            metrics.JAX_COMPILE_CACHE.inc(result="hit")
        elif event == "/jax/compilation_cache/cache_misses":
            metrics.JAX_COMPILE_CACHE.inc(result="miss")

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
