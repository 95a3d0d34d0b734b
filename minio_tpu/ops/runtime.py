"""Process-level device runtime: where compiled programs are cached,
what this process compiled, which device it holds, and the first
failure of every rung of the backend ladder.

The ladder (docs/ROBUSTNESS.md) answers every request byte-identically
from a lower rung when a device rung fails — which is also how a kernel
that never compiled on the real chip stays invisible. The helpers here
make the device boundary speak without changing what it serves:

- :func:`ensure_compile_cache` places JAX's persistent compilation cache
  before the first device compile of a process. ``JAX_COMPILATION_CACHE_DIR``
  wins (JAX reads it itself; nothing is set in code); otherwise the cache
  lives at a FIXED path inside the checkout — the path is part of the
  cache key, so a temp name, pid or timestamp would never hit.
- :func:`compile_stats` counts programs compiled vs loaded from that cache
  and the seconds spent, from JAX's own monitoring events.
- :func:`device_info` names the device as JAX reports it.
- :func:`report_rung_failure` writes the first exception swallowed on
  each (rung, shape) to stderr, once.

Importing this module does not import jax: the worker-pool supervisor and
``MINIO_TPU_BACKEND=numpy`` workers must stay off the chip.
"""

from __future__ import annotations

import os
import sys
import threading

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (git-ignored): minio_tpu/ops/runtime.py -> repo root
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_cache_dir: str | None = None  # set once ensure_compile_cache() ran
_stats = {"programs": 0, "compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}
_announced = False
_reported: set[tuple[str, str]] = set()  # (rung, shape) already on stderr


def resolve_cache_dir() -> str | None:
    """The directory this code must SET, or None when the environment
    already places the cache (JAX reads JAX_COMPILATION_CACHE_DIR at
    import; code then sets nothing)."""
    if os.environ.get(CACHE_ENV):
        return None
    return DEFAULT_CACHE_DIR


def _on_duration(event: str, duration_s: float, **_kw) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        with _lock:
            _stats["programs"] += 1
            _stats["compile_s"] += duration_s


def _on_event(event: str, **_kw) -> None:
    key = {_CACHE_HIT_EVENT: "cache_hits", _CACHE_MISS_EVENT: "cache_misses"}.get(event)
    if key is not None:
        with _lock:
            _stats[key] += 1


def ensure_compile_cache() -> str:
    """Place the persistent compile cache and start counting compiles.
    Call before the first device compile; idempotent. Returns the
    directory in effect."""
    global _cache_dir
    with _lock:
        if _cache_dir is not None:
            return _cache_dir
    import jax

    target = resolve_cache_dir()
    if target is not None:
        jax.config.update("jax_compilation_cache_dir", target)
    with _lock:
        if _cache_dir is None:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _cache_dir = target or os.environ[CACHE_ENV]
        return _cache_dir


def compile_stats() -> dict | None:
    """Programs through the backend compiler in this process (a cache
    load counts as a program with a short duration), seconds spent
    there, and persistent-cache hits (loaded) / misses (compiled and
    written; a program that compiles in under JAX's 1 s threshold is
    neither). Tracing and lowering are Python-side and in neither the
    seconds nor the cache. None until :func:`ensure_compile_cache` ran
    (no device plane in this process)."""
    with _lock:
        if _cache_dir is None:
            return None
        return {**_stats, "cache_dir": _cache_dir}


def device_info() -> dict:
    """The device as JAX reports it. Initializes the backend — call only
    from a process that is meant to hold the device."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def announce_device_plane() -> None:
    """One boot-time stderr line naming the device this process holds and
    where its compiles are cached."""
    global _announced
    with _lock:
        if _announced:
            return
        _announced = True
    info = device_info()
    print(
        f"minio_tpu device plane: platform={info['platform']} "
        f"kind={info['kind']!r} devices={info['count']} "
        f"compile_cache={ensure_compile_cache()}",
        file=sys.stderr, flush=True,
    )


def report_rung_failure(rung: str, shape: str, err: BaseException) -> None:
    """First swallowed exception per (rung, shape) -> one stderr line.
    The ladder keeps serving from the rung below; this is what says why."""
    key = (rung, shape)
    with _lock:
        if key in _reported:
            return
        _reported.add(key)
    print(
        f"minio_tpu backend ladder: first failure on rung={rung} "
        f"shape={shape}: {type(err).__name__}: {err} "
        "(served from the rung below; later failures are only counted)",
        file=sys.stderr, flush=True,
    )
