"""The JAX backend as the daemon sees it: where the persistent compilation
cache lives, what device serves, and what one dispatch costs.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
this module sets no other directory. Otherwise the cache goes to a fixed
``<checkout>/.jax_cache`` (gitignored). The path is part of the cache's
key, so it must not move between runs.
"""

import os
import time
from pathlib import Path
from typing import Dict, Optional

from .lockorder import make_lock

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# JAX monitoring events this process has seen (cache_stats and
# compile_counts read them); the listeners are registered once per process
_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
}
_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_counts: Dict[str, int] = {
    name: 0 for name in (*_EVENTS.values(), *_DURATION_EVENTS.values())
}
_counts_lock = make_lock("platform.compile_counts")
_listening: list = []  # non-empty once the listeners are registered


def compilation_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)


def _count(name) -> None:
    if name is not None:
        with _counts_lock:
            _counts[name] += 1


def _count_event(event: str, **_kw) -> None:
    _count(_EVENTS.get(event))


def _count_duration_event(event: str, _secs: float, **_kw) -> None:
    _count(_DURATION_EVENTS.get(event))


def listen_for_compiles() -> None:
    """Count JAX's compile events from here on (idempotent)."""
    import jax

    with _counts_lock:
        if not _listening:
            jax.monitoring.register_event_listener(_count_event)
            jax.monitoring.register_event_duration_secs_listener(_count_duration_event)
            _listening.append(True)


def enable_compilation_cache(platform: str) -> Optional[str]:
    """Turn the persistent compilation cache on for an accelerator backend
    and return its directory; None on ``"cpu"``.

    Call before the first compile: JAX decides once, at its first
    compile, whether a cache is in use. Prewarming compiles dozens of
    shapes, so a restart deserializes them instead of compiling again.
    On the CPU it declines: XLA's CPU loader warns on every cache hit,
    and CPU compiles are ~10-100ms anyway.
    """
    if platform == "cpu":
        return None
    import jax

    path = compilation_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    # cache small computations too — this workload is many small
    # scatter/gather shapes whose compile times sit under the default
    # min-compile-time threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    listen_for_compiles()
    return path


def cache_stats() -> Dict[str, int]:
    """{"requests", "hits"}: compiles that consulted the persistent cache
    since enable_compilation_cache, and how many it answered."""
    with _counts_lock:
        return {"requests": _counts["requests"], "hits": _counts["hits"]}


def compile_counts() -> Dict[str, int]:
    """{"trace", "compile", "cache_load"} since listen_for_compiles: jaxpr
    traces (a new program or shape), backend compiles, and programs loaded
    from the persistent cache instead of compiled."""
    with _counts_lock:
        return {"trace": _counts["trace"], "compile": _counts["compile"],
                "cache_load": _counts["hits"]}


def device_info() -> dict:
    """The serving backend as JAX reports it, plus the first device's
    memory high-water mark where the backend tracks one."""
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    stats = devices[0].memory_stats() or {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if key in stats:
            info[key] = int(stats[key])
    return info


def dispatch_round_trip(n: int = 15) -> float:
    """Median seconds for one small jitted op, dispatch to host result:
    what any single device call costs before its arithmetic."""
    import jax
    import numpy as np

    f = jax.jit(lambda x: x + 1)
    x = jax.device_put(np.ones(8, dtype=np.int64))
    np.asarray(f(x))  # compile
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        np.asarray(f(x))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))
