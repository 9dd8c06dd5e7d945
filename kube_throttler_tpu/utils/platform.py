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

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# JAX monitoring events this process has seen (cache_stats reads them)
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
}
_cache_counts: Dict[str, int] = {v: 0 for v in _CACHE_EVENTS.values()}
_listening: list = []  # non-empty once the listener is registered


def compilation_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)


def _count_cache_event(event: str, **_kw) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        _cache_counts[name] += 1


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
    if not _listening:
        jax.monitoring.register_event_listener(_count_cache_event)
        _listening.append(True)
    return path


def cache_stats() -> Dict[str, int]:
    """{"requests", "hits"}: compiles that consulted the persistent cache
    since enable_compilation_cache, and how many it answered."""
    return dict(_cache_counts)


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
