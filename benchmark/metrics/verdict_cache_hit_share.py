"""Verdict cache hits over lookups in the window, in %."""


def read(run):
    hits = run.counter("kube_throttler_verdict_cache_hits_total")
    misses = run.counter("kube_throttler_verdict_cache_misses_total")
    return 100.0 * hits / (hits + misses) if hits + misses else None
