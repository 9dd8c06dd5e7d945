"""Client-side p95 of every /v1/prefilter sent in the window, in ms.

The p99 read 14-23% apart between runs at 51 s windows (PR 22), over the
12.5% that the largest bound the check allows admits; the p95 holds to
8-15%. Both print on the run's latency line."""
import statistics


def read(run):
    lat = run.latencies("prefilter")
    if len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=100)[94] * 1e3
