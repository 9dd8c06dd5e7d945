"""Launcher for the served daemon: ``kube_throttler_tpu.cli serve`` in this
process, so the chip's owner also runs the benchmark's own instruments.

    python benchmark/daemon.py <control dir> <cli serve arguments...>

- Counts XLA work through ``jax.monitoring``: jaxpr traces (a new program
  or shape), backend compiles and persistent-cache loads. The counts are
  rewritten to ``<control dir>/compiles.json`` after each event, so the
  harness can warm up until nothing new appears.
- SIGUSR1 opens the measured window: it marks the counts and, when
  ``<control dir>/trace`` exists, starts ``jax.profiler`` into it.
  SIGUSR2 closes it: it stops the profiler and writes
  ``<control dir>/window.json`` with the counts inside the window and the
  profiler's start on ``time.time_ns``. ``cli.main`` claims only SIGINT and
  SIGTERM.
- ``KTB_FAULT`` names a fault from ``benchmark/tests/faults.py`` to plant
  under the timed path; only the tests set it.
"""

import json
import os
import signal
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/backend_compile_duration": "compiles",
}
CACHE_HIT = "/jax/compilation_cache/cache_hits"


_write_lock = threading.Lock()


def _write(path: str, obj) -> None:
    with _write_lock:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(obj, f)
        os.replace(tmp, path)


def main(argv) -> int:
    ctl, serve_args = argv[0], argv[1:]
    sys.path.insert(0, ROOT)
    import kube_throttler_tpu  # noqa: F401 — flips x64 before any other jax use
    import jax
    from jax import monitoring

    counts = {"traces": 0, "compiles": 0, "cache_loads": 0}
    mark = {}
    compiles_path = os.path.join(ctl, "compiles.json")

    def on_duration(event, _secs, **_):
        name = EVENTS.get(event)
        if name:
            counts[name] += 1
            _write(compiles_path, counts)

    def on_event(event, **_):
        if event == CACHE_HIT:
            counts["cache_loads"] += 1
            _write(compiles_path, counts)

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    _write(compiles_path, counts)
    trace_dir = os.path.join(ctl, "trace")

    def open_window(*_):
        mark.update(counts)
        mark["trace_start_ns"] = None
        if os.path.isdir(trace_dir):
            mark["trace_start_ns"] = time.time_ns()
            # no Python tracer: it would time every call of the daemon
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)

    def close_window(*_):
        if mark.get("trace_start_ns") is not None:
            jax.profiler.stop_trace()
        _write(os.path.join(ctl, "window.json"), {
            **{k: counts[k] - mark.get(k, 0) for k in counts},
            "trace_start_ns": mark.get("trace_start_ns"),
        })

    signal.signal(signal.SIGUSR1, open_window)
    signal.signal(signal.SIGUSR2, close_window)
    fault = os.environ.get("KTB_FAULT")
    if fault:
        sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
        import faults

        faults.install(fault)
    from kube_throttler_tpu.cli import main as serve

    return serve(["serve", *serve_args])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
