"""Tracing / profiling — the TPU build's observability beyond gauges.

The reference's only tracing is klog verbosity levels V(2)-V(5) plus a
dynamic log-level endpoint (SURVEY §5: plugin.go:157,
reserved_resource_amounts.go:197, Makefile:94-95). The TPU-native
equivalent here is richer, per the survey's prescription:

- :class:`PhaseTracer` — per-phase wall-clock histograms
  (``kube_throttler_phase_duration_seconds{phase=...}``) exported through
  the same registry that serves ``/metrics``; phases cover the scheduling
  hot path (prefilter/reserve/unreserve), the async state engine
  (reconcile), and host↔device sync.
  While a ``jax.profiler`` session runs, every span is also written into
  the profiler's trace as a ``kt.<phase>`` host event (nested per thread,
  optional ``#key=value#`` stats), on the same clock as the device's
  operations — so a device idle gap can be named by the host work under
  it. Outside a session a span costs one ``is_enabled`` check more.
- klog-style verbosity: :func:`set_verbosity` / :func:`v_enabled` /
  :func:`vlog` map V-levels onto the stdlib logger the way klog maps them
  onto --v (V(2)≈INFO detail … V(5)≈trace). The daemon's
  ``PUT /debug/flags/v`` analog calls ``set_verbosity`` at runtime.
"""

from __future__ import annotations

import logging
import time
from typing import Dict, Optional

from .lockorder import make_lock

try:
    from jax.profiler import TraceAnnotation

    _profiling = TraceAnnotation.is_enabled
except ImportError:  # a jaxlib without the profiler: spans reach the histogram only
    TraceAnnotation = None

    def _profiling() -> bool:
        return False


logger = logging.getLogger("kube_throttler_tpu")

_verbosity_lock = make_lock("tracing.verbosity")
_verbosity = 0


def set_verbosity(level: int) -> int:
    """Set the global V-level (klog --v / PUT /debug/flags/v analog).
    Returns the previous level."""
    global _verbosity
    with _verbosity_lock:
        prev, _verbosity = _verbosity, int(level)
    return prev


def get_verbosity() -> int:
    return _verbosity


def v_enabled(level: int) -> bool:
    """klog ``klog.V(level).Enabled()``."""
    return _verbosity >= level


def vlog(level: int, msg: str, *args) -> None:
    """klog ``klog.V(level).Infof`` — emits at INFO when the global
    verbosity admits the level, else drops (lazily formatted)."""
    if _verbosity >= level:
        logger.info(msg, *args)


class PhaseTracer:
    """Per-phase wall-clock histograms over a metrics Registry.

    One family, labeled by phase, so dashboards slice p50/p99 per phase:
    ``kube_throttler_phase_duration_seconds_bucket{phase="prefilter",...}``.
    """

    FAMILY = "kube_throttler_phase_duration_seconds"

    def __init__(self, registry) -> None:
        self._hist = registry.histogram_vec(
            self.FAMILY,
            "Wall-clock duration of kube-throttler phases (scheduling hot "
            "path, reconcile, device sync)",
            ["phase"],
        )

    def trace(self, phase: str, **stats) -> "_Trace":
        """Time the enclosed block as ``phase``. ``stats`` (e.g. a request
        id) ride on the profiler event only; the histogram ignores them."""
        # a slotted context object, not @contextmanager: the generator
        # protocol costs ~3µs per enter/exit and the serving hot path
        # crosses 6+ trace scopes per decision
        return _Trace(self._hist, phase, stats)

    def observe(self, phase: str, seconds: float) -> None:
        self._hist.observe({"phase": phase}, seconds)

    def snapshot(self, phase: str) -> Optional[Dict[str, float]]:
        """{"sum": s, "count": n, "mean": s/n} or None if never observed."""
        snap = self._hist.snapshot({"phase": phase})
        if snap is None:
            return None
        total, count = snap
        return {"sum": total, "count": count, "mean": total / count if count else 0.0}


class _Trace:
    """Slotted timing scope: observes phase duration into the histogram
    family on exit and, while a profiler session runs, spans the block
    with a ``kt.<phase>`` trace event."""

    __slots__ = ("_hist", "_phase", "_stats", "_event", "_start")

    def __init__(self, hist, phase: str, stats: dict) -> None:
        self._hist = hist
        self._phase = phase
        self._stats = stats

    def __enter__(self) -> None:
        self._event = None
        if _profiling():
            self._event = TraceAnnotation("kt." + self._phase, **self._stats)
            self._event.__enter__()
        self._start = time.perf_counter()

    def __exit__(self, exc_type, exc, tb) -> None:
        self._hist.observe_key((self._phase,), time.perf_counter() - self._start)
        if self._event is not None:
            self._event.__exit__(exc_type, exc, tb)


class _NoopHist:
    def observe(self, labels, value) -> None:
        pass

    def observe_key(self, key, value) -> None:
        pass

    def snapshot(self, labels):
        return None


class NoopTracer(PhaseTracer):
    """Tracer that records nothing (for callers constructed without a
    registry)."""

    def __init__(self) -> None:  # deliberately no super().__init__
        self._hist = _NoopHist()

