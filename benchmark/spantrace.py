"""Read the daemon's own spans out of a JAX profiler trace and put them
beside the device's operations on the trace's clock.

While a profiler session runs, every ``PhaseTracer`` span of the daemon is
also a ``kt.<phase>`` event in the ``/host:CPU`` plane, on the line of the
thread that ran it, nested inside the spans around it; ``http_<route>``
spans carry ``rid`` and ``wait_us`` stats. A daemon without those spans
leaves the plane without ``kt.*`` events, and every result here is empty.

:func:`reduce` names each idle gap of the device by the host work under
it and finds how much device time falls inside each phase's spans. Like
``devtrace``, reading the file imports jax's profiler reader only.
"""

from typing import Dict, List, Tuple

import devtrace

HOST_PLANE = "/host:CPU"
PREFIX = "kt."


def span_events(path: str) -> List[List[Tuple[str, int, int, dict]]]:
    """The ``kt.*`` events of the host plane, one list a thread line:
    [[(phase, start ns, end ns, stats)]], starts relative to the
    profiler's start, each list in order of start."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for ln in plane.lines:
            evs = [(e.name[len(PREFIX):], int(e.start_ns), int(e.start_ns + e.duration_ns),
                    {k: v for k, v in e.stats})
                   for e in ln.events if e.name.startswith(PREFIX)]
            if evs:
                lines.append(sorted(evs, key=lambda ev: ev[1]))
    return lines


def innermost(spans, t: int) -> List[str]:
    """The phase of the innermost span in flight at ``t`` on each thread
    line, sorted, without repeats."""
    names = set()
    for line in spans:
        inner = None
        for phase, s, e, _ in line:
            if s > t:
                break
            if t < e:
                inner = phase  # later starts lie inside earlier ones
        if inner is not None:
            names.add(inner)
    return sorted(names)


def _overlap(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(events: Dict[str, List[Tuple[str, int, int]]], window_ns: Tuple[int, int],
           calls: List[Tuple[str, int, int]], spans, top: int = 10) -> dict:
    """``devtrace.reduce`` with the daemon's spans: ``events``,
    ``window_ns`` and ``calls`` as there, ``spans`` from
    :func:`span_events`. Returns:

    - ``idle_gaps`` [[label, s]], the longest gaps, each labelled
      ``<route>/<innermost spans in flight at its middle>`` (threads
      joined by ``+``), or ``<route>`` where no span is in flight;
    - ``idle_by_span`` {label: s} over every gap;
    - ``span_durations`` {phase: [s]} of the spans that start in the window;
    - ``device_in_span`` {phase: [device busy s inside the union of the
      phase's spans, spans]}, busy time averaged over the devices."""
    w0, w1 = window_ns
    busy = [devtrace.union([(max(s, w0), min(e, w1)) for _, s, e in evs
                            if min(e, w1) > max(s, w0)]) for evs in events.values()]
    n = max(len(busy), 1)

    def route(mid: int) -> str:
        inflight = sorted({r for r, s, e in calls if s <= mid < e})
        return "+".join(inflight) if inflight else "no call in flight"

    gaps = []
    for evs in busy:
        edges = [w0] + [x for iv in evs for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]

    def label(s: int, e: int) -> str:
        mid = (s + e) // 2
        inner = innermost(spans, mid)
        return route(mid) + ("/" + "+".join(inner) if inner else "")

    by_label: Dict[str, int] = {}
    for s, e in gaps:
        lab = label(s, e)
        by_label[lab] = by_label.get(lab, 0) + (e - s)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]

    durations: Dict[str, List[float]] = {}
    intervals: Dict[str, List[Tuple[int, int]]] = {}
    for line in spans:
        for phase, s, e, _ in line:
            if w0 <= s < w1:
                durations.setdefault(phase, []).append((e - s) / 1e9)
                intervals.setdefault(phase, []).append((s, min(e, w1)))
    device_in = {phase: [sum(_overlap(devtrace.union(ivs), b) for b in busy) / n / 1e9,
                         len(ivs)]
                 for phase, ivs in intervals.items()}
    return {
        "idle_gaps": [[label(s, e), (e - s) / 1e9] for s, e in longest],
        "idle_by_span": {k: v / 1e9 / n for k, v in by_label.items()},
        "span_durations": durations,
        "device_in_span": device_in,
    }
