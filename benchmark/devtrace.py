"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, the
top device operations and the longest idle gaps.

The device's operations are the events of each ``/device:TPU:<n>``
plane's "XLA Ops" line; its "XLA Modules" line names the programs they
belong to. The CPU backend has no device plane; there the XLA client
threads of ``/host:CPU`` stand in (ops only), so the reduction can be
tested on a CPU trace.
Busy time is the union of those intervals, averaged over the devices;
an idle gap is a stretch of the window in which no device runs anything,
named by the harness's call in flight at its middle.

Reading the file imports jax's profiler reader only; it starts no backend.
"""

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CPU_OPS_LINE_PREFIX = "tf_XLAPjRtCpuClient"


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _events(lines, skip=()):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for ln in lines for e in ln.events
            if e.duration_ns > 0 and not e.name.startswith(skip)]


def device_events(path: str) -> Tuple[Dict[str, list], Dict[str, list]]:
    """({device plane: [(op, start ns, end ns)]}, {device plane: [(program,
    start ns, end ns)]}), starts relative to the profiler's start."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules = {}, {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = list(plane.lines)
            ops[plane.name] = _events([ln for ln in lines if ln.name == OPS_LINE])
            modules[plane.name] = _events([ln for ln in lines if ln.name == MODULES_LINE])
    if not ops:
        for plane in pd.planes:
            if plane.name == "/host:CPU":
                ops[plane.name] = _events(
                    [ln for ln in plane.lines if ln.name.startswith(CPU_OPS_LINE_PREFIX)],
                    skip=("ThreadpoolListener",))
    return ops, modules


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _clipped_sums(events, w0: int, w1: int) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for evs in events.values():
        for name, s, e in evs:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                out[name] = out.get(name, 0) + (e - s)
    return out


def reduce(events: Dict[str, List[Tuple[str, int, int]]], window_ns: Tuple[int, int],
           calls: List[Tuple[str, int, int]], top: int = 10, modules=None) -> dict:
    """``window_ns`` and ``calls`` (route, start, end) are on the trace's
    clock (ns since the profiler started). Returns busy_s (mean over
    devices), window_s, device_ops [[name, s]] (by program where
    ``modules`` has any, else by op) and idle_gaps [[label, s]]."""
    w0, w1 = window_ns
    busy = []
    gaps: List[Tuple[int, int]] = []
    for evs in events.values():
        merged = union([(max(s, w0), min(e, w1)) for _, s, e in evs if min(e, w1) > max(s, w0)])
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for span in merged for x in span] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = max(len(events), 1)
    per_op = _clipped_sums(modules, w0, w1) if modules and any(modules.values()) else {}
    per_op = per_op or _clipped_sums(events, w0, w1)

    def label(mid: int) -> str:
        inflight = sorted({route for route, s, e in calls if s <= mid < e})
        return "+".join(inflight) if inflight else "no call in flight"

    by_label: Dict[str, int] = {}
    for s, e in gaps:
        lab = label((s + e) // 2)
        by_label[lab] = by_label.get(lab, 0) + (e - s)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [[k, v / 1e9 / n] for k, v in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label((s + e) // 2), (e - s) / 1e9] for s, e in longest],
        "idle_by_call": {k: v / 1e9 / n for k, v in by_label.items()},
    }
