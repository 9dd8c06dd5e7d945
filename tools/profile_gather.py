"""Time and profile the sparse [P,K] gather kernel (``check_pods_gather``)
or, with ``--tick``, the fused tick program (``full_update_step_gather``:
thresholds, the ``used`` aggregation, the same gather) on one device, at
the shapes the daemon dispatches at 100k pods x 10k throttles. Run from
the root of a checkout (on a TPU, or on the CPU at a tiny size):

    python tools/profile_gather.py [--tick] [--pcap 131072] [--tcap 16384]
        [--R 8] [--k 32 --k 64] [--peak-k 2048] [--out <trace dir>]

The state is synthetic, drawn from --seed in the shape of the
k8s-large-100k configuration: throttles in groups of 20 with a pod-count
and a cpu threshold, used and reserved near the thresholds, a few
status flags; each pod matches its group's 20 throttles and one open
one. The tick takes the same thresholds as a schedule without overrides
and counts every valid pod into ``used``. Per K it prints one JSON line:
the mean device time of a call (host clock around
``block_until_ready``), the time per [P,K] slot, and from a
``jax.profiler`` trace of a few calls the device time of each HLO op of
the program, largest first, with the kinds of op it holds (gather,
scatter, sort, reduce-window), the share of all op time spent in ops
of each kind, and a digest of the outputs, which two checkouts' runs
must share. ``--peak-k`` runs that rung last and reports the device's
peak bytes in use, where the backend reports it. Traces go under
``--out`` (a new temporary directory by default).
"""

import argparse
import hashlib
import json
import os
import re
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())

import numpy as np  # noqa: E402


def synthetic(seed: int, pcap: int, tcap: int, R: int, pods: int, throttles: int):
    """(ThrottleState, PodBatch, matches int32[pods, 21]) as numpy arrays."""
    from kube_throttler_tpu.ops.schema import PodBatch, ThrottleState

    rng = np.random.default_rng(seed)
    group_size = 20
    groups = throttles // group_size
    live = np.zeros(tcap, dtype=bool)
    live[:throttles] = True
    z = lambda *s: np.zeros(s, dtype=np.int64)  # noqa: E731
    f = lambda *s: np.zeros(s, dtype=bool)  # noqa: E731
    req = rng.choice(np.arange(100, 800, 100), size=pods).astype(np.int64)
    grp = np.arange(pods) % groups
    group_cpu = np.bincount(grp, weights=req, minlength=groups).astype(np.int64)
    group_cnt = np.bincount(grp, minlength=groups).astype(np.int64)
    tg = np.arange(throttles) // group_size
    thr_cnt, thr_req, used_cnt, used_req = z(tcap), z(tcap, R), z(tcap), z(tcap, R)
    thr_cnt[:throttles] = group_cnt[tg] + rng.choice([1, 3, 10, 30, 100], size=throttles)
    thr_req[:throttles, 0] = (group_cpu[tg] * rng.choice(
        [0.97, 1.0, 1.02, 1.05, 1.1, 1.5], size=throttles)).astype(np.int64)
    used_cnt[:throttles] = group_cnt[tg]
    used_req[:throttles, 0] = group_cpu[tg]
    res_cnt, res_req = z(tcap), z(tcap, R)
    res_req[:throttles, 0] = rng.integers(0, 2, size=throttles) * 100
    present = live.copy()
    req_present = f(tcap, R)
    req_present[:throttles, 0] = True
    st_req = f(tcap, R)
    st_req[:throttles, 0] = used_req[:throttles, 0] >= thr_req[:throttles, 0]
    state = ThrottleState(
        valid=live, thr_cnt=thr_cnt, thr_cnt_present=present, thr_req=thr_req,
        thr_req_present=req_present, used_cnt=used_cnt, used_cnt_present=present.copy(),
        used_req=used_req, used_req_present=req_present.copy(), res_cnt=res_cnt,
        res_cnt_present=f(tcap), res_req=res_req, res_req_present=req_present.copy(),
        st_cnt_throttled=used_cnt >= thr_cnt, st_req_throttled=st_req,
        st_req_flag_present=req_present.copy(),
    )
    pvalid = np.zeros(pcap, dtype=bool)
    pvalid[:pods] = True
    preq, ppres = z(pcap, R), f(pcap, R)
    preq[:pods, 0] = req
    ppres[:pods, 0] = True
    batch = PodBatch(valid=pvalid, req=preq, req_present=ppres)
    matches = (grp[:, None] * group_size + np.arange(group_size)[None, :]).astype(np.int32)
    open_col = np.full((pods, 1), throttles - 1, dtype=np.int32)
    return state, batch, np.concatenate([matches, open_col], axis=1)


def tick_inputs(state, batch):
    """(the schedule, the arguments after cols) of the fused tick, from
    the synthetic state: its thresholds as a schedule without overrides,
    every valid pod counted, its reservations and validity, the epoch as
    now."""
    from kube_throttler_tpu.ops.overrides import OverrideSchedule

    T, R = state.thr_req.shape
    sched = OverrideSchedule(
        ov_valid=np.zeros((T, 1), dtype=bool), ov_begin=np.zeros((T, 1), dtype=np.int64),
        ov_end=np.zeros((T, 1), dtype=np.int64), ov_cnt=np.zeros((T, 1), dtype=np.int64),
        ov_cnt_present=np.zeros((T, 1), dtype=bool),
        ov_req=np.zeros((T, 1, R), dtype=np.int64),
        ov_req_present=np.zeros((T, 1, R), dtype=bool), spec_cnt=state.thr_cnt,
        spec_cnt_present=state.thr_cnt_present, spec_req=state.thr_req,
        spec_req_present=state.thr_req_present,
    )
    rest = (batch.valid.copy(), state.res_cnt, state.res_cnt_present, state.res_req,
            state.res_req_present, state.valid, np.int64(0))
    return sched, rest


def cols_for(matches: np.ndarray, pcap: int, k: int) -> np.ndarray:
    cols = np.full((pcap, k), -1, dtype=np.int32)
    w = min(k, matches.shape[1])
    cols[: matches.shape[0], :w] = matches[:, :w]
    return cols


KINDS = ("gather", "scatter", "sort", "reduce-window")


def _kinds_of(text: str) -> set:
    return {k for k in KINDS if f" {k}(" in text}


def _computation_kinds(hlo: str) -> dict:
    """Computation name → the KINDS of op it holds, from an optimized HLO
    module's text."""
    kinds, current = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?(%\S+) ", line)
        if m:
            current = m.group(1)
        elif current:
            kinds.setdefault(current, set()).update(_kinds_of(line))
    return kinds


def op_profile(trace_dir: str, hlo: str, top: int):
    """({kind: share of device op time in ops of that kind}, [[op, shape,
    seconds, share, kinds]]) from the trace. Control-flow ops (a while
    around the blocks) span their body's ops and are left out of the
    total."""
    from benchmark.devtrace import find_xplane
    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    if path is None:
        return None, []
    computations = _computation_kinds(hlo)
    sums, info = {}, {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                m = re.match(r"(%\S+) = (\S+) ", e.name)
                if m is None or re.search(r" (while|conditional|call)\(", e.name):
                    continue
                calls = re.search(r"calls=(%[^,\s]+)", e.name)
                kinds = _kinds_of(e.name) | computations.get(calls.group(1) if calls else "", set())
                sums[m.group(1)] = sums.get(m.group(1), 0) + e.duration_ns
                info[m.group(1)] = (m.group(2).split("{")[0], sorted(kinds))
    if not sums:
        return None, []
    total = sum(sums.values())
    shares = {k: sum(ns for op, ns in sums.items() if k in info[op][1]) / total for k in KINDS}
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:top]
    return shares, [[op, info[op][0], ns / 1e9, ns / total, info[op][1]] for op, ns in ranked]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tick", action="store_true")
    ap.add_argument("--pcap", type=int, default=131072)
    ap.add_argument("--tcap", type=int, default=16384)
    ap.add_argument("--R", type=int, default=8)
    ap.add_argument("--pods", type=int, default=100000)
    ap.add_argument("--throttles", type=int, default=10000)
    ap.add_argument("--k", type=int, action="append")
    ap.add_argument("--peak-k", type=int, default=0)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=2718281829)
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    out_dir = args.out or tempfile.mkdtemp(prefix="gather_profile-")

    import jax

    import kube_throttler_tpu  # noqa: F401  (x64 on before any array)
    from kube_throttler_tpu.ops.check import check_pods_gather
    from kube_throttler_tpu.parallel.sharded import full_update_step_gather

    dev = jax.devices()[0]
    state, batch, matches = synthetic(
        args.seed, args.pcap, args.tcap, args.R, args.pods, args.throttles)
    if args.tick:
        fn = full_update_step_gather
        sched, rest = jax.device_put(tick_inputs(state, batch))
        batch = jax.device_put(batch)
        args_for = lambda cols: (sched, batch, cols) + rest  # noqa: E731
    else:
        fn = check_pods_gather
        state, batch = jax.device_put((state, batch))
        args_for = lambda cols: (state, batch, cols)  # noqa: E731
    ks = args.k or [32]
    for k, peak in [(k, False) for k in ks] + ([(args.peak_k, True)] if args.peak_k else []):
        cols = jax.device_put(cols_for(matches, args.pcap, k))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args_for(cols)))
        compile_s = time.perf_counter() - t0
        calls = 1 if peak else args.calls
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args_for(cols))
        jax.block_until_ready(out)
        call_s = (time.perf_counter() - t0) / calls
        out = [np.asarray(o) for o in out]
        rec = {"program": fn.__name__, "k": k, "pcap": args.pcap, "tcap": args.tcap,
               "R": args.R, "compile_s": compile_s, "call_ms": call_s * 1e3,
               "ns_per_slot": call_s * 1e9 / (args.pcap * k),
               "schedulable": int(out[1].sum()),
               "counts": out[0].sum(axis=0).tolist(),
               "outputs_sha256": hashlib.sha256(b"".join(o.tobytes() for o in out)).hexdigest(),
               "device": {"platform": dev.platform, "kind": dev.device_kind}}
        if peak:
            rec["peak_bytes_in_use"] = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        else:
            trace_dir = os.path.join(out_dir, f"k{k}")
            with jax.profiler.trace(trace_dir):
                for _ in range(3):
                    out = fn(*args_for(cols))
                jax.block_until_ready(out)
            hlo = fn.lower(*args_for(cols)).compile().as_text()
            rec["trace"] = trace_dir
            rec["shares"], rec["ops"] = op_profile(trace_dir, hlo, args.top)
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
