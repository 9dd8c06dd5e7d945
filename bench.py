"""Benchmark harness — the five BASELINE.json configs.

Prints ONE JSON line to stdout (the headline: single-pod PreFilter decision
latency against 100k-pod × 10k-throttle state on one chip); per-config
detail goes to stderr.

Timing methodology: true device time is measured by slope — run N
data-dependent chained iterations inside ONE dispatch (lax.fori_loop),
materialize to host, and take (t(N2)-t(N1))/(N2-N1) — so per-dispatch
overhead cancels. The dispatch round trip of one small jitted op is
reported separately.

The bench runs on whatever backend JAX finds (or ``JAX_PLATFORMS``
names) and never switches platform itself; the JSON names the platform.
Every config is individually fenced so a single failure cannot cost the
run its output, and a global watchdog thread (KT_BENCH_DEADLINE_S,
default 1800s) emits the best-so-far JSON line at the deadline and exits
— rc=0 if a usable measurement (value>0) made it out, rc=1 otherwise.
The final JSON line is ALWAYS printed.

Run: python bench.py            (full shapes)
     python bench.py --quick    (1/10-scale shapes for smoke runs)
"""

import json
import os
import sys
import threading
import time
import traceback
from datetime import datetime, timezone

sys.path.insert(0, ".")

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from kube_throttler_tpu.ops.check import check_step
from kube_throttler_tpu.ops.aggregate import aggregate_used, apply_pod_delta
from kube_throttler_tpu.ops.overrides import NS_MAX, NS_MIN, OverrideSchedule, calculate_thresholds
from kube_throttler_tpu.ops.schema import PodBatch, ThrottleState
from kube_throttler_tpu.utils.platform import dispatch_round_trip, enable_compilation_cache

NOW = datetime(2024, 1, 15, tzinfo=timezone.utc)
NOW_NS = np.int64(int(NOW.timestamp()) * 10**9)

GiB_m = 1024**3 * 1000  # 1Gi in milli-units


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------- global watchdog
#
# A blocking device call that never returns is a hang the per-config
# fences cannot catch (the exception never raises). The contract is ONE
# JSON line no matter what, so a deadline thread snapshots whatever has
# been measured so far and emits it. State the emitter needs is
# progressively published into RESULT_STATE by main().

RESULT_STATE: dict = {"detail": {}, "errors": {}}
_EMITTED = threading.Event()
_EMIT_LOCK = threading.Lock()
_DEADLINE: list = [None]  # [monotonic deadline] once main() sets it


def time_left() -> float:
    return float("inf") if _DEADLINE[0] is None else _DEADLINE[0] - time.monotonic()


def emit(out: dict) -> bool:
    """Print the one JSON line exactly once, whoever gets there first.

    Atomic test-and-set: the watchdog and the main thread can race here at
    the deadline boundary, and two JSON lines would break the driver's
    single-line contract.
    """
    with _EMIT_LOCK:
        if _EMITTED.is_set():
            return False
        _EMITTED.set()
    print(json.dumps(out), flush=True)
    return True


def _watchdog_main(margin: float = 30.0) -> None:
    while not _EMITTED.is_set():
        left = time_left() - margin
        if left <= 0:
            break
        time.sleep(min(left, 5.0))
    if _EMITTED.is_set():
        return
    log("WATCHDOG: deadline reached; emitting best-so-far result and exiting")
    RESULT_STATE["errors"]["watchdog"] = "global deadline hit; remaining configs skipped"
    try:
        out = build_result()
    except BaseException as e:  # noqa: BLE001 — last resort, never die silently
        out = {
            "metric": "bench deadline hit before any measurement",
            "value": -1.0,
            "unit": "ms",
            "vs_baseline": 0.0,
            "error": f"{e.__class__.__name__}: {str(e)[:200]}",
        }
    emit(out)
    # A wedged device call cannot be unwound; exit hard. rc=0 only if a
    # usable partial measurement made it out (same contract as __main__).
    os._exit(0 if out.get("value", -1.0) > 0 else 1)


def start_watchdog() -> None:
    try:
        budget = float(os.environ.get("KT_BENCH_DEADLINE_S", "1800"))
    except ValueError:
        budget = 1800.0  # malformed override must not kill the bench
    _DEADLINE[0] = time.monotonic() + budget
    t = threading.Thread(target=_watchdog_main, name="bench-watchdog", daemon=True)
    t.start()


# --------------------------------------------------------------- synthesis


def synth_state(rng, T, R, sat_frac=0.3):
    """Synthetic throttle state: thresholds over cpu/mem/gpu + pod counts;
    ``sat_frac`` of throttles already saturated."""
    thr_cnt = rng.integers(1, 50, T).astype(np.int64)
    thr_cnt_present = rng.random(T) < 0.8
    thr_req = np.zeros((T, R), dtype=np.int64)
    thr_req_present = np.zeros((T, R), dtype=bool)
    thr_req[:, 0] = rng.integers(1, 64, T) * 1000  # cpu cores (milli)
    thr_req[:, 1] = rng.integers(1, 256, T) * GiB_m  # memory
    thr_req[:, 2] = rng.integers(0, 8, T) * 1000  # gpu
    thr_req_present[:, :3] = rng.random((T, 3)) < 0.9

    saturated = rng.random(T) < sat_frac
    used_cnt = np.where(saturated, thr_cnt, (thr_cnt * rng.random(T) * 0.8)).astype(np.int64)
    frac = np.where(saturated[:, None], 1.0, rng.random((T, 1)) * 0.8)
    used_req = (thr_req * frac).astype(np.int64)
    used_cnt_present = used_cnt > 0
    used_req_present = thr_req_present & (rng.random((T, R)) < 0.95)

    st_req = used_req_present & (used_req >= thr_req) & thr_req_present
    return ThrottleState(
        valid=np.ones(T, dtype=bool),
        thr_cnt=thr_cnt,
        thr_cnt_present=thr_cnt_present,
        thr_req=thr_req,
        thr_req_present=thr_req_present,
        used_cnt=used_cnt,
        used_cnt_present=used_cnt_present,
        used_req=used_req,
        used_req_present=used_req_present,
        res_cnt=np.zeros(T, dtype=np.int64),
        res_cnt_present=np.zeros(T, dtype=bool),
        res_req=np.zeros((T, R), dtype=np.int64),
        res_req_present=np.zeros((T, R), dtype=bool),
        st_cnt_throttled=used_cnt_present & thr_cnt_present & (used_cnt >= thr_cnt),
        st_req_throttled=st_req,
        st_req_flag_present=thr_req_present,
    )


def synth_pods(rng, P, T, R, matches_per_pod=2):
    req = np.zeros((P, R), dtype=np.int64)
    present = np.zeros((P, R), dtype=bool)
    req[:, 0] = rng.integers(1, 8, P) * 100  # 100m..700m cpu
    req[:, 1] = rng.integers(1, 32, P) * (GiB_m // 4)
    present[:, :2] = True
    batch = PodBatch(valid=np.ones(P, dtype=bool), req=req, req_present=present)

    mask = np.zeros((P, T), dtype=bool)
    rows = np.repeat(np.arange(P), matches_per_pod)
    cols = rng.integers(0, T, P * matches_per_pod)
    mask[rows, cols] = True
    return batch, mask


# ------------------------------------------------------------------ timing


def _host_time(fn, repeats=3):
    """Wall time to a full host materialization (device work included)."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        np.asarray(jax.tree_util.tree_leaves(out)[0])
        best = min(best, time.perf_counter() - t0)
    return best


def device_time_per_iter(make_chained, n1=2, n2=12):
    """Slope timing: chained(n) runs n data-dependent iterations in one
    dispatch; per-iteration device time = (t(n2)-t(n1))/(n2-n1). The
    single-number (median) view of device_time_stats."""
    return device_time_stats(make_chained, n1=n1, n2=n2, samples=3)["p50"]


def device_time_stats(make_chained, n1=2, n2=12, samples=8):
    """Repeated paired-slope estimates → distribution of per-iteration device
    time. Each sample is an independent (t(n1), t(n2)) pair, so dispatch
    round-trip jitter common to both dispatches cancels in the difference.

    NOTE on what the percentiles mean: each slope sample averages (n2-n1)
    chained device iterations, so this is the distribution of the slope
    ESTIMATOR, not of individual decision latencies — a per-decision
    device tail hides under the dispatch round trip. True per-call tail
    latency is measured separately on the host paths (host_percentiles).

    Returns {mean, p50, p99, cv, samples}; cv = std/mean of the slope
    samples — a noisy measurement (cv>0.5) is retried once with double the
    samples and the top/bottom outliers dropped, and cv is recomputed."""
    f1, f2 = make_chained(n1), make_chained(n2)
    _host_time(f1, repeats=1)  # compile
    _host_time(f2, repeats=1)

    def collect(k):
        est = []
        for _ in range(k):
            # min-of-3 per endpoint: a single ms-scale round-trip spike on
            # one dispatch would otherwise swing (or negate) a µs-scale slope
            t1 = _host_time(f1, repeats=3)
            t2 = _host_time(f2, repeats=3)
            est.append(max((t2 - t1) / (n2 - n1), 1e-9))
        return np.array(est)

    est = collect(samples)
    cv = float(est.std() / est.mean()) if est.mean() > 0 else 0.0
    if cv > 0.5:  # noisy measurement: double the sample count, trim outliers
        est = np.sort(np.concatenate([est, collect(samples)]))[1:-1]
        cv = float(est.std() / est.mean()) if est.mean() > 0 else 0.0
    return {
        "mean": float(est.mean()),
        "p50": float(np.percentile(est, 50)),
        "p99": float(np.percentile(est, 99)),
        "cv": cv,
        "samples": int(len(est)),
    }


def host_percentiles(fn, n, warmup=50, max_seconds=None):
    """True per-call latency distribution of a host-side function. With
    ``max_seconds`` the sample count adapts to the call cost (a call that
    costs two dispatch round trips makes 2000 sequential samples slow) —
    sampling stops at the time budget, never below 200 samples, so
    percentiles stay meaningful."""
    for _ in range(min(warmup, n)):
        fn()
    times = []
    deadline = time.perf_counter() + max_seconds if max_seconds else None
    for i in range(n):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
        if deadline is not None and len(times) >= 200 and time.perf_counter() > deadline:
            break
    arr = np.asarray(times)
    return {
        "mean": float(arr.mean()),
        "p50": float(np.percentile(arr, 50)),
        "p99": float(np.percentile(arr, 99)),
        "samples": len(times),
    }


# ------------------------------------------------------------------ benches


def chained_check(state, batch, mask, n):
    """n data-dependent full check sweeps in one dispatch."""

    @jax.jit
    def run(state, batch, mask):
        def body(i, acc):
            b = PodBatch(
                valid=batch.valid,
                req=batch.req + acc % 2 + i,  # data-dependence blocks reordering
                req_present=batch.req_present,
            )
            counts, _ = check_step(state, b, mask)
            return acc + jnp.sum(counts, dtype=jnp.int64)

        return lax.fori_loop(0, n, body, jnp.int64(0))

    return lambda: run(state, batch, mask)


def bench_batched(rng, P, T, R, label):
    state = synth_state(rng, T, R)
    batch, mask = synth_pods(rng, P, T, R)
    device = jax.devices()[0]
    state = jax.device_put(state, device)
    batch = jax.device_put(batch, device)
    mask = jax.device_put(mask, device)

    per_iter = device_time_per_iter(lambda n: chained_check(state, batch, mask, n))
    dps = P / per_iter
    log(
        f"[{label}] batched check {P}x{T}x{R}: {per_iter*1e3:.2f}ms/sweep device time "
        f"-> {dps:,.0f} pod-decisions/sec ({P*T/per_iter:,.0f} pair-cells/sec)"
    )
    return state, batch, mask, dps, per_iter


def bench_single_pod(rng, state, T, R, label):
    """Single-pod PreFilter decision ([1,T] check) device latency."""
    pod_req = np.zeros((1, R), dtype=np.int64)
    pod_present = np.zeros((1, R), dtype=bool)
    pod_req[0, 0] = 300
    pod_present[0, 0] = True
    batch = PodBatch(valid=np.ones(1, dtype=bool), req=pod_req, req_present=pod_present)
    mask_row = np.zeros((1, T), dtype=bool)
    mask_row[0, rng.integers(0, T, 3)] = True
    device = jax.devices()[0]
    batch = jax.device_put(batch, device)
    mask_row = jax.device_put(mask_row, device)

    per_check = device_time_per_iter(
        lambda n: chained_check(state, batch, mask_row, n), n1=10, n2=200
    )
    log(f"[{label}] single-pod check vs T={T}: {per_check*1e3:.4f}ms device time per decision")
    return per_check * 1e3


def bench_pallas_sweep(rng, P, T, R, label):
    """Dense sweep via the tiled Pallas kernel (ops/pallas_check.py):
    int32-limb compares + VMEM tiling vs the XLA broadcast fusion."""
    from kube_throttler_tpu.ops.fastcheck import precompute_check_state
    from kube_throttler_tpu.ops.pallas_check import BP, BT, pallas_check_pods

    P = P + (-P) % BP
    T = T + (-T) % BT
    state = synth_state(rng, T, R)
    batch, mask = synth_pods(rng, P, T, R)
    device = jax.devices()[0]
    state = jax.device_put(state, device)
    batch = jax.device_put(batch, device)
    mask = jax.device_put(mask, device)
    pre = precompute_check_state(state)
    jax.block_until_ready(pre.resid)

    def make(n):
        @jax.jit
        def run(pre, batch, mask):
            def body(i, acc):
                b = PodBatch(
                    valid=batch.valid,
                    req=batch.req + acc % 2 + i,
                    req_present=batch.req_present,
                )
                st = pallas_check_pods.__wrapped__(pre, b, mask, False, True, False)
                return acc + jnp.sum(st == 1, dtype=jnp.int64)

            return lax.fori_loop(0, n, body, jnp.int64(0))

        return lambda: run(pre, batch, mask)

    per_iter = device_time_per_iter(make, n1=2, n2=8)
    log(
        f"[{label}] pallas sweep {P}x{T}x{R}: {per_iter*1e3:.2f}ms/sweep "
        f"-> {P/per_iter:,.0f} pod-decisions/sec ({P*T/per_iter/1e9:.1f}G pair-cells/sec)"
    )
    return per_iter


def bench_donation(rng, P, T, label):
    """Buffer-donation on/off delta for the incremental device-cache refresh
    (VERDICT r3 weak #5 / r4 task 1). Measures a [P,T] bool cache updated by
    ``.at[rows].set()`` — the same pattern devicestate uses to refresh its
    device mask/pods/cols caches (devicestate.py ``_device_mask.at[rows]``).
    With ``donate_argnums`` XLA scatters into the input buffer in place;
    without it every refresh allocates a fresh P×T array and copies the
    unchanged rows (HBM-bandwidth-bound: ~P*T bytes per refresh).

    The production caches deliberately do NOT donate: versioned serving
    snapshots hold references to the pre-update buffer (devicestate
    ``device_state()``/``device_pods()``), and donating a still-referenced
    buffer deletes it under those readers. This entry quantifies what that
    safety costs per refresh, and what a single-writer path (no concurrent
    snapshot readers — e.g. the sharded tick's private columns) saves by
    donating. The aggregate/rebase path originally named by r3 weak #5 is
    host-resident since b8b02f4, so the cache refresh is the remaining
    device-side in-place candidate.

    Timing: donation only takes effect across dispatch boundaries (an
    in-jit fori_loop chain reuses buffers regardless), so this streams n
    sequential dependent dispatches and slope-times the stream; the final
    1-element slice materialization waits for the whole chain without
    downloading the P×T result."""
    rows_n = min(256, P)
    device = jax.devices()[0]

    def scatter(arr, rows, vals):
        return arr.at[rows].set(vals)

    variants = {
        "nodonate": jax.jit(scatter),
        "donate": jax.jit(scatter, donate_argnums=(0,)),
    }
    rows = jax.device_put(
        rng.integers(0, P, rows_n).astype(np.int32), device
    )
    vals = jax.device_put(np.ones((rows_n, T), dtype=bool), device)
    alloc = jax.jit(lambda: jnp.zeros((P, T), dtype=bool))  # on-device, no upload

    out = {}
    for name, fn in variants.items():

        def stream(n, fn=fn):
            def run():
                arr = alloc()
                for _ in range(n):
                    arr = fn(arr, rows, vals)
                return arr[0:1, 0]  # tiny materialization, waits on the chain

            return run

        stream(1)()  # compile both the alloc and the scatter
        t1 = _host_time(stream(4), repeats=3)
        t2 = _host_time(stream(24), repeats=3)
        # the donated scatter (256 rows in place) can slope-time below host
        # timer resolution; floor at 1µs so the ratio stays meaningful
        # ("≥Nx") instead of exploding on a sub-noise denominator
        out[name] = max((t2 - t1) / 20, 1e-6)
    speedup = out["nodonate"] / out["donate"]
    log(
        f"[{label}] donation delta on [{P}x{T}] row-refresh: "
        f"nodonate {out['nodonate']*1e3:.3f}ms/update, "
        f"donate {out['donate']*1e3:.3f}ms/update -> {speedup:.1f}x"
    )
    return {
        "donation_nodonate_ms": round(out["nodonate"] * 1e3, 4),
        "donation_donate_ms": round(out["donate"] * 1e3, 4),
        "donation_speedup": round(speedup, 2),
    }


def bench_single_pod_indexed(rng, state, T, R, label, K=64):
    """The real PreFilter hot path: gather the pod's K affected-throttle rows
    (host index supplies them) and classify O(K*R) — T-independent."""
    from kube_throttler_tpu.ops.fastcheck import (
        fast_check_pod_packed,
        pack_check_state,
        precompute_check_state,
    )

    pre = pack_check_state(precompute_check_state(state))
    jax.block_until_ready(pre.vals)

    pod_req = np.zeros(R, dtype=np.int64)
    pod_present = np.zeros(R, dtype=bool)
    pod_req[0] = 300
    pod_present[0] = True
    idx = np.zeros(K, dtype=np.int32)
    valid = np.zeros(K, dtype=bool)
    idx[:3] = rng.integers(0, T, 3)
    valid[:3] = True
    device = jax.devices()[0]
    pod_req, pod_present, idx, valid = (
        jax.device_put(a, device) for a in (pod_req, pod_present, idx, valid)
    )

    def make(n):
        @jax.jit
        def run(pre, pod_req, pod_present, idx, valid):
            def body(i, acc):
                st = fast_check_pod_packed.__wrapped__(
                    pre, pod_req + acc % 2 + i, pod_present, idx, valid, False, True
                )
                return acc + jnp.sum(st == 1, dtype=jnp.int64)

            return lax.fori_loop(0, n, body, jnp.int64(0))

        return lambda: run(pre, pod_req, pod_present, idx, valid)

    stats = device_time_stats(make, n1=10, n2=500, samples=12)
    log(
        f"[{label}] indexed single-pod check (K={K} gathered of T={T}): "
        f"{stats['mean']*1e6:.2f}us mean / {stats['p99']*1e6:.2f}us p99-of-slope device time "
        f"per decision (cv={stats['cv']:.3f}, {stats['samples']} slope samples)"
    )
    return stats


def bench_streaming_batched(rng, T, R, label, n_events=1000):
    """Event-burst ingest: all n_events in ONE scatter dispatch."""
    from kube_throttler_tpu.ops.aggregate import apply_pod_deltas_batched

    used_cnt = np.asarray(rng.integers(0, 50, T), dtype=np.int64)
    used_req = np.asarray(rng.integers(0, 64, (T, R)), dtype=np.int64) * 1000
    contrib = np.asarray(rng.integers(0, 10, (T, R)), dtype=np.int32)
    K = 4
    ids = rng.integers(0, T, (n_events, K)).astype(np.int32)
    signs = rng.choice(np.array([-1, 1], dtype=np.int64), (n_events, K))
    pod_req = np.zeros((n_events, R), dtype=np.int64)
    pod_req[:, 0] = 100
    pod_present = np.zeros((n_events, R), dtype=bool)
    pod_present[:, 0] = True
    device = jax.devices()[0]
    args = [
        jax.device_put(a, device)
        for a in (used_cnt, used_req, contrib, ids, signs, pod_req, pod_present)
    ]

    def make(n):
        @jax.jit
        def run(used_cnt, used_req, contrib, ids, signs, pod_req, pod_present):
            def body(i, carry):
                uc, ur, co = carry
                uc, ur, co = apply_pod_deltas_batched.__wrapped__(
                    uc + i % 2, ur, co, ids, signs, pod_req, pod_present
                )
                return (uc, ur, co)

            uc, ur, co = lax.fori_loop(0, n, body, (used_cnt, used_req, contrib))
            return uc[0] + ur[0, 0] + co[0, 0]

        return lambda: run(*args)

    per_batch = device_time_per_iter(make, n1=2, n2=12)
    eps = n_events / per_batch
    log(
        f"[{label}] batched streaming deltas T={T}: {eps:,.0f} events/sec "
        f"device-side ({per_batch*1e3:.3f}ms per {n_events}-event batch)"
    )
    return eps


def bench_overrides(rng, T, O, R, label):
    ov_valid = rng.random((T, O)) < 0.8
    ov_begin = np.full((T, O), NS_MIN, dtype=np.int64)
    ov_end = np.full((T, O), NS_MAX, dtype=np.int64)
    active = rng.random((T, O)) < 0.5
    ov_begin[active] = NOW_NS - 3_600_000_000_000
    ov_end[active] = NOW_NS + 3_600_000_000_000
    ov_begin[~active] = NOW_NS + 3_600_000_000_000
    sched = OverrideSchedule(
        ov_valid=ov_valid,
        ov_begin=ov_begin,
        ov_end=ov_end,
        ov_cnt=rng.integers(1, 50, (T, O)).astype(np.int64),
        ov_cnt_present=rng.random((T, O)) < 0.5,
        ov_req=rng.integers(1, 64, (T, O, R)).astype(np.int64) * 1000,
        ov_req_present=rng.random((T, O, R)) < 0.5,
        spec_cnt=rng.integers(1, 50, T).astype(np.int64),
        spec_cnt_present=np.ones(T, dtype=bool),
        spec_req=rng.integers(1, 64, (T, R)).astype(np.int64) * 1000,
        spec_req_present=np.ones((T, R), dtype=bool),
    )
    sched = jax.device_put(sched, jax.devices()[0])

    def make(n):
        @jax.jit
        def run(sched):
            def body(i, acc):
                cnt, cnt_p, req, req_p = calculate_thresholds(sched, NOW_NS + i + acc % 2)
                return acc + jnp.sum(cnt) + jnp.sum(req[:, 0])

            return lax.fori_loop(0, n, body, jnp.int64(0))

        return lambda: run(sched)

    per_iter = device_time_per_iter(make)
    log(f"[{label}] threshold resolution T={T} O={O}: {per_iter*1e3:.3f}ms device time")
    return per_iter


def bench_streaming(rng, T, R, label, n_events=1000):
    """Streaming reconcile: scatter-add pod-churn deltas into used. All
    n_events applied as one chained scan (the device-side rate)."""
    used_cnt = np.asarray(rng.integers(0, 50, T), dtype=np.int64)
    used_req = np.asarray(rng.integers(0, 64, (T, R)), dtype=np.int64) * 1000
    contrib = np.asarray(rng.integers(0, 10, (T, R)), dtype=np.int32)
    K = 4
    ids = rng.integers(0, T, (n_events, K)).astype(np.int32)
    signs = rng.choice(np.array([-1, 1], dtype=np.int64), (n_events, K))
    pod_req = np.zeros((n_events, R), dtype=np.int64)
    pod_req[:, 0] = 100
    pod_present = np.zeros((n_events, R), dtype=bool)
    pod_present[:, 0] = True

    device = jax.devices()[0]
    args = [jax.device_put(a, device) for a in (used_cnt, used_req, contrib, ids, signs, pod_req, pod_present)]

    @jax.jit
    def run_all(used_cnt, used_req, contrib, ids, signs, pod_req, pod_present):
        def body(carry, ev):
            uc, ur, co = carry
            i, s, pr, pp = ev
            uc, ur, co = apply_pod_delta(uc, ur, co, i, s, pr, pp)
            return (uc, ur, co), None

        (uc, ur, co), _ = lax.scan(body, (used_cnt, used_req, contrib), (ids, signs, pod_req, pod_present))
        return uc, ur, co

    t = _host_time(lambda: run_all(*args), repeats=1)  # compile
    t = _host_time(lambda: run_all(*args))
    eps = n_events / t
    log(f"[{label}] streaming deltas T={T}: {eps:,.0f} events/sec device-side (target 1k/s)")
    return eps


# the serving-rung measurement anchors moved to the package so the
# scenario engine's SLO gates and the bench ladder measure with ONE
# implementation (kube_throttler_tpu/scenarios/measure.py); the historical
# underscore names stay bound here for every rung below
from kube_throttler_tpu.scenarios.measure import (  # noqa: E402
    flip_band_mc as _flip_band_mc,
    flip_watch_of as _flip_watch_of,
    group_keys_of as _group_keys_of,
    lag_tracker as _lag_tracker,
    served_state as _served_state,
    served_throttle as _served_throttle,
)


def build_served_stack(P, T, groups=500, label="served"):
    """The REAL daemon stack at scale: store events → device mirror →
    controllers → statuses, exactly what production serves from. Returns
    (store, plugin). Setup cost is logged per phase (it is the honest cost
    of cold-starting this state)."""
    from kube_throttler_tpu.api.pod import Namespace
    from kube_throttler_tpu.engine.store import Store
    from kube_throttler_tpu.plugin import KubeThrottler, decode_plugin_args

    store = Store()
    plugin = KubeThrottler(
        decode_plugin_args(
            {"name": "kube-throttler", "targetSchedulerName": "my-scheduler"}
        ),
        store,
        use_device=True,
    )
    store.create_namespace(Namespace("default"))
    throttles, pods = _served_state(P, T, groups)

    t0 = time.perf_counter()
    for thr in throttles:
        store.create_throttle(thr)
    t_thr = time.perf_counter() - t0
    log(f"[{label}] created {T} throttles in {t_thr:.1f}s "
        f"(flip band: every 24th cpu threshold at {_flip_band_mc(P, groups)}m)")

    t0 = time.perf_counter()
    for pod in pods:
        store.create_pod(pod)
    t_pods = time.perf_counter() - t0
    log(f"[{label}] created {P} bound pods in {t_pods:.1f}s "
        f"({t_pods/P*1e6:.0f}us/event through the live index)")

    t0 = time.perf_counter()
    n = plugin.run_pending_once()
    t_rec = time.perf_counter() - t0
    log(f"[{label}] initial reconcile of {n} keys in {t_rec:.1f}s "
        f"(batched device aggregates)")

    if plugin.device_manager is not None:
        t0 = time.perf_counter()
        nk = len(plugin.device_manager.prewarm())
        log(f"[{label}] prewarmed {nk} kernel shapes in {time.perf_counter()-t0:.1f}s")
    # same pre-serving step the daemon takes (cli.py): freeze the startup
    # heap so automatic full GCs never rescan the 100k-pod graph — without
    # it gen2 pauses (500-750ms at full scale) land inside reconcile
    # drains and dominate the flip-publication tail
    from kube_throttler_tpu.utils.gchygiene import freeze_startup_heap

    frozen = freeze_startup_heap()
    if frozen > 0:
        log(f"[{label}] gc hygiene: froze {frozen} startup objects; gen2 deferred")
    return store, plugin


def bench_served_prefilter(plugin, label, groups=500, n=2000):
    """(VERDICT r2 task 4a) END-TO-END plugin.pre_filter latency through
    DeviceStateManager.check_pod — lock, request encode, mask row, kernel
    dispatch, decode, reason strings — against the live state. This is the
    number BASELINE's north star names (<1ms p99 per decision)."""
    from kube_throttler_tpu.api.pod import make_pod

    probes = [
        make_pod(
            f"probe{i}",
            labels={"grp": f"g{i % groups}"},
            requests={"cpu": f"{(i % 7 + 1) * 100}m"},
        )
        for i in range(64)
    ]
    i = [0]

    def one():
        plugin.pre_filter(probes[i[0] % len(probes)])
        i[0] += 1

    # stability protocol (VERDICT r4 task 4): ≥3 interleaved repeats with a
    # cross-run CV in the JSON, so a single-core host's run-to-run variance
    # (~2× observed between rounds) is distinguishable from a real
    # regression inside one bench record instead of across rounds
    runs = []
    stats = None
    for _rep in range(3):
        s = host_percentiles(one, n // 3, max_seconds=40.0)
        runs.append(1.0 / s["mean"])
        if stats is None or s["p50"] < stats["p50"]:
            stats = s  # keep the least-interfered pass's percentiles
        time.sleep(0.05)  # yield: let background noise land between passes
    rates = np.asarray(runs)
    stats["decisions_per_sec_median"] = float(np.median(rates))
    stats["decisions_cv"] = float(rates.std() / rates.mean()) if rates.mean() else 0.0
    log(
        f"[{label}] SERVED pre_filter p50 {stats['p50']*1e3:.3f}ms / "
        f"p99 {stats['p99']*1e3:.3f}ms per decision; "
        f"{stats['decisions_per_sec_median']:,.0f} decisions/sec "
        f"single-threaded (median of {len(runs)} interleaved runs, "
        f"cross-run CV {stats['decisions_cv']:.3f})"
    )

    # thread scaling (VERDICT r2 task 5 done-bar): the device-state lock
    # covers only host-side snapshot grabs, so concurrent checkers should
    # scale until dispatch overhead saturates
    import threading as _threading

    def measure_threads(k, duration=2.0):
        stop = _threading.Event()
        counts = [0] * k

        def worker(idx):
            j = idx
            while not stop.is_set():
                plugin.pre_filter(probes[j % len(probes)])
                counts[idx] += 1
                j += k

        threads = [_threading.Thread(target=worker, args=(w,)) for w in range(k)]
        for th in threads:
            th.start()
        time.sleep(duration)
        stop.set()
        for th in threads:
            th.join(timeout=10)
        return sum(counts) / duration

    rate1 = measure_threads(1)
    rate4 = measure_threads(4)
    # the micro-batching front-end (plugin/coalesce.py): concurrent callers
    # share one fused dispatch per window — the designed scaling path for
    # interactive traffic (pre_filter_batch remains the bulk surface)
    co = plugin.coalescer()
    co.pre_filter(probes[0])  # compile the (B,K) rungs the batch will hit

    def measure_threads_coalesced(k, duration=2.0):
        stop = _threading.Event()
        counts = [0] * k

        def worker(idx):
            j = idx
            while not stop.is_set():
                co.pre_filter(probes[j % len(probes)])
                counts[idx] += 1
                j += k

        threads = [_threading.Thread(target=worker, args=(w,)) for w in range(k)]
        for th in threads:
            th.start()
        time.sleep(duration)
        stop.set()
        for th in threads:
            th.join(timeout=10)
        return sum(counts) / duration

    rate4_co = measure_threads_coalesced(4)
    log(
        f"[{label}] served check throughput: {rate1:,.0f}/s x1 thread, "
        f"{rate4:,.0f}/s x4 threads (scaling {rate4/max(rate1,1e-9):.2f}x); "
        f"{rate4_co:,.0f}/s x4 threads COALESCED "
        f"({rate4_co/max(rate1,1e-9):.2f}x of 1-thread direct)"
    )
    return stats, rate1, rate4, rate4_co


def bench_coalesce_crossover(plugin, label, dispatch_ms=1.0, threads=4, duration=2.0):
    """(VERDICT r5 rec 6) The coalescer's designed win condition, emulated:
    per-dispatch cost ≥1ms — the shape of a remote-accelerator round
    trip, where every direct pre_filter pays TWO blocking dispatches (one
    per kind) while the coalescer amortizes two across a whole window's
    batch. The manager's check entry points are wrapped with a sleep of
    ``dispatch_ms`` per dispatch (sleep releases the GIL, exactly like a
    blocking device read), 4-thread direct vs coalesced throughput is
    measured, and the wrappers are removed. On this single-core CPU host
    the UN-emulated comparison loses (~0.4× r5) — this measures the
    crossover itself, empirically, instead of asserting it."""
    import threading as _threading

    from kube_throttler_tpu.api.pod import make_pod

    dm = plugin.device_manager
    probes = [
        make_pod(
            f"xprobe{i}",
            labels={"grp": f"g{i % 500}"},
            requests={"cpu": f"{(i % 7 + 1) * 100}m"},
        )
        for i in range(64)
    ]
    orig_pod, orig_multi = dm.check_pod, dm.check_pods_multi
    delay = dispatch_ms / 1e3

    def slow_pod(*a, **k):
        time.sleep(delay)
        return orig_pod(*a, **k)

    def slow_multi(*a, **k):
        time.sleep(delay)
        return orig_multi(*a, **k)

    def measure(fn):
        stop = _threading.Event()
        counts = [0] * threads

        def worker(idx):
            j = idx
            while not stop.is_set():
                fn(probes[j % len(probes)])
                counts[idx] += 1
                j += threads

        ts = [_threading.Thread(target=worker, args=(w,)) for w in range(threads)]
        for t in ts:
            t.start()
        time.sleep(duration)
        stop.set()
        for t in ts:
            t.join(timeout=10)
        return sum(counts) / duration

    co = plugin.coalescer()
    plugin.pre_filter(probes[0])
    co.pre_filter(probes[0])  # warm both paths before arming the delay
    dm.check_pod, dm.check_pods_multi = slow_pod, slow_multi
    try:
        direct = measure(plugin.pre_filter)
        coalesced = measure(co.pre_filter)
    finally:
        dm.check_pod, dm.check_pods_multi = orig_pod, orig_multi
    ratio = coalesced / max(direct, 1e-9)
    log(
        f"[{label}] COALESCE CROSSOVER (emulated {dispatch_ms:.1f}ms/dispatch, "
        f"{threads} threads): direct {direct:,.0f}/s vs coalesced "
        f"{coalesced:,.0f}/s -> {ratio:.2f}x "
        f"({'coalescer wins' if ratio > 1 else 'direct wins'})"
    )
    return {
        "dispatch_ms": dispatch_ms,
        "direct_per_sec": direct,
        "coalesced_per_sec": coalesced,
        "ratio": ratio,
    }


def bench_served_batch(plugin, label, iters=5):
    """Bulk triage through the SERVED surface: plugin.pre_filter_batch
    classifies every stored pod against both kinds' full state in one
    coherent snapshot (two device dispatches). The per-pod cost amortizes
    the dispatch across the whole store — the batched counterpart of the
    per-decision served p99."""
    out = plugin.pre_filter_batch()  # warm (compiles the batch kernels)
    n = len(out["schedulable"])
    before = {
        ph: (plugin.tracer.snapshot(ph) or {"sum": 0.0, "count": 0})
        for ph in ("batch_dispatch", "batch_merge")
    }
    t0 = time.perf_counter()
    for _ in range(iters):
        out = plugin.pre_filter_batch()
    dt = (time.perf_counter() - t0) / iters
    pods_per_sec = n / dt if dt else 0.0
    phases = {}
    for ph, b in before.items():
        s = plugin.tracer.snapshot(ph)
        if s and s["count"] > b["count"]:
            phases[ph] = (s["sum"] - b["sum"]) / (s["count"] - b["count"])
    split = ", ".join(f"{ph}={v*1e3:.1f}ms" for ph, v in phases.items())
    log(
        f"[{label}] SERVED pre_filter_batch: {n} pods in {dt*1e3:.1f}ms "
        f"({pods_per_sec:,.0f} pod-verdicts/sec, one snapshot per call; "
        f"phase split: {split or 'n/a'} — dispatch is the sparse [P,K] "
        f"gather kernel, merge is the AND across kinds + ns routing)"
    )
    return {"pods": n, "secs": dt, "pods_per_sec": pods_per_sec}


def bench_served_tick(plugin, label):
    """The fused reconcile+PreFilter sweep (`plugin.full_tick_sharded`, the
    POST /v1/tick surface) on one device: override-resolved thresholds,
    used re-aggregation, throttled flags, and the full [P,T] admission
    classification for BOTH kinds from one coherent snapshot. The
    freshest-possible whole-cluster verdict in a single device program."""
    plugin.full_tick_sharded(1)  # warm/compile
    tracer = plugin.device_manager.tracer
    phases = ("tick_snapshot", "tick_encode", "tick_device")
    before = {
        ph: (tracer.snapshot(ph) or {"sum": 0.0, "count": 0}) for ph in phases
    }
    t0 = time.perf_counter()
    out = plugin.full_tick_sharded(1)
    dt = time.perf_counter() - t0
    parts = []
    for ph in phases:
        s = tracer.snapshot(ph)
        if s and s["count"] > before[ph]["count"]:
            parts.append(f"{ph.removeprefix('tick_')}={1e3*(s['sum']-before[ph]['sum']):.1f}ms")
    log(
        f"[{label}] SERVED full tick (1 device): {len(out['schedulable'])} pods "
        f"x both kinds, fused reconcile+classify in {dt*1e3:.0f}ms "
        f"(phases: {', '.join(parts) or 'n/a'}; device phase is the sparse "
        f"[P,K] gather step on a 1x1 mesh, the dense shard_map program on "
        f"real meshes)"
    )
    return dt


def _drive_pod_churn(store, group_keys, pending, pend_lock, rng, duration, pace_hz,
                     flip_state=None, apply=None):
    """The cfg5 churn generator, SHARED by the in-process and remote-wire
    serving benches so their lag numbers stay comparable: paced pod
    updates that are REAL state changes every time — the cpu value always
    differs from the last written one (seeded from the pod's actual stored
    request, so even a pod's first update cannot be a no-op that leaves a
    stale pending timestamp poisoning later lag samples). Every event
    pre-registers its group's throttle keys in ``pending`` for the
    event→status-commit pairing.

    ``flip_state`` = (flip_watch, run_sums, flip_pending) arms
    crossing-anchored flip stamping: the generator maintains each group's
    running cpu sum and, when an update moves the sum across a watched
    throttle's threshold, stamps ``flip_pending[key]`` — the event that
    actually made the published flag wrong (see ``_lag_tracker``). Returns
    (n_events, fire-window seconds, crossings stamped).

    ``apply`` overrides how an updated pod reaches the store (default: the
    direct ``store.update_pod`` call) — the micro-batch sweep passes the
    ingest pipeline's submit here."""
    from dataclasses import replace as _replace

    from kube_throttler_tpu.api.pod import make_pod
    from kube_throttler_tpu.resourcelist import pod_request_resource_list

    pods = store.list_pods()
    if apply is None:
        apply = store.update_pod
    cur_cpu: dict = {}  # pod name → last cpu we wrote
    flip_watch, run_sums, flip_pending = flip_state or ({}, {}, {})
    n_crossings = 0
    n_events = 0
    t_start = time.perf_counter()
    deadline = t_start + duration
    while time.perf_counter() < deadline:
        if pace_hz:
            next_at = t_start + n_events / pace_hz
            delay = next_at - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        pod = pods[rng.randrange(len(pods))]
        g = pod.labels["grp"]
        prev = cur_cpu.get(pod.name)
        if prev is None:  # seed from the pod's actual stored request
            stored = pod_request_resource_list(pod).get("cpu")
            prev = int(stored * 1000) if stored else 0
        new_cpu = rng.randrange(1, 8) * 100
        if new_cpu == prev:
            new_cpu = new_cpu % 700 + 100
        cur_cpu[pod.name] = new_cpu
        updated = make_pod(pod.name, labels=pod.labels, requests={"cpu": f"{new_cpu}m"})
        updated = _replace(updated, spec=_replace(updated.spec, node_name="node-1"))
        updated.status.phase = "Running"
        now = time.perf_counter()
        with pend_lock:
            for key in group_keys.get(g, ()):
                pending.setdefault(key, now)
            watch = flip_watch.get(g)
            if watch:
                s_old = run_sums.get(g, 0)
                s_new = s_old + new_cpu - prev
                run_sums[g] = s_new
                for key, thr_mc in watch:
                    if (s_old >= thr_mc) != (s_new >= thr_mc):
                        flip_pending[key] = now  # latest crossing wins
                        n_crossings += 1
        apply(updated)
        n_events += 1
    return n_events, time.perf_counter() - t_start, n_crossings


def bench_served_streaming(
    store, plugin, label, groups=500, duration=5.0, pace_hz=0.0,
    ingest_batch=None,
):
    """(VERDICT r2 task 4b) BASELINE cfg5 driven as store events through the
    CONTROLLERS: pod churn with workers running; reports the sustained
    pipeline rate and the event→status-commit lag (time from the first
    store event touching a throttle to the status write that reflects it —
    the reference's watch→reconcile→UpdateStatus latency,
    throttle_controller.go:84-211).

    ``pace_hz=0`` fires at max rate (measures CAPACITY; lag there reflects
    saturation backlog). ``pace_hz=1000`` fires at the BASELINE target rate
    (measures steady-state status-write lag under the nominal load).

    ``ingest_batch`` routes the churn through the micro-batched ingest
    pipeline (engine/ingest.py): ``"adaptive"`` or a fixed batch size; None
    keeps the direct per-event store calls (the PR 2 comparison rung)."""
    import random
    import threading as _threading
    from dataclasses import replace as _replace

    from kube_throttler_tpu.api.pod import make_pod
    from kube_throttler_tpu.engine.store import EventType

    rng = random.Random(1)
    # key → time of the first event not yet reflected in a status write
    pending, flip_pending, pend_lock, lags, flip_lags, _flip_walls, on_throttle_write = (
        _lag_tracker()
    )
    group_keys = _group_keys_of(store)
    flip_watch, run_sums = _flip_watch_of(store)
    store.add_event_handler("Throttle", on_throttle_write, replay=False)
    pipeline = None
    apply = None
    if ingest_batch is not None:
        from kube_throttler_tpu.engine.ingest import MicroBatchIngest

        pipeline = MicroBatchIngest(store, max_batch=64, batch_policy=ingest_batch)
        apply = lambda pod: pipeline.submit("update", "Pod", pod)  # noqa: E731
    plugin.start()
    try:
        n_events, t_fired, n_crossings = _drive_pod_churn(
            store, group_keys, pending, pend_lock, rng, duration, pace_hz,
            flip_state=(flip_watch, run_sums, flip_pending), apply=apply,
        )
        t_start = time.perf_counter() - t_fired
        # drain: the ingest queue first, then both workqueues, then writes
        if pipeline is not None:
            pipeline.flush(timeout=60.0)
        for ctr in (plugin.throttle_ctr, plugin.cluster_throttle_ctr):
            while len(ctr.workqueue) > 0:
                time.sleep(0.02)
        time.sleep(0.2)
        t_total = time.perf_counter() - t_start
    finally:
        # workers stay up (the caller may run another window and owns
        # plugin.stop() — a stopped workqueue is terminally shut down)
        store.remove_event_handler("Throttle", on_throttle_write)
        if pipeline is not None:
            pipeline.stop()

    n_applied = n_events
    if pipeline is not None:
        # capacity must count APPLIED events: at open-loop max rate the
        # bounded ingest queue drop-oldest-sheds — submitted ≠ ingested
        ps0 = pipeline.stats()
        n_applied = ps0["events_applied"]
    eps = n_applied / t_total
    lag_arr = np.asarray(lags) if lags else np.asarray([0.0])
    flip_arr = np.asarray(flip_lags) if flip_lags else np.asarray([0.0])
    result = {
        "events_per_sec": eps,
        # the rate the generator actually achieved DURING the window —
        # for paced runs this shows whether ingest kept the requested pace
        # (events_per_sec also amortizes the post-window drain tail, which
        # under-reads steady-state pacing by the drain fraction)
        "fired_events_per_sec": n_events / t_fired,
        "events_applied": n_applied,
        "lag_p50_ms": float(np.percentile(lag_arr, 50)) * 1e3,
        "lag_p99_ms": float(np.percentile(lag_arr, 99)) * 1e3,
        "status_writes": len(lags),
        # flip lag: crossing-event → flag-visible for writes that changed
        # throttled/calculatedThreshold ([0.0] sentinel when
        # flip_samples == 0 — don't read the percentiles then)
        "flip_lag_p50_ms": float(np.percentile(flip_arr, 50)) * 1e3,
        "flip_lag_p99_ms": float(np.percentile(flip_arr, 99)) * 1e3,
        "flip_samples": len(flip_lags),
        "flip_crossings": n_crossings,
    }
    if pipeline is not None:
        ps = pipeline.stats()
        result["ingest_batches"] = ps["batches"]
        result["ingest_mean_batch"] = round(
            ps["events_applied"] / max(ps["batches"], 1), 2
        )
        result["ingest_max_batch"] = ps["max_batch_seen"]
        result["ingest_dropped"] = ps["dropped"]
    mode = f"paced {pace_hz:,.0f}/s" if pace_hz else "max rate"
    log(
        f"[{label}] cfg5 THROUGH CONTROLLERS ({mode}): {n_events} events in "
        f"{t_total:.2f}s -> {eps:,.0f} events/sec sustained incl. drain "
        f"({result['fired_events_per_sec']:,.0f}/s during the fire window of "
        f"{t_fired:.2f}s); event->status-commit lag p50 "
        f"{result['lag_p50_ms']:.1f}ms / p99 {result['lag_p99_ms']:.1f}ms "
        f"over {len(lags)} status writes; FLIP lag p50 "
        f"{result['flip_lag_p50_ms']:.1f}ms / p99 {result['flip_lag_p99_ms']:.1f}ms "
        f"over {len(flip_lags)} flips from {n_crossings} crossings "
        f"(target: 1k events/sec, flip p99 <150ms)"
    )
    return result


def bench_ingest_burst(store, plugin, label, n=40_000, policy="adaptive", repeats=2):
    """Burst-drain ingest capacity: N real churn events are PRE-BUILT
    (producer cost off the clock) and preloaded into the micro-batch
    queue; the measurement is how fast the engine fully digests them —
    pipeline apply through reconcile-drain to empty workqueues. This is
    the clean capacity number: the open-loop max-rate window measures a
    producer/pipeline GIL fight plus drop-oldest shedding once the queue
    caps, neither of which is engine capacity.

    ``repeats``: capacity is a supremum — single-core GIL scheduling
    swings identical consecutive runs by up to ~1.5× (measured), and
    noise only subtracts — so the rung runs ``repeats`` times and reports
    the BEST, with every run recorded under ``runs``."""
    import random
    from dataclasses import replace as _replace

    from kube_throttler_tpu.api.pod import make_pod
    from kube_throttler_tpu.engine.ingest import MicroBatchIngest
    from kube_throttler_tpu.resourcelist import pod_request_resource_list

    rng = random.Random(4)
    pods = store.list_pods()
    cur_cpu: dict = {}

    def _mk_ops():
        ops = []
        for _ in range(n):
            pod = pods[rng.randrange(len(pods))]
            prev = cur_cpu.get(pod.name)
            if prev is None:
                stored = pod_request_resource_list(pod).get("cpu")
                prev = int(stored * 1000) if stored else 0
            new_cpu = rng.randrange(1, 8) * 100
            if new_cpu == prev:
                new_cpu = new_cpu % 700 + 100
            cur_cpu[pod.name] = new_cpu
            updated = make_pod(
                pod.name, labels=pod.labels, requests={"cpu": f"{new_cpu}m"}
            )
            updated = _replace(updated, spec=_replace(updated.spec, node_name="node-1"))
            updated.status.phase = "Running"
            ops.append(("update", "Pod", updated))
        return ops

    plugin.start()
    runs = []
    for rep in range(max(1, int(repeats))):
        ops = _mk_ops()
        pipeline = MicroBatchIngest(store, max_batch=64, batch_policy=policy, maxsize=n)
        t0 = time.perf_counter()
        pipeline.submit_many(ops)
        ok = pipeline.flush(timeout=300.0)
        t_apply = time.perf_counter() - t0
        for ctr in (plugin.throttle_ctr, plugin.cluster_throttle_ctr):
            while len(ctr.workqueue) > 0:
                time.sleep(0.02)
        time.sleep(0.2)
        t_total = time.perf_counter() - t0
        st = pipeline.stats()
        pipeline.stop()
        run = {
            "events": n,
            "flushed": ok,
            "apply_events_per_sec": round(n / t_apply),
            "events_per_sec_sustained": round(st["events_applied"] / t_total),
            "ingest_mean_batch": round(st["events_applied"] / max(st["batches"], 1), 2),
            "dropped": st["dropped"],
        }
        runs.append(run)
        log(
            f"[{label}] ingest BURST ({policy}, run {rep + 1}/{repeats}): {n} "
            f"events applied in {t_apply:.2f}s ({run['apply_events_per_sec']:,}/s "
            f"through the pipeline), fully reconciled in {t_total:.2f}s -> "
            f"{run['events_per_sec_sustained']:,} events/s sustained "
            f"(mean batch {run['ingest_mean_batch']})"
        )
    result = dict(max(runs, key=lambda r: r["events_per_sec_sustained"]))
    result["runs"] = runs
    return result


def bench_ingest_sweep(store, plugin, label, slo_pace=3300.0, duration=8.0):
    """PR 5 micro-batched ingest sweep over the full-scale capacity window:

    - ``direct`` — per-event store calls at max rate, the PR 2 comparison
      rung (the producer applies inline, so its fired rate IS the
      engine's per-event ceiling);
    - ``fixed64`` / ``adaptive`` — burst-drain capacity through the
      micro-batch pipeline at a fixed 64-event rung and the adaptive
      policy (see bench_ingest_burst — the clean "what can the engine
      digest" number);
    - ``adaptive-slo`` — the adaptive batcher PACED at ``slo_pace``: the
      sustained rate the pipeline holds while the flip-publication SLO
      (p99 ≤ 150ms) is met — "how fast can it go while admission-relevant
      flips stay fresh". The pace sits below the saturation knee on
      purpose: at the knee, queueing is bistable and the flip tail with
      it (the open-loop rungs document the over-the-knee regime).
    """
    out: dict = {"rungs": {}}
    # warmup (not recorded): the first window after stack build pays cold
    # code paths — measured ~1.4× slower than the identical next burst
    bench_ingest_burst(store, plugin, f"{label}:warmup", n=8_000, repeats=1)
    s = bench_served_streaming(
        store, plugin, f"{label}:direct", duration=duration, ingest_batch=None
    )
    out["rungs"]["direct"] = {
        "events_per_sec_sustained": round(s["events_per_sec"]),
        "events_per_sec_fired": round(s["fired_events_per_sec"]),
        "flip_lag_p50_ms": round(s["flip_lag_p50_ms"], 1),
        "flip_lag_p99_ms": round(s["flip_lag_p99_ms"], 1),
        "flip_samples": s["flip_samples"],
        "lag_p99_ms": round(s["lag_p99_ms"], 1),
        "pace_hz": 0.0,
    }
    for name, policy in (("fixed64", 64), ("adaptive", "adaptive")):
        out["rungs"][name] = bench_ingest_burst(
            store, plugin, f"{label}:{name}", policy=policy
        )
    # SLO knee search: the engine sits at ~85-95% utilization at these
    # paces on one core, where queueing is bistable run to run — so the
    # sweep measures a short ladder of paces and keeps the FASTEST rung
    # whose flip p99 met the 150ms SLO (every attempt is recorded).
    attempts = []
    best = None
    for pace in (slo_pace, slo_pace - 200.0, slo_pace - 400.0):
        s = bench_served_streaming(
            store, plugin, f"{label}:adaptive-slo@{pace:.0f}",
            duration=duration + 7.0, pace_hz=pace, ingest_batch="adaptive",
        )
        att = {
            "events_per_sec_sustained": round(s["events_per_sec"]),
            "events_per_sec_fired": round(s["fired_events_per_sec"]),
            "flip_lag_p50_ms": round(s["flip_lag_p50_ms"], 1),
            "flip_lag_p99_ms": round(s["flip_lag_p99_ms"], 1),
            "flip_samples": s["flip_samples"],
            "lag_p99_ms": round(s["lag_p99_ms"], 1),
            "pace_hz": pace,
            "ingest_mean_batch": s.get("ingest_mean_batch"),
        }
        attempts.append(att)
        if att["flip_lag_p99_ms"] <= 150.0 and (
            best is None
            or att["events_per_sec_sustained"] > best["events_per_sec_sustained"]
        ):
            best = att
    if best is None:  # nothing met the SLO: report the lowest-tail attempt
        best = min(attempts, key=lambda a: a["flip_lag_p99_ms"])
    out["rungs"]["adaptive-slo"] = dict(best)
    out["slo_attempts"] = attempts
    return out


def run_ingest_sweep() -> None:
    """``python bench.py --ingest-sweep``: the PR 5 acceptance artifact —
    full-scale (100k×10k) capacity sweep, written to BENCH_PR5_<platform>_
    <stamp>.json next to the PR 2 record, with the PR 2 reference numbers
    embedded for side-by-side reading."""
    platform = "cpu"
    try:
        import jax

        platform = jax.devices()[0].platform
    except Exception:
        pass
    store, plugin = build_served_stack(100_000, 10_000, label="pr5-sweep")
    try:
        sweep = bench_ingest_sweep(store, plugin, "pr5-sweep")
    finally:
        plugin.stop()
    # the PR 2 reference (committed record), embedded for comparison
    ref = {}
    try:
        import glob

        ref_files = sorted(glob.glob("BENCH_PR2_*.json"))
        if ref_files:
            with open(ref_files[-1]) as f:
                pr2 = json.load(f)
            ref = {
                "file": ref_files[-1],
                "fullscale_cfg5_maxrate_events_per_sec": pr2.get(
                    "fullscale_cfg5_maxrate_events_per_sec"
                ),
                "fullscale_cfg5_maxrate_fired_per_sec": pr2.get(
                    "fullscale_cfg5_maxrate_fired_per_sec"
                ),
                "fullscale_cfg5_flip_lag_p99_ms": pr2.get(
                    "fullscale_cfg5_flip_lag_p99_ms"
                ),
            }
    except Exception as e:  # noqa: BLE001 — the sweep numbers still stand
        ref = {"error": f"{e.__class__.__name__}: {e}"}
    baseline = float(ref.get("fullscale_cfg5_maxrate_events_per_sec") or 1399.0)
    cap = sweep["rungs"]["adaptive"]["events_per_sec_sustained"]
    slo = sweep["rungs"]["adaptive-slo"]
    out = {
        "metric": (
            "full-scale (100k pods x 10k throttles) sustained ingest "
            "capacity, micro-batched pipeline (adaptive), burst-drain "
            "(pipeline apply + full reconcile drain)"
        ),
        "value": cap,
        "unit": "events/s",
        "platform": platform,
        "scale": [100_000, 10_000],
        "pr2_reference": ref,
        "capacity_x_pr2": round(cap / baseline, 2),
        "slo_window": {
            "events_per_sec_sustained": slo["events_per_sec_sustained"],
            "flip_lag_p99_ms": slo["flip_lag_p99_ms"],
            "flip_slo_ms": 150.0,
            "x_pr2": round(slo["events_per_sec_sustained"] / baseline, 2),
        },
        **sweep,
    }
    stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    path = f"BENCH_PR5_{platform.upper()}_{stamp}.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    log(f"ingest sweep written to {path}")
    emit(out)


def build_sharded_stack(P, T, n_shards, groups=500, label="shards"):
    """The PR 9 multiprocess stack at scale: scatter-gather admission
    front in THIS process, ``n_shards`` worker processes (each a full
    vertical: store+index+device planes+controllers) spawned by the
    supervisor. Topology (incl. the flip band) is identical to
    build_served_stack so the rungs compare apples to apples; objects
    are seeded THROUGH the front in batches (the honest routing cost)."""
    import os as _os

    from kube_throttler_tpu.api.pod import Namespace, make_pod
    from kube_throttler_tpu.sharding.front import AdmissionFront
    from kube_throttler_tpu.sharding.supervisor import ShardSupervisor

    import random

    rng = random.Random(0)
    front = AdmissionFront(n_shards)
    supervisor = ShardSupervisor(
        front,
        use_device=True,
        env={**_os.environ, "KT_SHARD_QUIET": "1", "KT_LOCK_ASSERT": "0"},
    )
    t0 = time.perf_counter()
    supervisor.start(ready_timeout=600.0)
    log(f"[{label}] {n_shards} workers ready in {time.perf_counter()-t0:.1f}s")

    front.store.create_namespace(Namespace("default"))
    flip_mc = _flip_band_mc(P, groups)
    t0 = time.perf_counter()
    ops = [
        ("create", "Throttle", _served_throttle(i, groups, flip_band_mc=flip_mc))
        for i in range(T)
    ]
    for s in range(0, len(ops), 512):
        front.store.apply_events(ops[s : s + 512])
    t_thr = time.perf_counter() - t0
    log(f"[{label}] routed {T} throttles in {t_thr:.1f}s")

    from dataclasses import replace as _replace

    t0 = time.perf_counter()
    pod_ops = []
    for i in range(P):
        pod = make_pod(
            f"p{i}",
            labels={"grp": f"g{rng.randrange(groups)}"},
            requests={"cpu": f"{rng.randrange(1, 8) * 100}m"},
        )
        pod = _replace(pod, spec=_replace(pod.spec, node_name="node-1"))
        pod.status.phase = "Running"
        pod_ops.append(("create", "Pod", pod))
    for s in range(0, len(pod_ops), 1024):
        front.store.apply_events(pod_ops[s : s + 1024])
    t_pods = time.perf_counter() - t0
    log(f"[{label}] routed {P} pods in {t_pods:.1f}s "
        f"({t_pods/P*1e6:.0f}us/event through the routing index)")
    t0 = time.perf_counter()
    front.drain(timeout=900.0)
    log(f"[{label}] shards drained initial reconcile in "
        f"{time.perf_counter()-t0:.1f}s")
    stats = front.stats()
    spread = {
        sid: s.get("objects", {}) for sid, s in stats["shards"].items()
    }
    log(f"[{label}] keyspace spread: {spread}")
    return front, supervisor


def _sharded_drain(front, pipeline, timeout=600.0):
    if pipeline is not None:
        pipeline.flush(timeout=timeout)
    front.drain(timeout=timeout)
    time.sleep(0.5)  # status pushes ride their own flush cadence


def bench_shard_burst(front, label, n=30_000, repeats=2):
    """Aggregate burst-drain capacity through the sharded stack: N
    pre-built churn events (producer cost off the clock) through the
    front's micro-batch pipeline → routing → per-shard ingest → full
    reconcile drain on every shard. Applied-not-submitted accounting:
    the count is the front pipeline's events_applied (each a DISTINCT
    event, applied at its owning shards), the clock stops when every
    shard reports empty queues+workqueues."""
    import random
    from dataclasses import replace as _replace

    from kube_throttler_tpu.api.pod import make_pod
    from kube_throttler_tpu.engine.ingest import MicroBatchIngest
    from kube_throttler_tpu.resourcelist import pod_request_resource_list

    rng = random.Random(4)
    pods = front.store.list_pods()
    cur_cpu: dict = {}

    def _mk_ops():
        ops = []
        for _ in range(n):
            pod = pods[rng.randrange(len(pods))]
            prev = cur_cpu.get(pod.name)
            if prev is None:
                stored = pod_request_resource_list(pod).get("cpu")
                prev = int(stored * 1000) if stored else 0
            new_cpu = rng.randrange(1, 8) * 100
            if new_cpu == prev:
                new_cpu = new_cpu % 700 + 100
            cur_cpu[pod.name] = new_cpu
            updated = make_pod(
                pod.name, labels=pod.labels, requests={"cpu": f"{new_cpu}m"}
            )
            updated = _replace(updated, spec=_replace(updated.spec, node_name="node-1"))
            updated.status.phase = "Running"
            ops.append(("update", "Pod", updated))
        return ops

    runs = []
    for rep in range(max(1, int(repeats))):
        ops = _mk_ops()
        pipeline = MicroBatchIngest(front.store, batch_policy="adaptive", maxsize=n)
        t0 = time.perf_counter()
        pipeline.submit_many(ops)
        pipeline.flush(timeout=900.0)
        t_apply = time.perf_counter() - t0
        front.drain(timeout=900.0)
        t_total = time.perf_counter() - t0
        st = pipeline.stats()
        pipeline.stop()
        run = {
            "events": n,
            "apply_events_per_sec": round(n / t_apply),
            "events_per_sec_sustained": round(st["events_applied"] / t_total),
            "events_applied": st["events_applied"],
            "dropped": st["dropped"],
        }
        runs.append(run)
        log(
            f"[{label}] shard BURST (run {rep + 1}/{repeats}): {n} events, "
            f"front apply {run['apply_events_per_sec']:,}/s, fully "
            f"reconciled across shards in {t_total:.2f}s -> "
            f"{run['events_per_sec_sustained']:,} ev/s aggregate sustained"
        )
    result = dict(max(runs, key=lambda r: r["events_per_sec_sustained"]))
    result["runs"] = runs
    return result


def bench_shard_streaming(front, label, duration=8.0, pace_hz=0.0):
    """Paced churn through the sharded stack with crossing-anchored flip
    measurement ON THE FRONT STORE — the flip clock includes routing,
    IPC, the owning shard's two-lane reconcile, and the status push back
    to the front: the end-to-end publication latency an operator sees."""
    import random

    from kube_throttler_tpu.engine.ingest import MicroBatchIngest

    rng = random.Random(1)
    pending, flip_pending, pend_lock, lags, flip_lags, _fw, on_throttle_write = (
        _lag_tracker()
    )
    group_keys = _group_keys_of(front.store)
    flip_watch, run_sums = _flip_watch_of(front.store)
    front.store.add_event_handler("Throttle", on_throttle_write, replay=False)
    pipeline = MicroBatchIngest(front.store, max_batch=64, batch_policy="adaptive")
    try:
        n_events, t_fired, n_crossings = _drive_pod_churn(
            front.store, group_keys, pending, pend_lock, rng, duration, pace_hz,
            flip_state=(flip_watch, run_sums, flip_pending),
            apply=lambda pod: pipeline.submit("update", "Pod", pod),
        )
        t_start = time.perf_counter() - t_fired
        _sharded_drain(front, pipeline)
        t_total = time.perf_counter() - t_start
    finally:
        front.store.remove_event_handler("Throttle", on_throttle_write)
        ps = pipeline.stats()
        pipeline.stop()
    n_applied = ps["events_applied"]
    lag_arr = np.asarray(lags) if lags else np.asarray([0.0])
    flip_arr = np.asarray(flip_lags) if flip_lags else np.asarray([0.0])
    result = {
        "events_per_sec_sustained": round(n_applied / t_total),
        "events_per_sec_fired": round(n_events / t_fired),
        "events_applied": n_applied,
        "lag_p99_ms": round(float(np.percentile(lag_arr, 99)) * 1e3, 1),
        "flip_lag_p50_ms": round(float(np.percentile(flip_arr, 50)) * 1e3, 1),
        "flip_lag_p99_ms": round(float(np.percentile(flip_arr, 99)) * 1e3, 1),
        "flip_samples": len(flip_lags),
        "flip_crossings": n_crossings,
        "pace_hz": pace_hz,
    }
    mode = f"paced {pace_hz:,.0f}/s" if pace_hz else "max rate"
    log(
        f"[{label}] sharded churn ({mode}): "
        f"{result['events_per_sec_sustained']:,} ev/s sustained "
        f"({result['events_per_sec_fired']:,}/s fired); FLIP p50 "
        f"{result['flip_lag_p50_ms']}ms / p99 {result['flip_lag_p99_ms']}ms "
        f"over {result['flip_samples']} flips"
    )
    return result


def bench_shard_decisions(front, label, threads=4, duration=2.0, groups=500):
    """Served decisions/s through the scatter-gather front: concurrent
    callers fan out to the owning shards (one RPC per matching shard)
    and AND-merge. With selector-affinity sharding a probe touches ONE
    shard, so N front threads drive N workers concurrently — the
    multi-core decision path the GIL denies the single process."""
    import threading as _threading

    from kube_throttler_tpu.api.pod import make_pod

    probes = [
        make_pod(
            f"probe{i}",
            labels={"grp": f"g{i % groups}"},
            requests={"cpu": f"{(i % 7 + 1) * 100}m"},
        )
        for i in range(64)
    ]
    front.pre_filter(probes[0])  # warm the RPC path

    def measure(k):
        stop = _threading.Event()
        counts = [0] * k

        def worker(idx):
            j = idx
            while not stop.is_set():
                front.pre_filter(probes[j % len(probes)])
                counts[idx] += 1
                j += k

        ts = [_threading.Thread(target=worker, args=(w,)) for w in range(k)]
        for t in ts:
            t.start()
        time.sleep(duration)
        stop.set()
        for t in ts:
            t.join(timeout=10)
        return sum(counts) / duration

    rate1 = measure(1)
    rate_k = measure(threads)
    log(
        f"[{label}] scatter-gather decisions: {rate1:,.0f}/s x1 thread, "
        f"{rate_k:,.0f}/s x{threads} threads "
        f"(scaling {rate_k/max(rate1,1e-9):.2f}x)"
    )
    return {
        "decisions_per_sec_1thread": round(rate1),
        f"decisions_per_sec_{threads}threads": round(rate_k),
        "thread_scaling": round(rate_k / max(rate1, 1e-9), 2),
    }


def run_shard_sweep() -> None:
    """``python bench.py --shard-sweep``: the PR 9 acceptance artifact —
    aggregate ingest, served decisions, and flip p99 per worker count
    {1,2,4} at the PR 5 topology (100k pods × 10k throttles), written to
    BENCH_PR9_<platform>_<stamp>.json. The 3× acceptance target assumes
    ≥4 cores (one per worker + the front); ``host_cores`` is recorded so
    an under-provisioned run (this container has 1) reads as what it is:
    the protocol at full scale, not a parallel-speedup measurement."""
    import os as _os

    platform = "cpu"
    try:
        platform = jax.devices()[0].platform
    except Exception:
        pass
    host_cores = len(_os.sched_getaffinity(0))
    P, T = (100_000, 10_000)
    if "--quick" in sys.argv:
        P, T = (10_000, 1_000)
    shard_counts = [1, 2, 4]
    pr5_baseline = 3593.0
    # the PR 9 acceptance gate, detected at bench start: the ≥3× aggregate
    # target is ENFORCED (non-zero exit) only on a host with ≥5 cores (4
    # workers + the front each need one); an undersubscribed host records
    # the fact explicitly and keeps the gate advisory — the sweep then
    # measures protocol overhead, not parallel speedup
    required_cores = max(shard_counts) + 1
    gate_enforced = host_cores >= required_cores
    log(
        f"shard sweep: host_cores={host_cores} required={required_cores} → "
        f"3x gate {'ENFORCED' if gate_enforced else 'ADVISORY (undersubscribed)'}"
    )
    out = {
        "metric": (
            "aggregate full-scale sustained ingest / served decisions / "
            "flip p99 across shared-nothing worker processes "
            "(scatter-gather front, applied-not-submitted accounting)"
        ),
        "platform": platform,
        "host_cores": host_cores,
        "scale": [P, T],
        "pr5_single_core_events_per_sec": pr5_baseline,
        "shard_counts": {},
    }
    for n_shards in shard_counts:
        label = f"shards{n_shards}"
        front = supervisor = None
        try:
            front, supervisor = build_sharded_stack(P, T, n_shards, label=label)
            rung = {"workers": n_shards}
            rung["burst"] = bench_shard_burst(front, label)
            cap = rung["burst"]["events_per_sec_sustained"]
            # SLO ladder relative to measured capacity: fastest pace whose
            # flip p99 meets the 150ms SLO wins (every attempt recorded)
            attempts = []
            best = None
            for frac in (0.85, 0.7, 0.55):
                pace = max(500.0, cap * frac)
                att = bench_shard_streaming(
                    front, f"{label}@{pace:.0f}", duration=10.0, pace_hz=pace
                )
                attempts.append(att)
                if att["flip_lag_p99_ms"] <= 150.0 and (
                    best is None
                    or att["events_per_sec_sustained"]
                    > best["events_per_sec_sustained"]
                ):
                    best = att
            if best is None:
                best = min(attempts, key=lambda a: a["flip_lag_p99_ms"])
            rung["slo_window"] = best
            rung["slo_attempts"] = attempts
            rung["decisions"] = bench_shard_decisions(front, label)
            stats = front.stats()
            rung["per_shard_applied"] = {
                sid: s.get("ingest", {}).get("events_applied")
                for sid, s in stats["shards"].items()
            }
            rung["route_misses"] = stats["route_misses"]
            out["shard_counts"][str(n_shards)] = rung
        except Exception as e:  # noqa: BLE001 — record and continue the sweep
            log(f"[{label}] FAILED: {e.__class__.__name__}: {e}")
            log(traceback.format_exc(limit=6))
            out["shard_counts"][str(n_shards)] = {
                "workers": n_shards,
                "error": f"{e.__class__.__name__}: {str(e)[:300]}",
            }
        finally:
            if supervisor is not None:
                supervisor.stop()
            if front is not None:
                front.stop()
    best4 = (
        out["shard_counts"].get("4", {}).get("burst", {}).get(
            "events_per_sec_sustained"
        )
    )
    if best4:
        out["aggregate_x_pr5"] = round(best4 / pr5_baseline, 2)
        out["meets_3x"] = bool(best4 >= 3 * pr5_baseline)
    out["undersubscribed"] = host_cores < required_cores
    out["gate_3x"] = {
        "required_cores": required_cores,
        "host_cores": host_cores,
        "enforced": gate_enforced,
        "meets_3x": out.get("meets_3x"),
        "advisory": (
            None
            if gate_enforced
            else (
                f"host exposes {host_cores} core(s) < {required_cores}: "
                f"{max(shard_counts)} workers + the front timeshare, so the "
                "sweep measures sharding-protocol overhead, not parallel "
                "speedup — rerun on a ≥5-core host to enforce the ≥3× "
                f"aggregate target vs PR 5's {pr5_baseline:,.0f} ev/s"
            )
        ),
    }
    stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    path = f"BENCH_PR9_{platform.upper()}_{stamp}.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    log(f"shard sweep written to {path}")
    emit(out)
    if gate_enforced and not out.get("meets_3x"):
        log(
            f"shard sweep FAILED the enforced 3x gate: aggregate "
            f"{best4 or 0:,.0f} ev/s < {3 * pr5_baseline:,.0f}"
        )
        raise SystemExit(1)


def run_gang_bench() -> None:
    """``python bench.py --gang``: the gang-admission rung — bursty
    all-or-nothing group arrivals (mixed sizes 2/4/8/16) against ONE hot
    throttle, with the cfg5 paced pod churn running through the
    controllers underneath. Reports the all-or-nothing admit rate (and
    asserts ZERO partial admissions observable in the ledger), group
    admission latency percentiles (batched feasibility dispatch + atomic
    group reserve), and the per-pod flip p99 of the concurrent churn
    window — the PR 5 SLO (≤150 ms) must hold with gangs in the mix.
    ``--full`` runs the 100k×10k shape; default is the 10k×1k rung."""
    import random
    import threading as _threading
    from collections import deque

    from kube_throttler_tpu.api.pod import make_pod
    from kube_throttler_tpu.api.types import (
        LabelSelector,
        ResourceAmount,
        Throttle,
        ThrottleSelector,
        ThrottleSelectorTerm,
        ThrottleSpec,
    )

    platform = "cpu"
    try:
        platform = jax.devices()[0].platform
    except Exception:
        pass
    full = "--full" in sys.argv
    P, T = (100_000, 10_000) if full else (10_000, 1_000)
    groups = 500
    store, plugin = build_served_stack(P, T, groups, label="gang")

    # the HOT throttle every gang lands on: a cpu budget of 16 admits 32
    # 500m ranks — bursts of mixed sizes oversubscribe it, so admit/reject
    # both happen and capacity cycles as held groups roll back
    hot = Throttle(
        name="gang-hot",
        spec=ThrottleSpec(
            throttler_name="kube-throttler",
            threshold=ResourceAmount.of(requests={"cpu": "16"}),
            selector=ThrottleSelector(
                selector_terms=(
                    ThrottleSelectorTerm(
                        LabelSelector(match_labels={"grp": "gang-hot"})
                    ),
                )
            ),
        ),
    )
    store.create_throttle(hot)

    # prewarm the gang kernel's shape rungs (member pads 8 and 16 cover
    # sizes 2/4/8/16): the first dispatch's XLA compile (~2s on CPU) must
    # not land inside the measured admission window
    for warm_n in (2, 16):
        warm = [
            make_pod(
                f"gangwarm{warm_n}-r{i}",
                labels={"grp": "gang-hot"},
                requests={"cpu": "500m"},
                group=f"gangwarm{warm_n}",
                group_size=warm_n,
            )
            for i in range(warm_n)
        ]
        plugin.pre_filter_gang(f"default/gangwarm{warm_n}", warm)

    stop = _threading.Event()
    gang_stats = {
        "admit_lat": [],
        "check_lat": [],
        "admitted": 0,
        "rejected": 0,
        "violations": 0,
        "sizes": {},
    }

    def gang_driver() -> None:
        rng = random.Random(7)
        held: deque = deque()  # (release_time, group_key)
        gid = 0
        sizes = (2, 4, 8, 16)
        cache = plugin.throttle_ctr.cache
        while not stop.is_set():
            now = time.perf_counter()
            while held and held[0][0] <= now:
                _, gk = held.popleft()
                plugin.unreserve_gang(gk)
            for _ in range(rng.randint(1, 4)):  # one bursty arrival wave
                gid += 1
                size = rng.choice(sizes)
                gk = f"default/gang{gid}"
                members = [
                    make_pod(
                        f"gang{gid}-r{i}",
                        labels={"grp": "gang-hot"},
                        requests={"cpu": "500m"},
                        group=f"gang{gid}",
                        group_size=size,
                    )
                    for i in range(size)
                ]
                t0 = time.perf_counter()
                st = plugin.pre_filter_gang(gk, members)
                t1 = time.perf_counter()
                ok = st.is_success() and plugin.reserve_gang(gk, members).is_success()
                t2 = time.perf_counter()
                gang_stats["check_lat"].append(t1 - t0)
                gang_stats["admit_lat"].append(t2 - t0)
                gang_stats["sizes"][size] = gang_stats["sizes"].get(size, 0) + 1
                # all-or-nothing witness straight from the ledger: every
                # member reserved on the hot key, or none of them
                reserved = cache.reserved_pod_keys(hot.key)
                member_keys = {m.key for m in members}
                n_in = len(member_keys & reserved)
                if ok:
                    gang_stats["admitted"] += 1
                    if n_in != size:
                        gang_stats["violations"] += 1
                    held.append((time.perf_counter() + 0.05, gk))
                else:
                    gang_stats["rejected"] += 1
                    if n_in != 0:
                        gang_stats["violations"] += 1
            stop.wait(0.05)
        while held:
            plugin.unreserve_gang(held.popleft()[1])

    driver = _threading.Thread(target=gang_driver, daemon=True)
    driver.start()
    try:
        streaming = bench_served_streaming(
            store, plugin, "gang-churn", groups=groups,
            duration=4.0 if not full else 8.0, pace_hz=1000.0,
            ingest_batch="adaptive",
        )
    finally:
        stop.set()
        driver.join(timeout=10)
        plugin.stop()

    lat = np.asarray(gang_stats["admit_lat"]) if gang_stats["admit_lat"] else np.asarray([0.0])
    chk = np.asarray(gang_stats["check_lat"]) if gang_stats["check_lat"] else np.asarray([0.0])
    total = gang_stats["admitted"] + gang_stats["rejected"]
    out = {
        "metric": (
            "gang admission p99 (batched group feasibility + atomic "
            "all-or-nothing reserve) under bursty mixed-size arrivals on "
            "one hot throttle, cfg5 churn paced 1k ev/s underneath"
        ),
        "value": round(float(np.percentile(lat, 99)) * 1e3, 3),
        "unit": "ms",
        "platform": platform,
        "scale": [P, T],
        "gang_groups_total": total,
        "gang_groups_admitted": gang_stats["admitted"],
        "gang_groups_rejected": gang_stats["rejected"],
        "gang_admit_rate": round(gang_stats["admitted"] / max(total, 1), 3),
        "gang_all_or_nothing_violations": gang_stats["violations"],
        "gang_sizes": gang_stats["sizes"],
        "gang_admission_p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "gang_admission_p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
        "gang_check_p50_ms": round(float(np.percentile(chk, 50)) * 1e3, 3),
        "gang_check_p99_ms": round(float(np.percentile(chk, 99)) * 1e3, 3),
        "churn_flip_lag_p99_ms": streaming["flip_lag_p99_ms"],
        "churn_flip_samples": streaming["flip_samples"],
        "flip_slo_ms": 150.0,
        "flip_slo_met": bool(
            streaming["flip_samples"] == 0
            or streaming["flip_lag_p99_ms"] <= 150.0
        ),
        "churn": streaming,
    }
    log(
        f"[gang] {total} groups ({gang_stats['admitted']} admitted / "
        f"{gang_stats['rejected']} rejected, admit rate "
        f"{out['gang_admit_rate']:.0%}), admission p50 "
        f"{out['gang_admission_p50_ms']:.2f}ms / p99 "
        f"{out['gang_admission_p99_ms']:.2f}ms, all-or-nothing violations "
        f"{gang_stats['violations']}; churn flip p99 "
        f"{streaming['flip_lag_p99_ms']:.1f}ms over "
        f"{streaming['flip_samples']} flips (SLO ≤150ms: "
        f"{'MET' if out['flip_slo_met'] else 'MISSED'})"
    )
    stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    path = f"BENCH_GANG_{platform.upper()}_{stamp}.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    log(f"gang rung written to {path}")
    emit(out)


def run_decision_sweep() -> None:
    """``python bench.py --decision-sweep``: the PR 17 acceptance artifact —
    the interned-verdict cache against the uncached reference on the REAL
    served stack. Rungs: uncached / cold / warm at 1 and 4 threads, on a
    DEGENERATE probe mix (few request shapes — the autoscaler-storm case
    the cache exists for) and a DIVERSE mix (every probe a distinct
    shape — the cache's worst case, where it must not regress the path).
    Then epoch-churn sensitivity: warm throughput + hit rate while a
    background mutator edits throttle thresholds at {0,10,100} Hz, and an
    oracle sweep interleaving mutations with cache-vs-recompute verdict
    comparisons. Gates (enforced, non-zero exit): warm degenerate ≥10×
    the uncached reference single-threaded, and ZERO wrong verdicts vs
    the oracle. ``--full`` runs 100k×10k; default is the 10k×1k rung."""
    import random
    import threading as _threading
    from dataclasses import replace as _replace

    from kube_throttler_tpu.api.pod import make_pod
    from kube_throttler_tpu.api.types import ResourceAmount

    platform = "cpu"
    try:
        platform = jax.devices()[0].platform
    except Exception:
        pass
    full = "--full" in sys.argv
    P, T = (100_000, 10_000) if full else (10_000, 1_000)
    groups = 500
    store, plugin = build_served_stack(P, T, groups, label="decisions")
    cache = plugin.verdict_cache
    if cache is None:
        log("decision sweep FAILED: plugin built without a verdict cache "
            "(KT_VERDICT_CACHE=0 or no device manager)")
        raise SystemExit(1)

    # DEGENERATE mix: 64 probe objects over 8 (grp, cpu) shapes — after one
    # pass every further decision is a pure hash probe. DIVERSE mix: 2000
    # probes each with a distinct (grp, cpu) pair, so the cache's first
    # pass is all misses and steady state still hits (2000 < capacity).
    degenerate = [
        make_pod(
            f"deg{i}",
            labels={"grp": f"g{i % 4}"},
            requests={"cpu": f"{((i // 4) % 2 + 1) * 100}m"},
        )
        for i in range(64)
    ]
    diverse = [
        make_pod(
            f"div{i}",
            labels={"grp": f"g{i % groups}"},
            requests={"cpu": f"{(i % 97 + 1) * 10}m"},
        )
        for i in range(2000)
    ]

    def _measure_once(probes, threads=1, duration=2.0):
        """Drive pre_filter over `probes` round-robin for `duration`;
        returns (decisions_per_sec, hit_rate) from cache stat deltas."""
        h0, m0 = cache.stats()[:2]
        stop = _threading.Event()
        counts = [0] * threads

        def worker(idx):
            j = idx
            n = len(probes)
            while not stop.is_set():
                plugin.pre_filter(probes[j % n])
                counts[idx] += 1
                j += threads

        ths = [_threading.Thread(target=worker, args=(w,)) for w in range(threads)]
        for th in ths:
            th.start()
        time.sleep(duration)
        stop.set()
        for th in ths:
            th.join(timeout=10)
        h1, m1 = cache.stats()[:2]
        dh, dm = h1 - h0, m1 - m0
        hit_rate = dh / max(dh + dm, 1)
        return sum(counts) / duration, hit_rate

    def measure(probes, threads=1, duration=1.5, reps=3):
        """Median of `reps` interleaved passes (same protocol as
        bench_served_prefilter): a single-core host's co-tenant noise
        moves one 2s window by ±30%, which would make the 10x gate flap."""
        runs = [_measure_once(probes, threads, duration) for _ in range(reps)]
        rates = sorted(r for r, _ in runs)
        hits = sorted(h for _, h in runs)
        return rates[len(rates) // 2], hits[len(hits) // 2]

    def measure_uncached(probes, threads=1, duration=1.5, reps=3):
        """The reference: same drive with the cache detached — every
        decision walks the full plane path. Median of `reps` passes."""
        saved, plugin.verdict_cache = plugin.verdict_cache, None
        try:
            rates = []
            for _rep in range(reps):
                stop = _threading.Event()
                counts = [0] * threads

                def worker(idx):
                    j = idx
                    n = len(probes)
                    while not stop.is_set():
                        plugin.pre_filter(probes[j % n])
                        counts[idx] += 1
                        j += threads

                ths = [
                    _threading.Thread(target=worker, args=(w,))
                    for w in range(threads)
                ]
                for th in ths:
                    th.start()
                time.sleep(duration)
                stop.set()
                for th in ths:
                    th.join(timeout=10)
                rates.append(sum(counts) / duration)
                time.sleep(0.05)
            return sorted(rates)[len(rates) // 2]
        finally:
            plugin.verdict_cache = saved

    def cold_pass(probes):
        """First-touch rate: fresh cache, ONE pass over the probe set —
        every decision is a miss + validate-after-compute insert."""
        cache.invalidate_all()
        t0 = time.perf_counter()
        for p in probes:
            plugin.pre_filter(p)
        dt = time.perf_counter() - t0
        return len(probes) / dt

    out: dict = {
        "metric": (
            "served decisions/s: interned-verdict cache vs uncached "
            "reference (degenerate + diverse probe mixes, real daemon stack)"
        ),
        "platform": platform,
        "host_cpus": os.cpu_count(),
        "scale": [P, T],
        "cache_capacity": cache.capacity,
        "mixes": {},
    }

    for name, probes in (("degenerate", degenerate), ("diverse", diverse)):
        rung: dict = {"probes": len(probes),
                      "shapes": 8 if name == "degenerate" else len(probes)}
        rung["uncached_1t"] = measure_uncached(probes, threads=1)
        rung["cold_pass"] = cold_pass(probes)
        # warm the cache fully before the steady-state rungs
        for p in probes:
            plugin.pre_filter(p)
        r1, hr1 = measure(probes, threads=1)
        r4, hr4 = measure(probes, threads=4)
        rung["warm_1t"], rung["warm_1t_hit_rate"] = r1, round(hr1, 4)
        rung["warm_4t"], rung["warm_4t_hit_rate"] = r4, round(hr4, 4)
        rung["speedup_warm_vs_uncached_1t"] = round(r1 / max(rung["uncached_1t"], 1e-9), 2)
        log(
            f"[decisions:{name}] uncached {rung['uncached_1t']:,.0f}/s, "
            f"cold {rung['cold_pass']:,.0f}/s, warm {r1:,.0f}/s x1 "
            f"(hit {hr1:.1%}) / {r4:,.0f}/s x4 (hit {hr4:.1%}) — "
            f"{rung['speedup_warm_vs_uncached_1t']}x warm vs uncached"
        )
        out["mixes"][name] = rung

    # ---- epoch-churn sensitivity: a mutator edits flip-band throttle
    # thresholds at a fixed pace while the degenerate warm rung runs. Each
    # edit bumps the touched cols' epochs, so every covered entry goes
    # stale and the next probe recomputes — hit rate degrades with pace
    # but throughput must degrade gracefully, not collapse.
    # mutate the throttles that SELECT the degenerate groups (t{i} selects
    # g{i%groups}) so every edit actually covers served entries
    churn_keys = [f"default/t{i}" for i in range(4)]

    def churn_rung(pace_hz: float, duration=2.0):
        stop = _threading.Event()
        edits = [0]

        def mutator():
            # the bench plugin runs workerless (build_served_stack drives
            # reconciles explicitly), so each edit is followed by the
            # reconcile that publishes it to the planes — that reconcile
            # is what bumps the covered cols' epochs
            rng = random.Random(17)
            period = 1.0 / pace_hz
            while not stop.is_set():
                key = churn_keys[edits[0] % len(churn_keys)]
                ns, nm = key.split("/")
                thr = store.get_throttle(ns, nm)
                mc = rng.randrange(1, 200) * 100
                store.update_throttle_spec(
                    _replace(
                        thr,
                        spec=_replace(
                            thr.spec,
                            threshold=ResourceAmount.of(requests={"cpu": f"{mc}m"}),
                        ),
                    )
                )
                plugin.run_pending_once()
                edits[0] += 1
                time.sleep(period)

        th = None
        if pace_hz > 0:
            th = _threading.Thread(target=mutator)
            th.start()
        rate, hit = measure(degenerate, threads=1, duration=duration)
        stop.set()
        if th is not None:
            th.join(timeout=10)
        return {"pace_hz": pace_hz, "decisions_per_sec": rate,
                "hit_rate": round(hit, 4), "edits": edits[0]}

    out["epoch_churn"] = [churn_rung(hz) for hz in (0.0, 10.0, 100.0)]
    for r in out["epoch_churn"]:
        log(
            f"[decisions:churn@{r['pace_hz']:.0f}Hz] "
            f"{r['decisions_per_sec']:,.0f}/s, hit {r['hit_rate']:.1%} "
            f"({r['edits']} threshold edits)"
        )

    # ---- oracle sweep: interleave mutations with cache-vs-recompute
    # comparisons. After each mutation the pending reconciles are drained
    # (the workerless bench plugin reconciles on demand), then every
    # probe's CACHED verdict must match a fresh recompute — code and
    # reason set both. Any divergence is a stale cache entry the epoch
    # discipline failed to kill.
    def settle():
        while plugin.run_pending_once():
            pass

    rng = random.Random(29)
    wrong = 0
    compared = 0
    oracle_probes = degenerate + diverse[:200]
    for round_i in range(30):
        key = churn_keys[rng.randrange(len(churn_keys))]
        ns, nm = key.split("/")
        thr = store.get_throttle(ns, nm)
        mc = rng.randrange(1, 200) * 100
        store.update_throttle_spec(
            _replace(
                thr,
                spec=_replace(
                    thr.spec,
                    threshold=ResourceAmount.of(requests={"cpu": f"{mc}m"}),
                ),
            )
        )
        settle()
        for p in rng.sample(oracle_probes, 24):
            got = plugin.pre_filter(p)
            want = plugin._pre_filter_uncached(p, emit_events=False)
            compared += 1
            if (got.code, tuple(sorted(got.reasons))) != (
                    want.code, tuple(sorted(want.reasons))):
                wrong += 1
                log(f"[decisions:oracle] WRONG verdict for {p.name}: "
                    f"cached {got.code}/{got.reasons} vs "
                    f"oracle {want.code}/{want.reasons}")
    hits, misses, entries, invalidations, insertions = cache.stats()
    out["oracle"] = {"compared": compared, "wrong": wrong, "rounds": 30}
    out["cache_stats"] = {
        "hits": hits, "misses": misses, "entries": entries,
        "invalidations": invalidations, "insertions": insertions,
    }
    log(f"[decisions:oracle] {compared} comparisons under churn, {wrong} wrong")

    speedup = out["mixes"]["degenerate"]["speedup_warm_vs_uncached_1t"]
    out["gate_10x"] = {
        "speedup_warm_vs_uncached_1t": speedup,
        "meets_10x": bool(speedup >= 10.0),
        "wrong_verdicts": wrong,
    }
    stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    path = f"BENCH_PR17_{platform.upper()}_{stamp}.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    log(f"decision sweep written to {path}")
    emit(out)
    if not out["gate_10x"]["meets_10x"] or wrong:
        log(
            f"decision sweep FAILED its gate: speedup {speedup}x "
            f"(need ≥10x), wrong verdicts {wrong} (need 0)"
        )
        raise SystemExit(1)


def run_route_sweep() -> None:
    """``python bench.py --route-sweep``: the PR 20 acceptance artifact —
    front-side event transport cost per event, the pickle-socketpair
    baseline against the zero-copy shm ring (sharding/shmring.py), under
    a BURST arrival shape (batch=256 — the informer-resync case) and a
    SUSTAINED shape (batch=8 — steady churn trickle). Both lanes are
    measured sender-side with a drainer on the other end, which is what
    the ≤20 µs/event routing target bounds: the worker's decode runs on
    the worker's core, not the front's. A third rung drives the REAL
    2-shard multiprocess fleet end-to-end (seed + churn + drain) with
    the ring on and off for a wall-clock sanity delta. Gates (burst
    rung): shm ≤20 µs/event AND ≥3.5x the pickle baseline — enforced on
    hosts with at least KT_SCENARIO_LATENCY_CORE_FLOOR cores (default
    2, scenarios/slo.py), advisory (reported, exit 0) below it, exactly
    like the scenario latency SLOs."""
    import socket
    import threading as _threading

    from kube_throttler_tpu.api.pod import make_pod
    from kube_throttler_tpu.sharding import ipc as _ipc
    from kube_throttler_tpu.sharding.shmring import (
        ShmEventLane,
        ShmRingReader,
        ShmRingWriter,
        shm_available,
    )

    platform = "cpu"
    try:
        platform = jax.devices()[0].platform
    except Exception:
        pass
    if not shm_available():
        log("route sweep FAILED: multiprocessing.shared_memory unavailable")
        raise SystemExit(1)

    # realistic routed-op mix: mostly Pod upserts (the hot class — every
    # pod create/update/phase flip fans out), a delete tail, distinct
    # label/request shapes across a few hundred pods so the shm string
    # table sees steady-state interning, not a degenerate single shape.
    # The pods go THROUGH a real Store first: the front routes arena-
    # absorbed objects (canonical shared label dicts + stamped request
    # shape ids), and both lanes get the same objects — pickle just
    # cannot exploit the stamps
    from kube_throttler_tpu.api.pod import Namespace
    from kube_throttler_tpu.engine.store import Store

    store = Store()
    store.create_namespace(Namespace("default"))
    pods = []
    for i in range(512):
        p = make_pod(
            f"bp{i}",
            labels={"grp": f"g{i % 32}", "tier": f"t{i % 5}"},
            requests={"cpu": f"{(i % 15 + 1) * 100}m", "memory": f"{(i % 7 + 1)}Gi"},
            node_name=f"node-{i % 16}",
            phase="Running",
        )
        store.create_pod(p)
        pods.append(p)
    ops = []
    for i, p in enumerate(pods):
        ops.append(("upsert", "Pod", p))
        if i % 8 == 7:
            ops.append(("delete", "Pod", f"default/bp{i - 7}"))

    def bench_pickle(batch: int, duration: float) -> float:
        """µs/event for send_frame(encode_evt_batch(...)) over a drained
        socketpair — exactly the ShardClient._send_loop fallback path."""
        a, b = socket.socketpair()
        stop = _threading.Event()

        def drain() -> None:
            try:
                while b.recv(1 << 16):
                    pass
            except OSError:
                pass

        th = _threading.Thread(target=drain, daemon=True)
        th.start()
        lock = _threading.Lock()
        sent, j, n = 0, 0, len(ops)
        t0 = time.perf_counter()
        try:
            while time.perf_counter() - t0 < duration:
                chunk = [ops[(j + k) % n] for k in range(batch)]
                j += batch
                _ipc.send_frame(
                    a, lock, "evt", 0, _ipc.encode_evt_batch(chunk), epoch=1
                )
                sent += batch
        finally:
            elapsed = time.perf_counter() - t0
            a.close()
            b.close()
            stop.set()
            th.join(timeout=5)
        return elapsed / sent * 1e6

    shm_seq = [0]

    def bench_shm(batch: int, duration: float) -> float:
        """µs/event for ShmEventLane.send (FrameEncoder + ring commit +
        doorbell) with an advancing reader on the other end."""
        shm_seq[0] += 1
        writer = ShmRingWriter(
            f"kt_bench_{os.getpid()}_{shm_seq[0]}",
            slots=4096,
            arena_bytes=32 << 20,
        )
        reader = ShmRingReader(writer.name)
        lane = ShmEventLane(writer)
        stop = _threading.Event()

        def drain() -> None:
            while not stop.is_set():
                view = reader.peek(timeout=0.05)
                if view is not None:
                    del view
                    reader.advance()

        th = _threading.Thread(target=drain, daemon=True)
        th.start()
        sent, j, n = 0, 0, len(ops)
        t0 = time.perf_counter()
        try:
            while time.perf_counter() - t0 < duration:
                chunk = [ops[(j + k) % n] for k in range(batch)]
                j += batch
                if not lane.send(chunk, epoch=1):
                    raise RuntimeError("bench ring died")
                sent += batch
        finally:
            elapsed = time.perf_counter() - t0
            stop.set()
            th.join(timeout=5)
            reader.close()
            lane.close()
        return elapsed / sent * 1e6

    def median3(fn, *a):
        return sorted(fn(*a) for _ in range(3))[1]

    duration = 2.0 if "--full" in sys.argv else 1.0
    out: dict = {"bench": "route_sweep", "platform": platform, "shapes": {}}
    for shape, batch in (("burst", 256), ("sustained", 8)):
        pk = median3(bench_pickle, batch, duration)
        sm = median3(bench_shm, batch, duration)
        out["shapes"][shape] = {
            "batch": batch,
            "pickle_us_per_event": round(pk, 3),
            "shm_us_per_event": round(sm, 3),
            "speedup": round(pk / sm, 2),
        }
        log(f"[route:{shape}] batch={batch} pickle={pk:.1f}us "
            f"shm={sm:.1f}us speedup={pk / sm:.2f}x")

    # end-to-end sanity rung: the real 2-shard fleet, ring on vs off.
    # Wall-clock here is dominated by worker recompute, not transport —
    # recorded for the artifact, never gated.
    def fleet_run(shm_on: bool) -> float:
        from kube_throttler_tpu.sharding.front import AdmissionFront
        from kube_throttler_tpu.sharding.supervisor import ShardSupervisor

        import tools.harness as H
        from kube_throttler_tpu.api.pod import Namespace

        env = {
            **os.environ,
            "KT_SHARD_QUIET": "1",
            "KT_LOCK_ASSERT": "0",
            "KT_SHM_RING": "1" if shm_on else "0",
        }
        os.environ["KT_SHM_RING"] = env["KT_SHM_RING"]
        front = AdmissionFront(2)
        sup = ShardSupervisor(front, use_device=False, env=env)
        try:
            sup.start(ready_timeout=180.0)
            t0 = time.perf_counter()
            front.store.create_namespace(Namespace("default"))
            for i in range(8):
                front.store.create_throttle(H.make_throttle(i))
            for i in range(400):
                front.store.create_pod(
                    make_pod(
                        f"fp{i}",
                        labels={"grp": f"g{i % 8}"},
                        requests={"cpu": f"{(i % 9 + 1) * 100}m"},
                        node_name="node-1",
                        phase="Running",
                    )
                )
            if not front.drain(timeout=120.0):
                raise RuntimeError("fleet drain timed out")
            return time.perf_counter() - t0
        finally:
            sup.stop()
            front.stop()
            os.environ.pop("KT_SHM_RING", None)

    try:
        out["fleet_end_to_end"] = {
            "pods": 400,
            "shm_seconds": round(fleet_run(True), 3),
            "pickle_seconds": round(fleet_run(False), 3),
        }
        log(f"[route:fleet] {out['fleet_end_to_end']}")
    except Exception as e:  # noqa: BLE001 — sanity rung, never gates
        out["fleet_end_to_end"] = {"error": f"{e.__class__.__name__}: {e}"}
        log(f"[route:fleet] skipped: {out['fleet_end_to_end']['error']}")

    from kube_throttler_tpu.scenarios.slo import _latency_gates_enforced

    burst = out["shapes"]["burst"]
    meets = burst["shm_us_per_event"] <= 20.0 and burst["speedup"] >= 3.5
    enforced = _latency_gates_enforced()
    out["gate"] = {
        "shm_us_per_event": burst["shm_us_per_event"],
        "bound_us": 20.0,
        "speedup": burst["speedup"],
        "bound_speedup": 3.5,
        "meets": bool(meets),
        "enforced": bool(enforced),
    }
    stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    path = f"BENCH_PR20_{platform.upper()}_{stamp}.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    log(f"route sweep written to {path}")
    emit(out)
    if not meets:
        msg = (
            f"route sweep gate: shm {burst['shm_us_per_event']}us/event "
            f"(need <=20), speedup {burst['speedup']}x (need >=3.5)"
        )
        if enforced:
            log(f"route sweep FAILED its gate: {msg}")
            raise SystemExit(1)
        log(f"route sweep ADVISORY (below core floor): {msg}")


def bench_remote_pipeline(label, P=10000, T=1000, groups=500, duration=6.0, pace_hz=1000.0):
    """cfg5 through the WIRE: pod churn lands on a (mock) apiserver, flows
    over real HTTP list+watch into the reflector-fed local cache, the
    controllers reconcile, and the status PUTs land back on the remote
    status subresource — the full remote-mode daemon loop
    (plugin.go:71-130 + throttle_controller.go:170 UpdateStatus). Lag is
    measured remote-commit to remote-commit: from the pod event at the
    apiserver to the throttle status write arriving back there. Rate
    limiting is disabled (qps=None) so this measures pipeline capacity,
    not the token bucket (the reference's client-go default of 50 QPS
    would bind ~50 writes/s)."""
    import random
    from dataclasses import replace as _replace

    from kube_throttler_tpu.api.pod import Namespace, make_pod
    from kube_throttler_tpu.client.mockserver import MockApiServer
    from kube_throttler_tpu.client.transport import RemoteSession, RestConfig
    from kube_throttler_tpu.engine.store import Store
    from kube_throttler_tpu.plugin import KubeThrottler, decode_plugin_args

    rng = random.Random(0)
    server = MockApiServer(bookmark_interval=1.0)
    remote = server.store
    remote.create_namespace(Namespace("default"))
    flip_mc = _flip_band_mc(P, groups)
    for i in range(T):
        remote.create_throttle(_served_throttle(i, groups, flip_band_mc=flip_mc))
    for i in range(P):
        pod = make_pod(
            f"p{i}",
            labels={"grp": f"g{rng.randrange(groups)}"},
            requests={"cpu": f"{rng.randrange(1, 8) * 100}m"},
        )
        pod = _replace(pod, spec=_replace(pod.spec, node_name="node-1"))
        pod.status.phase = "Running"
        remote.create_pod(pod)
    server.start()

    local = Store()
    from kube_throttler_tpu.metrics import Registry as _Registry

    session_registry = _Registry()
    session = RemoteSession(
        RestConfig(server=server.url), local, metrics_registry=session_registry, qps=None
    )
    plugin = None
    wire_rtt_ms = 0.0
    commit_counts: dict = {}
    # lag is remote-commit→remote-commit: the tracker watches the REMOTE
    # store's Throttle MODIFIEDs (the arriving status PUTs)
    pending, flip_pending, pend_lock, lags, flip_lags, _flip_walls, on_remote_status = (
        _lag_tracker()
    )
    group_keys = _group_keys_of(remote)
    flip_watch, run_sums = _flip_watch_of(remote)
    try:
        session.start(sync_timeout=30)
        plugin = KubeThrottler(
            decode_plugin_args(
                {"name": "kube-throttler", "targetSchedulerName": "my-scheduler"}
            ),
            local,
            use_device=True,
            start_workers=True,
            # the async committer (what the daemon wires in production):
            # batch submit + newest-wins coalescing + N concurrent PUT
            # workers over keep-alive connections
            status_writer=session.status_committer,
        )
        # initial statuses converge before the measured window (every group
        # has pods, so every throttle ends with a materialized used count)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if all(
                t.status.used.resource_counts is not None
                for t in remote.list_throttles()
            ):
                break
            time.sleep(0.25)
        # pre-serving GC posture, same as the daemon (cli.py /
        # build_served_stack): freeze the converged heap so full-GC pauses
        # don't land in the measured window
        from kube_throttler_tpu.utils.gchygiene import freeze_startup_heap

        freeze_startup_heap()
        # raw wire capacity probe: one warm status PUT round trip, repeated
        # — the per-request floor every commit pays (http.client +
        # http.server protocol overhead shares the same core as the whole
        # pipeline on this host, so it bounds achievable PUTs/s)
        probe_thrs = remote.list_throttles()
        if probe_thrs:
            done = 0
            t0 = time.perf_counter()
            for _ in range(30):
                try:
                    session.status_writer.update_throttle_status(probe_thrs[0])
                    done += 1
                except Exception:
                    # 409 against a committer PUT still in flight for this
                    # key is possible; a lost probe must not lose the bench
                    pass
            if done:
                wire_rtt_ms = (time.perf_counter() - t0) / done * 1e3
        remote.add_event_handler("Throttle", on_remote_status, replay=False)
        n_events, t_fired, n_crossings = _drive_pod_churn(
            remote, group_keys, pending, pend_lock, rng, duration, pace_hz,
            flip_state=(flip_watch, run_sums, flip_pending),
        )
        # drain tail: give in-flight writes a bounded window to land
        session.status_committer.flush(timeout=min(3.0, duration / 2))
        time.sleep(0.3)
        commit_counter = session_registry.counter_vec(
            "kube_throttler_remote_status_commit_total", "", ["kind", "result"]
        )
        for (kind, result), v in commit_counter.collect().items():
            commit_counts[f"{kind}:{result}"] = int(v)
    finally:
        if plugin is not None:
            plugin.stop()
        session.stop()
        server.stop()

    # [0.0] sentinel when nothing landed (status_writes=0 disambiguates):
    # NaN would propagate into the one-line report and break strict JSON
    lag_arr = np.asarray(lags) if lags else np.asarray([0.0])
    flip_arr = np.asarray(flip_lags) if flip_lags else np.asarray([0.0])
    result = {
        "events_per_sec": n_events / t_fired,  # rate during the fire window
        "lag_p50_ms": float(np.percentile(lag_arr, 50)) * 1e3,
        "lag_p99_ms": float(np.percentile(lag_arr, 99)) * 1e3,
        "status_writes": len(lags),
        "flip_lag_p50_ms": float(np.percentile(flip_arr, 50)) * 1e3,
        "flip_lag_p99_ms": float(np.percentile(flip_arr, 99)) * 1e3,
        "flip_samples": len(flip_lags),
        "flip_crossings": n_crossings,
        "wire_put_rtt_ms": round(wire_rtt_ms, 3),
        "commit_counts": commit_counts,
    }
    log(
        f"[{label}] cfg5 REMOTE WIRE ({P} pods x {T} throttles, paced "
        f"{pace_hz:,.0f}/s): {n_events} events -> {result['events_per_sec']:,.0f}/s; "
        f"remote-commit lag p50 {result['lag_p50_ms']:.1f}ms / p99 "
        f"{result['lag_p99_ms']:.1f}ms over {len(lags)} status PUTs; FLIP "
        f"lag p50 {result['flip_lag_p50_ms']:.1f}ms / p99 "
        f"{result['flip_lag_p99_ms']:.1f}ms over {len(flip_lags)} flips "
        f"from {n_crossings} crossings (two-lane committer); raw wire PUT "
        f"RTT {wire_rtt_ms:.2f}ms (the "
        f"per-request protocol floor this host's single core pays "
        f"in-pipeline); committer outcomes {commit_counts} (watch -> "
        "reflector -> reconcile -> async committer -> HTTP status "
        "subresource)"
    )
    return result


def bench_example_scenario(label):
    """BASELINE config 1: the examples/throttle.yaml t1 + walkthrough pods
    through the FULL plugin stack on the host-oracle path (the 'CPU
    PreFilter reference scenario' — what the reference's Go hot path does
    per attempt, here per-decision host latency)."""
    import yaml

    from kube_throttler_tpu.api.pod import Namespace
    from kube_throttler_tpu.api.serialization import object_from_dict
    from kube_throttler_tpu.engine.store import Store
    from kube_throttler_tpu.plugin import KubeThrottler, decode_plugin_args

    store = Store()
    store.create_namespace(Namespace("default"))
    plugin = KubeThrottler(
        decode_plugin_args({"name": "kube-throttler", "targetSchedulerName": "my-scheduler"}),
        store,
        use_device=False,
    )
    with open("examples/throttle.yaml") as f:
        store.create_throttle(object_from_dict(yaml.safe_load(f)))
    pods = []
    with open("examples/pods.yaml") as f:
        for doc in yaml.safe_load_all(f):
            pod = object_from_dict(doc)
            store.create_pod(pod)
            pods.append(pod)
    plugin.run_pending_once()

    i = [0]

    def one():
        plugin.pre_filter(pods[i[0] % len(pods)])
        i[0] += 1

    stats = host_percentiles(one, 2000)
    log(
        f"[{label}] example t1 + pods1-3, host-oracle PreFilter: "
        f"{stats['mean']*1e6:.1f}us mean / {stats['p99']*1e6:.1f}us p99 per decision "
        f"({1/stats['mean']:,.0f} decisions/sec)"
    )
    plugin.stop()
    return stats


def bench_selector_index(label, T=10_000, n_pods=200):
    """Host-side selector-mask maintenance (SURVEY hard part 3): per-pod-event
    row recompute against T compiled selector columns, native C++ vs Python."""
    import random

    from kube_throttler_tpu.api.pod import Namespace, make_pod
    from kube_throttler_tpu.api.types import (
        LabelSelector,
        ResourceAmount,
        Throttle,
        ThrottleSelector,
        ThrottleSelectorTerm,
        ThrottleSpec,
    )
    from kube_throttler_tpu.engine.index import SelectorIndex
    from kube_throttler_tpu.native import available

    rng = random.Random(0)
    throttles = [
        Throttle(
            name=f"t{i}",
            spec=ThrottleSpec(
                throttler_name="x",
                threshold=ResourceAmount.of(pod=1),
                selector=ThrottleSelector(
                    selector_terms=(
                        ThrottleSelectorTerm(
                            LabelSelector(match_labels={"grp": f"g{i % 500}"})
                        ),
                    )
                ),
            ),
        )
        for i in range(T)
    ]
    pods = [
        make_pod(f"p{i}", labels={"grp": f"g{rng.randrange(500)}"}) for i in range(n_pods)
    ]

    for use_native, name in ((True, "native C++"), (False, "python")):
        if use_native and not available():
            log(f"[{label}] native tier unavailable (no toolchain or KT_TPU_NO_NATIVE=1); python tier only")
            continue
        idx = SelectorIndex("throttle", pod_capacity=n_pods, throttle_capacity=T, use_native=use_native)
        idx.upsert_namespace(Namespace("default"))
        for thr in throttles:
            idx.upsert_throttle(thr)
        t0 = time.perf_counter()
        for pod in pods:
            idx.upsert_pod(pod)  # one mask-row recompute per pod event
        dt = (time.perf_counter() - t0) / n_pods
        log(f"[{label}] pod-event row recompute vs T={T} ({name}): {dt*1e6:.1f}us/event")


def _gc_pause_tracker():
    """Attach a gc callback recording collection pause durations; returns
    the mutable stats dict (max/count) and the callback (for removal)."""
    import gc

    state = {"max_s": 0.0, "count": 0, "_t0": None}

    def cb(phase, info):
        if phase == "start":
            state["_t0"] = time.perf_counter()
        elif state["_t0"] is not None:
            pause = time.perf_counter() - state["_t0"]
            state["_t0"] = None
            state["count"] += 1
            if pause > state["max_s"]:
                state["max_s"] = pause

    gc.callbacks.append(cb)
    return state, cb


def _heap_objects() -> int:
    """Tracked containers + the permanent generation (frozen) — the
    comparable total across the freeze/no-freeze postures."""
    import gc

    return len(gc.get_objects()) + gc.get_freeze_count()


def _maxrss_mb() -> float:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _mega_status_write_rate(store, rounds=3) -> dict:
    """Batched status-write throughput through the LIVE stack: rewrite
    every throttle's status via the store's batched UpdateStatus (the
    controllers' commit path — device-mirror echo, informer mirror, and
    controller handlers all subscribed). Median of ``rounds``."""
    import dataclasses

    thrs = store.list_throttles()
    rates = []
    for _ in range(rounds):
        batch = [t.with_status(dataclasses.replace(t.status)) for t in store.list_throttles()]
        t0 = time.perf_counter()
        store.update_throttle_statuses(batch)
        dt = time.perf_counter() - t0
        rates.append(len(batch) / dt)
    return {
        "throttles": len(thrs),
        "writes_per_sec_median": float(np.median(rates)),
        "writes_per_sec_runs": [round(r) for r in rates],
    }


def _mega_churn_window(store, plugin, P, groups, seconds=20.0, batch=256) -> dict:
    """Paced pod-churn window: request-size updates (the cfg5 shape)
    through ``apply_events`` batches, with GC pauses tracked. Returns
    applied events/s + max GC pause inside the window."""
    import random

    from kube_throttler_tpu.api.pod import make_pod
    from dataclasses import replace as _replace

    # replay build_served_stack's label assignment (same seed + draw
    # order) so churn is the cfg5 REQUEST-RESIZE shape — a wrong group
    # would turn every event into a label move (reservation migration +
    # index row re-match), a different and far heavier workload
    grp_rng = random.Random(0)
    grp_of = []
    for _ in range(P):
        grp_of.append(grp_rng.randrange(groups))
        grp_rng.randrange(1, 8)
    rng = random.Random(7)
    gc_stats, cb = _gc_pause_tracker()
    applied = 0
    t0 = time.perf_counter()
    try:
        while time.perf_counter() - t0 < seconds:
            ops = []
            for _ in range(batch):
                i = rng.randrange(P)
                pod = make_pod(
                    f"p{i}",
                    labels={"grp": f"g{grp_of[i]}"},
                    requests={"cpu": f"{rng.randrange(1, 8) * 100}m"},
                )
                pod = _replace(pod, spec=_replace(pod.spec, node_name="node-1"))
                pod.status.phase = "Running"
                ops.append(("upsert", "Pod", pod))
            store.apply_events(ops)
            applied += len(ops)
    finally:
        import gc

        gc.callbacks.remove(cb)
    dt = time.perf_counter() - t0
    return {
        "events_applied": applied,
        "events_per_sec": round(applied / dt, 1),
        "window_s": round(dt, 1),
        "gc_collections": gc_stats["count"],
        "gc_max_pause_ms": round(gc_stats["max_s"] * 1e3, 2),
    }


def _mega_equivalence_sweep(n_pods=1500, n_thr=120, seed=11) -> dict:
    """Seeded columnar ≡ frozen-dict ≡ batched ≡ sequential sweep: one op
    stream (creates / label moves / request updates / deletes / status
    recomputes) applied to (a) a columnar store batched, (b) a columnar
    store sequentially, (c) the frozen-dict reference store — asserting
    identical store dumps, identical published st_* planes, and identical
    pre_filter verdicts. The bench-level twin of
    tests/test_columnar_store.py's sweep, run at a larger shape."""
    import random

    from dataclasses import replace as _replace

    from kube_throttler_tpu.api.pod import Namespace, make_pod
    from kube_throttler_tpu.engine.store import Store
    from tools.harness import build_plugin, dump_store, make_throttle, recompute_status, verdicts

    def op_stream():
        rng = random.Random(seed)
        ops = []
        for i in range(n_thr):
            ops.append(("create", "Throttle", _replace(make_throttle(i % 40), name=f"t{i}")))
        for i in range(n_pods):
            pod = make_pod(
                f"p{i}", labels={"grp": f"g{rng.randrange(40)}"},
                requests={"cpu": f"{rng.randrange(1, 8) * 100}m"},
            )
            pod = _replace(pod, spec=_replace(pod.spec, node_name="node-1"))
            pod.status.phase = "Running"
            ops.append(("create", "Pod", pod))
        for _ in range(n_pods // 2):
            i = rng.randrange(n_pods)
            verb = rng.choice(["move", "resize", "delete", "revive"])
            if verb == "delete":
                ops.append(("delete", "Pod", f"default/p{i}"))
            else:
                pod = make_pod(
                    f"p{i}", labels={"grp": f"g{rng.randrange(40)}"},
                    requests={"cpu": f"{rng.randrange(1, 8) * 100}m"},
                )
                pod = _replace(pod, spec=_replace(pod.spec, node_name="node-1"))
                pod.status.phase = "Running"
                ops.append(("upsert", "Pod", pod))
        return ops

    # ONE op stream shared by all three runs: uids come from a process
    # counter, so regenerating per run would make the dumps differ by
    # uid alone (objects are immutable-by-convention — sharing them
    # across stores is safe, and the columnar absorb only canonicalizes
    # label/annotation dict identity, never content)
    shared_ops = op_stream()
    shared_ns = Namespace("default")  # one uid across all three runs

    def run(columnar: bool, batched: bool):
        store = Store(columnar=columnar)
        plugin = build_plugin(store)
        store.create_namespace(shared_ns)
        ops = shared_ops
        if batched:
            for s in range(0, len(ops), 64):
                store.apply_events(ops[s : s + 64])
        else:
            for op in ops:
                store.apply_events([op])
        # deterministic status writes (no wall-clock in the payload)
        for thr in store.list_throttles():
            store.update_throttle_status(recompute_status(store, thr))
        return (
            dump_store(store),
            plugin.device_manager.published_flags(),
            verdicts(plugin, store),
        )

    col_b = run(True, batched=True)
    col_s = run(True, batched=False)
    ref = run(False, batched=False)
    return {
        "pods": n_pods,
        "throttles": n_thr,
        "batched_eq_sequential": col_b == col_s,
        "columnar_eq_reference": col_s == ref,
        "dumps_equal": col_b[0] == col_s[0] == ref[0],
        "planes_equal": col_b[1] == col_s[1] == ref[1],
        "verdicts_equal": col_b[2] == col_s[2] == ref[2],
    }


def run_mega() -> None:
    """``python bench.py --mega``: the PR 11 acceptance artifact — the
    columnar-arena ladder up to 1M pods × 100k throttles on one host,
    recording RSS high-water, heap object count, and max GC pause
    alongside throughput; plus the 100k×10k status-write rung against the
    PR 2 frozen-dict baseline and the seeded equivalence sweep. Written
    to BENCH_PR11_<platform>_<stamp>.json."""
    import gc

    platform = "cpu"
    try:
        platform = jax.devices()[0].platform
    except Exception:
        pass
    out: dict = {
        "metric": (
            "columnar arena store ladder: 1M pods x 100k throttles on one "
            "host (RSS high-water, heap objects, max GC pause) + "
            "status-write throughput vs the PR 2 frozen-dict baseline"
        ),
        "platform": platform,
        "host_cores": os.cpu_count(),
        "columnar": True,
        "rungs": {},
    }

    log("[mega] seeded equivalence sweep (columnar vs frozen-dict reference)")
    eq = _mega_equivalence_sweep()
    out["equivalence"] = eq
    log(f"[mega] equivalence: {eq}")
    if not (eq["dumps_equal"] and eq["planes_equal"] and eq["verdicts_equal"]):
        log("[mega] EQUIVALENCE FAILED — aborting before the ladder")
        out["value"] = 0.0
        emit(out)
        return

    # PR 2 reference: the measured frozen-dict status-write ceiling
    # (docs/PERFORMANCE.md "What bounds each path")
    PR2_STATUS_WRITES_PER_SEC = 8000.0
    ladder = [
        ("100kx10k", 100_000, 10_000, 500),
        ("1Mx100k", 1_000_000, 100_000, 5000),
    ]
    for name, P, T, groups in ladder:
        log(f"[mega] ==== rung {name}: {P} pods x {T} throttles ====")
        gc_build, cb = _gc_pause_tracker()
        rss_before, heap_before = _maxrss_mb(), _heap_objects()
        t0 = time.perf_counter()
        store, plugin = build_served_stack(P, T, groups=groups, label=f"mega-{name}")
        build_s = time.perf_counter() - t0
        gc.callbacks.remove(cb)
        rung: dict = {
            "pods": P,
            "throttles": T,
            "build_seconds": round(build_s, 1),
            "rss_high_water_mb": round(_maxrss_mb(), 1),
            "rss_delta_mb": round(_maxrss_mb() - rss_before, 1),
            "heap_objects": _heap_objects(),
            "heap_objects_delta": _heap_objects() - heap_before,
            "heap_objects_per_pod": round((_heap_objects() - heap_before) / P, 4),
            "rss_bytes_per_pod": round((_maxrss_mb() - rss_before) * 1024 * 1024 / P),
            "build_gc_max_pause_ms": round(gc_build["max_s"] * 1e3, 2),
            "arena": store.pod_arena.stats() if store.pod_arena else None,
        }
        try:
            sw = _mega_status_write_rate(store)
            rung["status_writes"] = sw
            rung["status_writes_x_pr2"] = round(
                sw["writes_per_sec_median"] / PR2_STATUS_WRITES_PER_SEC, 2
            )
            log(
                f"[mega:{name}] status writes {sw['writes_per_sec_median']:,.0f}/s "
                f"({rung['status_writes_x_pr2']}x the PR2 8k/s baseline)"
            )
            churn = _mega_churn_window(store, plugin, P, groups)
            rung["churn"] = churn
            log(
                f"[mega:{name}] churn {churn['events_per_sec']:,.0f} ev/s, "
                f"max GC pause {churn['gc_max_pause_ms']}ms "
                f"({churn['gc_collections']} collections)"
            )
            r = host_percentiles(
                lambda: plugin.pre_filter(
                    make_probe_pod(groups)
                ),
                300,
                warmup=20,
                max_seconds=30.0,
            )
            rung["prefilter_p50_ms"] = round(r["p50"] * 1e3, 3)
            rung["prefilter_p99_ms"] = round(r["p99"] * 1e3, 3)
            log(
                f"[mega:{name}] pre_filter p50 {rung['prefilter_p50_ms']}ms / "
                f"p99 {rung['prefilter_p99_ms']}ms"
            )
        finally:
            plugin.stop()
            del store, plugin
            gc.collect()
        out["rungs"][name] = rung
        log(
            f"[mega:{name}] RSS {rung['rss_high_water_mb']}MB "
            f"({rung['rss_bytes_per_pod']}B/pod), heap {rung['heap_objects']:,} "
            f"objects ({rung['heap_objects_per_pod']}/pod), build {build_s:.0f}s"
        )

    big = out["rungs"].get("1Mx100k", {})
    out["value"] = float(big.get("churn", {}).get("events_per_sec", 0.0))
    out["unit"] = "events/s sustained at 1M pods x 100k throttles"
    stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    path = f"BENCH_PR11_{platform.upper()}_{stamp}.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    log(f"mega ladder written to {path}")
    emit(out)


def make_probe_pod(groups: int):
    import random

    from kube_throttler_tpu.api.pod import make_pod

    i = random.randrange(groups)
    return make_pod(
        f"probe{i}", labels={"grp": f"g{i}"}, requests={"cpu": "300m"}
    )


def main():
    if "--mega" in sys.argv:
        # PR 11 acceptance artifact: the 1M x 100k columnar-arena ladder
        run_mega()
        return
    if "--ingest-sweep" in sys.argv:
        # PR 5 acceptance artifact: the full-scale batch-size sweep alone
        run_ingest_sweep()
        return
    if "--shard-sweep" in sys.argv:
        # PR 9 acceptance artifact: aggregate ingest/decisions/flip p99
        # across {1,2,4} shared-nothing worker processes
        run_shard_sweep()
        return
    if "--gang" in sys.argv:
        # gang-admission rung: bursty group arrivals + churn SLO check
        run_gang_bench()
        return
    if "--decision-sweep" in sys.argv:
        # PR 17 acceptance artifact: interned-verdict cache vs uncached
        # reference (cold/warm, 1/4 threads, epoch churn, oracle agreement)
        run_decision_sweep()
        return
    if "--route-sweep" in sys.argv:
        # PR 20 acceptance artifact: pickle-socketpair vs zero-copy shm
        # ring event transport (burst + sustained), plus the real-fleet
        # end-to-end sanity rung
        run_route_sweep()
        return
    quick = "--quick" in sys.argv
    rng = np.random.default_rng(0)
    start_watchdog()

    detail: dict = RESULT_STATE["detail"]
    errors: dict = RESULT_STATE["errors"]

    def safe(name, fn, *a, **k):
        """Fence one config: a failure records an error but never kills the run."""
        try:
            return fn(*a, **k)
        except Exception as e:
            log(f"[{name}] FAILED: {e.__class__.__name__}: {str(e)[:300]}")
            log(traceback.format_exc(limit=4))
            errors[name] = f"{e.__class__.__name__}: {str(e)[:200]}"
            return None

    devices = safe("init", jax.devices)
    log(f"devices: {devices}")
    platform = devices[0].platform if devices else "none"
    RESULT_STATE["platform"] = platform
    if devices:
        RESULT_STATE["device"] = {
            "platform": platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }
    if devices and enable_compilation_cache(platform):
        log("persistent XLA compilation cache enabled")

    scale = 10 if quick else 1
    RESULT_STATE["scale"] = scale

    rtt = safe("rtt", dispatch_round_trip) if devices else None
    RESULT_STATE["rtt"] = rtt
    if rtt is not None:
        log(f"dispatch round trip: {rtt*1e3:.3f}ms")
        detail["dispatch_rtt_ms"] = rtt * 1e3

    R = 8

    # config 1: the reference example scenario end-to-end (host path; device-free)
    cfg1 = safe("cfg1", bench_example_scenario, "cfg1:example")
    RESULT_STATE["cfg1"] = cfg1
    if cfg1:
        detail["cfg1_host_prefilter_p99_us"] = round(cfg1["p99"] * 1e6, 1)
    safe("host:index", bench_selector_index, "host:index", T=10_000 // scale)

    # The SERVED path (last section) is the headline; the bare-kernel
    # configs are supporting detail. When the deadline is short, skip
    # straight to the headline instead of spending what's left on kernels
    # and letting the watchdog kill the part that matters. Budgets
    # (measured: quick-CPU kernels ~2min + served ~2min; full-TPU kernels
    # dominated by cfg4 compiles): thresholds must fit inside the default
    # 1800s deadline, or full runs would always skip the kernels.
    served_budget = 240.0 if scale == 10 else 900.0
    kernel_budget = 120.0 if scale == 10 else 420.0
    kernels_ok = time_left() > served_budget + kernel_budget
    if not kernels_ok:
        log(f"time budget low ({time_left():.0f}s left): skipping bare-kernel configs")
        errors["kernels"] = "skipped: low time budget"

    single_stats = None
    if devices and kernels_ok:
        # config 2: 1k pods x 100 throttles, 4 active dims
        safe("cfg2", bench_batched, rng, 1000 // scale, 100, R, "cfg2:1kx100")

        # config 3: 10k x 1k
        safe("cfg3", bench_batched, rng, 10_000 // scale, 1000 // scale, R, "cfg3:10kx1k")

        # config 4: 100k x 10k with overrides (the headline)
        P, T = 100_000 // scale, 10_000 // scale
        safe("cfg4:overrides", bench_overrides, rng, T, 4, R, "cfg4:overrides")
        big = safe("cfg4:batched", bench_batched, rng, P, T, R, "cfg4:100kx10k")
        if platform == "tpu":
            safe("cfg4:pallas", bench_pallas_sweep, rng, P, T, R, "cfg4:100kx10k")
        else:
            log("[cfg4:pallas] skipped: pallas mosaic kernel needs the TPU backend")
        don = safe("cfg4:donation", bench_donation, rng, P, T, "cfg4:donation")
        if don:
            detail.update(don)
        if big is not None:
            state = big[0]
            safe("cfg4:single", bench_single_pod, rng, state, T, R, "cfg4:100kx10k")
            single_stats = safe(
                "cfg4:indexed", bench_single_pod_indexed, rng, state, T, R, "cfg4:100kx10k"
            )
            RESULT_STATE["single_stats"] = single_stats

        # config 5: streaming reconcile (bare device kernels)
        eps_scan = safe("cfg5:scan", bench_streaming, rng, T, R, "cfg5:streaming")
        eps_batch = safe("cfg5:batched", bench_streaming_batched, rng, T, R, "cfg5:streaming")
        if eps_batch:
            detail["cfg5_kernel_events_per_sec"] = round(eps_batch)
        elif eps_scan:
            detail["cfg5_kernel_events_per_sec"] = round(eps_scan)

    # ---- the SERVED paths (VERDICT r2 task 4): the full daemon stack at
    # the cfg4 scale — pre_filter end-to-end through check_pod (headline),
    # and cfg5 as store events through the controllers ----
    served_stats = None
    if devices and time_left() < served_budget:
        log(f"time budget exhausted ({time_left():.0f}s left): skipping served path")
        errors["served"] = "skipped: low time budget"
    elif devices:
        stack = safe(
            "served:setup", build_served_stack, 100_000 // scale, 10_000 // scale
        )
        if stack:
            store_s, plugin_s = stack
            # cfg5_*/served_* numbers are NOT comparable across scales: at
            # the full config every churn event dirties ~40 throttle keys
            # (20 per group per kind) vs 4 at the quick scale
            detail["served_scale"] = [100_000 // scale, 10_000 // scale]
            r = safe("served:prefilter", bench_served_prefilter, plugin_s, "served")
            if r:
                served_stats, rate1, rate4, rate4_co = r
                detail["served_decisions_per_sec_4t_coalesced"] = round(rate4_co)
                RESULT_STATE["served_stats"] = served_stats
                detail["served_p50_ms"] = round(served_stats["p50"] * 1e3, 4)
                detail["served_decisions_per_sec_1t"] = round(rate1)
                detail["served_decisions_per_sec_4t"] = round(rate4)
                detail["served_decisions_per_sec_median"] = round(
                    served_stats["decisions_per_sec_median"]
                )
                detail["served_decisions_cv"] = round(
                    served_stats["decisions_cv"], 4
                )
                detail["served_thread_scaling"] = round(rate4 / max(rate1, 1e-9), 2)
            cx = safe("served:coalesce-x", bench_coalesce_crossover, plugin_s, "served")
            if cx:
                detail["coalesce_emulated_dispatch_ms"] = cx["dispatch_ms"]
                detail["coalesce_direct_4t_per_sec"] = round(cx["direct_per_sec"])
                detail["coalesce_coalesced_4t_per_sec"] = round(
                    cx["coalesced_per_sec"]
                )
                detail["coalesce_crossover_ratio"] = round(cx["ratio"], 2)
            b = safe("served:batch", bench_served_batch, plugin_s, "served")
            if b:
                detail["served_batch_pods_per_sec"] = round(b["pods_per_sec"])
                detail["served_batch_ms"] = round(b["secs"] * 1e3, 2)
            tick = safe("served:tick", bench_served_tick, plugin_s, "served")
            if tick:
                detail["served_tick_ms"] = round(tick * 1e3)
            s = safe(
                "served:streaming",
                bench_served_streaming,
                store_s,
                plugin_s,
                "served",
                # the max-rate tail needs a longer window than the paced
                # run: p99 over a 5s window is ~10 drain cycles and lands
                # anywhere within this 1-CPU host's ~2x scheduling noise;
                # 10s halves the spread
                duration=10.0,
            )
            if s:
                detail["cfg5_served_events_per_sec"] = round(s["events_per_sec"])
                detail["cfg5_maxrate_lag_p99_ms"] = round(s["lag_p99_ms"], 2)
            # lag at a SUSTAINED 2.5k ev/s (VERDICT r3 task 2's "≥2k ev/s"
            # framing): max rate is open-loop saturation where lag is
            # definitionally backlog-bound; this measures the tail with the
            # pipeline loaded but not drowning
            s25 = safe(
                "served:streaming-2500",
                bench_served_streaming,
                store_s,
                plugin_s,
                "served",
                pace_hz=2500.0,
                duration=10.0,
            )
            if s25:
                detail["cfg5_2500hz_events_per_sec"] = round(s25["events_per_sec"])
                detail["cfg5_2500hz_lag_p99_ms"] = round(s25["lag_p99_ms"], 2)
            # the REMOTE wire loop (watch → reflector → reconcile → HTTP
            # status PUT), small fixed scale — wire overhead dominates and
            # the number answers "does remote mode keep up", not "how big"
            rw = safe("served:remote-wire", bench_remote_pipeline, "served")
            if rw:
                detail["cfg5_remote_events_per_sec"] = round(rw["events_per_sec"])
                detail["cfg5_remote_lag_p50_ms"] = round(rw["lag_p50_ms"], 2)
                detail["cfg5_remote_lag_p99_ms"] = round(rw["lag_p99_ms"], 2)
                detail["cfg5_remote_status_puts"] = rw["status_writes"]
                detail["cfg5_remote_flip_lag_p50_ms"] = round(rw["flip_lag_p50_ms"], 2)
                detail["cfg5_remote_flip_lag_p99_ms"] = round(rw["flip_lag_p99_ms"], 2)
                detail["cfg5_remote_flip_samples"] = rw["flip_samples"]
                detail["cfg5_remote_flip_crossings"] = rw["flip_crossings"]
                detail["cfg5_remote_wire_put_rtt_ms"] = rw["wire_put_rtt_ms"]
            # steady-state status-write lag at the BASELINE 1k/s target load
            s2 = safe(
                "served:streaming-paced",
                bench_served_streaming,
                store_s,
                plugin_s,
                "served",
                pace_hz=1000.0,
            )
            if s2:
                detail["cfg5_paced_events_per_sec"] = round(s2["events_per_sec"])
                detail["cfg5_paced_fired_per_sec"] = round(s2["fired_events_per_sec"])
                detail["cfg5_status_lag_p50_ms"] = round(s2["lag_p50_ms"], 2)
                detail["cfg5_status_lag_p99_ms"] = round(s2["lag_p99_ms"], 2)
                detail["cfg5_flip_lag_p50_ms"] = round(s2["flip_lag_p50_ms"], 2)
                detail["cfg5_flip_lag_p99_ms"] = round(s2["flip_lag_p99_ms"], 2)
                detail["cfg5_flip_samples"] = s2["flip_samples"]
                detail["cfg5_flip_crossings"] = s2["flip_crossings"]
                detail["cfg5_lag_mode"] = "paced-1k"
            elif s:  # paced window failed: keep the max-rate lag measurement
                detail["cfg5_status_lag_p50_ms"] = round(s["lag_p50_ms"], 2)
                detail["cfg5_status_lag_p99_ms"] = round(s["lag_p99_ms"], 2)
                detail["cfg5_lag_mode"] = "max-rate"
            safe("served:stop", plugin_s.stop)

        if scale != 1 and time_left() > 240.0:
            # FULL-SCALE entries even on the quick path (VERDICT r4
            # task 2): the 100k×10k daemon is viable on one CPU core since
            # the host-side sparse rebase (setup ~55s, was ~363s) — run a
            # bounded full-scale setup + batch triage + paced cfg5 window
            # and label the entries explicitly. Each number is honest about
            # its window; nothing here overwrites the quick-scale fields.
            def fullscale():
                t0 = time.perf_counter()
                store_f, plugin_f = build_served_stack(
                    100_000, 10_000, label="served-full"
                )
                detail["fullscale_setup_s"] = round(time.perf_counter() - t0, 1)
                try:
                    # the BASELINE north-star metric AT ITS OWN SCALE:
                    # per-decision PreFilter percentiles against the live
                    # 100k×10k daemon state (the gather path is O(K·R), so
                    # this also demonstrates decision cost ~independent of
                    # cluster size). Becomes the headline when present.
                    # n sized for headline stability: 3 interleaved bands of
                    # 800 calls ≈ 0.9s each at full scale — short bands sat
                    # inside single scheduler slices and read CV ~0.26
                    fs_stats, fs_r1, fs_r4, fs_r4co = bench_served_prefilter(
                        plugin_f, "served-full", n=2400
                    )
                    detail["fullscale_p50_ms"] = round(fs_stats["p50"] * 1e3, 4)
                    detail["fullscale_p99_ms"] = round(fs_stats["p99"] * 1e3, 4)
                    detail["fullscale_decisions_per_sec"] = round(
                        fs_stats["decisions_per_sec_median"]
                    )
                    detail["fullscale_decisions_cv"] = round(
                        fs_stats["decisions_cv"], 4
                    )
                    RESULT_STATE["served_stats_full"] = fs_stats
                    b = bench_served_batch(plugin_f, "served-full", iters=3)
                    detail["fullscale_batch_pods_per_sec"] = round(
                        b["pods_per_sec"]
                    )
                    try:
                        tick_f = bench_served_tick(plugin_f, "served-full")
                        detail["fullscale_tick_ms"] = round(tick_f * 1e3)
                    except Exception as e:  # noqa: BLE001 — isolate like
                        # safe('served:tick'): a tick failure must not drop
                        # the downstream full-scale cfg5 measurements
                        errors["served-full:tick"] = f"{e.__class__.__name__}: {e}"
                    plugin_f.start()
                    # capacity window FIRST (max rate, longer window so the
                    # fixed drain tail doesn't dilute the sustained rate):
                    # the ≥1k events/s criterion reads this one
                    sm = bench_served_streaming(
                        store_f, plugin_f, "served-full", duration=12.0,
                    )
                    detail["fullscale_cfg5_maxrate_events_per_sec"] = round(
                        sm["events_per_sec"]
                    )
                    detail["fullscale_cfg5_maxrate_fired_per_sec"] = round(
                        sm["fired_events_per_sec"]
                    )
                    detail["fullscale_cfg5_maxrate_lag_p99_ms"] = round(
                        sm["lag_p99_ms"], 1
                    )
                    # then the steady-state window at the nominal 1k/s load
                    # — the lag and flip-lag numbers come from here
                    sf = bench_served_streaming(
                        store_f, plugin_f, "served-full",
                        duration=8.0, pace_hz=1000.0,
                    )
                    detail["fullscale_cfg5_events_per_sec"] = round(
                        sf["events_per_sec"]
                    )
                    detail["fullscale_cfg5_fired_per_sec"] = round(
                        sf["fired_events_per_sec"]
                    )
                    detail["fullscale_cfg5_lag_p50_ms"] = round(
                        sf["lag_p50_ms"], 1
                    )
                    detail["fullscale_cfg5_lag_p99_ms"] = round(
                        sf["lag_p99_ms"], 1
                    )
                    detail["fullscale_cfg5_flip_lag_p50_ms"] = round(
                        sf["flip_lag_p50_ms"], 1
                    )
                    detail["fullscale_cfg5_flip_lag_p99_ms"] = round(
                        sf["flip_lag_p99_ms"], 1
                    )
                    detail["fullscale_cfg5_flip_samples"] = sf["flip_samples"]
                    detail["fullscale_cfg5_flip_crossings"] = sf["flip_crossings"]
                    detail["fullscale_scale"] = [100_000, 10_000]
                    if time_left() > 120.0:
                        # micro-batched ingest rungs (PR 5): burst-drain
                        # capacity + the paced flip-SLO window (the full
                        # 1/adaptive/fixed sweep lives in --ingest-sweep)
                        si = bench_ingest_burst(
                            store_f, plugin_f, "served-full:ingest", n=30_000
                        )
                        detail["fullscale_ingest_capacity_events_per_sec"] = si[
                            "events_per_sec_sustained"
                        ]
                        ss = bench_served_streaming(
                            store_f, plugin_f, "served-full:ingest-slo",
                            duration=10.0, pace_hz=3200.0,
                            ingest_batch="adaptive",
                        )
                        detail["fullscale_ingest_slo_events_per_sec"] = round(
                            ss["events_per_sec"]
                        )
                        detail["fullscale_ingest_slo_flip_p99_ms"] = round(
                            ss["flip_lag_p99_ms"], 1
                        )
                finally:
                    try:
                        plugin_f.stop()
                    except Exception:
                        pass

            safe("served:fullscale", fullscale)

    emit(build_result())


def build_result() -> dict:
    """Assemble the one JSON line from whatever RESULT_STATE holds so far.

    Called by main() on the normal path and by the watchdog thread on the
    deadline path — every input is read with a safe default so a partial
    run still produces an honest (partial) record.
    """
    def _snap(d: dict) -> dict:
        # the watchdog thread snapshots while main may be inserting; a dict
        # resize mid-copy raises RuntimeError — retry rather than lose the
        # collected measurements to the bare fallback
        for _ in range(8):
            try:
                return dict(d)
            except RuntimeError:
                time.sleep(0.01)
        return {}

    detail = _snap(RESULT_STATE["detail"])
    errors = _snap(RESULT_STATE["errors"])
    served_stats = RESULT_STATE.get("served_stats")
    single_stats = RESULT_STATE.get("single_stats")
    cfg1 = RESULT_STATE.get("cfg1")
    platform = RESULT_STATE.get("platform", "none")
    scale = RESULT_STATE.get("scale", 10)

    target_ms = 1.0  # BASELINE north star: <1ms p99 on one v5e-1
    # when the full-scale (100k×10k) per-decision measurement ran, IT is
    # the headline — the north-star metric at the north-star scale; the
    # quick-scale percentiles stay in detail (served_p99_raw_ms etc.)
    served_stats_full = RESULT_STATE.get("served_stats_full")
    headline_scale = scale
    if served_stats_full is not None:
        served_stats = served_stats_full
        headline_scale = 1
    if served_stats is not None:
        # THE headline: end-to-end PreFilter through the real daemon stack,
        # reported RAW: the served per-decision path makes ZERO blocking
        # device reads on accelerators (backend-routed host classifier),
        # so no dispatch round trip is netted out. dispatch_rtt_ms stays
        # in the JSON as context for the kernel slope timings.
        raw_p99_ms = served_stats["p99"] * 1e3
        value_ms = max(raw_p99_ms, 1e-3)
        detail["served_p99_raw_ms"] = round(raw_p99_ms, 4)
        if served_stats_full is not None:
            # headline is the full-scale measurement; its p50 pairs with it
            # (the quick-scale p50 stays under served_p50_ms)
            detail["served_p50_raw_ms"] = round(served_stats_full["p50"] * 1e3, 4)
        else:
            detail["served_p50_raw_ms"] = detail.pop("served_p50_ms", None)
        if single_stats is not None:
            detail["kernel_p99_ms"] = round(
                max(float(single_stats["p99"]) * 1e3, 1e-4), 4
            )
            detail["single_cv"] = round(single_stats["cv"], 4)
        state_label = (
            f"{100_000 // headline_scale // 1000}k-pod/"
            f"{10_000 // headline_scale // 1000}k-throttle"
        )
        metric = (
            "SERVED PreFilter decision p99 latency: plugin.pre_filter end-to-end "
            f"(device-indexed check) vs live {state_label} daemon state, "
            f"1 {platform} chip"
        )
        comparable = True
    elif single_stats is not None:
        value_ms = max(float(single_stats["p99"]) * 1e3, 1e-4)  # slope noise floor
        detail["single_mean_ms"] = round(max(single_stats["mean"] * 1e3, 1e-4), 4)
        detail["single_cv"] = round(single_stats["cv"], 4)
        metric = (
            f"PreFilter decision latency, single pod vs "
            f"{100_000 // scale // 1000}k-pod/{10_000 // scale // 1000}k-throttle state "
            "(p99 over slope estimates, bare kernel — served path unavailable, "
            f"see errors; 1 {platform} chip)"
        )
        comparable = True
    elif cfg1 is not None:
        # device headline config unavailable (backend down, or cfg4 itself
        # failed — see `errors`): fall back to the honest host-path p99 so the
        # round still records a real measurement rather than nothing.
        value_ms = cfg1["p99"] * 1e3
        metric = "PreFilter decision p99 latency, host-oracle path (device headline config unavailable)"
        comparable = False
    else:
        value_ms, metric = -1.0, "bench failed; see errors"
        comparable = False

    # vs_baseline compares against the device-path north star; a host-only
    # fallback number is not comparable and must not record a fake win.
    comparable = comparable and value_ms > 0
    out = {
        "metric": metric,
        "value": round(value_ms, 4),
        "unit": "ms",
        "vs_baseline": round(target_ms / value_ms, 3) if comparable else 0.0,
        "p99_ms": round(value_ms, 4),
        "platform": platform,
        "device": RESULT_STATE.get("device"),
        # context for the thread-scaling numbers: all host-side work (GIL,
        # controllers, the bench's own load generators) shares these cores
        "host_cpus": os.cpu_count(),
        **detail,
    }
    if errors:
        out["errors"] = errors  # already a point-in-time snapshot (_snap)
    return out


if __name__ == "__main__":
    try:
        main()
    except BaseException as e:  # absolute last resort: never exit without the JSON line
        if isinstance(e, SystemExit) and not e.code:
            raise
        log(traceback.format_exc())
        RESULT_STATE["errors"]["fatal"] = f"{e.__class__.__name__}: {str(e)[:300]}"
        try:
            out = build_result()
        except BaseException:
            out = {
                "metric": "bench crashed",
                "value": -1.0,
                "unit": "ms",
                "vs_baseline": 0.0,
                "error": f"{e.__class__.__name__}: {str(e)[:300]}",
            }
        emit(out)
        # rc=0 only when a usable partial MEASUREMENT made it out (value>0);
        # a crash that measured nothing must stay distinguishable by rc.
        usable = out.get("value", -1.0) > 0
        sys.exit(130 if isinstance(e, KeyboardInterrupt) else (0 if usable else 1))
