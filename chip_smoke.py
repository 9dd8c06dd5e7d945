#!/usr/bin/env python3
"""The served daemon, end to end, on one local TPU at the north-star scale.

    python chip_smoke.py              # one chip: every served surface
    python chip_smoke.py --chips 4    # four chips: /v1/tick over a mesh only

One process owns the chip: this script starts ONE daemon child
(``python -m kube_throttler_tpu.cli serve`` with ``JAX_PLATFORMS=tpu``)
and never initializes a JAX backend itself (asserted at the end). The
state is ``scenarios.measure.served_state`` (100k bound Running pods ×
10k throttles in 500 selector groups, from ``--seed``), handed to the
daemon as a snapshot in a scratch ``--data-dir`` that ``serve`` recovers
from. The host reference (``Throttle.check_throttled_for``, api/types.py)
is computed here, in the parent, from the same objects.

One chip drives PreFilter, Reserve/Unreserve, PreFilter-batch and the
fused tick, and checks: every PreFilter verdict against the reference,
batch ≡ tick on every pod, a seeded sample of pods against the
reference, zero device fallbacks, and a ``tpu`` platform. ``--chips 4``
runs the tick alone over a [2,2] and a [4,1] mesh against the
one-device tick. Earlier lines carry the daemon's log, the load and
prewarm seconds, per-surface latency (client side, host clock), the
dispatch round trip and the device's peak memory. The last line is one
JSON object; ``"ok": true`` only when every phase passed on a TPU.
"""

import argparse
import json
from collections import Counter
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Daemon:
    """One ``cli serve`` child on the chip, its log in a file."""

    def __init__(self, args, data_dir: str, log_path: str):
        self.port = free_port()
        self.log_path = log_path
        env = dict(os.environ, JAX_PLATFORMS=args.platform)
        if args.platform == "cpu" and args.chips > 1:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}"
            )
        cmd = [
            sys.executable, "-m", "kube_throttler_tpu.cli", "serve",
            "--port", str(self.port),
            "--data-dir", data_dir,
            "--snapshot-every", "0",
            "--name", "kube-throttler",
            "--target-scheduler-name", "my-scheduler",
        ]
        self.log = open(log_path, "w")
        self.t0 = time.perf_counter()
        self.proc = subprocess.Popen(
            cmd, cwd=REPO, env=env, stdout=self.log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def call(self, method: str, path: str, body=None, timeout: float = 600.0):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", data=data, method=method,
            headers={"Content-Type": "application/json"},
        )
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            raw = resp.read()
        dt = time.perf_counter() - t0
        if path == "/metrics":
            return raw.decode(), dt
        return json.loads(raw), dt

    def wait_ready(self, timeout: float) -> float:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(f"daemon exited with code {self.proc.returncode}")
            try:
                body, _ = self.call("GET", "/readyz", timeout=5.0)
                if body.get("state") != "down":
                    return time.perf_counter() - self.t0
            except OSError:
                pass
            time.sleep(0.5)
        raise SmokeFailure(f"daemon not ready after {timeout:.0f}s")

    def log_lines(self):
        with open(self.log_path) as f:
            return f.read().splitlines()

    def stop(self) -> None:
        if self.proc.poll() is None:
            os.killpg(self.proc.pid, signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                os.killpg(self.proc.pid, signal.SIGKILL)
                self.proc.wait()
        self.log.close()


# ------------------------------------------------------------ host reference


def reference(throttles, pods, now):
    """Each throttle with the status a reconcile derives from ``pods``
    (used = every bound pod its selector matches; throttled on equal),
    and the throttles each selector group maps to."""
    from kube_throttler_tpu.api.types import ResourceAmount, ThrottleStatus, resource_amount_of_pod

    by_group = {}
    for pod in pods:
        g = pod.labels["grp"]
        by_group[g] = by_group.get(g, ResourceAmount()).add(resource_amount_of_pod(pod))
    group_thrs = {}
    ref = {}
    for thr in throttles:
        g = thr.spec.selector.selector_terms[0].pod_selector.match_labels["grp"]
        used = by_group.get(g, ResourceAmount())
        calc = thr.spec.calculate_threshold(now)
        ref[thr.key] = thr.with_status(
            ThrottleStatus(calculated_threshold=calc,
                           throttled=calc.threshold.is_throttled(used, True), used=used)
        )
        group_thrs.setdefault(g, []).append(thr.key)
    return ref, group_thrs


def ref_verdict(ref, group_thrs, pod, reserved=None):
    """{throttle_key: status} for every throttle that blocks ``pod``."""
    from kube_throttler_tpu.api.types import ResourceAmount

    out = {}
    for key in group_thrs.get(pod.labels.get("grp"), ()):
        st = ref[key].check_throttled_for(pod, reserved or ResourceAmount(), False)
        if st != "not-throttled":
            out[key] = st
    return out


def served_verdict(resp):
    """{throttle_key: status} from a /v1/prefilter response's reasons."""
    out = {}
    for reason in resp["reasons"]:
        head, _, names = reason.partition("=")
        status = head[head.index("[") + 1:-1]
        for key in names.split(","):
            out[key] = status
    return out


def pct(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q / 100 * len(xs)))]


# ------------------------------------------------------------------- phases


def load_state(args, data_dir):
    """The served-stack state as a snapshot ``serve --data-dir`` recovers."""
    from kube_throttler_tpu.api.pod import Namespace
    from kube_throttler_tpu.engine.snapshot import SnapshotManager
    from kube_throttler_tpu.engine.store import Store
    from kube_throttler_tpu.scenarios.measure import served_state

    t0 = time.perf_counter()
    throttles, pods = served_state(args.pods, args.throttles, args.groups, args.seed)
    store = Store()
    store.create_namespace(Namespace("default"))
    for thr in throttles:
        store.create_throttle(thr)
    for pod in pods:
        store.create_pod(pod)
    SnapshotManager(data_dir, store).write(reason="chip_smoke")
    say(f"state: {args.pods} pods x {args.throttles} throttles, {args.groups} groups, "
        f"seed {args.seed}; built and snapshotted in {time.perf_counter()-t0:.1f}s")
    return throttles, pods


def wait_quiesced(d, ref, timeout=300.0):
    """Until the workqueues are empty and every written status' pod count
    matches the reference (the reconcile of the loaded state is done)."""
    want = {k: t.status.used.resource_counts or 0 for k, t in ref.items()}
    deadline = time.monotonic() + timeout
    t0 = time.perf_counter()
    while time.monotonic() < deadline:
        ready, _ = d.call("GET", "/readyz")
        if not any(ready.get("workqueues", {}).values()):
            listed, _ = d.call("GET", "/v1/throttles")
            got = {
                f"{t['metadata']['namespace']}/{t['metadata']['name']}":
                    t["status"]["used"].get("resourceCounts", {}).get("pod") or 0
                for t in listed
            }
            if got == want:
                return time.perf_counter() - t0
        time.sleep(0.5)
    raise SmokeFailure(f"statuses did not converge on the reference in {timeout:.0f}s")


def probe_pods(args, n):
    from kube_throttler_tpu.api.pod import make_pod

    rng = random.Random(f"{args.seed}/probes")
    return [
        make_pod(f"probe{i}", labels={"grp": f"g{rng.randrange(args.groups)}"},
                 requests={"cpu": f"{rng.randrange(1, 40) * 100}m"})
        for i in range(n)
    ]


def phase_prefilter(d, args, ref, group_thrs, lat):
    from kube_throttler_tpu.api.serialization import object_to_dict

    probes = probe_pods(args, args.prefilters)
    bad = 0
    seen = Counter()
    for pod in probes:
        resp, dt = d.call("POST", "/v1/prefilter", object_to_dict(pod))
        lat.setdefault("prefilter", []).append(dt)
        want = ref_verdict(ref, group_thrs, pod)
        got = served_verdict(resp)
        ok = (resp["code"] == "Success") == (not want) and got == want
        bad += not ok
        seen.update(got.values())
        seen["schedulable"] += not got
    check(bad == 0, f"prefilter: {bad}/{len(probes)} verdicts differ from the reference")
    say(f"prefilter: {len(probes)} pending pods, every blocking throttle and status "
        f"matches the host reference (pods schedulable and throttle statuses: "
        f"{dict(seen)})")


def phase_reserve(d, args, ref, group_thrs, lat):
    """Reserve a pod, see a groupmate's verdict move as the reference says
    it must, unreserve, see it return."""
    from kube_throttler_tpu.api.pod import make_pod
    from kube_throttler_tpu.api.serialization import object_to_dict
    from kube_throttler_tpu.api.types import resource_amount_of_pod

    rng = random.Random(f"{args.seed}/reserve")
    for i in range(args.reserves):
        g = f"g{rng.randrange(args.groups)}"
        held = make_pod(f"held{i}", labels={"grp": g}, requests={"cpu": "3"})
        mate = make_pod(f"mate{i}", labels={"grp": g}, requests={"cpu": "500m"})
        for reserved in (True, False):
            path = "/v1/reserve" if reserved else "/v1/unreserve"
            resp, dt = d.call("POST", path, object_to_dict(held))
            lat.setdefault(path[4:], []).append(dt)
            check(resp["code"] == "Success", f"{path}: {resp}")
            resp, _ = d.call("POST", "/v1/prefilter", object_to_dict(mate))
            amount = resource_amount_of_pod(held) if reserved else None
            want = ref_verdict(ref, group_thrs, mate, amount)
            check(served_verdict(resp) == want,
                  f"prefilter after {path} of {held.key}: {resp} != {want}")
    say(f"reserve/unreserve: {args.reserves} pods, groupmate verdicts match the reference")


def compare_ticks(one, other, label):
    check(not other["errors"], f"{label}: errors {other['errors'][:5]}")
    check(other["schedulable"] == one["schedulable"],
          f"{label}: {sum(one['schedulable'].get(k) != v for k, v in other['schedulable'].items())}"
          " pods differ from the one-device tick")
    check(other["used"] == one["used"], f"{label}: used differs from the one-device tick")


def run_one_chip(d, args, pods, ref, group_thrs):
    lat = {}
    phase_prefilter(d, args, ref, group_thrs, lat)
    phase_reserve(d, args, ref, group_thrs, lat)
    wait_quiesced(d, ref)
    batch, lat["prefilter-batch"] = d.call("POST", "/v1/prefilter-batch", {})
    tick, lat["tick"] = d.call("POST", "/v1/tick", {"devices": 1})
    tick2, lat["tick (warm)"] = d.call("POST", "/v1/tick", {"devices": 1})
    check(not batch["errors"] and not tick["errors"], "batch/tick reported errors")
    check(len(batch["schedulable"]) == len(pods), "batch did not classify every pod")
    check(batch["schedulable"] == tick["schedulable"] == tick2["schedulable"],
          f"batch and tick differ on "
          f"{sum(batch['schedulable'][k] != v for k, v in tick['schedulable'].items())} pods")
    n_sched = sum(batch["schedulable"].values())
    say(f"prefilter-batch == tick on all {len(pods)} pods ({n_sched} schedulable)")
    want_used = {k: t.status.used.resource_counts or 0 for k, t in ref.items()}
    check(tick["used"]["throttle"] == want_used, "tick used counts differ from the reference")
    sample = random.Random(f"{args.seed}/sample").sample(pods, min(args.sample, len(pods)))
    wrong = [p.key for p in sample
             if batch["schedulable"][p.key] != (not ref_verdict(ref, group_thrs, p))]
    check(not wrong, f"{len(wrong)}/{len(sample)} sampled pods differ from the reference: "
                     f"{wrong[:5]}")
    say(f"sample: {len(sample)} pods match the host reference (api/types.py)")
    return lat


def run_four_chips(d, args, pods):
    lat = {}
    one, lat["tick devices=1"] = d.call("POST", "/v1/tick", {"devices": 1})
    check(not one["errors"] and len(one["schedulable"]) == len(pods),
          "one-device tick did not classify every pod")
    for shape in ([2, 2], [4, 1]):
        label = f"tick devices=4 shape={shape}"
        out, lat[label] = d.call("POST", "/v1/tick", {"devices": 4, "shape": shape})
        compare_ticks(one, out, label)
        check(out["mesh"] == shape, f"{label}: mesh {out['mesh']}")
        check(len(set(out["devices"])) == 4, f"{label}: verdicts on devices {out['devices']}")
        _, lat[label + " (warm)"] = d.call("POST", "/v1/tick", {"devices": 4, "shape": shape})
        say(f"{label}: equal to the one-device tick on all {len(pods)} pods; "
            f"verdicts on devices {out['devices']}")
    return lat


def report_latency(lat):
    for name, v in lat.items():
        if isinstance(v, list):
            say(f"latency {name}: n={len(v)} p50={pct(v, 50)*1e3:.3f}ms "
                f"p99={pct(v, 99)*1e3:.3f}ms max={max(v)*1e3:.3f}ms (client side)")
        else:
            say(f"latency {name}: {v*1e3:.1f}ms (client side)")


def fallback_total(metrics: str) -> float:
    return sum(
        float(line.rsplit(" ", 1)[1])
        for line in metrics.splitlines()
        if line.startswith("kube_throttler_device_fallback_total")
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pods", type=int, default=100_000)
    ap.add_argument("--throttles", type=int, default=10_000)
    ap.add_argument("--groups", type=int, default=500)
    ap.add_argument("--prefilters", type=int, default=300)
    ap.add_argument("--reserves", type=int, default=4)
    ap.add_argument("--sample", type=int, default=2000)
    ap.add_argument("--startup-timeout", type=float, default=600.0)
    ap.add_argument("--platform", default="tpu",
                    help="JAX_PLATFORMS for the daemon; anything but tpu fails "
                    "the last check (a rehearsal of every other phase)")
    args = ap.parse_args()

    sys.path.insert(0, REPO)
    try:
        from datetime import datetime, timezone

        import kube_throttler_tpu  # noqa: F401 — the repo must be here
    except ImportError as e:
        print(json.dumps({"ok": False, "error": f"kube_throttler_tpu not importable: {e}"}))
        return 1

    scratch = tempfile.mkdtemp(prefix="chip_smoke-")
    d = None
    try:
        throttles, pods = load_state(args, os.path.join(scratch, "data"))
        ref, group_thrs = reference(throttles, pods, datetime.now(timezone.utc))
        d = Daemon(args, os.path.join(scratch, "data"), os.path.join(scratch, "daemon.log"))
        ready_s = d.wait_ready(args.startup_timeout)
        settle_s = wait_quiesced(d, ref)
        say(f"load: daemon ready {ready_s:.1f}s after spawn (recovery, replay, prewarm), "
            f"statuses reconciled {settle_s:.1f}s later")
        if args.chips == 1:
            lat = run_one_chip(d, args, pods, ref, group_thrs)
        else:
            lat = run_four_chips(d, args, pods)
        report_latency(lat)
        metrics, _ = d.call("GET", "/metrics")
        fallbacks = fallback_total(metrics)
        say(f"kube_throttler_device_fallback_total: {fallbacks:g}")
        check(fallbacks == 0, "the device circuit breaker tripped")
        ready, _ = d.call("GET", "/readyz")
        dev = ready["device"]
        say(f"device: {json.dumps(dev)}")
        for key in ("peak_bytes_in_use", "bytes_limit"):
            if key in dev:
                say(f"device {key}: {dev[key]} ({dev[key] / 2**30:.2f} GiB)")
        check(dev.get("breaker") == "closed", f"breaker {dev.get('breaker')}")
        check(dev.get("count", 0) >= args.chips, f"{dev.get('count')} devices < {args.chips}")
        import jax._src.xla_bridge as xb

        check(not xb.backends_are_initialized(), "the parent initialized a JAX backend")
        check(dev.get("platform") == "tpu", f"platform {dev.get('platform')!r} is not tpu")
        result = {"ok": True,
                  "device": {"platform": dev["platform"], "kind": dev["kind"],
                             "count": dev["count"]}}
    except Exception as e:  # noqa: BLE001 — any failure fails the run, with its line
        result = {"ok": False, "error": f"{e.__class__.__name__}: {e}"}
    finally:
        if d is not None:
            d.stop()
            lines = d.log_lines()
            for line in lines[:400]:
                say(f"daemon| {line[:300]}")
            if len(lines) > 400:
                say(f"daemon| ... {len(lines) - 400} more lines")
        shutil.rmtree(scratch, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
