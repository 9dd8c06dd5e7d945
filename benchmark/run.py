#!/usr/bin/env python3
"""One run of one benchmark cell against the served daemon.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration's file (which names a generator under ``generators/``), its
traffic mix (``traffic/<mix>.json``, a list of streams, each naming a
route), one module per route (``routes/<route>.py``: ``drive``,
``warm_round``, ``check`` and optionally ``notes``) and one reader per
metric (``metrics/<metric>.py``, ``read(run) -> float | None``).

A run: generate the state from ``--seed`` and snapshot it into a scratch
``--data-dir``; start ``cli serve`` through ``daemon.py`` (the one process
on the chip); build the plain reference while the daemon starts; wait for
``/readyz`` and for every written status to match the reference; warm each
stream's calls until a round compiles nothing; measure for ``--seconds``;
stop the daemon; check every answer of the window against the reference;
print route and health lines, the compared numbers (also last on stderr),
and last one JSON line. This process never starts a JAX backend.
"""

import argparse
import collections
import http.client
import importlib.util
import json
import os
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import tempfile
import threading
import time

T_PROC = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]
import devtrace  # noqa: E402
import reference  # noqa: E402

READY_TIMEOUT_S = 1000.0


class RunError(Exception):
    """The run cannot produce a result (no result line is printed)."""


def say(msg: str) -> None:
    print(msg, flush=True)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise RunError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


# ----------------------------------------------------------------- daemon


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class CallError(Exception):
    pass


class Daemon:
    """``cli serve`` through ``daemon.py``, in its own session, log to a file."""

    def __init__(self, scratch: str, data_dir: str, cfg: dict):
        self.port = free_port()
        self.ctl = os.path.join(scratch, "ctl")
        os.makedirs(self.ctl)
        self.log_path = os.path.join(scratch, "daemon.log")
        env = dict(os.environ)
        if env.get("JAX_PLATFORMS") != "cpu":
            env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
        cmd = [sys.executable, os.path.join(BENCH, "daemon.py"), self.ctl,
               "--port", str(self.port), "--data-dir", data_dir, "--snapshot-every", "0",
               "--name", cfg["throttler_name"],
               "--target-scheduler-name", cfg["scheduler_name"]]
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=self.log,
                                     stderr=subprocess.STDOUT, start_new_session=True)

    def call(self, method: str, path: str, body=None, timeout: float = 600.0) -> bytes:
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=timeout)
        try:
            data = None if body is None else json.dumps(body).encode()
            conn.request(method, path, body=data,
                         headers={"Content-Type": "application/json"} if data else {})
            resp = conn.getresponse()
            raw = resp.read()
        finally:
            conn.close()
        if resp.status != 200:
            raise CallError(f"{method} {path}: HTTP {resp.status} {raw[:300]!r}")
        return raw

    def get(self, path: str):
        return json.loads(self.call("GET", path, timeout=60.0))

    def wait_ready(self) -> dict:
        deadline = time.monotonic() + READY_TIMEOUT_S
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RunError(f"daemon exited with code {self.proc.returncode}")
            try:
                body = self.get("/readyz")
                if body.get("state") not in (None, "down"):
                    return body
            except (OSError, CallError, ValueError):
                pass
            time.sleep(0.25)
        raise RunError(f"daemon not ready after {READY_TIMEOUT_S:.0f}s")

    def compiles(self) -> dict:
        with open(os.path.join(self.ctl, "compiles.json")) as f:
            return json.load(f)

    def signal(self, sig) -> None:
        os.kill(self.proc.pid, sig)

    def close_window(self, timeout: float = 180.0) -> dict:
        path = os.path.join(self.ctl, "window.json")
        self.signal(signal.SIGUSR2)
        deadline = time.monotonic() + timeout
        while not os.path.exists(path):
            if time.monotonic() > deadline or self.proc.poll() is not None:
                raise RunError("daemon did not close the window")
            time.sleep(0.05)
        with open(path) as f:
            return json.load(f)

    def log_lines(self):
        with open(self.log_path, errors="replace") as f:
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


def write_snapshot(state: dict, data_dir: str) -> None:
    """The generated manifests as a snapshot ``serve --data-dir`` recovers."""
    from kube_throttler_tpu.api.serialization import object_from_dict
    from kube_throttler_tpu.engine.snapshot import SnapshotManager
    from kube_throttler_tpu.engine.store import Store

    store = Store()
    for ns in state["namespaces"]:
        store.create_namespace(object_from_dict(ns))
    for thr in state["throttles"]:
        obj = object_from_dict(thr)
        if thr["kind"] == "Throttle":
            store.create_throttle(obj)
        else:
            store.create_cluster_throttle(obj)
    for pod in state["pods"]:
        store.create_pod(object_from_dict(pod))
    SnapshotManager(data_dir, store).write(reason="benchmark")


def wait_converged(d: Daemon, ref: reference.Cluster, timeout: float = 600.0) -> None:
    """Until the workqueues are empty and every written status' pod count
    matches the reference."""
    want = ref.used_counts()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not any(d.get("/readyz").get("workqueues", {}).values()):
            got = {}
            for path in ("/v1/throttles", "/v1/clusterthrottles"):
                for t in d.get(path):
                    ns = t["metadata"].get("namespace")
                    key = f"{ns}/{t['metadata']['name']}" if ns else f"/{t['metadata']['name']}"
                    got[key] = t["status"]["used"].get("resourceCounts", {}).get("pod") or 0
            if got == want:
                return
        time.sleep(0.5)
    raise RunError(f"statuses did not converge on the reference in {timeout:.0f}s")


def parse_metrics(text: str) -> dict:
    """Prometheus exposition → {(name, frozenset(labels)): value}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        name, _, labels = head.partition("{")
        pairs = frozenset((k, v.strip('"')) for k, _, v in (
            kv.partition("=") for kv in labels.rstrip("}").split(",") if "=" in kv))
        try:
            out[(name, pairs)] = float(value)
        except ValueError:
            pass
    return out


# ------------------------------------------------------------------ traffic


class Window:
    """The calls of the measured window, on the host clock."""

    def __init__(self):
        self.records = []  # (route, t0, t1, ok, payload), perf_counter seconds
        self.lock = threading.Lock()
        self.perf0 = time.perf_counter()
        self.wall0 = time.time_ns()

    def wall_ns(self, t: float) -> int:
        return self.wall0 + int((t - self.perf0) * 1e9)

    def timed(self, d: Daemon, route: str, path: str, body, keep=None):
        t0 = time.perf_counter()
        try:
            raw = d.call("POST", path, body)
            ok = True
        except (OSError, CallError, http.client.HTTPException) as e:
            raw, ok = str(e).encode(), False
        t1 = time.perf_counter()
        with self.lock:
            self.records.append((route, t0, t1, ok, raw if keep is None else (keep, raw)))
        return ok, raw

    now = staticmethod(time.perf_counter)

    def repeat(self, d: Daemon, route: str, path: str, stream: dict, start: float,
               deadline: float) -> None:
        """``path`` with the stream's ``body``, back to back, or every
        ``period_s`` from ``first_s``, until the deadline."""
        body, period = stream.get("body", {}), stream.get("period_s")
        due = start + stream.get("first_s", 0.0)
        while True:
            if period:
                if due >= deadline:
                    return
                time.sleep(max(0.0, due - time.perf_counter()))
                due += period
            elif time.perf_counter() >= deadline:
                return
            self.timed(d, route, path, body)


def warm(d: Daemon, route, stream: dict, state: dict) -> int:
    """The route's warm-up round until a round compiles nothing (at least
    two rounds). Returns the rounds taken."""
    for rounds in range(1, 9):
        before = d.compiles()
        route.warm_round(d, stream, state)
        if rounds >= 2 and d.compiles() == before:
            return rounds
    raise RunError(f"{stream['route']}: still compiling after {rounds} warm-up rounds")


def check_all(records, ref: reference.Cluster, routes: dict) -> dict:
    """Each route's check of the window's records, each from the state at
    the window's start. Returns {name: (value, limit)}."""
    base = dict(ref.used)
    compared = {}
    for name in sorted(routes):
        ref.used = dict(base)
        compared.update(routes[name].check(records, ref, say))
    ref.used = base
    return compared


# ------------------------------------------------------------------ metrics


class Run:
    """What a metric reader may read: the window's calls, the daemon's
    span and counter deltas over the window, and the trace's reduction."""

    def __init__(self, cell, window, start, deadline, setup_s, before, after, trace):
        self.cell, self.window, self.setup_s, self.trace = cell, window, setup_s, trace
        self.start, self.deadline = start, deadline
        self._before, self._after = before, after

    def calls(self, route: str):
        return [(t0, t1, raw) for r, t0, t1, ok, raw in self.window.records
                if r == route and ok and t0 < self.deadline]

    def latencies(self, route: str):
        return [t1 - t0 for t0, t1, _ in self.calls(route)]

    def busy_until(self, route: str):
        calls = self.calls(route)
        return max(t1 for _, t1, _ in calls) - self.start if calls else None

    def counter(self, name: str, **labels) -> float:
        want = set(labels.items())
        return sum(v - self._before.get(k, 0.0) for k, v in self._after.items()
                   if k[0] == name and want <= set(k[1]))

    def span(self, phase: str):
        """(seconds, count) of a PhaseTracer phase over the window."""
        fam = "kube_throttler_phase_duration_seconds"
        return (self.counter(fam + "_sum", phase=phase),
                int(self.counter(fam + "_count", phase=phase)))

    def span_mean_ms(self, phase: str):
        total, n = self.span(phase)
        return total / n * 1e3 if n else None

    def items(self, route: str) -> int:
        """Pods classified by the window's answers of ``route``."""
        n = 0
        for _, _, raw in self.calls(route):
            resp = json.loads(raw)
            n += len(resp.get("schedulable", {})) + len(resp.get("errors", []))
        return n

    def client_mean_ms(self, route: str):
        lat = self.latencies(route)
        return statistics.fmean(lat) * 1e3 if lat else None


# --------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        return run(args)
    except RunError as e:
        print(f"no result: {e}", file=sys.stderr, flush=True)
        return 2


def run(args) -> int:
    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        import kube_throttler_tpu  # noqa: F401 — the system under test
    except (OSError, ImportError) as e:
        raise RunError(f"not a checkout of the system: {e}")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise RunError(f"unknown workload {args.workload!r}")
    cell = cells[args.workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    kinds = bench["per_layer"] if args.trace else bench["end_to_end"]
    readers = {m["name"]: (m, load_module(os.path.join(BENCH, "metrics", f"{m['name']}.py"),
                                          f"metric_{m['name']}"))
               for m in kinds if applies(m, cell["name"])}
    routes = {s["route"]: load_module(os.path.join(BENCH, "routes", f"{s['route']}.py"),
                                      f"route_{s['route']}") for s in traffic["streams"]}
    generator = load_module(os.path.join(BENCH, "generators", f"{cfg['generator']}.py"),
                            f"gen_{cfg['generator']}")
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)

    scratch = tempfile.mkdtemp(prefix="ktb-")
    d = None
    try:
        state = generator.generate(cfg, traffic, args.seed)
        data_dir = os.path.join(scratch, "data")
        write_snapshot(state, data_dir)
        say(f"state: {len(state['pods'])} pods ({len(state['pending'])} pending), "
            f"{len(state['throttles'])} throttles, {len(state['namespaces'])} namespaces; "
            f"seed {args.seed}; snapshot written {time.perf_counter() - T_PROC:.1f}s after start")
        d = Daemon(scratch, data_dir, cfg)
        ref = reference.Cluster(state["namespaces"], state["throttles"], state["pods"],
                                cfg["throttler_name"], cfg["scheduler_name"])
        ready = d.wait_ready()
        dev = ready.get("device", {})
        if dev.get("platform") in (None, "cpu") and not os.environ.get("KTB_ALLOW_CPU"):
            raise RunError(f"no accelerator: the daemon runs on {dev.get('platform')!r}")
        if dev.get("count", 0) < cell["chips"]:
            raise RunError(f"{dev.get('count')} devices, the cell asks for {cell['chips']}")
        peak = None if dev["platform"] == "cpu" else peaks.get(dev["kind"])
        if peak is None and dev["platform"] != "cpu":
            raise RunError(f"device kind {dev['kind']!r} is not in peaks.json")
        say(f"daemon ready {time.perf_counter() - T_PROC:.1f}s after start on "
            f"{dev['platform']} {dev['kind']} x{dev['count']}")
        wait_converged(d, ref)
        for stream in traffic["streams"]:
            say(f"warm-up {stream['route']}: {warm(d, routes[stream['route']], stream, state)} rounds, "
                f"compiles so far {d.compiles()}")
        if args.trace:
            os.makedirs(os.path.join(d.ctl, "trace"))
        before = parse_metrics(d.call("GET", "/metrics").decode())

        w = Window()
        d.signal(signal.SIGUSR1)
        start = time.perf_counter()
        setup_s = start - T_PROC
        deadline = start + args.seconds
        threads = [threading.Thread(target=routes[s["route"]].drive,
                                    args=(d, w, s, state, start, deadline))
                   for s in traffic["streams"]]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        end = max([start + args.seconds] + [r[2] for r in w.records])
        inside = d.close_window()
        after = parse_metrics(d.call("GET", "/metrics").decode())
        health = d.get("/readyz")
        d.stop()
        for line in d.log_lines():
            if any(k in line for k in ("recovery:", "prewarmed", "round trip", "Traceback")):
                say(f"daemon| {line[:400]}")
        d_dev = health.get("device", {})

        trace = None
        if args.trace:
            path = devtrace.find_xplane(os.path.join(d.ctl, "trace"))
            if path is None or inside.get("trace_start_ns") is None:
                raise RunError("the traced run left no trace")
            t_ns = inside["trace_start_ns"]
            events, modules = devtrace.device_events(path)
            say(f"trace: {os.path.getsize(path)} bytes; device planes {sorted(events)}")
            calls = [(r, w.wall_ns(t0) - t_ns, w.wall_ns(t1) - t_ns)
                     for r, t0, t1, *_ in w.records]
            trace = devtrace.reduce(events, (w.wall_ns(start) - t_ns, w.wall_ns(end) - t_ns), calls,
                                    modules=modules)
            if trace["busy_s"] <= 0:
                say("trace: WARNING no operation ran on the device in the traced window")
            say(f"trace: idle time by call in flight {trace['idle_by_call']}")

        # route and health lines
        runinfo = Run(cell["name"], w, start, deadline, setup_s, before, after, trace)
        phases = {dict(k[1])["phase"]: int(v - before.get(k, 0.0)) for k, v in after.items()
                  if k[0] == "kube_throttler_phase_duration_seconds_count" and v > before.get(k, 0.0)}
        n_calls = collections.Counter(r[0] for r in w.records)
        say(f"route: calls in window {dict(n_calls)}; phase counts {phases}")
        for route in routes.values():
            for line in getattr(route, "notes", lambda *_: [])(n_calls, phases):
                say(line)
        say(f"route: compiles inside the window {inside}")
        fallbacks = runinfo.counter("kube_throttler_device_fallback_total")
        say(f"health: kube_throttler_device_fallback_total +{fallbacks:g} in window "
            f"(total {sum(v for k, v in after.items() if k[0] == 'kube_throttler_device_fallback_total'):g}); "
            f"breaker {d_dev.get('breaker')}; peak HBM {d_dev.get('peak_bytes_in_use')} B"
            + (f" ({d_dev['peak_bytes_in_use'] / peak['hbm_bytes']:.1%} of {dev['kind']})"
               if peak and "peak_bytes_in_use" in d_dev else ""))
        import jax._src.xla_bridge as xb

        if xb.backends_are_initialized():
            raise RunError("this process initialized a JAX backend")
        say("health: this process never initialized a JAX backend")

        records = sorted(w.records, key=lambda r: r[1])
        attempted = len(records)
        failed = sum(not r[3] for r in records)
        compared = {"failed_calls": (failed, 0)}
        compared.update(check_all(records, ref, routes))
        correct = attempted > 0 and all(v <= lim for v, lim in compared.values())
        for route in sorted(n_calls):
            lat = sorted(runinfo.latencies(route))
            if lat:
                cuts = " ".join(f"p{q}={lat[min(len(lat) - 1, int(len(lat) * q / 100))] * 1e3:.3f}"
                                for q in (50, 90, 95, 99, 99.9))
                say(f"latency {route} (ms, client side, window): n={len(lat)} {cuts} "
                    f"max={lat[-1] * 1e3:.3f}")
        metrics = {}
        for name, (m, mod) in readers.items():
            value = mod.read(runinfo)
            if value is not None:
                metrics[name] = {"value": value, "unit": m["unit"]}
        device = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
                  "memory_peak_bytes": d_dev.get("peak_bytes_in_use", 0)}
        result = {"correct": correct, "attempted": attempted, "failed": failed,
                  "metrics": metrics, "device": device}
        if trace is not None:
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
        result["compared"] = {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}
        for k, (v, lim) in compared.items():
            print(f"compared {k}: {v} (limit {lim})", file=sys.stderr, flush=True)
        say(f"run: {time.perf_counter() - T_PROC:.1f}s from start to result")
        print(json.dumps(result), flush=True)
        return 0
    except BaseException:
        if d is not None:
            d.stop()
            for line in d.log_lines()[-60:]:
                print(f"daemon| {line[:400]}", file=sys.stderr)
        raise
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
