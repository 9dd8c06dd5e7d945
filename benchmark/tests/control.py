"""The control: the reference put in the program's place with one guarantee
broken, judged by the harness's own comparison. The configurations state
that a throttle whose used reaches its threshold blocks, and that a pod
which takes used exactly to the threshold fits ("on equal"); the control
flips that rule everywhere, the off-by-one a later PR is most tempted by.

    python benchmark/tests/control.py <cell> <seed> [<seed> ...]

runs it at the cell's own size on each seed and prints the compared
numbers, which must fail (exceed their limits) on every seed. The cycle
is driven as the window drives it: the control's own answers decide which
pods are reserved and bound.
"""

import collections
import contextlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH]
import reference  # noqa: E402
import run as harness  # noqa: E402


@contextlib.contextmanager
def on_equal_flipped():
    flags = reference.throttled_flags
    reference.throttled_flags = lambda threshold, used, on_equal: flags(threshold, used, not on_equal)
    try:
        yield
    finally:
        reference.throttled_flags = flags


def served_reasons(verdict: dict) -> list:
    """A /v1/prefilter answer's reasons, in the daemon's format."""
    by = collections.defaultdict(list)
    for key, state in sorted(verdict.items()):
        kind = "throttle" if not key.startswith("/") else "clusterthrottle"
        by[f"{kind}[{state}]"].append(key)
    return [f"{head}={','.join(keys)}" for head, keys in by.items()]


def control_records(state: dict, cfg: dict, traffic: dict, calls: int) -> list:
    """The window's records, answered by the control: ``calls`` PreFilters
    of the cycle (binding what it admits), or one answer of the batch route."""
    with on_equal_flipped():
        ctl = reference.Cluster(state["namespaces"], state["throttles"], state["pods"],
                                cfg["throttler_name"], cfg["scheduler_name"])
        records, t = [], 0.0
        routes = [s["route"] for s in traffic["streams"]]
        if "cycle" in routes:
            queue = collections.deque(state["pending"])
            for _ in range(calls):
                if not queue:
                    break
                pk = queue.popleft()
                verdict = ctl.verdict(pk)
                code = "UnschedulableAndUnresolvable" if verdict else "Success"
                raw = json.dumps({"code": code, "reasons": served_reasons(verdict)}).encode()
                records.append(("prefilter", t, t + 1e-3, True, (pk, raw)))
                if verdict:
                    queue.append(pk)
                else:
                    ctl.bind(pk)
                    records.append(("bind", t + 2e-3, t + 3e-3, True, (pk, b"{}")))
                t += 1e-2
        else:
            answer = {"schedulable": ctl.schedulable(), "errors": []}
            if "tick" in routes:
                answer["used"] = {"throttle": {k: v for k, v in ctl.used_counts().items()
                                               if not k.startswith("/")},
                                  "clusterthrottle": {k: v for k, v in ctl.used_counts().items()
                                                      if k.startswith("/")}}
            raw = json.dumps(answer).encode()
            records = [(routes[0], 0.0, 0.5, True, raw)]
    return records


def compared(cell: str, seed: int, root: str = os.path.dirname(BENCH), calls: int = 5000) -> dict:
    """The harness's compared numbers for the control in cell ``cell``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    spec = next(w for w in bench["workloads"] if w["name"] == cell)
    entry = next(c for c in bench["configs"] if c["name"] == spec["config"])
    with open(os.path.join(root, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic", f"{spec['traffic']}.json")) as f:
        traffic = json.load(f)
    gen = harness.load_module(os.path.join(root, "benchmark", "generators",
                                           f"{cfg['generator']}.py"), "gen")
    state = gen.generate(cfg, traffic, seed)
    records = control_records(json.loads(json.dumps(state)), cfg, traffic, calls)
    ref = reference.Cluster(state["namespaces"], state["throttles"], state["pods"],
                            cfg["throttler_name"], cfg["scheduler_name"])
    routes = {s["route"]: harness.load_module(
        os.path.join(root, "benchmark", "routes", f"{s['route']}.py"), f"route_{s['route']}")
        for s in traffic["streams"]}
    out = harness.check_all(records, ref, routes)
    return {k: {"value": v, "limit": lim} for k, (v, lim) in out.items()}


if __name__ == "__main__":
    cell, seeds = sys.argv[1], [int(s) for s in sys.argv[2:]]
    for seed in seeds:
        res = compared(cell, seed)
        fails = any(v["value"] > v["limit"] for v in res.values())
        print(json.dumps({"cell": cell, "seed": seed, "control_fails": fails, "compared": res}),
              flush=True)
