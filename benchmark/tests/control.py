"""The control: the reference put in the program's place with one guarantee
broken, judged by the harness's own comparison. The configurations state
that a throttle whose used reaches its threshold blocks, and that a pod
which takes used exactly to the threshold fits ("on equal"); the control
flips that rule everywhere, the off-by-one a later PR is most tempted by.

    python benchmark/tests/control.py <cell> <seed> [<seed> ...]

runs it at the cell's own size on each seed and prints the compared
numbers by route: each route of the cell's mix must fail (exceed a limit)
on its own numbers, on every seed, so that no route's check passes on
another's. Each stream of the mix is answered by its route's
``control(ctl, stream, state, calls)``, which returns the records the
window would hold with the reference ``ctl`` in the program's place. The
cycle's is driven as the window drives it: the control's own answers
decide which pods are bound. The records' times map to the wall clock from
the moment the control starts, for the control's answers and the check
alike.
"""

import contextlib
import json
import os
import sys
import time

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


def control_records(state: dict, cfg: dict, traffic: dict, routes: dict, calls: int,
                    wall_ns) -> list:
    """The window's records, each stream answered by its route's
    ``control`` from a flipped reference of its own over the starting
    state, on the clock ``wall_ns``."""
    records = []
    with on_equal_flipped():
        for stream in traffic["streams"]:
            own = json.loads(json.dumps(state))
            ctl = reference.Cluster(own["namespaces"], own["throttles"], own["pods"],
                                    cfg["throttler_name"], cfg["scheduler_name"])
            ctl.wall_ns = wall_ns
            records += routes[stream["route"]].control(ctl, stream, own, calls)
    return sorted(records, key=lambda r: r[1])


def compared(cell: str, seed: int, root: str = os.path.dirname(BENCH), calls: int = 5000) -> dict:
    """The harness's compared numbers for the control in cell ``cell``, by
    route: {route: {name: {"value", "limit"}}}, each from its route's check
    alone."""
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
    routes = {s["route"]: harness.load_module(
        os.path.join(root, "benchmark", "routes", f"{s['route']}.py"), f"route_{s['route']}")
        for s in traffic["streams"]}
    wall0 = time.time_ns()

    def wall_ns(t: float) -> int:
        return wall0 + int(t * 1e9)

    records = control_records(state, cfg, traffic, routes, calls, wall_ns)
    ref = reference.Cluster(state["namespaces"], state["throttles"], state["pods"],
                            cfg["throttler_name"], cfg["scheduler_name"])
    ref.wall_ns, ref.window = wall_ns, (0.0, max(r[2] for r in records))
    return {name: {k: {"value": v, "limit": lim}
                   for k, (v, lim) in harness.check_all(records, ref, {name: mod}).items()}
            for name, mod in routes.items()}


def fails(by_route: dict) -> bool:
    """Whether every route fails on its own numbers."""
    return all(any(v["value"] > v["limit"] for v in out.values()) for out in by_route.values())


if __name__ == "__main__":
    cell, seeds = sys.argv[1], [int(s) for s in sys.argv[2:]]
    for seed in seeds:
        res = compared(cell, seed)
        print(json.dumps({"cell": cell, "seed": seed, "control_fails": fails(res), "compared": res}),
              flush=True)
