"""A checkout for CPU rehearsals: the program linked in, the benchmark
copied, and the tiny test-only configuration and traffic files added
beside the real ones, as a later PR would add its own. ``BENCHMARK.json``
there names the same cells over the tiny files, plus one per-layer metric
whose reader exists only as an added file.

Everything is found by name. A configuration's tiny size is
``tests/data/tiny/<configuration>.json``, which may list under
``silent_at_tiny`` the per-layer metrics that read nothing at that size;
a traffic mix with a tiny form has ``tests/data/tiny/traffic-<mix>.json``.
A configuration without a tiny file is left out of the checkout with its
cells (``test_rehearsal`` fails on it by name). The planted faults a cell
must catch are its routes' ``FAULTS``.

    python benchmark/tests/rehearsal.py <dir>    # builds it and prints the path
"""

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
TINY = os.path.join(BENCH, "tests", "data", "tiny")
sys.path[:0] = [BENCH]
import run as harness  # noqa: E402

DUMMY_METRIC = '''"""Calls the window sent, of every route: a reader added as a file only."""


def read(run):
    return float(len(run.window.records))
'''


def benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny_config(config: str) -> str:
    return os.path.join(TINY, f"{config}.json")


def tiny_traffic(mix: str) -> str:
    return os.path.join(TINY, f"traffic-{mix}.json")


def configs() -> list:
    """The configurations that have a tiny size."""
    return [c for c in benchmark()["configs"] if os.path.isfile(tiny_config(c["name"]))]


def cells() -> list:
    """The cells whose configuration has a tiny size."""
    sized = {c["name"] for c in configs()}
    return [c for c in benchmark()["workloads"] if c["config"] in sized]


def cell(name: str) -> dict:
    return next(c for c in benchmark()["workloads"] if c["name"] == name)


def silent_at_tiny(name: str) -> set:
    """The per-layer metrics cell ``name`` may leave out at the tiny size."""
    with open(tiny_config(cell(name)["config"])) as f:
        return set(json.load(f).get("silent_at_tiny", []))


def faults(name: str) -> list:
    """The planted faults cell ``name`` must catch: its routes' ``FAULTS``."""
    with open(os.path.join(BENCH, "traffic", f"{cell(name)['traffic']}.json")) as f:
        routes = sorted({s["route"] for s in json.load(f)["streams"]})
    out = []
    for route in routes:
        mod = harness.load_module(os.path.join(BENCH, "routes", f"{route}.py"), f"route_{route}")
        out += [f for f in mod.FAULTS if f not in out]
    return out


def make_root(where: str) -> str:
    root = os.path.join(where, "checkout")
    os.makedirs(root)
    os.symlink(os.path.join(REPO, "kube_throttler_tpu"), os.path.join(root, "kube_throttler_tpu"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "benchmark", "metrics", "dummy_calls.py"), "w") as f:
        f.write(DUMMY_METRIC)
    bench = benchmark()
    bench["configs"] = configs()
    for cfg in bench["configs"]:
        cfg["file"] = f"benchmark/configs/tiny-{cfg['name']}.json"
        shutil.copy(tiny_config(cfg["name"]), os.path.join(root, cfg["file"]))
    bench["workloads"] = cells()
    for c in bench["workloads"]:
        if os.path.isfile(tiny_traffic(c["traffic"])):
            shutil.copy(tiny_traffic(c["traffic"]),
                        os.path.join(root, "benchmark", "traffic", f"tiny-{c['traffic']}.json"))
            c["traffic"] = f"tiny-{c['traffic']}"
    bench["per_layer"].append({
        "name": "dummy_calls", "unit": "calls", "better": "higher", "source": "host_clock",
        "layer": "benchmark", "moves": "setup_s",
        "workloads": [c["name"] for c in bench["workloads"]]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root


if __name__ == "__main__":
    print(make_root(sys.argv[1]))
