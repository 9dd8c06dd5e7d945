"""A checkout for CPU rehearsals: the program linked in, the benchmark
copied, and the tiny test-only configuration and traffic files added
beside the real ones, as a later PR would add its own. ``BENCHMARK.json``
there names the same cells over the tiny files, plus one per-layer metric
whose reader exists only as an added file.

    python benchmark/tests/rehearsal.py <dir>    # builds it and prints the path
"""

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
TINY_CONFIG = {"k8s-large-100k": "tiny-large", "tenant-10k": "tiny-tenant"}
TINY_TRAFFIC = {"cycle": "tiny-cycle"}
DUMMY_METRIC = '''"""Calls the window sent, of every route: a reader added as a file only."""


def read(run):
    return float(len(run.window.records))
'''


def make_root(where: str) -> str:
    root = os.path.join(where, "checkout")
    os.makedirs(root)
    os.symlink(os.path.join(REPO, "kube_throttler_tpu"), os.path.join(root, "kube_throttler_tpu"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in TINY_CONFIG.values():
        shutil.copy(os.path.join(DATA, f"{name}.json"), os.path.join(root, "benchmark", "configs"))
    for name in TINY_TRAFFIC.values():
        shutil.copy(os.path.join(DATA, f"{name}.json"), os.path.join(root, "benchmark", "traffic"))
    with open(os.path.join(root, "benchmark", "metrics", "dummy_calls.py"), "w") as f:
        f.write(DUMMY_METRIC)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cfg in bench["configs"]:
        tiny = TINY_CONFIG[cfg["name"]]
        cfg.update(name=tiny, file=f"benchmark/configs/{tiny}.json")
    for cell in bench["workloads"]:
        cell["config"] = TINY_CONFIG[cell["config"]]
        cell["traffic"] = TINY_TRAFFIC.get(cell["traffic"], cell["traffic"])
    bench["per_layer"].append({
        "name": "dummy_calls", "unit": "calls", "better": "higher", "source": "host_clock",
        "layer": "benchmark", "moves": "setup_s",
        "workloads": [c["name"] for c in bench["workloads"]]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root


if __name__ == "__main__":
    print(make_root(sys.argv[1]))
