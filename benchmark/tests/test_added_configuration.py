"""A configuration joins the benchmark as new files and ``BENCHMARK.json``
entries only, the way a later PR adds one: a renamed copy of a configuration
on its existing generator, its tiny size, a route copied under a new name
with its ``FAULTS`` and ``control``, a traffic mix and a cell. The copy's own
rehearsal, whose files the fixture copies and never writes, must find the
new cell, run it correct, see each of its faults and its control come out
not correct, and check the configuration's states against the program's
oracle."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import rehearsal

CONFIG, ROUTE, MIX, CELL = "added-config", "added_route", "added-mix", "added.cell"
BASE_ROUTE = "triage"
TESTS = ["benchmark/tests/test_rehearsal.py", "benchmark/tests/test_reference.py"]
# which of the rehearsal's tests each proof step runs for the new cell
STEPS = {
    "runs": ("test_cell_runs_and_is_correct", "test_traced_run_reads_every_per_layer_metric"),
    "faults": ("test_broken_timed_path_is_not_correct",),
    "control": ("test_control_is_not_correct", "test_every_configuration_has_a_tiny_size",
                "test_seeded_states_match_program_oracle"),
}


def load(path):
    with open(path) as f:
        return json.load(f)


def dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)


def routes_of(cell):
    mix = load(os.path.join(rehearsal.BENCH, "traffic", f"{cell['traffic']}.json"))
    return [s["route"] for s in mix["streams"]]


@pytest.fixture(scope="module")
def source(tmp_path_factory):
    """A repository with the additions beside an unedited benchmark."""
    root = str(tmp_path_factory.mktemp("added") / "repo")
    bench_dir = os.path.join(root, "benchmark")
    os.makedirs(root)
    os.symlink(os.path.join(rehearsal.REPO, "kube_throttler_tpu"),
               os.path.join(root, "kube_throttler_tpu"))
    shutil.copytree(rehearsal.BENCH, bench_dir, ignore=shutil.ignore_patterns("__pycache__"))
    bench = rehearsal.benchmark()
    # the base: the cheapest to rehearse of the cells on a mix of the base route alone
    base = min((c for c in rehearsal.cells() if routes_of(c) == [BASE_ROUTE]),
               key=lambda c: load(rehearsal.tiny_config(c["config"]))["pods"])
    entry = next(c for c in bench["configs"] if c["name"] == base["config"])

    cfg = dict(load(os.path.join(rehearsal.REPO, entry["file"])), name=CONFIG)
    dump(cfg, os.path.join(bench_dir, "configs", f"{CONFIG}.json"))
    tiny = dict(load(rehearsal.tiny_config(base["config"])), name=f"tiny-{CONFIG}")
    dump(tiny, os.path.join(bench_dir, "tests", "data", "tiny", f"{CONFIG}.json"))
    shutil.copy(os.path.join(rehearsal.BENCH, "routes", f"{BASE_ROUTE}.py"),
                os.path.join(bench_dir, "routes", f"{ROUTE}.py"))
    mix = load(os.path.join(rehearsal.BENCH, "traffic", f"{base['traffic']}.json"))
    mix["streams"] = [dict(s, route=ROUTE) for s in mix["streams"]]
    dump(mix, os.path.join(bench_dir, "traffic", f"{MIX}.json"))

    bench["configs"].append(dict(entry, name=CONFIG, file=f"benchmark/configs/{CONFIG}.json",
                                 why=f"a renamed copy of {entry['name']}"))
    bench["workloads"].append(dict(base, name=CELL, config=CONFIG, traffic=MIX,
                                   why=f"{base['name']} over the copied configuration and route"))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if base["name"] in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    dump(bench, os.path.join(root, "BENCHMARK.json"))
    return root


def pytest_in(root, *args):
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-m", "pytest", "-p", "no:cacheprovider", *args],
                          cwd=root, env=env, capture_output=True, text=True, timeout=540)


@pytest.fixture(scope="module")
def added_tests(source):
    """The rehearsal's tests in the copy that name the new cell or configuration."""
    proc = pytest_in(source, "--collect-only", "-q", *TESTS)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return [line for line in proc.stdout.splitlines()
            if re.search(rf"\[(.*-)?({re.escape(CELL)}|{re.escape(CONFIG)})(-.*)?\]$", line)]


def test_the_rehearsal_finds_the_added_cell(added_tests):
    for step in STEPS.values():
        for test in step:
            assert any(f"::{test}[" in t for t in added_tests), (test, added_tests)
    faults = {t.rsplit("-", 1)[1].rstrip("]") for t in added_tests
              if "::test_broken_timed_path_is_not_correct[" in t}
    route = rehearsal.harness.load_module(
        os.path.join(rehearsal.BENCH, "routes", f"{BASE_ROUTE}.py"), "base_route")
    assert faults == set(route.FAULTS)


@pytest.mark.parametrize("step", sorted(STEPS))
def test_the_added_cell_passes_the_rehearsal(source, added_tests, step):
    chosen = [t for t in added_tests if t.split("::")[1].split("[")[0] in STEPS[step]]
    proc = pytest_in(source, "-q", *chosen)
    assert proc.returncode == 0 and f"{len(chosen)} passed" in proc.stdout, (
        proc.stdout[-4000:] + proc.stderr[-2000:])
