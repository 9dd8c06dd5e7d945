"""A configuration joins the benchmark as new files and ``BENCHMARK.json``
entries only, the way a later PR adds one: a renamed copy of a configuration
on its existing generator, its tiny size, a route copied under a new name
with its ``FAULTS`` and ``control``, a traffic mix and a cell. The copy's own
rehearsal, whose files the fixture copies and never writes, must find the
new cell, run it correct, see each of its faults and its control come out
not correct, and check the configuration's states against the program's
oracle.

The second proof adds a configuration whose thresholds change with time the
same way: a generator of its own (``grouped_throttles`` with a calendar of
overlapping ``temporaryThresholdOverrides`` from the moment of generation,
boundaries every 0.5 s on the cluster), on a copy of the ``tick`` route
whose ``FAULTS`` add ``overrides_ignored``."""

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
# the configuration with overrides: its generator, and its cell over a copied tick route
O_CONFIG, O_GENERATOR, O_ROUTE, O_MIX, O_CELL = (
    "added-overrides", "added_calendar", "added_tick", "added-tick-mix", "added.overrides")
O_BASE_ROUTE, O_FAULT = "tick", "overrides_ignored"
CALENDAR = {"override_step_s": 0.5, "override_steps": 240}
GENERATOR_TAIL = '''

import time
from datetime import datetime, timedelta, timezone

_grouped = generate


def _stamp(ns):
    t = datetime(1970, 1, 1, tzinfo=timezone.utc) + timedelta(microseconds=ns // 1000)
    return t.strftime("%Y-%m-%dT%H:%M:%S.%f") + "Z"


def generate(cfg, traffic, seed):
    """The grouped state, each throttle given overlapping override windows
    on a grid of ``override_step_s`` from the moment of generation,
    ``override_steps`` long: open at the start, open at the end, a window
    across, now and then one of a single instant or one that does not
    parse."""
    state = _grouped(cfg, traffic, seed)
    rng = random.Random(seed ^ 0x5EED)
    step = int(cfg["override_step_s"] * 1e9)
    anchor = time.time_ns() // step * step
    per_group = cfg["pods"] // cfg["groups"]
    # a bound pod fits a count of per_group + 1 exactly: the on-equal rule decides
    choices = [{"resourceCounts": {"pod": per_group + d}} for d in (-1, 1, 1, 1, 2, 2, 50)] + [
        {"resourceRequests": {"cpu": "1m"}}, {"resourceRequests": {"cpu": "100000"}},
        {"resourceCounts": {"pod": per_group + 1}, "resourceRequests": {"cpu": "100000"}}]
    for thr in state["throttles"]:
        p, q, r = (_stamp(anchor + k * step)
                   for k in sorted(rng.sample(range(1, cfg["override_steps"]), 3)))
        ovs = [{"end": q}, {"begin": p, "end": r}, {"begin": q}]
        if rng.random() < 0.2:
            ovs.insert(rng.randrange(4), rng.choice([{"begin": r, "end": r},
                                                     {"begin": "never", "end": r}]))
        for o in ovs:
            o["threshold"] = rng.choice(choices)
        thr["spec"]["temporaryThresholdOverrides"] = ovs
    return state
'''
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


def add_cell(root, base_route, names, extra=None, route_text=None, generator=None):
    """A repository with a configuration, route, mix and cell added as files
    and entries beside an unedited benchmark, copied from the cheapest to
    rehearse of the cells on a mix of ``base_route`` alone. ``names`` are
    the configuration's, route's, mix's and cell's; ``extra`` adds keys to
    the configuration, ``route_text`` edits the copied route's text and
    ``generator``, a name and a text, gives the configuration a generator
    of its own: the base's generator with that text after it."""
    config, route, mix_name, cell = names
    bench_dir = os.path.join(root, "benchmark")
    os.makedirs(root)
    os.symlink(os.path.join(rehearsal.REPO, "kube_throttler_tpu"),
               os.path.join(root, "kube_throttler_tpu"))
    shutil.copytree(rehearsal.BENCH, bench_dir, ignore=shutil.ignore_patterns("__pycache__"))
    bench = rehearsal.benchmark()
    base = min((c for c in rehearsal.cells() if routes_of(c) == [base_route]),
               key=lambda c: load(rehearsal.tiny_config(c["config"]))["pods"])
    entry = next(c for c in bench["configs"] if c["name"] == base["config"])

    cfg = dict(load(os.path.join(rehearsal.REPO, entry["file"])), name=config, **(extra or {}))
    tiny = dict(load(rehearsal.tiny_config(base["config"])), name=f"tiny-{config}", **(extra or {}))
    if generator:
        with open(os.path.join(rehearsal.BENCH, "generators", f"{cfg['generator']}.py")) as f:
            text = f.read()
        cfg["generator"] = tiny["generator"] = generator[0]
        with open(os.path.join(bench_dir, "generators", f"{generator[0]}.py"), "w") as f:
            f.write(text + generator[1])
    dump(cfg, os.path.join(bench_dir, "configs", f"{config}.json"))
    dump(tiny, os.path.join(bench_dir, "tests", "data", "tiny", f"{config}.json"))
    with open(os.path.join(rehearsal.BENCH, "routes", f"{base_route}.py")) as f:
        text = f.read()
    with open(os.path.join(bench_dir, "routes", f"{route}.py"), "w") as f:
        f.write(route_text(text) if route_text else text)
    mix = load(os.path.join(rehearsal.BENCH, "traffic", f"{base['traffic']}.json"))
    mix["streams"] = [dict(s, route=route) for s in mix["streams"]]
    dump(mix, os.path.join(bench_dir, "traffic", f"{mix_name}.json"))

    bench["configs"].append(dict(entry, name=config, file=f"benchmark/configs/{config}.json",
                                 why=f"a renamed copy of {entry['name']}"))
    bench["workloads"].append(dict(base, name=cell, config=config, traffic=mix_name,
                                   why=f"{base['name']} over the copied configuration and route"))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if base["name"] in metric.get("workloads", ()):
            metric["workloads"].append(cell)
    dump(bench, os.path.join(root, "BENCHMARK.json"))
    return root


@pytest.fixture(scope="module")
def source(tmp_path_factory):
    """A repository with the additions beside an unedited benchmark."""
    return add_cell(str(tmp_path_factory.mktemp("added") / "repo"), BASE_ROUTE,
                    (CONFIG, ROUTE, MIX, CELL))


def with_fault(text: str) -> str:
    """The route's text with ``O_FAULT`` added to its ``FAULTS``."""
    head, sep, tail = text.partition("FAULTS = (")
    assert sep, "the route names no FAULTS"
    return f"{head}{sep}{O_FAULT!r}, {tail}"


@pytest.fixture(scope="module")
def override_source(tmp_path_factory):
    """A repository with the configuration with overrides added."""
    return add_cell(str(tmp_path_factory.mktemp("overrides") / "repo"), O_BASE_ROUTE,
                    (O_CONFIG, O_ROUTE, O_MIX, O_CELL), extra=CALENDAR,
                    route_text=with_fault, generator=(O_GENERATOR, GENERATOR_TAIL))


def pytest_in(root, *args):
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-m", "pytest", "-p", "no:cacheprovider", *args],
                          cwd=root, env=env, capture_output=True, text=True, timeout=540)


def collected_naming(root, cell, config):
    """The rehearsal's tests in the copy ``root`` that name ``cell`` or ``config``."""
    proc = pytest_in(root, "--collect-only", "-q", *TESTS)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return [line for line in proc.stdout.splitlines()
            if re.search(rf"\[(.*-)?({re.escape(cell)}|{re.escape(config)})(-.*)?\]$", line)]


def found_with_faults(tests) -> set:
    """Asserts every proof step's tests are among ``tests``; returns the
    faults they plant."""
    for step in STEPS.values():
        for test in step:
            assert any(f"::{test}[" in t for t in tests), (test, tests)
    return {t.rsplit("-", 1)[1].rstrip("]") for t in tests
            if "::test_broken_timed_path_is_not_correct[" in t}


def passes(root, tests, step):
    chosen = [t for t in tests if t.split("::")[1].split("[")[0] in STEPS[step]]
    proc = pytest_in(root, "-q", *chosen)
    assert proc.returncode == 0 and f"{len(chosen)} passed" in proc.stdout, (
        proc.stdout[-4000:] + proc.stderr[-2000:])


def base_faults(route: str) -> set:
    return set(rehearsal.harness.load_module(
        os.path.join(rehearsal.BENCH, "routes", f"{route}.py"), f"base_{route}").FAULTS)


@pytest.fixture(scope="module")
def added_tests(source):
    """The rehearsal's tests in the copy that name the new cell or configuration."""
    return collected_naming(source, CELL, CONFIG)


@pytest.fixture(scope="module")
def override_tests(override_source):
    return collected_naming(override_source, O_CELL, O_CONFIG)


def test_the_rehearsal_finds_the_added_cell(added_tests):
    assert found_with_faults(added_tests) == base_faults(BASE_ROUTE)


@pytest.mark.parametrize("step", sorted(STEPS))
def test_the_added_cell_passes_the_rehearsal(source, added_tests, step):
    passes(source, added_tests, step)


def test_the_rehearsal_finds_the_override_cell(override_tests):
    assert found_with_faults(override_tests) == base_faults(O_BASE_ROUTE) | {O_FAULT}


@pytest.mark.parametrize("step", sorted(STEPS))
def test_the_override_cell_passes_the_rehearsal(override_source, override_tests, step):
    passes(override_source, override_tests, step)
