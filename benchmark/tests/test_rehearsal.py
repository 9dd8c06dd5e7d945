"""Every cell of ``BENCHMARK.json`` end to end on the CPU at a tiny size,
from a checkout that holds the tiny configuration and traffic files and an
added metric reader (``rehearsal.make_root``); then the same runs with the
timed path broken underneath by each fault its routes declare, and the
control, each of which must come out not correct."""

import json
import os
import subprocess
import sys

import pytest

import control
import rehearsal

CONFIGS = [c["name"] for c in rehearsal.benchmark()["configs"]]
CELLS = [c["name"] for c in rehearsal.cells()]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.make_root(str(tmp_path_factory.mktemp("rehearsal")))


def run_cell(root, cell, trace=0, seed=2**31 + 11, fault=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", KTB_ALLOW_CPU="1")
    env.pop("KTB_FAULT", None)
    if fault:
        env["KTB_FAULT"] = fault
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", str(seed),
         "--seconds", "3", "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(root, cell):
    result, out = run_cell(root, cell)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "compared"
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(result["metrics"]) == want and "setup_s" in want
    assert "compiles inside the window {'traces': 0, 'compiles': 0" in out


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reads_every_per_layer_metric(root, cell):
    result, _ = run_cell(root, cell, trace=1)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    want = {m["name"] for m in bench["per_layer"] if cell in m["workloads"]}
    got = set(result["metrics"])
    # the metric that exists only as an added file is found by name
    assert "dummy_calls" in got
    # what the tiny configuration says may stay silent at its size, as the
    # device readers do where the host dedupe answers triage
    assert got <= want and want - got <= rehearsal.silent_at_tiny(cell)
    assert result["correct"] and "busy_s" in result["device"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell,fault", [(c, f) for c in CELLS for f in rehearsal.faults(c)])
def test_broken_timed_path_is_not_correct(root, cell, fault):
    result, _ = run_cell(root, cell, fault=fault)
    assert result["correct"] is False
    assert any(v["value"] > v["limit"] for v in result["compared"].values())


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [3, 2**31 + 3, 2**33 + 1])
def test_control_is_not_correct(root, cell, seed):
    # each route of the mix on its own numbers: the cycle's PreFilter check
    # must catch the flip whatever the scan's triage check reads
    by_route = control.compared(cell, seed, root=root)
    assert by_route and control.fails(by_route), by_route


@pytest.mark.parametrize("config", CONFIGS)
def test_every_configuration_has_a_tiny_size(config):
    path = rehearsal.tiny_config(config)
    left_out = [c["name"] for c in rehearsal.benchmark()["workloads"] if c["config"] == config]
    assert os.path.isfile(path), f"no tiny size at {path}: cells {left_out} are not rehearsed"
    entry = next(c for c in rehearsal.benchmark()["configs"] if c["name"] == config)
    with open(os.path.join(rehearsal.REPO, entry["file"])) as f, open(path) as g:
        assert json.load(f)["generator"] == json.load(g)["generator"]


def test_no_result_without_the_program(tmp_path):
    import shutil

    shutil.copytree(rehearsal.BENCH, tmp_path / "benchmark")
    shutil.copy(os.path.join(rehearsal.REPO, "BENCHMARK.json"), tmp_path)
    cell = rehearsal.benchmark()["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path, capture_output=True, text=True)
    assert proc.returncode != 0 and not proc.stdout.strip()
