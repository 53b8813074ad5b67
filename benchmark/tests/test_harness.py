"""The harness's pieces on the CPU at a tiny size: discovery by name, the
replayed window, the result line, and the refusals of the command."""

import json
import os
import subprocess
import sys

import pytest
from conftest import BENCH, REPO

import harness as H
import run as R

CELLS = [w["name"] for w in H.manifest(REPO)["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_every_cell_config_and_metric_is_found_by_name():
    m = H.manifest(REPO)
    for w in m["workloads"]:
        cell = H.load_cell(w["name"], 2**31 + 7)
        assert cell.shadow["general"]["seed"] == 2**31 + 7
        assert cell.horizon_ns > 0 and cell.chips == w["chips"]
        H.reference_module(cell.model)
    assert [e["name"] for e, _ in H.metric_readers(REPO)] == [
        e["name"] for e in m["per_layer"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cpu_run_replays_whole_segments_and_is_correct(tiny_root, cell):
    res = R.run(["--workload", cell, "--seed", "4294967311", "--seconds", "1",
                 "--trace", "0"], root=tiny_root, require_tpu=False)
    assert list(res) == KEYS
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    rate = res["metrics"]["sim_s_per_wall_s"]
    assert rate["unit"] == "sim-s/wall-s" and rate["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] >= 1
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("chips", [1, 4])
def test_new_cell_and_metric_are_found_without_editing_a_file(tiny_root, chips):
    """A later PR adds a workload file, a metric file and their entries;
    a four-chip cell runs sharded (over four CPU devices here)."""
    man_path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(man_path) as f:
        man = json.load(f)
    man["workloads"].append({
        "name": "phold_10k_1node.slow", "config": "phold_10k_1node",
        "traffic": "slow", "chips": chips, "why": "test"})
    man["per_layer"].append({
        "name": "test.rounds_per_segment", "unit": "rounds", "better": "lower",
        "source": "program_counter", "layer": "test", "moves": "sim_s_per_wall_s"})
    with open(man_path, "w") as f:
        json.dump(man, f)
    with open(os.path.join(tiny_root, "benchmark", "workloads",
                           "phold_10k_1node.slow.json"), "w") as f:
        json.dump({"config": "phold_10k_1node", "traffic": "slow",
                   "horizon": "1 s", "model_args": {"mean_delay": "300 ms"}}, f)
    with open(os.path.join(tiny_root, "benchmark", "metrics",
                           "test.rounds_per_segment.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.rounds / ctx.segments\n")

    cell = H.load_cell("phold_10k_1node.slow", 5, tiny_root)
    procs = [h["processes"][0] for h in cell.shadow["hosts"].values()]
    assert all(p["model_args"]["mean_delay"] == "300 ms" for p in procs)
    readers = dict((e["name"], r) for e, r in H.metric_readers(tiny_root))
    ctx = H.TraceContext(reduction=None, window_s=1.0, segments=2, rounds=40, microsteps=90)
    assert readers["test.rounds_per_segment"](ctx) == 20
    res = R.run(["--workload", "phold_10k_1node.slow", "--seed", "5", "--seconds", "0.5",
                 "--trace", "0"], root=tiny_root, require_tpu=False)
    assert res["correct"] is True and res["device"]["count"] >= chips


def _command(root, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_cpu_and_prints_no_result():
    p = _command(REPO)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark")
    p = _command(str(tmp_path))
    assert p.returncode != 0 and p.stdout == ""
