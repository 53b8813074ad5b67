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
import trace_reduce as T

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


# per-layer readers a later PR could add as files of their own: a
# `shadow_*` marker no benchmark code names, a counter of the model's
# report over the resolved configuration, and one kept to another cell
NEW_READERS = {
    "test.rounds_per_segment": "def read(ctx):\n    return ctx.rounds / ctx.segments\n",
    "test.merge_bounds_ms_per_round": (
        "def read(ctx):\n"
        "    s = ctx.reduction.markers.get('shadow_merge_bounds', 0.0)\n"
        "    return 1e3 * s / ctx.rounds if s > 0 and ctx.rounds else None\n"),
    "test.handled_per_host_round": (
        "def read(ctx):\n"
        "    n = sum(r['model_report']['total_events'] for r in ctx.reports)\n"
        "    return n / ctx.rounds / ctx.engine_cfg.num_hosts\n"),
    "test.elsewhere": "def read(ctx):\n    raise AssertionError('read outside its cells')\n",
}


def _fake_trace(seen):
    """A traced window on the CPU: the window untraced, and a reduction made
    by hand in place of the chip's trace."""

    def traced_window(rp, seconds, hlo, chips):
        results, wall = rp.window(min(seconds, R.TRACE_S))
        seen["segments"] = results
        seen["num_hosts"] = rp.sim.engine.cfg.num_hosts
        red = T.Reduction(
            busy_s=0.5 * wall, span_s=wall,
            scopes={"microsteps": 0.01, "exchange": 0.02, "other": 0.001},
            markers={"shadow_merge_bounds": 0.004}, host_spans={}, chunk_dispatches=1,
            top_ops=[], idle_gaps=[])
        return results, wall, red

    return traced_window


@pytest.mark.parametrize("chips", [1, 4])
def test_new_cell_and_metric_are_found_without_editing_a_file(tiny_root, monkeypatch, chips):
    """A later PR adds a workload file, metric files and their entries; a
    four-chip cell runs sharded (over four CPU devices here). The traced
    window's readers get the reduction's markers, the segments' reports and
    the engine's configuration; an entry that lists other cells is not read."""
    man_path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(man_path) as f:
        man = json.load(f)
    man["workloads"].append({
        "name": "phold_10k_1node.slow", "config": "phold_10k_1node",
        "traffic": "slow", "chips": chips, "why": "test"})
    for name in NEW_READERS:
        man["per_layer"].append({
            "name": name, "unit": "1", "better": "lower", "source": "program_counter",
            "layer": "test", "moves": "sim_s_per_wall_s"})
    man["per_layer"][-1]["workloads"] = [CELLS[0]]
    with open(man_path, "w") as f:
        json.dump(man, f)
    with open(os.path.join(tiny_root, "benchmark", "workloads",
                           "phold_10k_1node.slow.json"), "w") as f:
        json.dump({"config": "phold_10k_1node", "traffic": "slow",
                   "horizon": "1 s", "model_args": {"mean_delay": "300 ms"}}, f)
    for name, code in NEW_READERS.items():
        with open(os.path.join(tiny_root, "benchmark", "metrics", f"{name}.py"), "w") as f:
            f.write(code)

    cell = H.load_cell("phold_10k_1node.slow", 5, tiny_root)
    procs = [h["processes"][0] for h in cell.shadow["hosts"].values()]
    assert all(p["model_args"]["mean_delay"] == "300 ms" for p in procs)
    seen = {}
    monkeypatch.setattr(R, "chunk_hlo", lambda rp: None)
    monkeypatch.setattr(R, "traced_window", _fake_trace(seen))
    res = R.run(["--workload", "phold_10k_1node.slow", "--seed", "5", "--seconds", "0.5",
                 "--trace", "1"], root=tiny_root, require_tpu=False)
    assert res["correct"] is True and res["device"]["count"] >= chips
    segs = seen["segments"]
    rounds = sum(s["rounds"] for s in segs)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    # the accepted readers without a list, the new ones; none kept to other cells
    listed = [e["name"] for e in man["per_layer"] if "workloads" in e]
    assert not set(listed) & set(got)
    assert set(got) == {e["name"] for e in man["per_layer"] if "workloads" not in e}
    assert got["test.rounds_per_segment"] == rounds / len(segs)
    assert got["test.merge_bounds_ms_per_round"] == 1e3 * 0.004 / rounds
    handled = sum(s["report"]["model_report"]["total_events"] for s in segs)
    assert handled == sum(s["events"] for s in segs) > 0
    assert seen["num_hosts"] >= cell.shadow["hosts"]["peer"]["count"]
    assert got["test.handled_per_host_round"] == handled / rounds / seen["num_hosts"]


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
