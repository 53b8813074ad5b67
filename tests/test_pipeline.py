"""The driver loop's chunk pipeline (core/pipeline.py): one chunk in
flight, each chunk's boundary numbers read one chunk late from one small
summary.

Gates:
  - exactness: a lag-1 run equals the same configuration forced to lag 0
    by an observer that changes no result (the runtime observatory) —
    determinism digest, per-host events, packet counters, rounds and
    model state — for PHOLD at world 1 and world 4 (alltoall, rung
    ladder on) and tgen-TCP at world 1;
  - the heartbeat prints the same line for the same chunk at either lag,
    apart from the wall-clock fields and what the observer adds (`rt=`);
  - a chunk dispatched on a `done` state leaves every leaf bit-identical
    (the successor in flight when the run ends is the run's result);
  - the summary folds the shards' rows to what the host reads lane by
    lane.
"""

from __future__ import annotations

import copy
import dataclasses
import io

import jax
import numpy as np
import pytest

from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.core import Engine, gears
from shadow_tpu.core.checkpoint import snapshot_state
from shadow_tpu.core.pipeline import host_summary
from shadow_tpu.sim import Simulation
from tests.engine_harness import build_sim, mk_hosts
from tests.test_a2a_rungs import _GRAPH, _SIM_CASES, _assert_same_run
from tools.parse_shadow import HEARTBEAT_RE

# (deployment, world, extra experimental keys)
_RUNS = {
    "phold-w1": ("phold", 1, {}),
    "phold-w4": ("phold", 4, {}),
    "tgen-w1": ("tgen", 1, {}),
}


@pytest.fixture
def low_rungs(monkeypatch):
    monkeypatch.setattr(gears, "RUNG_BASE", 8)


def _sim(case, world, *, runtime=False, heartbeat=None, **experimental):
    c = _SIM_CASES[case]
    general = {"stop_time": c["stop"], "seed": 1}
    if heartbeat:
        general["heartbeat_interval"] = heartbeat
    d = {
        "general": general,
        "network": {"graph": {"type": "gml", "inline": _GRAPH.format(
            bw=c["bw"], lat=c["lat"])}},
        "experimental": {
            "event_queue_capacity": c["qcap"],
            "sends_per_host_round": c["budget"],
            "rounds_per_chunk": 16,
            **experimental,
        },
        "observability": {"runtime": runtime},
        "hosts": {"peer": {
            "count": c["hosts"], "network_node_id": 0,
            "processes": [copy.deepcopy(c["process"])],
        }},
    }
    return Simulation(ConfigOptions.from_dict(d), world=world)


def _needs(world):
    if jax.device_count() < world:
        pytest.skip("needs the virtual-device conftest mesh")


@pytest.mark.parametrize("run", sorted(_RUNS))
def test_lag_one_equals_lag_zero(run, low_rungs):
    case, world, extra = _RUNS[run]
    _needs(world)
    a = _sim(case, world, **extra)
    ra = a.run(progress=False)
    b = _sim(case, world, runtime=True, **extra)
    rb = b.run(progress=False)
    pa, pb = ra["chunk_pipeline"], rb["chunk_pipeline"]
    assert (pa["lag"], pb["lag"]) == (1, 0)
    assert pa["chunks"] == pb["chunks"] > 1
    assert pa["ahead"] == pa["chunks"] and pb["ahead"] == 0
    assert pa["discarded"] == pb["discarded"] == 0
    _assert_same_run(a, ra, b, rb)
    assert ra["microsteps"] == rb["microsteps"]
    ma, mb = jax.device_get(a.state.model), jax.device_get(b.state.model)
    for k in ma:
        np.testing.assert_array_equal(
            np.asarray(ma[k]), np.asarray(mb[k]), err_msg=k
        )
    if world > 1:
        # the rung ladder engaged; at lag 1 a downshift lands a chunk later
        assert "a2a_rungs" in ra and "a2a_rungs" in rb
        assert sum(ra["a2a_rungs"]["chunks_per_rung"].values()) == pa["chunks"]


# what a heartbeat reads off the host, not the chunk: the wall clock,
# the process's resources, and the runtime observatory's `rt=`
_HOST_KEYS = {"wall", "ratio", "rt", "rss_gib", "utime_min", "stime_min",
              "mem_avail_gib"}


def _heartbeats(sim):
    log = io.StringIO()
    sim.run(progress=False, log=log)
    out = []
    for line in log.getvalue().splitlines():
        m = HEARTBEAT_RE.search(line)
        if m:
            d = {k: v for k, v in m.groupdict().items() if v is not None}
            out.append({k: v for k, v in d.items() if k not in _HOST_KEYS})
    return out


@pytest.mark.parametrize("run", ["phold-w1", "phold-w4-hier"])
def test_heartbeat_lines_match_across_lags(run):
    """Every heartbeat field but the host's own (wall clock, resources)
    and the observer's (`rt=`) is read from the accepted chunk's summary:
    the lag-1 run prints the lag-0 run's lines, line for line."""
    extra = {"exchange": "hierarchical"} if run.endswith("hier") else {}
    world = 4 if "w4" in run else 1
    _needs(world)
    a = _heartbeats(_sim("phold", world, heartbeat="500 ms", **extra))
    b = _heartbeats(_sim("phold", world, runtime=True, heartbeat="500 ms",
                         **extra))
    assert len(a) > 4
    assert a == b
    if extra:
        assert "xw_inter" in a[-1]


@pytest.mark.parametrize("world", [1, 4])
def test_chunk_on_done_state_is_identity(world, low_rungs):
    """A chunk dispatched on a `done` state runs zero rounds: every leaf
    of its output equals its input, bit for bit (at world 4 also at a
    rung below the top, the program the last chunk in flight may run)."""
    _needs(world)
    hosts = mk_hosts(32, {"mean_delay": "20 ms", "population": 3})
    cfg, m, params, mstate, events = build_sim(
        "phold", hosts, 300_000_000, world=world, loss=0.1,
        exchange="alltoall" if world > 1 else "gather", rounds_per_chunk=16,
    )
    mesh = None
    if world > 1:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:world]), ("hosts",))
    eng = Engine(cfg, m, mesh)
    state, params = eng.init_state(params, mstate, events, seed=1)
    while not bool(state.done):
        state = eng.run_chunk(state, params)
    before = jax.device_get(snapshot_state(state))
    programs = [lambda s: eng.run_chunk(s, params)]
    if world > 1:
        assert eng.cfg.a2a_ladder
        low = gears.resolve_block_ladder(eng.cfg.a2a_auto_block)[0]
        programs.append(
            lambda s: eng.run_chunk_resized(s, params, 0, 0, 0, low)
        )
    for run in programs:
        after = jax.device_get(run(snapshot_state(state)))
        flat_b, tree_b = jax.tree_util.tree_flatten(before)
        flat_a, tree_a = jax.tree_util.tree_flatten(after)
        assert tree_a == tree_b
        for x, y in zip(flat_b, flat_a):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("world", [1, 4])
def test_device_summary_equals_host_summary(world):
    """`Engine.chunk_summary` (a row per shard, folded on the host) reads
    what the host reads lane by lane, at world 1 and on a mesh."""
    _needs(world)
    hosts = mk_hosts(32, {"mean_delay": "20 ms", "population": 3})
    cfg, m, params, mstate, events = build_sim(
        "phold", hosts, 300_000_000, world=world, loss=0.1,
        exchange="alltoall" if world > 1 else "gather", rounds_per_chunk=8,
        integrity=True,
    )
    cfg = dataclasses.replace(cfg, pressure_abort=True)
    mesh = None
    if world > 1:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:world]), ("hosts",))
    eng = Engine(cfg, m, mesh)
    state, params = eng.init_state(params, mstate, events, seed=1)
    state = eng.run_chunk(state, params)
    dev, host = eng.chunk_summary(state).read(), host_summary(state)
    assert vars(dev) == vars(host)
    assert dev.rounds == int(state.stats.rounds) > 0
    assert dev.events == int(np.asarray(state.stats.events).sum()) > 0
    assert dev.pressure == 0 and dev.integrity == 0
    assert dev.iv_signature == () and dev.done == bool(state.done)
    assert dev.now == int(state.now)
