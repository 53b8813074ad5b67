"""Alltoall block rungs: the auto `a2a_block` as a ladder of block widths
with exact abort-and-replay (core/gears.BlockRungController, the
`a2a_rung` axis of Engine.run_chunk_resized).

The gate mirrors the gears': a run whose blocks follow the rows each
shard pair actually sent, shedding and replaying where a rung proves too
narrow, ends bit-identical to a run pinned at today's auto block
(digests, per-host events, packet counters, drops). The ladder's lowest
rung is lowered here (`RUNG_BASE`) so that test-sized meshes have rungs
below their auto block.
"""

from __future__ import annotations

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.core import Engine
from shadow_tpu.core import gears
from shadow_tpu.core.engine import _run_chunk
from shadow_tpu.core.gears import BlockRungController, resolve_block_ladder
from shadow_tpu.core.pressure import ResilienceController
from shadow_tpu.config.options import PressureOptions
from shadow_tpu.sim import Simulation
from tests.engine_harness import build_sim, mk_hosts

WORLD = 4

_GRAPH = (
    "graph [\n  directed 0\n  node [ id 0 host_bandwidth_down \"{bw}\" "
    "host_bandwidth_up \"{bw}\" ]\n  edge [ source 0 target 0 latency "
    "\"{lat}\" ]\n]"
)

# Simulation-level cases: the two benchmark deployments, shrunk
_SIM_CASES = {
    "phold": dict(
        stop="8 s", bw="81920 Kibit", lat="50 ms", qcap=13, budget=9,
        hosts=144,
        process={"model": "phold", "model_args": {
            "population": 1, "mean_delay": "100 ms", "payload_bytes": 64}},
    ),
    "tgen": dict(
        stop="60 ms", bw="1 Gbit", lat="1 ms", qcap=21, budget=19,
        hosts=64,
        process={"model": "tgen_tcp", "model_args": {
            "flow_segs": 24, "mss": 1460, "flows": 2, "cwnd_cap": 4}},
    ),
}


@pytest.fixture
def low_rungs(monkeypatch):
    monkeypatch.setattr(gears, "RUNG_BASE", 8)


def _needs_mesh():
    if jax.device_count() < WORLD:
        pytest.skip("needs the virtual-device conftest mesh")


def _sim(case, **experimental):
    c = _SIM_CASES[case]
    d = {
        "general": {"stop_time": c["stop"], "seed": 1},
        "network": {"graph": {"type": "gml", "inline": _GRAPH.format(
            bw=c["bw"], lat=c["lat"])}},
        "experimental": {
            "event_queue_capacity": c["qcap"],
            "sends_per_host_round": c["budget"],
            **experimental,
        },
        "hosts": {"peer": {
            "count": c["hosts"], "network_node_id": 0,
            "processes": [copy.deepcopy(c["process"])],
        }},
    }
    return Simulation(ConfigOptions.from_dict(d), world=WORLD)


def _assert_same_run(a, ra, b, rb):
    assert ra["determinism_digest"] == rb["determinism_digest"]
    sa, sb = jax.device_get(a.state.stats), jax.device_get(b.state.stats)
    n = a._num_real
    np.testing.assert_array_equal(
        np.asarray(sa.events)[:n], np.asarray(sb.events)[:n]
    )
    for field in ("pkts_sent", "pkts_delivered", "pkts_lost",
                  "pkts_unreachable", "pkts_codel_dropped",
                  "pkts_budget_dropped", "monotonic_violations"):
        np.testing.assert_array_equal(
            np.asarray(getattr(sa, field))[:n],
            np.asarray(getattr(sb, field))[:n], err_msg=field,
        )
    for key in ("queue_overflow_dropped", "packets_budget_dropped",
                "outbox_overflow_dropped", "alltoall_shed_dropped",
                "events_processed", "packets_sent", "rounds"):
        assert ra[key] == rb[key], key
    assert ra["model_report"] == rb["model_report"]


@pytest.mark.parametrize("case", sorted(_SIM_CASES))
def test_ladder_run_matches_pinned_auto_block(case, low_rungs):
    """The acceptance gate: PHOLD and tgen-TCP on a world-4 mesh, the
    block following the rung ladder, equal a run pinned at `a2a_block` =
    today's auto size."""
    _needs_mesh()
    a = _sim(case)
    assert a.engine_cfg.a2a_ladder
    ra = a.run(progress=False)
    rungs = ra["a2a_rungs"]
    top = a.engine_cfg.a2a_auto_block
    assert rungs["ladder"][-1] == top
    assert rungs["pair_hwm"] > 0
    # the ladder engaged: chunks ran below the top
    assert any(int(k) < top for k in rungs["chunks_per_rung"])
    assert ra["ici_bytes"] > 0
    b = _sim(case, a2a_block=top)
    rb = b.run(progress=False)
    assert "a2a_rungs" not in rb
    _assert_same_run(a, ra, b, rb)
    # the narrower blocks moved fewer bytes over the wire
    assert ra["ici_bytes"] < rb["ici_bytes"]


_ENGINE_CASES = {
    "phold": ("phold", mk_hosts(32, {"mean_delay": "20 ms", "population": 3}),
              300_000_000, dict(loss=0.1)),
    "tgen": ("tgen_tcp",
             mk_hosts(16, {"flow_segs": 8, "flows": 1, "cwnd_cap": 8,
                           "rto_min": "100 ms"}),
             600_000_000,
             dict(loss=0.05, latency=10_000_000, sends_budget=16)),
}


def _build(model, hosts, stop, world=WORLD, pressure_abort=False, **kw):
    cfg, m, params, mstate, events = build_sim(
        model, hosts, stop, world=world, exchange="alltoall",
        rounds_per_chunk=16, **kw
    )
    cfg = dataclasses.replace(cfg, pressure_abort=pressure_abort)
    mesh = None
    if world > 1:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:world]), ("hosts",))
    eng = Engine(cfg, m, mesh)
    state, params = eng.init_state(params, mstate, events, seed=1)
    return cfg, eng, state, params


@pytest.mark.parametrize("case", sorted(_ENGINE_CASES))
def test_too_small_start_rung_sheds_replays_and_matches(case, low_rungs):
    """A controller seated at the lowest rung sheds; each shed aborts the
    chunk, restores the snapshot and replays at a rung that fits, and the
    run ends bit-identical to the top-rung run."""
    _needs_mesh()
    model, hosts, stop, kw = _ENGINE_CASES[case]
    _, eng, full, params = _build(model, hosts, stop, **kw)
    while not bool(full.done):
        full = eng.run_chunk(full, params)

    cfg, eng, state, params = _build(model, hosts, stop, **kw)
    ctl = BlockRungController(cfg.a2a_auto_block)
    assert len(ctl.ladder) > 2
    ctl.gear = ctl.ladder[0]
    rc = ResilienceController(rungctl=ctl)
    while not bool(state.done):
        state, _, _ = rc.run_chunk(
            state,
            lambda s, g, c, b, k: eng.run_chunk_resized(s, params, g, c, b, k),
        )
    assert ctl.replays >= 1
    f, g = jax.device_get(full.stats), jax.device_get(state.stats)
    for field in ("digest", "events", "pkts_sent", "pkts_lost",
                  "pkts_delivered", "pkts_budget_dropped"):
        np.testing.assert_array_equal(
            np.asarray(getattr(f, field)), np.asarray(getattr(g, field)),
            err_msg=field,
        )
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(full.queue.dropped)),
        np.asarray(jax.device_get(state.queue.dropped)),
    )
    assert int(np.asarray(g.a2a_shed).sum()) == int(np.asarray(f.a2a_shed).sum())
    assert int(np.asarray(g.gear_shed).max()) == 0


@pytest.mark.parametrize("case", sorted(_ENGINE_CASES))
def test_too_small_start_rung_discards_in_flight_chunk_and_matches(
    case, low_rungs
):
    """The same seating through the driver loop's pipeline at lag 1: the
    successor is queued before a chunk's summary is read, so each shed
    also discards the chunk in flight; the replays stay exact and the run
    ends bit-identical to the top-rung run."""
    _needs_mesh()
    from shadow_tpu.core.pipeline import ChunkPipeline

    model, hosts, stop, kw = _ENGINE_CASES[case]
    _, eng, full, params = _build(model, hosts, stop, **kw)
    while not bool(full.done):
        full = eng.run_chunk(full, params)

    cfg, eng, state, params = _build(model, hosts, stop, **kw)
    ctl = BlockRungController(cfg.a2a_auto_block)
    ctl.gear = ctl.ladder[0]
    pipe = ChunkPipeline(
        lambda s, g, c, b, k: eng.run_chunk_resized(s, params, g, c, b, k),
        eng.chunk_summary,
        seam=ResilienceController(rungctl=ctl),
        lag=1,
    )
    s = pipe.start(state)
    while not s.done:
        s = pipe.advance()
    state = pipe.state
    rep = pipe.report()
    assert ctl.replays >= 1 and rep["discarded"] >= 1
    assert rep["ahead"] == rep["chunks"]
    f, g = jax.device_get(full.stats), jax.device_get(state.stats)
    for field in ("digest", "events", "pkts_sent", "pkts_lost",
                  "pkts_delivered", "pkts_budget_dropped", "rounds"):
        np.testing.assert_array_equal(
            np.asarray(getattr(f, field)), np.asarray(getattr(g, field)),
            err_msg=field,
        )
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(full.queue.dropped)),
        np.asarray(jax.device_get(state.queue.dropped)),
    )
    assert int(np.asarray(g.gear_shed).max()) == 0


def test_second_run_starts_at_carried_rung_and_compiles_nothing(low_rungs):
    """The controller lives on the Simulation: a second run() from the
    same initial state (the benchmark's replayed segment) starts where
    the first left the rung, runs only programs the first compiled, and
    does not shed."""
    _needs_mesh()
    from shadow_tpu.core.checkpoint import restore_snapshot, snapshot_state

    sim = _sim("phold")
    snap = snapshot_state(sim.state)
    # both runs start from a restored copy, as the benchmark's do (a
    # restored state's leaves can be placed unlike the built state's)
    sim.state = restore_snapshot(snap)
    r1 = sim.run(progress=False)
    ctl = sim._rungctl
    carried = ctl.rung
    programs = set(sim.engine._resized_chunks)
    compiles = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    sim.state = restore_snapshot(snap)
    start = sim._block_rungs().rung
    assert sim._rungctl is ctl
    assert start >= carried
    sim.state = restore_snapshot(snap)
    r2 = sim.run(progress=False)
    assert not compiles
    assert set(sim.engine._resized_chunks) == programs
    assert r2["a2a_rungs"]["replays"] == 0
    assert set(r2["a2a_rungs"]["chunks_per_rung"]) <= set(
        r1["a2a_rungs"]["chunks_per_rung"]
    )
    assert r2["determinism_digest"] == r1["determinism_digest"]
    # `run_chunk` is the program of the rung the next chunk runs at
    assert sim.engine.run_chunk is sim.engine._chunk_program(0, 0, 0, ctl.rung)


def test_explicit_a2a_block_gives_no_ladder(low_rungs):
    _needs_mesh()
    sim = _sim("phold", a2a_block=96)
    assert not sim.engine_cfg.a2a_ladder
    assert sim.state.stats.a2a_pair_hwm is None
    report = sim.run(progress=False)
    assert "a2a_rungs" not in report
    assert sim._rungctl is None
    assert not sim.engine._resized_chunks
    # a rung request cannot move a pinned block
    assert sim.engine.resized_cfg(
        0, sim.engine_cfg.queue_capacity,
        sim.engine_cfg.sends_per_host_round, 8,
    ).a2a_block_size == 96


def test_world1_program_unchanged():
    """World 1 has no alltoall: no pair signal in the carry, and a rung
    request leaves the chunk program's jaxpr exactly as it was."""
    model, hosts, stop, kw = _ENGINE_CASES["phold"]
    cfg, eng, state, params = _build(model, hosts, stop, world=1, **kw)
    assert not cfg.a2a_ladder
    assert state.stats.a2a_pair_hwm is None
    rung = dataclasses.replace(cfg, a2a_rung=8)
    assert not rung.a2a_rung_active
    assert rung.a2a_block_size == cfg.a2a_block_size

    def jaxpr(c):
        return str(jax.make_jaxpr(
            lambda s, p: _run_chunk(c, eng.model, None, s, p)
        )(state, params))

    assert jaxpr(rung) == jaxpr(cfg)


def test_escalate_rebuilds_the_ladder_from_the_regrown_budget(low_rungs):
    """Pressure escalation regrows the outbox; the ladder's top follows
    the auto block at the grown budget, and the escalated run equals the
    one without rungs."""
    _needs_mesh()
    model, hosts, stop, kw = _ENGINE_CASES["phold"]
    kw = {**kw, "sends_budget": 1}

    def escalated(with_rungs):
        cfg, eng, state, params = _build(
            model, hosts, stop, qcap=16, pressure_abort=True, **kw
        )
        specs = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(eng.mesh, s),
            eng.state_specs(),
        )
        ctl = BlockRungController(cfg.a2a_auto_block) if with_rungs else None
        rc = ResilienceController(
            rungctl=ctl,
            rung_top=lambda b: dataclasses.replace(
                cfg, sends_per_host_round=b
            ).a2a_auto_block,
            pressure=PressureOptions(policy="escalate", max_outbox=16),
            reshard=lambda st: jax.device_put(st, specs),
        )

        def dispatch(s, g, c, b, k=0):
            return eng.run_chunk_resized(s, params, g, c, b, k)

        while not bool(state.done):
            state, _, _ = rc.run_chunk(state, dispatch)
        return cfg, state, ctl

    cfg, state, ctl = escalated(True)
    _, ref, _ = escalated(False)
    budget = state.outbox.t.shape[1]
    assert budget > 1
    assert ctl.top == dataclasses.replace(
        cfg, sends_per_host_round=budget
    ).a2a_auto_block
    assert ctl.top == resolve_block_ladder(ctl.top)[-1]
    s, r = jax.device_get(state.stats), jax.device_get(ref.stats)
    np.testing.assert_array_equal(np.asarray(s.digest), np.asarray(r.digest))
    np.testing.assert_array_equal(np.asarray(s.events), np.asarray(r.events))
    assert int(np.asarray(s.pkts_budget_dropped).sum()) == 0


# ---------------------------------------------------------------------------
# the controller alone
# ---------------------------------------------------------------------------


def test_block_ladder_resolution():
    assert resolve_block_ladder(45_000) == [
        512, 1024, 2048, 4096, 8192, 16384, 32768, 45_000,
    ]
    assert resolve_block_ladder(4096) == [512, 1024, 2048, 4096]
    assert resolve_block_ladder(300) == [300]


def test_block_rung_controller_fit_and_hysteresis():
    ctl = BlockRungController(45_000)
    # the first run seats the rung from the state: 2 x per-pair pending
    assert ctl.begin_run(2_500) == 8192
    # twice the high-water must fit: 1024 fits 2048, 1025 does not
    assert ctl._fit(1024) == 2048
    assert ctl._fit(1025) == 4096
    assert ctl._fit(30_000) == 45_000
    # downshift only after DOWN_LAG chunks that fit lower
    assert ctl.note_chunk(8192, 900) == 8192
    assert ctl.note_chunk(8192, 900) == 2048
    # upshift at once
    assert ctl.note_chunk(2048, 1500) == 4096
    # a shed replays at the fit of what the aborted chunk saw
    assert ctl.note_shed(9_000) == 32768
    assert ctl.replays == 1
    rep = ctl.report()
    assert rep["pair_hwm"] == 9_000
    assert rep["chunks_per_rung"] == {"2048": 1, "8192": 2}
    # a later run starts at the carried rung or the state's, the larger,
    # and counts its own chunks and replays
    assert ctl.begin_run(100) == 32768
    assert ctl.report()["replays"] == 0
    ctl.gear = 2048
    assert ctl.begin_run(2_500) == 8192
    # a regrown outbox moves the top; rungs below it stay
    ctl.retop(90_000)
    assert ctl.top == 90_000 and ctl.rung == 8192
    ctl.gear = ctl.top
    ctl.retop(60_000)
    assert ctl.rung == 60_000


def test_pair_signal_is_per_shard_max_of_rows_sent(low_rungs):
    """`stats.a2a_pair_hwm` reads the most rows one shard pair sent in a
    round, before truncation: at least the total sent / pairs / rounds."""
    _needs_mesh()
    model, hosts, stop, kw = _ENGINE_CASES["phold"]
    cfg, eng, state, params = _build(model, hosts, stop, **kw)
    state = eng.run_chunk(state, params)
    hwm = np.asarray(jax.device_get(state.stats.a2a_pair_hwm))
    sent = int(jnp.sum(state.stats.pkts_sent))
    rounds = int(state.stats.rounds)
    assert hwm.shape == (WORLD,)
    assert 0 < hwm.max() <= cfg.a2a_auto_block
    assert hwm.max() * WORLD * WORLD * rounds >= sent
