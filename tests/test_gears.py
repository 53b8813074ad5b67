"""Occupancy-adaptive merge gears (`experimental.merge_gears`, PR 4):
the shed-exact replay equivalence gate plus controller/ladder units.

The contract mirrors the earlier bit-identity PRs: running the exchange
merge at ANY gear ladder — including chunks that shed and replay one gear
up from the pre-chunk snapshot — produces digests, per-host event counts,
and drop counters bit-identical to the full-width engine, across
echo/phold/tgen, flat and bucketed queue layouts, K in {1, 4}, and
world in {1, 8} (gather AND alltoall exchanges). The gear-1 start forces
real sheds, so the replay path is exercised, not just reachable.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from shadow_tpu.core import Engine
from shadow_tpu.core.checkpoint import restore_snapshot, snapshot_state
from shadow_tpu.core.gears import (
    GearController,
    resolve_gear_ladder,
    run_adaptive_chunk,
)
from tests.engine_harness import build_sim, mk_hosts

# the test_tracer workload trio: short horizons, exchange-heavy enough to
# exercise the merge every round
_CASES = {
    "phold": ("phold", mk_hosts(8, {"mean_delay": "20 ms", "population": 3}),
              300_000_000, dict(loss=0.1)),
    "echo": ("udp_echo",
             [dict(host_id=0, name="server", start_time=0,
                   model_args={"role": "server"})]
             + [dict(host_id=i, name=f"c{i}", start_time=0,
                     model_args={"role": "client", "peer": "server",
                                 "interval": "4 ms", "size_bytes": 2000})
                for i in range(1, 5)],
             200_000_000, dict(bw_bits=2_000_000, loss=0.05)),
    "tgen": ("tgen_tcp",
             mk_hosts(5, {"flow_segs": 8, "flows": 1, "cwnd_cap": 8,
                          "rto_min": "100 ms"}),
             1_500_000_000,
             dict(loss=0.05, latency=10_000_000, sends_budget=16)),
}


def _build(model, hosts, stop, world=1, **kw):
    cfg, m, params, mstate, events = build_sim(
        model, hosts, stop, world=world, **kw
    )
    mesh = None
    if world > 1:
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:world]), ("hosts",)
        )
    eng = Engine(cfg, m, mesh)
    state, params = eng.init_state(params, mstate, events, seed=1)
    return cfg, eng, state, params


def _run_full(model, hosts, stop, world=1, **kw):
    _, eng, state, params = _build(model, hosts, stop, world, **kw)
    while not bool(state.done):
        state = eng.run_chunk(state, params)
    return state


def _run_geared(model, hosts, stop, world=1, start_low=True, **kw):
    """Drive the gear ladder exactly like the drivers do (the shared
    run_adaptive_chunk loop), starting at the LOWEST gear to force sheds."""
    cfg, eng, state, params = _build(model, hosts, stop, world, **kw)
    ladder = resolve_gear_ladder("auto", cfg.sends_per_host_round)
    ctl = GearController(ladder)
    if start_low:
        ctl.gear = ladder[0]
    while not bool(state.done):
        state, _, _ = run_adaptive_chunk(
            ctl, state, lambda st, g: eng.run_chunk_gear(st, params, g)
        )
    return state, ctl


def _assert_identical(full, geared):
    f = jax.device_get(full.stats)
    g = jax.device_get(geared.stats)
    np.testing.assert_array_equal(np.asarray(f.digest), np.asarray(g.digest))
    np.testing.assert_array_equal(np.asarray(f.events), np.asarray(g.events))
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(full.queue.dropped)),
        np.asarray(jax.device_get(geared.queue.dropped)),
    )
    for field in ("pkts_sent", "pkts_lost", "pkts_codel_dropped",
                  "pkts_budget_dropped", "pkts_delivered", "q_occ_hwm"):
        np.testing.assert_array_equal(
            np.asarray(getattr(f, field)), np.asarray(getattr(g, field)),
            err_msg=field,
        )
    # per-SHARD counters ([world]-shaped) compare by total across mesh shapes
    assert int(np.asarray(g.a2a_shed).sum()) == int(np.asarray(f.a2a_shed).sum())
    # accepted chunks never shed (shedding attempts were discarded)
    assert int(np.asarray(g.gear_shed).max()) == 0


@pytest.mark.parametrize("qb", [0, 8], ids=["flat", "bucketed"])
@pytest.mark.parametrize("k", [1, 4], ids=["k1", "k4"])
@pytest.mark.parametrize("case", sorted(_CASES), ids=sorted(_CASES))
def test_gear_ladder_bit_identical_with_forced_replay(case, k, qb):
    """The acceptance gate: a gear-ladder run started at the BOTTOM gear
    (so low-width chunks genuinely shed and replay) finishes bit-identical
    to the full-width engine — digests, events, every drop counter."""
    model, hosts, stop, kw = _CASES[case]
    full = _run_full(model, hosts, stop, queue_block=qb,
                     microstep_events=k, **kw)
    geared, ctl = _run_geared(model, hosts, stop, queue_block=qb,
                              microstep_events=k, **kw)
    _assert_identical(full, geared)
    # the gear-1 start must have forced at least one shed->replay (these
    # workloads all stage multi-send rounds)
    assert ctl.replays > 0


@pytest.mark.parametrize("exchange", ["gather", "alltoall"])
def test_gear_ladder_mesh_invariant(exchange):
    """world=8 dryrun (both exchange strategies): sheds are psum'd so the
    chunk abort is mesh-uniform, and the replayed result matches the
    single-device full-width digest."""
    model, hosts, stop, kw = _CASES["phold"]
    full = _run_full(model, hosts, stop, world=1, **kw)
    geared, ctl = _run_geared(
        model, hosts, stop, world=8, exchange=exchange, **kw
    )
    _assert_identical(full, geared)
    assert ctl.replays > 0


def test_snapshot_survives_donation_and_repeated_restores():
    """The replay loop's memory contract: the snapshot is an independent
    device copy (the jitted chunk donates its input), and each restore
    hands out a FRESH copy so a mid-ladder replay can shed again and
    restore again."""
    model, hosts, stop, kw = _CASES["phold"]
    _, eng, state, params = _build(model, hosts, stop, **kw)
    snap = snapshot_state(state)
    now0 = int(state.now)
    state = eng.run_chunk(state, params)  # donates its input buffers
    assert int(state.now) > now0
    r1 = restore_snapshot(snap)
    r1 = eng.run_chunk(r1, params)  # consumes the first restore...
    r2 = restore_snapshot(snap)  # ...snapshot still serves a second
    assert int(r2.now) == now0
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(r2.stats.digest)),
        np.asarray(jax.device_get(snap.stats.digest)),
    )
    # and the two replays from the same snapshot are bit-identical
    r2 = eng.run_chunk(r2, params)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(r1.stats.digest)),
        np.asarray(jax.device_get(r2.stats.digest)),
    )


def test_outbox_hwm_tracks_max_sends():
    """stats.outbox_hwm (always on) records the max sends any one host
    staged in a round — on a full-width run it never resets, so it bounds
    every round's per-host send count and is > 0 on send-heavy work."""
    model, hosts, stop, kw = _CASES["phold"]
    state = _run_full(model, hosts, stop, **kw)
    hwm = int(np.asarray(jax.device_get(state.stats.outbox_hwm)).max())
    budget = 8  # harness default sends_budget
    assert 0 < hwm <= budget


# ------------------------------------------------------------------ units


def test_resolve_gear_ladder():
    assert resolve_gear_ladder(0, 8) == []
    assert resolve_gear_ladder(None, 8) == []
    assert resolve_gear_ladder(False, 8) == []
    assert resolve_gear_ladder("off", 8) == []  # the documented string form
    assert resolve_gear_ladder("auto", 8) == [1, 2, 4, 8]
    assert resolve_gear_ladder(True, 8) == [1, 2, 4, 8]
    assert resolve_gear_ladder("auto", 24) == [3, 6, 12, 24]
    # tiny budgets collapse duplicate rungs; a ladder of only the full
    # width is no ladder at all
    assert resolve_gear_ladder("auto", 1) == []
    assert resolve_gear_ladder("auto", 2) == [1, 2]
    # explicit lists: sorted, deduped, full width appended
    assert resolve_gear_ladder([4, 1], 8) == [1, 4, 8]
    assert resolve_gear_ladder([8, 2], 8) == [2, 8]
    assert resolve_gear_ladder(2, 8) == [2, 8]
    assert resolve_gear_ladder([8], 8) == []
    with pytest.raises(ValueError):
        resolve_gear_ladder([0, 4], 8)
    with pytest.raises(ValueError):
        resolve_gear_ladder([9], 8)
    with pytest.raises(ValueError):
        resolve_gear_ladder("fast", 8)


def test_gear_controller_policy():
    ctl = GearController([1, 2, 4, 8], down_lag=2)
    assert ctl.gear == 8  # starts at the top (boot occupancy unknown)
    # hwm 1 fits gear 2 (strict headroom) — downshift after down_lag chunks
    assert ctl.note_chunk(8, 1) == 8
    assert ctl.note_chunk(8, 1) == 2
    # exactly-filled width steps up preemptively (hwm == gear)
    assert ctl.note_chunk(2, 2) == 4
    # a shed steps one gear up and counts a replay
    ctl2 = GearController([1, 2, 4, 8])
    ctl2.gear = 1
    assert ctl2.note_shed() == 2
    assert ctl2.note_shed() == 4
    assert ctl2.note_shed() == 8
    assert ctl2.note_shed() == 8  # top clamps
    assert ctl2.replays == 4
    # a shed carrying the aborted chunk's high-water jumps straight to a
    # fitting gear (one replay, not a rung-by-rung walk)
    ctl3 = GearController([1, 2, 4, 8])
    ctl3.gear = 1
    assert ctl3.note_shed(7) == 8
    assert ctl3.replays == 1
    ctl3.gear = 1
    assert ctl3.note_shed(2) == 4  # fit(2)=4 beats the one-rung step
    # accepted-chunk histogram + report shape
    ctl2.note_chunk(8, 3)
    rep = ctl2.report()
    assert rep["ladder"] == [1, 2, 4, 8]
    assert rep["chunks_per_gear"] == {"8": 1}
    assert rep["replays"] == 4


def test_adaptive_chunk_skips_controller_on_zero_round_window():
    """Hybrid guarded windows can retire ZERO rounds (probe fires at
    entry); run_adaptive_chunk must not feed the controller those
    windows' hwm of 0 — two idle windows would otherwise downshift past
    real occupancy and buy the next busy window a guaranteed replay."""
    from typing import Any, NamedTuple

    import jax.numpy as jnp

    class _Stats(NamedTuple):
        gear_shed: Any
        outbox_hwm: Any
        rounds: Any

    class _State(NamedTuple):
        stats: _Stats

    def st(rounds):
        return _State(_Stats(
            jnp.zeros((1,), jnp.int64), jnp.zeros((1,), jnp.int64),
            jnp.asarray(rounds, jnp.int64),
        ))

    ctl = GearController([1, 2, 4, 8], down_lag=1)
    # idle window (rounds unchanged): controller untouched
    _, gear, hwm = run_adaptive_chunk(ctl, st(0), lambda s, g: s, rounds0=0)
    assert ctl.chunks == {} and ctl.gear == 8 and hwm == 0
    # a window that advanced rounds feeds it (hwm 0 -> bottom at lag 1)
    _, gear, _ = run_adaptive_chunk(ctl, st(1), lambda s, g: s, rounds0=0)
    assert ctl.chunks == {8: 1} and ctl.gear == 1


def test_engine_config_rejects_bad_gear():
    from shadow_tpu.core import EngineConfig

    with pytest.raises(ValueError, match="gear_cols"):
        EngineConfig(num_hosts=4, stop_time=1, sends_per_host_round=4,
                     gear_cols=5)
    with pytest.raises(ValueError, match="gear_cols"):
        EngineConfig(num_hosts=4, stop_time=1, gear_cols=-1)


def test_merge_gears_config_parse():
    from shadow_tpu.config.options import ConfigError, ExperimentalOptions

    assert ExperimentalOptions.from_dict(None).merge_gears == 0
    assert ExperimentalOptions.from_dict(
        {"merge_gears": "auto"}
    ).merge_gears == "auto"
    assert ExperimentalOptions.from_dict(
        {"merge_gears": "off"}
    ).merge_gears == 0
    assert ExperimentalOptions.from_dict(
        {"merge_gears": [2, 4]}
    ).merge_gears == [2, 4]
    assert ExperimentalOptions.from_dict({"merge_gears": 2}).merge_gears == 2
    assert ExperimentalOptions.from_dict({"merge_gears": 0}).merge_gears == 0
    with pytest.raises(ConfigError, match="merge_gears"):
        ExperimentalOptions.from_dict({"merge_gears": "fast"})
    with pytest.raises(ConfigError, match="merge_gears"):
        ExperimentalOptions.from_dict({"merge_gears": [2, "x"]})


def test_gear_shed_count_exact():
    import jax.numpy as jnp

    from shadow_tpu.ops.merge import gear_shed_count

    sent = jnp.asarray([0, 1, 2, 5, 8], jnp.int32)
    assert int(gear_shed_count(sent, 2)) == 0 + 0 + 0 + 3 + 6
    assert int(gear_shed_count(sent, 8)) == 0  # full width never sheds
