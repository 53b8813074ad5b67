"""Pressure plane (`pressure:` config block, PR 8): exactness-gated
capacity migration, drop-free escalation, abort policy, OOM fallback,
and cross-capacity checkpoint restore.

The acceptance contract mirrors the gear plane's: an escalate-mode run
that WOULD drop at the seed capacity finishes with zero drops and a
digest bit-identical to a run launched at the final shape (with the
valve pins Engine.run_chunk_resized documents); `pressure: drop` (the
default) traces no pressure code at all; a forced-OOM fallback path runs
without killing the process. Engine-harness runs only — the stable
in-process path on this box (CHANGES.md env notes)."""

from __future__ import annotations

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.config.options import ConfigError, PressureOptions
from shadow_tpu.core import Engine
from shadow_tpu.core.pressure import (
    PressureAbort,
    ResilienceController,
    resolve_ladder,
)
from shadow_tpu.ops.events import (
    ORDER_MAX,
    bucket_rebuild,
    grow_bucket_queue,
    grow_queue,
    make_bucket_queue,
    make_queue,
    migrate_queue,
    migration_fits,
    pack_order,
    q_clear_popped,
    q_len,
    q_pop_k,
    q_pop_min,
    q_push_many,
)
from shadow_tpu.simtime import TIME_MAX
from tests.engine_harness import build_sim, mk_hosts

MS = 1_000_000


# ---------------------------------------------------------------------------
# grow-op property tests: any push/pop sequence replayed across C < C'
# ---------------------------------------------------------------------------


def _mk(h, cap, block):
    return (
        make_bucket_queue(h, cap, block) if block else make_queue(h, cap)
    )


def _random_ops(rng, h, n_steps):
    """A reproducible (op, args) schedule heavy enough to overflow small
    capacities: bursts of pushes with unique order keys + windowed pops."""
    ops = []
    seq = 0
    t_base = 0
    for _ in range(n_steps):
        kind = rng.integers(0, 3)
        if kind == 0:  # push burst
            burst = []
            for _ in range(int(rng.integers(1, 4))):
                t = t_base + int(rng.integers(0, 50)) * MS
                burst.append((t, seq))
                seq += 1
            ops.append(("push", burst))
        elif kind == 1:  # windowed pop
            ops.append(("pop", t_base + int(rng.integers(10, 80)) * MS))
        else:  # K-way pop
            ops.append(("popk", t_base + int(rng.integers(10, 80)) * MS))
        t_base += int(rng.integers(0, 20)) * MS
    return ops


def _apply(q, op, k):
    """Apply one schedule step; returns (q', observation tuple)."""
    name, arg = op
    h = q.t.shape[0]
    if name == "push":
        pushes = []
        for t, seq in arg:
            mask = jnp.ones((h,), bool)
            order = pack_order(1, jnp.arange(h, dtype=jnp.int64), seq)
            pushes.append((
                mask,
                jnp.full((h,), t, jnp.int64),
                order,
                jnp.full((h,), 3, jnp.int32),
                jnp.full((h, 4), seq, jnp.int32),
            ))
        q = q_push_many(q, pushes)
        return q, ("push", np.asarray(q.dropped).copy())
    if name == "pop":
        q, ev, active = q_pop_min(q, jnp.int64(arg))
        return q, (
            "pop", np.asarray(ev.t).copy(), np.asarray(ev.order).copy(),
            np.asarray(active).copy(),
        )
    popped = q_pop_k(q, jnp.int64(arg), k)
    m = jnp.sum(popped.active.astype(jnp.int32), axis=1)
    q = q_clear_popped(q, popped, m)
    return q, (
        "popk", np.asarray(popped.t).copy(), np.asarray(popped.order).copy(),
        np.asarray(popped.active).copy(),
    )


@pytest.mark.parametrize("block", [0, 4], ids=["flat", "bucketed"])
@pytest.mark.parametrize("k", [1, 4], ids=["k1", "k4"])
def test_grow_midstream_equals_big_capacity(block, k):
    """The migration exactness property: run a random push/pop schedule;
    path A starts at C=8 and GROWS to C'=16 at a drop-free cut point,
    path B runs the whole schedule at C'=16. Every observation after the
    cut — popped events, actives, drop deltas, occupancies — must be
    bit-identical (before the cut the small queue may drop; the cut is
    chosen after a drain so both paths hold the same event multiset)."""
    rng = np.random.default_rng(1234 + block * 10 + k)
    h, c_small, c_big = 5, 8, 16
    ops = _random_ops(rng, h, 24)
    # phase 1 is drop-free by construction: small bursts + draining pops
    warm = [("push", [(5 * MS, 900), (7 * MS, 901)]), ("pop", 100 * MS)]

    qa = _mk(h, c_small, block)
    for op in warm:
        qa, _ = _apply(qa, op, k)
    drops_a0 = np.asarray(qa.dropped).copy()
    assert drops_a0.sum() == 0, "warm phase must be drop-free"
    qb = _mk(h, c_big, block)
    for op in warm:
        qb, _ = _apply(qb, op, k)
    # the cut: grow path A to the big capacity
    qa = (
        grow_bucket_queue(qa, c_big) if block else grow_queue(qa, c_big)
    )
    np.testing.assert_array_equal(np.asarray(q_len(qa)), np.asarray(q_len(qb)))
    for i, op in enumerate(ops):
        qa, obs_a = _apply(qa, op, k)
        qb, obs_b = _apply(qb, op, k)
        for x, y in zip(obs_a, obs_b):
            if isinstance(x, str):
                assert x == y
            else:
                np.testing.assert_array_equal(x, y, err_msg=f"op {i} {op[0]}")
        np.testing.assert_array_equal(
            np.asarray(qa.dropped), np.asarray(qb.dropped), err_msg=f"op {i}"
        )
        np.testing.assert_array_equal(
            np.asarray(q_len(qa)), np.asarray(q_len(qb)), err_msg=f"op {i}"
        )


def test_grow_preserves_events_and_caches():
    """Growth pads empty sentinel columns only: the live slots are
    untouched, and a grown bucketed queue's caches equal a wholesale
    rebuild of its slab (the block-min invariant holds post-grow)."""
    q = make_queue(3, 4)
    q = q_push_many(q, [(
        jnp.ones((3,), bool), jnp.full((3,), 7 * MS, jnp.int64),
        pack_order(1, jnp.arange(3, dtype=jnp.int64), 0),
        jnp.full((3,), 2, jnp.int32), jnp.zeros((3, 4), jnp.int32),
    )])
    g = grow_queue(q, 8)
    assert g.t.shape == (3, 8)
    np.testing.assert_array_equal(np.asarray(g.t[:, :4]), np.asarray(q.t))
    assert (np.asarray(g.t[:, 4:]) == TIME_MAX).all()
    assert (np.asarray(g.order[:, 4:]) == ORDER_MAX).all()
    bq = bucket_rebuild(q, 2)
    gb = grow_bucket_queue(bq, 8)
    ref = bucket_rebuild(gb, gb.block)
    for field in ("bt", "bo", "bfill"):
        np.testing.assert_array_equal(
            np.asarray(getattr(gb, field)), np.asarray(getattr(ref, field)),
        )


def test_shrink_compacts_and_refuses_overfull():
    """Shrink compacts live events to the front (stable) and the
    `migration_fits` predicate names exactly the hosts that cannot."""
    q = make_queue(2, 8)
    pushes = []
    for s in range(5):
        mask = jnp.asarray([True, s < 2])  # host 0: 5 live, host 1: 2
        pushes.append((
            mask, jnp.full((2,), (s + 1) * MS, jnp.int64),
            pack_order(1, jnp.arange(2, dtype=jnp.int64), s),
            jnp.full((2,), 1, jnp.int32), jnp.zeros((2, 4), jnp.int32),
        ))
    q = q_push_many(q, pushes)
    fits = np.asarray(migration_fits(q, 4))
    np.testing.assert_array_equal(fits, [False, True])
    assert np.asarray(migration_fits(q, 5)).all()
    small = migrate_queue(q, 5)
    assert small.t.shape == (2, 5)
    # identical pop sequence off the compacted slab
    a, b = q, small
    for _ in range(5):
        a, ev_a, act_a = q_pop_min(a, jnp.int64(100 * MS))
        b, ev_b, act_b = q_pop_min(b, jnp.int64(100 * MS))
        np.testing.assert_array_equal(np.asarray(ev_a.t), np.asarray(ev_b.t))
        np.testing.assert_array_equal(
            np.asarray(ev_a.order), np.asarray(ev_b.order)
        )
        np.testing.assert_array_equal(np.asarray(act_a), np.asarray(act_b))


def test_migrate_queue_validation():
    q = make_queue(2, 8)
    with pytest.raises(ValueError, match="new_capacity"):
        migrate_queue(q, 0)
    with pytest.raises(ValueError, match="block"):
        migrate_queue(q, 8, block=3)
    with pytest.raises(ValueError, match="exceed"):
        grow_queue(q, 8)


# ---------------------------------------------------------------------------
# escalate end-to-end: digest gate vs a run launched at the final shape
# ---------------------------------------------------------------------------

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


def _build(model, hosts, stop, pressure_abort=False, **kw):
    cfg, m, params, mstate, events = build_sim(
        model, hosts, stop, rounds_per_chunk=16, **kw
    )
    if pressure_abort:
        cfg = dataclasses.replace(cfg, pressure_abort=True)
    eng = Engine(cfg, m)
    state, params = eng.init_state(params, mstate, events, seed=1)
    return cfg, eng, state, params


def _run_escalated(model, hosts, stop, policy="escalate", **kw):
    cfg, eng, state, params = _build(
        model, hosts, stop, pressure_abort=True, **kw
    )
    rc = ResilienceController(
        pressure=PressureOptions(policy=policy, max_capacity=256,
                                 max_outbox=64),
        queue_block=cfg.queue_block,
    )
    chunks = 0
    while not bool(state.done):
        state, _, _ = rc.run_chunk(
            state,
            lambda s, g, c, b: eng.run_chunk_resized(s, params, g, c, b),
        )
        chunks += 1
        assert chunks < 500
    return cfg, state, rc


def _assert_drop_free_and_identical(state, ref):
    s, r = jax.device_get(state.stats), jax.device_get(ref.stats)
    np.testing.assert_array_equal(np.asarray(s.digest), np.asarray(r.digest))
    np.testing.assert_array_equal(np.asarray(s.events), np.asarray(r.events))
    for field in ("pkts_sent", "pkts_lost", "pkts_delivered",
                  "pkts_budget_dropped"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s, field)), np.asarray(getattr(r, field)),
            err_msg=field,
        )
    assert int(np.asarray(jax.device_get(state.queue.dropped)).sum()) == 0
    assert int(np.asarray(s.pkts_budget_dropped).sum()) == 0
    press = np.asarray(s.pressure) if s.pressure is not None else None
    assert press is None or int(press.max()) == 0


@pytest.mark.parametrize("qb", [0, 4], ids=["flat", "bucketed"])
@pytest.mark.parametrize("k", [1, 4], ids=["k1", "k4"])
@pytest.mark.parametrize("case", sorted(_CASES), ids=sorted(_CASES))
def test_escalate_drop_free_and_bit_identical(case, k, qb):
    """The acceptance gate: starting from a queue capacity that WOULD
    drop, the escalate policy finishes with zero drops and digests /
    events / drop counters bit-identical to a run LAUNCHED at the final
    shape (same pinned valve), having genuinely regrown along the way."""
    model, hosts, stop, kw = _CASES[case]
    # per-case undersized start capacity (small enough that the workload
    # GENUINELY pressures it; phold's population-3 steady state fits 8)
    qcap0 = {"phold": 4, "echo": 8, "tgen": 4}[case]
    cfg0, state, rc = _run_escalated(
        model, hosts, stop, qcap=qcap0, queue_block=qb,
        microstep_events=k, **kw,
    )
    cap_f = state.queue.t.shape[1]
    budget_f = state.outbox.t.shape[1]
    assert rc.regrows + rc.proactive_regrows > 0, "nothing escalated"
    # reference: LAUNCHED at the final shape with the escalation's pins
    # (valve = base effective limit; auto max_round_inserts follows cap)
    _, eng_r, ref, params_r = _build(
        model, hosts, stop,
        qcap=cap_f, queue_block=qb, microstep_events=k,
        **{**kw, "sends_budget": budget_f},
    )
    eng_r.cfg = dataclasses.replace(
        eng_r.cfg, microstep_limit=cfg0.effective_microstep_limit,
        max_round_inserts=cap_f if cfg0.max_round_inserts == qcap0
        else cfg0.max_round_inserts,
    )
    eng_r._build_run_chunk()
    while not bool(ref.done):
        ref = eng_r.run_chunk(ref, params_r)
    _assert_drop_free_and_identical(state, ref)


@pytest.mark.parametrize("case", ["phold", "tgen"])
def test_escalate_with_a_chunk_in_flight_matches_lag_zero(case):
    """Escalation through the driver loop's pipeline at lag 1: a drop is
    seen one chunk late, in the summary, with the successor already
    queued; the successor is discarded, the dropping chunk replayed at
    the grown shape, and the run equals the lag-0 seam's, shapes and
    regrows included."""
    from shadow_tpu.core.pipeline import ChunkPipeline

    model, hosts, stop, kw = _CASES[case]
    qcap0 = 4
    cfg0, ref, rc0 = _run_escalated(model, hosts, stop, qcap=qcap0, **kw)
    cfg, eng, state, params = _build(
        model, hosts, stop, pressure_abort=True, qcap=qcap0, **kw
    )
    rc = ResilienceController(
        pressure=PressureOptions(policy="escalate", max_capacity=256,
                                 max_outbox=64),
        queue_block=cfg.queue_block,
    )
    pipe = ChunkPipeline(
        lambda s, g, c, b: eng.run_chunk_resized(s, params, g, c, b),
        eng.chunk_summary, seam=rc, lag=1,
    )
    s = pipe.start(state)
    while not s.done:
        s = pipe.advance()
        assert pipe.chunks < 500
    state = pipe.state
    assert rc.replays >= 1 and pipe.discarded >= 1
    assert (rc.regrows, rc.proactive_regrows, rc.replays) == (
        rc0.regrows, rc0.proactive_regrows, rc0.replays
    )
    assert state.queue.t.shape == ref.queue.t.shape
    assert state.outbox.t.shape == ref.outbox.t.shape
    _assert_drop_free_and_identical(state, ref)


def test_escalate_mesh_invariant():
    """world=8 dryrun: the pressure signal is psum'd, so the first-drop
    abort is mesh-uniform, migration re-shards onto the mesh specs, and
    the escalated result matches the single-device run launched at the
    final shape."""
    model, hosts, stop, kw = _CASES["phold"]
    if jax.device_count() < 8:
        pytest.skip("needs the 8-virtual-device conftest mesh")
    cfg, m, params, mstate, events = build_sim(
        model, hosts, stop, world=8, qcap=4, rounds_per_chunk=16, **kw
    )
    cfg = dataclasses.replace(cfg, pressure_abort=True)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]), ("hosts",))
    eng = Engine(cfg, m, mesh)
    state, params = eng.init_state(params, mstate, events, seed=1)
    specs = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), eng.state_specs()
    )
    rc = ResilienceController(
        pressure=PressureOptions(policy="escalate", max_capacity=256),
        reshard=lambda st: jax.device_put(st, specs),
    )
    while not bool(state.done):
        state, _, _ = rc.run_chunk(
            state,
            lambda s, g, c, b: eng.run_chunk_resized(s, params, g, c, b),
        )
    assert rc.regrows + rc.proactive_regrows > 0
    assert int(np.asarray(jax.device_get(state.queue.dropped)).sum()) == 0
    cap_f = state.queue.t.shape[1]
    _, eng_r, ref, params_r = _build(
        model, hosts, stop, qcap=cap_f, **kw
    )
    eng_r.cfg = dataclasses.replace(
        eng_r.cfg, microstep_limit=cfg.effective_microstep_limit,
        max_round_inserts=cap_f,
    )
    eng_r._build_run_chunk()
    while not bool(ref.done):
        ref = eng_r.run_chunk(ref, params_r)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(state.stats.digest)),
        np.asarray(jax.device_get(ref.stats.digest)),
    )


def test_gears_only_controller_dispatches_base_shapes():
    """Regression (r8 review): a gears-only ResilienceController (no
    pressure block — exactly how Simulation.run and bench wire it) never
    reads the state's shapes and passes capacity/budget 0 to the
    dispatch; Engine.run_chunk_resized must treat 0 as the BASE shape,
    not compile a zero-width program."""
    from shadow_tpu.core.gears import GearController, resolve_gear_ladder

    model, hosts, stop, kw = _CASES["phold"]
    cfg, eng, state, params = _build(model, hosts, stop, qcap=16, **kw)
    ladder = resolve_gear_ladder([2, 4], cfg.sends_per_host_round)
    rc = ResilienceController(gearctl=GearController(ladder))
    while not bool(state.done):
        state, _, _ = rc.run_chunk(
            state,
            lambda s, g, c, b: eng.run_chunk_resized(s, params, g, c, b),
        )
    _, eng_r, refst, params_r = _build(model, hosts, stop, qcap=16, **kw)
    while not bool(refst.done):
        refst = eng_r.run_chunk(refst, params_r)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(state.stats.digest)),
        np.asarray(jax.device_get(refst.stats.digest)),
    )


def test_oom_on_outbox_only_growth_falls_back():
    """Regression (r8 review): an OOM on a program grown only on the
    OUTBOX axis must fall back (and poison the outbox rung) instead of
    re-raising — and the poisoned rung corners the next budget drop into
    a graceful PressureAbort."""
    model, hosts, stop, kw = _CASES["phold"]
    kw = {**kw, "sends_budget": 1}
    cfg, eng, state, params = _build(
        model, hosts, stop, qcap=16, pressure_abort=True, **kw
    )
    rc = ResilienceController(
        pressure=PressureOptions(policy="escalate", max_outbox=8),
    )

    class XlaRuntimeError(RuntimeError):
        pass

    def dispatch(s, g, c, b):
        if b > 1:
            raise XlaRuntimeError("RESOURCE_EXHAUSTED: outbox slab")
        return eng.run_chunk_resized(s, params, g, c, b)

    with pytest.raises(PressureAbort, match="cornered"):
        while not bool(state.done):
            state, _, _ = rc.run_chunk(state, dispatch)
    assert rc.oom_fallbacks >= 1
    assert rc.report()["outbox_poisoned"]
    assert rc.abort_export_state() is not None


def test_oom_fallback_refuses_truncating_shrink():
    """Regression (r8 review 2): an OOM fallback whose lower rung can no
    longer hold the live events must corner into a loud PressureAbort —
    silently compact-truncating them would be exactly the loss the
    plane exists to prevent."""
    from shadow_tpu.ops.events import grow_queue

    model, hosts, stop, kw = _CASES["phold"]
    cfg, eng, state, params = _build(
        model, hosts, stop, qcap=4, pressure_abort=True, **kw
    )
    # simulate a prior escalation 4 -> 8 whose occupancy then rose past
    # the base rung: grow the slab and stuff it to 6 live events/host
    h = state.queue.t.shape[0]
    q = grow_queue(state.queue, 8)
    extra = [(
        jnp.ones((h,), bool), jnp.full((h,), 50 * MS, jnp.int64),
        pack_order(1, jnp.arange(h, dtype=jnp.int64), 7000 + i),
        jnp.full((h,), 3, jnp.int32), jnp.zeros((h, 4), jnp.int32),
    ) for i in range(3)]
    state = state._replace(queue=q_push_many(q, extra))
    assert int(np.asarray(jax.device_get(q_len(state.queue))).max()) > 4
    rc = ResilienceController(
        pressure=PressureOptions(policy="escalate", max_capacity=64),
    )
    rc._cap_ladder = [4, 8, 16, 32, 64]
    rc._box_ladder = [state.outbox.t.shape[1]]

    class XlaRuntimeError(RuntimeError):
        pass

    def dispatch(s, g, c, b):
        raise XlaRuntimeError("RESOURCE_EXHAUSTED: transient")

    with pytest.raises(PressureAbort, match="no longer fit"):
        rc.run_chunk(state, dispatch)
    assert rc.aborted
    # the pre-chunk snapshot (grown shape, events intact) still exports
    good = rc.abort_export_state()
    assert good is not None and good.queue.t.shape[1] == 8


def test_gears_and_escalate_compose():
    """Both axes through the one snapshot-replay loop: a gear ladder
    started at the bottom (forcing shed replays) composes with capacity
    escalation (forcing regrow replays) — the accepted result is still
    bit-identical to the full-width run launched at the final shape."""
    from shadow_tpu.core.gears import GearController, resolve_gear_ladder

    model, hosts, stop, kw = _CASES["phold"]
    cfg, eng, state, params = _build(
        model, hosts, stop, qcap=4, pressure_abort=True, **kw
    )
    ladder = resolve_gear_ladder("auto", cfg.sends_per_host_round)
    gearctl = GearController(ladder)
    gearctl.gear = ladder[0]  # bottom start forces real sheds
    rc = ResilienceController(
        gearctl=gearctl,
        pressure=PressureOptions(policy="escalate", max_capacity=256),
    )
    while not bool(state.done):
        state, _, _ = rc.run_chunk(
            state,
            lambda s, g, c, b: eng.run_chunk_resized(s, params, g, c, b),
        )
    assert gearctl.replays > 0 and rc.regrows + rc.proactive_regrows > 0
    assert int(np.asarray(jax.device_get(state.queue.dropped)).sum()) == 0
    cap_f = state.queue.t.shape[1]
    _, eng_r, ref, params_r = _build(model, hosts, stop, qcap=cap_f, **kw)
    eng_r.cfg = dataclasses.replace(
        eng_r.cfg, microstep_limit=cfg.effective_microstep_limit,
        max_round_inserts=cap_f,
    )
    eng_r._build_run_chunk()
    while not bool(ref.done):
        ref = eng_r.run_chunk(ref, params_r)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(state.stats.digest)),
        np.asarray(jax.device_get(ref.stats.digest)),
    )


def test_escalate_would_have_dropped():
    """Evidence the gate is not vacuous: the same workload at the seed
    capacity under the default drop policy genuinely sheds."""
    model, hosts, stop, kw = _CASES["phold"]
    _, eng, state, params = _build(model, hosts, stop, qcap=4, **kw)
    while not bool(state.done):
        state = eng.run_chunk(state, params)
    assert int(np.asarray(jax.device_get(state.queue.dropped)).sum()) > 0


def test_escalate_grows_outbox_on_budget_pressure():
    """Send-budget drops are pressure too: a tiny outbox escalates to a
    wider one and the accepted run carries zero budget drops."""
    model, hosts, stop, kw = _CASES["phold"]
    kw = {**kw, "sends_budget": 1}
    _, state, rc = _run_escalated(model, hosts, stop, qcap=16, **kw)
    assert state.outbox.t.shape[1] > 1
    assert int(np.asarray(
        jax.device_get(state.stats.pkts_budget_dropped)
    ).sum()) == 0


def test_abort_policy_stops_at_first_drop():
    """`pressure: abort`: the run raises at the first dropping chunk and
    the export state is the honest record — drops visible, flagged."""
    model, hosts, stop, kw = _CASES["phold"]
    cfg, eng, state, params = _build(
        model, hosts, stop, qcap=4, pressure_abort=True, **kw
    )
    rc = ResilienceController(pressure=PressureOptions(policy="abort"))
    with pytest.raises(PressureAbort, match="first capacity drop"):
        while not bool(state.done):
            state, _, _ = rc.run_chunk(
                state,
                lambda s, g, c, b: eng.run_chunk_resized(s, params, g, c, b),
            )
    assert rc.aborted
    exported = rc.abort_export_state()
    assert exported is not None
    total = (
        int(np.asarray(jax.device_get(exported.queue.dropped)).sum())
        + int(np.asarray(
            jax.device_get(exported.stats.pkts_budget_dropped)
        ).sum())
    )
    assert total > 0  # the drop is IN the honest record


def test_oom_fallback_survives_and_corners_gracefully():
    """Forced-OOM degradation: the grown program's dispatch raising the
    RESOURCE_EXHAUSTED signature falls back one rung (process alive,
    counted), and with drops persisting and every higher rung poisoned
    the controller aborts via PressureAbort with the last good pre-chunk
    snapshot still exportable."""
    model, hosts, stop, kw = _CASES["phold"]
    cfg, eng, state, params = _build(
        model, hosts, stop, qcap=4, pressure_abort=True, **kw
    )
    rc = ResilienceController(
        pressure=PressureOptions(policy="escalate", max_capacity=64),
        queue_block=cfg.queue_block,
    )

    class XlaRuntimeError(RuntimeError):
        pass

    def dispatch(s, g, c, b):
        if c > 4:
            raise XlaRuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory allocating grown slab"
            )
        return eng.run_chunk_resized(s, params, g, c, b)

    with pytest.raises(PressureAbort, match="cornered"):
        while not bool(state.done):
            state, _, _ = rc.run_chunk(state, dispatch)
    assert rc.oom_fallbacks >= 1
    assert rc.aborted
    rep = rc.report()
    assert rep["capacity_poisoned"]  # the OOM'd rungs are recorded
    good = rc.abort_export_state()
    assert good is not None
    # the exported prefix is clean: pre-chunk snapshots never hold drops
    assert int(np.asarray(jax.device_get(good.queue.dropped)).sum()) == 0


def test_drop_policy_traces_no_pressure_code():
    """The default policy is program-identical to the pre-pressure
    engine: no pressure lane in the carry, no abort condition traced."""
    from shadow_tpu.core.engine import EngineConfig, _init_stats

    cfg = EngineConfig(num_hosts=4, stop_time=1)
    assert cfg.pressure_abort is False
    assert _init_stats(cfg).pressure is None
    model, hosts, stop, kw = _CASES["phold"]
    _, eng, state, params = _build(model, hosts, stop, qcap=16, **kw)
    assert state.stats.pressure is None


def test_resolve_ladder():
    assert resolve_ladder(4, 64, 2) == [4, 8, 16, 32, 64]
    assert resolve_ladder(4, 60, 2) == [4, 8, 16, 32]
    assert resolve_ladder(8, 8, 2) == [8]
    assert resolve_ladder(3, 50, 4) == [3, 12, 48]


def test_pressure_options_parse():
    assert PressureOptions.from_dict(None).policy == "drop"
    assert not PressureOptions.from_dict(None).active
    p = PressureOptions.from_dict(
        {"policy": "escalate", "max_capacity": 128, "headroom": 0.5}
    )
    assert p.active and p.max_capacity == 128 and p.headroom == 0.5
    for bad in (
        {"policy": "grow"},
        {"max_capacity": -1},
        {"growth_factor": 1},
        {"headroom": 1.5},
        {"unknown": 1},
    ):
        with pytest.raises(ConfigError):
            PressureOptions.from_dict(bad)


def test_simulation_build_wiring_and_rejections():
    """Config-level wiring: policies set the engine static; unsupported
    combinations fail loudly at build."""
    from shadow_tpu.config.options import ConfigOptions
    from shadow_tpu.sim import Simulation

    def cfg_dict(**pressure):
        return {
            "general": {"stop_time": "1 s", "seed": 1},
            "network": {"graph": {"type": "1_gbit_switch"}},
            **({"pressure": pressure} if pressure else {}),
            "hosts": {
                "n": {"count": 4, "network_node_id": 0,
                      "processes": [{"model": "phold",
                                     "model_args": {"population": 1}}]},
            },
        }

    sim = Simulation(ConfigOptions.from_dict(cfg_dict()), world=1)
    assert sim.engine_cfg.pressure_abort is False
    sim = Simulation(
        ConfigOptions.from_dict(cfg_dict(policy="escalate")), world=1
    )
    assert sim.engine_cfg.pressure_abort is True
    # cpu-reference oracle cannot model the pressure plane
    d = cfg_dict(policy="abort")
    d["experimental"] = {"scheduler": "cpu-reference"}
    with pytest.raises(ConfigError, match="cpu-reference"):
        Simulation(ConfigOptions.from_dict(d), world=1)
    # explicit a2a_block sheds are not capacity-curable either
    d = cfg_dict(policy="escalate")
    d["experimental"] = {"a2a_block": 64}
    with pytest.raises(ConfigError, match="a2a_block"):
        Simulation(ConfigOptions.from_dict(d), world=1)
    # ceilings below the configured shapes are config errors
    d = cfg_dict(policy="escalate", max_capacity=8)
    d["experimental"] = {"event_queue_capacity": 16}
    with pytest.raises(ConfigError, match="max_capacity"):
        Simulation(ConfigOptions.from_dict(d), world=1)


def test_hybrid_rejects_escalate():
    from shadow_tpu.config.options import ConfigOptions
    from shadow_tpu.cosim import HybridSimulation

    cfg = ConfigOptions.from_dict({
        "general": {"stop_time": "1 s", "seed": 1},
        "network": {"graph": {"type": "1_gbit_switch"}},
        "pressure": {"policy": "escalate"},
        "hosts": {
            "a": {"network_node_id": 0,
                  "processes": [{"path": "udp_echo_server",
                                 "args": ["port=9000"]}]},
        },
    })
    with pytest.raises(ConfigError, match="hybrid"):
        HybridSimulation(cfg, world=1)


def test_hybrid_abort_policy_clean_run():
    """The hybrid driver accepts the abort policy and a drop-free run
    completes normally, reporting the pressure block (the roomy hybrid
    slab never pressures here — the loud-stop path is gated at the
    engine level, same detector the modeled driver tests exercise)."""
    from shadow_tpu.config.options import ConfigOptions
    from shadow_tpu.cosim import HybridSimulation

    cfg = ConfigOptions.from_dict({
        "general": {"stop_time": "2 s", "seed": 4},
        "network": {"graph": {"type": "1_gbit_switch"}},
        "pressure": {"policy": "abort"},
        "hosts": {
            "server": {"network_node_id": 0,
                       "processes": [{"path": "udp_echo_server",
                                      "args": ["port=9000"]}]},
            "cli": {"network_node_id": 0,
                    "processes": [{"path": "udp_ping",
                                   "args": ["server=server", "port=9000",
                                            "count=2"],
                                   "expected_final_state": {"exited": 0}}]},
        },
    })
    sim = HybridSimulation(cfg, world=1)
    r = sim.run()
    assert r["process_failures"] == 0
    assert r["pressure"]["policy"] == "abort"
    assert "pressure_aborted" not in r
    assert sim.engine_cfg.pressure_abort is True


def test_campaign_rejects_pressure():
    from tools.campaign import build_campaign

    with pytest.raises(ConfigError, match="pressure"):
        build_campaign({
            "general": {"stop_time": "1 s", "seed": 1},
            "network": {"graph": {"type": "1_gbit_switch"}},
            "pressure": {"policy": "escalate"},
            "campaign": {"seeds": [1, 2]},
            "hosts": {
                "n": {"count": 2, "network_node_id": 0,
                      "processes": [{"model": "phold",
                                     "model_args": {"population": 1}}]},
            },
        })


# ---------------------------------------------------------------------------
# cross-capacity checkpoint restore
# ---------------------------------------------------------------------------


def _harness_sim(model, hosts, stop, rounds_per_chunk=16, **kw):
    """A minimal object with the attribute surface save/load_checkpoint
    need (state, engine_cfg, params, engine) — the engine-harness
    stand-in for a full Simulation."""
    cfg, m, params, mstate, events = build_sim(
        model, hosts, stop, rounds_per_chunk=rounds_per_chunk, **kw
    )
    eng = Engine(cfg, m)
    state, params = eng.init_state(params, mstate, events, seed=1)
    ns = types.SimpleNamespace(
        state=state, engine_cfg=cfg, params=params, engine=eng,
        cfg=types.SimpleNamespace(pressure=PressureOptions()),
    )
    return ns


_ROUNDTRIP_SCRIPT = """
import json, sys, types
import numpy as np
import jax
from shadow_tpu.core import Engine
from shadow_tpu.core.checkpoint import load_checkpoint, save_checkpoint
from shadow_tpu.config.options import PressureOptions
from tests.engine_harness import build_sim, mk_hosts

hosts = mk_hosts(8, {"mean_delay": "20 ms", "population": 3})
KW = dict(loss=0.1, microstep_limit=32, rounds_per_chunk=4)

def fresh(qcap):
    cfg, m, params, mstate, events = build_sim(
        "phold", hosts, 300_000_000, qcap=qcap, **KW
    )
    eng = Engine(cfg, m)
    state, params = eng.init_state(params, mstate, events, seed=1)
    return types.SimpleNamespace(
        state=state, engine_cfg=cfg, params=params, engine=eng,
        cfg=types.SimpleNamespace(pressure=PressureOptions()),
    )

def dig(st):
    return int(np.bitwise_xor.reduce(
        np.asarray(jax.device_get(st.stats.digest))
    ))

# one short chunk at C=16, checkpoint mid-run
a = fresh(16)
a.state = a.engine.run_chunk(a.state, a.params)
assert not bool(a.state.done)
now_saved = int(a.state.now)
path = save_checkpoint(sys.argv[1], a)

# resume into a sim built at C'=32: exact guard differs only in the
# migratable capacity shape -> migration path
b = fresh(32)
load_checkpoint(path, b)
resumed_cap = b.state.queue.t.shape[1]
resumed_now = int(b.state.now)
while not bool(b.state.done):
    b.state = b.engine.run_chunk(b.state, b.params)

digs = {}
drops = {}
for qcap in (16, 32):
    r = fresh(qcap)
    while not bool(r.state.done):
        r.state = r.engine.run_chunk(r.state, r.params)
    digs[qcap] = dig(r.state)
    drops[qcap] = int(np.asarray(jax.device_get(r.state.queue.dropped)).sum())
print(json.dumps({
    "resumed_cap": resumed_cap, "now_saved": now_saved,
    "resumed_now": resumed_now, "resumed_digest": dig(b.state),
    "digest_16": digs[16], "digest_32": digs[32],
    "drops_16": drops[16], "drops_32": drops[32],
}))
"""


def test_checkpoint_cross_capacity_roundtrip(tmp_path):
    """A checkpoint written at C resumes at C' > C through the migration
    ops, and the continued run is bit-identical to both an uninterrupted
    run at C and one at C' (the prefix was drop-free, the valve is
    pinned equal, so all three trajectories coincide). Subprocess-
    isolated: multiple compiled runs in one process are this box's
    heap-corruption magnet (tests/subproc.py)."""
    from tests.subproc import run_isolated_json

    r = run_isolated_json(_ROUNDTRIP_SCRIPT, str(tmp_path / "ck"))
    assert r["resumed_cap"] == 32
    assert r["resumed_now"] == r["now_saved"]
    assert r["drops_16"] == 0 and r["drops_32"] == 0
    assert r["digest_16"] == r["digest_32"] == r["resumed_digest"]


def test_checkpoint_shrink_refuses_when_overfull(tmp_path):
    """Refusal only when migration is impossible: resuming into a
    capacity the checkpoint's live events cannot fit raises loudly."""
    from shadow_tpu.core.checkpoint import (
        CheckpointError, load_checkpoint, save_checkpoint,
    )

    model, hosts, stop, kw = _CASES["phold"]
    kw = dict(kw, qcap=16, microstep_limit=32, rounds_per_chunk=4)
    a = _harness_sim(model, hosts, stop, **kw)
    a.state = a.engine.run_chunk(a.state, a.params)
    assert not bool(a.state.done)
    # stuff the queue past the target capacity (state content is not in
    # the guard, so the checkpoint remains loadable-in-principle)
    h = a.state.queue.t.shape[0]
    extra = [(
        jnp.ones((h,), bool), jnp.full((h,), 250 * MS, jnp.int64),
        pack_order(1, jnp.arange(h, dtype=jnp.int64), 5000 + i),
        jnp.full((h,), 3, jnp.int32), jnp.zeros((h, 4), jnp.int32),
    ) for i in range(8)]
    a.state = a.state._replace(queue=q_push_many(a.state.queue, extra))
    occ = int(np.asarray(jax.device_get(q_len(a.state.queue))).max())
    assert occ > 8
    path = save_checkpoint(str(tmp_path / "ck"), a)
    b = _harness_sim(model, hosts, stop, **{**kw, "qcap": 8})
    with pytest.raises(CheckpointError, match="cannot resume"):
        load_checkpoint(path, b)


_ESCALATED_CKPT_SCRIPT = """
import dataclasses, json, sys, types
from shadow_tpu.core import Engine
from shadow_tpu.core.checkpoint import load_checkpoint, save_checkpoint
from shadow_tpu.core.pressure import ResilienceController
from shadow_tpu.config.options import PressureOptions
from tests.engine_harness import build_sim, mk_hosts

hosts = mk_hosts(8, {"mean_delay": "20 ms", "population": 3})
KW = dict(loss=0.1, qcap=4, microstep_limit=16, rounds_per_chunk=4)
PRESS = PressureOptions(policy="escalate", max_capacity=64)

def fresh():
    cfg, m, params, mstate, events = build_sim(
        "phold", hosts, 300_000_000, **KW
    )
    cfg = dataclasses.replace(cfg, pressure_abort=True)
    eng = Engine(cfg, m)
    state, params = eng.init_state(params, mstate, events, seed=1)
    return types.SimpleNamespace(
        state=state, engine_cfg=cfg, params=params, engine=eng,
        cfg=types.SimpleNamespace(pressure=PRESS),
    )

a = fresh()
rc = ResilienceController(pressure=PRESS)
for _ in range(3):
    if bool(a.state.done):
        break
    a.state, _, _ = rc.run_chunk(
        a.state,
        lambda s, g, c, b: a.engine.run_chunk_resized(s, a.params, g, c, b),
    )
grown = a.state.queue.t.shape[1]
now_saved = int(a.state.now)
path = save_checkpoint(sys.argv[1], a)

# same config, escalate policy target: keeps the grown shape
b = fresh()
load_checkpoint(path, b)
print(json.dumps({
    "grown": grown, "now_saved": now_saved,
    "resumed_cap": b.state.queue.t.shape[1],
    "resumed_now": int(b.state.now),
}))
"""


def test_checkpoint_escalated_state_resumes(tmp_path):
    """A checkpoint written MID-ESCALATION (state regrown past the
    configured base) restores, and under an escalate target it keeps the
    grown shape. Subprocess-isolated (heap-corruption magnet, see the
    round-trip test)."""
    from tests.subproc import run_isolated_json

    r = run_isolated_json(_ESCALATED_CKPT_SCRIPT, str(tmp_path / "ck"))
    assert r["grown"] > 4  # the escalation genuinely regrew pre-save
    assert r["resumed_cap"] == r["grown"]
    assert r["resumed_now"] == r["now_saved"]


# ---------------------------------------------------------------------------
# full-driver end-to-end (subprocess-isolated: compiled Simulation runs
# intermittently heap-corrupt in-process on this box — CHANGES.md)
# ---------------------------------------------------------------------------

_DRIVER_SCRIPT = """
import io, json, sys
from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.sim import Simulation

def cfg(policy, qcap):
    return ConfigOptions.from_dict({
        "general": {"stop_time": "2 s", "seed": 7,
                    "heartbeat_interval": "500 ms"},
        "network": {"graph": {"type": "1_gbit_switch"}},
        "experimental": {"event_queue_capacity": qcap,
                         "sends_per_host_round": 4,
                         "rounds_per_chunk": 16},
        **({"pressure": {"policy": policy, "max_capacity": 64}}
           if policy else {}),
        "hosts": {
            "n": {"count": 16, "network_node_id": 0,
                  "processes": [{"model": "phold",
                                 "model_args": {"population": 6,
                                                "mean_delay": "100 ms"}}]},
        },
    })

mode = sys.argv[1]
log = io.StringIO()
sim = Simulation(cfg("escalate" if mode == "esc" else None, 8), world=1)
rep = sim.run(log=log)
print(json.dumps({mode: rep, "log": log.getvalue()}))
"""


def test_simulation_driver_escalates_end_to_end():
    """The Simulation driver wiring, end to end: an escalate run over an
    undersized queue finishes drop-free with the pressure block + flat
    counters in sim-stats and cap= on the heartbeat line, while the
    default-policy twin genuinely sheds. One subprocess per leg (each
    compiled Simulation run is its own corruption-isolation domain)."""
    from tests.subproc import run_isolated_json

    esc_rep = run_isolated_json(_DRIVER_SCRIPT, "esc")
    drop_rep = run_isolated_json(_DRIVER_SCRIPT, "drop")
    esc, drop = esc_rep["esc"], drop_rep["drop"]
    reps = {"log": esc_rep["log"]}
    assert drop["queue_overflow_dropped"] > 0  # the gate is not vacuous
    assert esc["queue_overflow_dropped"] == 0
    assert esc["packets_budget_dropped"] == 0
    p = esc["pressure"]
    assert p["policy"] == "escalate"
    assert esc["pressure_regrows"] > 0
    assert p["capacity"] > p["base_capacity"]
    assert "pressure_aborted" not in esc
    # heartbeat carries the ACTIVE capacity on pressure runs
    assert "cap=" in reps["log"]


# ---------------------------------------------------------------------------
# heartbeat cap= + parser compatibility
# ---------------------------------------------------------------------------


def test_heartbeat_cap_field_parses(tmp_path):
    from shadow_tpu.sim import heartbeat_line
    from tools.parse_shadow import parse_heartbeats

    new = heartbeat_line(
        2_000_000_000, 3.0, 99, 80, 40, 4096, 7, gear=4, cap=32
    )
    old = heartbeat_line(2_000_000_000, 3.0, 99, 80, 40, 4096, 7)
    log = tmp_path / "run.log"
    log.write_text(new + "\n" + old + "\n")
    rows = parse_heartbeats(str(log), strict=True)
    assert len(rows) == 2
    assert rows[0]["cap"] == 32 and rows[0]["gear"] == 4
    assert "cap" not in rows[1]
