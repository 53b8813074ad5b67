"""Timer wheel, and the exchange merge's two cond forms.

Three layers of gates, mirroring the bucketed-queue/popk precedent:

  1. per-op property sweeps (hypothesis-style seeded randomized
     sequences, no hypothesis dep): wheel push/cancel/pop-due against a
     sorted-list reference model;
  2. engine digest matrix: wheel ON is event-for-event identical
     (digests, events, every drop counter) to wheel OFF across
     echo/phold/tgen x flat/bucketed queue layouts, including a
     spill-forcing tiny wheel; and the merge fused inside the empty-round
     cond (capacity < 48) identical to the split plan/apply form;
  3. checkpoint round-trip + cross-slot migration restore (subprocess-
     isolated: compiled Simulation sequences are this box's documented
     corruption magnet — tests/subproc.py classifies and retries).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.ops.events import (
    ORDER_MAX,
    pack_order,
    q_len,
)
from shadow_tpu.ops.wheel import (
    make_wheel,
    migrate_wheel,
    resolve_wheel_block,
    wheel_cancel,
    wheel_free,
    wheel_len,
    wheel_next_time,
    wheel_pop_min,
    wheel_push_many,
)
from shadow_tpu.simtime import TIME_MAX
from tests.engine_harness import mk_hosts, run_sim

P = 4  # EVENT_PAYLOAD_WORDS


# --------------------------------------------------------------------------
# 1a. wheel op property sweep vs a sorted-list reference
# --------------------------------------------------------------------------


def test_resolve_wheel_block():
    assert resolve_wheel_block(16) == 4
    assert resolve_wheel_block(8) == 2  # sqrt(8) ~ 2.83 -> divisor 2
    assert resolve_wheel_block(12, 6) == 6
    assert resolve_wheel_block(7) in (1, 7)
    with pytest.raises(ValueError):
        resolve_wheel_block(8, 3)
    with pytest.raises(ValueError):
        resolve_wheel_block(0)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("slots,block", [(8, 0), (12, 4), (5, 1)])
def test_wheel_ops_match_reference(seed, slots, block):
    """Randomized push / cancel / pop-due sequences: the wheel's visible
    behavior (popped (t, order, kind) sequence, next_time, occupancy,
    cancel hits) must equal a per-host sorted-set reference. The wheel
    never drops (the caller contract masks overflow away via
    wheel_free) — asserted via the dropped lane staying zero."""
    rng = np.random.default_rng(seed)
    h = 4
    w = make_wheel(h, slots, block)
    ref = [set() for _ in range(h)]  # host -> {(t, order, kind)}
    seq = 0
    for _step in range(60):
        op = rng.integers(0, 3)
        if op == 0:  # push (masked to hosts with free slots)
            t = rng.integers(1, 1000, size=h).astype(np.int64)
            kind = rng.integers(0, 7, size=h).astype(np.int32)
            order = np.asarray(
                pack_order(1, np.arange(h), np.full(h, seq))
            )
            seq += 1
            free = np.asarray(wheel_free(w))
            mask = (rng.random(h) < 0.8) & (free > 0)
            w = wheel_push_many(
                w,
                [(
                    jnp.asarray(mask),
                    jnp.asarray(t),
                    jnp.asarray(order),
                    jnp.asarray(kind),
                    jnp.zeros((h, P), jnp.int32),
                )],
            )
            for i in range(h):
                if mask[i]:
                    ref[i].add((int(t[i]), int(order[i]), int(kind[i])))
        elif op == 1:  # pop-due below a random limit
            limit = int(rng.integers(1, 1100))
            w, ev, active = wheel_pop_min(w, jnp.int64(limit))
            ev = jax.device_get(ev)
            active = np.asarray(active)
            for i in range(h):
                due = [e for e in ref[i] if e[0] < limit]
                if due:
                    want = min(due)  # (t, order) lexicographic min
                    assert bool(active[i])
                    got = (int(ev.t[i]), int(ev.order[i]), int(ev.kind[i]))
                    assert got == want, f"host {i}: {got} != {want}"
                    ref[i].remove(want)
                else:
                    assert not bool(active[i])
        else:  # cancel a (sometimes live, sometimes stale) order key
            targets = np.full(h, -1, np.int64)
            for i in range(h):
                if ref[i] and rng.random() < 0.7:
                    targets[i] = sorted(ref[i])[
                        rng.integers(0, len(ref[i]))
                    ][1]
                else:
                    targets[i] = int(
                        pack_order(1, i, 10_000 + int(rng.integers(100)))
                    )
            mask = rng.random(h) < 0.8
            w, found = wheel_cancel(
                w, jnp.asarray(mask), jnp.asarray(targets)
            )
            found = np.asarray(found)
            for i in range(h):
                live = [e for e in ref[i] if e[1] == targets[i]]
                if mask[i] and live:
                    assert bool(found[i])
                    ref[i].remove(live[0])
                else:
                    assert not bool(found[i])
        # invariants after every op
        nt = np.asarray(wheel_next_time(w))
        ln = np.asarray(wheel_len(w))
        for i in range(h):
            want_nt = min((e[0] for e in ref[i]), default=TIME_MAX)
            assert int(nt[i]) == want_nt
            assert int(ln[i]) == len(ref[i])
        assert int(np.asarray(w.dropped).sum()) == 0
        # block caches agree with the slab (the BucketQueue invariant)
        occ = np.asarray((jax.device_get(w.t) != TIME_MAX).sum(axis=1))
        assert (np.asarray(w.bfill).sum(axis=1) == occ).all()


def test_wheel_migrate_roundtrip():
    """Grow and shrink re-seat the same timer multiset (positions are
    unobservable — popping everything yields the identical sequence)."""
    h = 3
    w = make_wheel(h, 6)
    seq = 0
    for t in (50, 30, 90, 10):
        order = pack_order(1, jnp.arange(h), jnp.full((h,), seq))
        seq += 1
        w = wheel_push_many(
            w,
            [(
                jnp.ones((h,), bool),
                jnp.full((h,), t, jnp.int64),
                order,
                jnp.full((h,), 1, jnp.int32),
                jnp.zeros((h, P), jnp.int32),
            )],
        )

    def drain(wheel):
        out = []
        for _ in range(10):
            wheel, ev, active = wheel_pop_min(wheel, jnp.int64(TIME_MAX))
            if not bool(np.asarray(active).any()):
                break
            out.append(
                (np.asarray(ev.t).tolist(), np.asarray(ev.order).tolist())
            )
        return out

    want = drain(w)
    assert drain(migrate_wheel(w, 12)) == want
    assert drain(migrate_wheel(w, 4)) == want  # 4 live timers fit exactly


# --------------------------------------------------------------------------
# 2. engine digest matrix: wheel ON == OFF, fused merge == split merge
# --------------------------------------------------------------------------

_CASES = {
    "echo": ("udp_echo",
             [dict(host_id=0, name="server", start_time=0,
                   model_args={"role": "server"})]
             + [dict(host_id=i, name=f"c{i}", start_time=0,
                     model_args={"role": "client", "peer": "server",
                                 "interval": "4 ms", "size_bytes": 2000})
                for i in range(1, 5)],
             200_000_000, dict(bw_bits=2_000_000, loss=0.05)),
    "phold": ("phold", mk_hosts(8, {"mean_delay": "20 ms", "population": 3}),
              300_000_000, dict(loss=0.1)),
    "tgen": ("tgen_tcp",
             mk_hosts(5, {"flow_segs": 8, "flows": 2, "cwnd_cap": 8,
                          "rto_min": "100 ms"}),
             2_000_000_000,
             dict(loss=0.05, latency=10_000_000, sends_budget=16)),
}

_DROP_FIELDS = (
    "pkts_sent", "pkts_lost", "pkts_unreachable", "pkts_codel_dropped",
    "pkts_delivered", "pkts_budget_dropped", "monotonic_violations",
)


def _assert_identical(st_a, s_a, st_b, s_b):
    np.testing.assert_array_equal(
        np.asarray(s_a.digest), np.asarray(s_b.digest)
    )
    np.testing.assert_array_equal(
        np.asarray(s_a.events), np.asarray(s_b.events)
    )
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(st_a.queue.dropped)),
        np.asarray(jax.device_get(st_b.queue.dropped)),
    )
    assert int(s_a.rounds) == int(s_b.rounds)
    for f in _DROP_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(s_a, f)), np.asarray(getattr(s_b, f)),
            err_msg=f,
        )


def _matrix_params():
    out = []
    for case in sorted(_CASES):
        for qb in (0, 8):
            # aligned half runs in tier-1; the cross combos add no code
            # path (wheel routing/pop-merge is layout-independent) and
            # ride the slow mark like the netobs matrix
            marks = () if (qb == 0) == (case != "phold") else (
                pytest.mark.slow,
            )
            out.append(pytest.param(
                case, qb,
                id=f"{case}-{'flat' if qb == 0 else 'bucketed'}",
                marks=marks,
            ))
    return out


@pytest.mark.parametrize("case,qb", _matrix_params())
def test_wheel_on_off_bit_identical(case, qb):
    """The ISSUE acceptance gate: wheel ON (ample slots) is bit-identical
    to OFF — digests, events, drops — and timers really ride the wheel
    (occupancy high-water > 0, zero wheel drops)."""
    model, hosts, stop, kw = _CASES[case]
    st0, s0, _ = run_sim(model, hosts, stop, queue_block=qb, **kw)
    st1, s1, _ = run_sim(
        model, hosts, stop, queue_block=qb, wheel_slots=8, **kw
    )
    _assert_identical(st0, s0, st1, s1)
    assert int(np.asarray(s1.wheel_occ_hwm).max()) > 0
    assert int(np.asarray(jax.device_get(st1.wheel.dropped)).sum()) == 0
    assert s0.wheel_occ_hwm is None  # off path carries no wheel lanes


@pytest.mark.parametrize("case", sorted(_CASES))
def test_wheel_spill_path_bit_identical(case):
    """A one-slot wheel forces spills: results stay bit-identical (the
    spilled timers are queue events exactly as in the off path) and the
    spill counter proves the path ran."""
    model, hosts, stop, kw = _CASES[case]
    st0, s0, _ = run_sim(model, hosts, stop, **kw)
    st1, s1, _ = run_sim(model, hosts, stop, wheel_slots=1, **kw)
    _assert_identical(st0, s0, st1, s1)
    if case != "echo":
        # echo keeps at most ONE pending tick per host — it can never
        # spill a 1-slot wheel; phold (population 3) and tgen
        # (RTO + DELACK + tick) genuinely contend for the slot
        assert int(np.asarray(s1.wheel_spilled).sum()) > 0
    assert int(np.asarray(jax.device_get(st1.wheel.dropped)).sum()) == 0


# echo's 2 Mbit/s links back the server's queue past 64 slots, so its
# drops (and then its digests) depend on the capacity; at 20 Mbit/s the
# server holds its load in 32 slots and the capacity changes nothing else
_NO_QUEUE_DROPS = {"echo": dict(bw_bits=20_000_000)}


def _cond_forms(case, **kw):
    """Run `case` at capacity 32 (the merge fused inside the empty-round
    cond) and at 64 (plan inside, apply outside), with the insert cap and
    the microstep limit pinned so that capacity is the only difference."""
    model, hosts, stop, base = _CASES[case]
    kw = dict(
        base, max_round_inserts=32, microstep_limit=64,
        **_NO_QUEUE_DROPS.get(case, {}), **kw,
    )
    fused = run_sim(model, hosts, stop, qcap=32, **kw)
    split = run_sim(model, hosts, stop, qcap=64, **kw)
    for st, _, _ in (fused, split):
        assert int(np.asarray(jax.device_get(st.queue.dropped)).sum()) == 0
    _assert_identical(*fused[:2], *split[:2])


@pytest.mark.parametrize("case", sorted(_CASES))
def test_merge_cond_forms_bit_identical(case):
    """The fused and the split merge leave the same digests, events and
    drops (every backend picks the form from the capacity alone)."""
    _cond_forms(case)


@pytest.mark.parametrize("case", sorted(_CASES))
def test_merge_cond_forms_bit_identical_bucketed(case):
    """The same with the bucketed queue (blocks of 8), whose block caches
    are rebuilt under the cond after either form."""
    _cond_forms(case, queue_block=8)


def test_merge_cond_forms_bit_identical_alltoall():
    """PHOLD at world 4 over the alltoall exchange: each shard merges its
    received blocks in either form."""
    _cond_forms("phold", world=4, exchange="alltoall")


def test_wheel_plus_netobs_reconciles():
    """The flagship combination (bench config 11): wheel + network
    observatory. Digests identical to the plain run AND the
    event-class accounting still reconciles (ec_timer + ec_pkt + ec_app
    == events) — the ec_timer count is exactly the wheel's traffic."""
    model, hosts, stop, kw = _CASES["tgen"]
    st0, s0, _ = run_sim(model, hosts, stop, **kw)
    st1, s1, _ = run_sim(
        model, hosts, stop, wheel_slots=8, netobs=True, flow_records=32,
        **kw
    )
    _assert_identical(st0, s0, st1, s1)
    ec = (
        int(np.asarray(s1.ec_timer).sum())
        + int(np.asarray(s1.ec_pkt).sum())
        + int(np.asarray(s1.ec_app).sum())
    )
    assert ec == int(np.asarray(s1.events).sum())
    assert int(np.asarray(s1.ec_timer).sum()) > 0


def test_wheel_with_integrity_sentinel_clean():
    """The sentinel's wheel-extended guards (slab floor over the wheel
    plane, wheel fill-cache agreement, zero wheel drops) stay quiet on a
    legal run."""
    model, hosts, stop, kw = _CASES["phold"]
    st, s, _ = run_sim(
        model, hosts, stop, wheel_slots=4, integrity=True, **kw
    )
    assert int(np.asarray(s.integrity).sum()) == 0
    assert int(np.asarray(s.iv_round).max()) == -1


def test_wheel_rejects_kway_and_empty_timer_models():
    from shadow_tpu.core.engine import Engine, EngineConfig

    with pytest.raises(ValueError, match="K-way"):
        EngineConfig(
            num_hosts=4, stop_time=1000, queue_capacity=8,
            wheel_slots=4, microstep_events=4,
        )
    with pytest.raises(ValueError, match="wheel_block"):
        EngineConfig(
            num_hosts=4, stop_time=1000, queue_capacity=8,
            wheel_slots=8, wheel_block=3,
        )

    class NoTimers:
        name = "no_timers"

    cfg = EngineConfig(
        num_hosts=4, stop_time=1000, queue_capacity=8, wheel_slots=4
    )
    with pytest.raises(ValueError, match="timer_kinds"):
        Engine(cfg, NoTimers())


def test_config_knobs_parse_and_validate():
    from shadow_tpu.config.options import ConfigError, ExperimentalOptions

    e = ExperimentalOptions.from_dict(
        {"timer_wheel": 16, "timer_wheel_block": 4}
    )
    assert (e.timer_wheel, e.timer_wheel_block) == (16, 4)
    with pytest.raises(ConfigError, match="timer_wheel_block"):
        ExperimentalOptions.from_dict(
            {"timer_wheel": 16, "timer_wheel_block": 5}
        )
    with pytest.raises(ConfigError, match="microstep_events"):
        ExperimentalOptions.from_dict(
            {"timer_wheel": 16, "microstep_events": 4}
        )
    with pytest.raises(ConfigError, match="timer_wheel"):
        ExperimentalOptions.from_dict({"timer_wheel": -1})


def test_wheel_lanes_priced_by_memory_model():
    """The HBM byte model prices the wheel planes: formula bytes ==
    actual carry-leaf bytes on a built wheel state (satellite 2)."""
    from shadow_tpu.core import lanes
    from shadow_tpu.obs.memory import (
        dims_of_state, lane_plane_bytes, leaf_nbytes,
    )
    from tests.engine_harness import build_sim
    from shadow_tpu.core.engine import Engine

    model, hosts, stop, kw = _CASES["phold"]
    cfg, mdl, params, mstate, events = build_sim(
        model, hosts, stop, wheel_slots=12, wheel_block=4, **kw
    )
    eng = Engine(cfg, mdl)
    state, params = eng.init_state(params, mstate, events, seed=1)
    dims = dims_of_state(cfg, state)
    assert dims["WS"] == 12 and dims["WNB"] == 3
    for path in lanes.STATE_LANES:
        if not path.startswith("wheel."):
            continue
        field = path.split(".", 1)[1]
        leaf = getattr(state.wheel, field)
        assert lane_plane_bytes(path, dims) == leaf_nbytes(leaf), path
    # wheel-off states price the planes as absent
    cfg0, mdl0, params0, mstate0, events0 = build_sim(
        model, hosts, stop, **kw
    )
    eng0 = Engine(cfg0, mdl0)
    state0, _ = eng0.init_state(params0, mstate0, events0, seed=1)
    dims0 = dims_of_state(cfg0, state0)
    assert lane_plane_bytes("wheel.t", dims0) is None
    assert lane_plane_bytes("stats.wheel_spilled", dims0) is None


def test_example_wheel_yaml_parses():
    import os

    from shadow_tpu.config.options import load_config

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = load_config(os.path.join(repo, "examples", "wheel.yaml"))
    assert cfg.experimental.timer_wheel == 4
    assert cfg.observability.network


def test_bench_compare_wheel_gates():
    """The bench_compare satellite: reconciliation drift and wheel drops
    are regressions; spill growth is a warning; losing the block is a
    coverage warning."""
    from tools.bench_compare import compare

    def row(timer=5, spilled=0, dropped=0, wheel=True):
        r = {
            "value": 10.0,
            "counters": {},
            "network": {"event_classes": {
                "timer": timer, "packet": 10, "app": 5, "total": 20,
            }},
        }
        if wheel:
            r["counters"]["wheel"] = {
                "slots": 4, "occupancy_hwm": 2,
                "spilled": spilled, "dropped": dropped,
            }
        return r

    def kinds(old, new):
        return [
            (f["kind"], f["severity"])
            for f in compare(old, new, 0.5, 0.5)
            if f["kind"] == "wheel"
        ]

    assert kinds({"m": row()}, {"m": row()}) == []
    # timer+packet+app != total -> regression
    assert ("wheel", "regression") in kinds(
        {"m": row()}, {"m": row(timer=4)}
    )
    # wheel dropped -> regression
    assert ("wheel", "regression") in kinds(
        {"m": row()}, {"m": row(dropped=3)}
    )
    # spill growth -> warning
    assert ("wheel", "warning") in kinds(
        {"m": row(spilled=0)}, {"m": row(spilled=7)}
    )
    # block lost -> coverage warning
    assert ("wheel", "warning") in kinds(
        {"m": row()}, {"m": row(wheel=False)}
    )


def test_net_report_breaks_out_wheel(capsys):
    """The net_report satellite: with a wheel{} block in sim-stats the
    verdict line breaks out occupancy and spills instead of arguing for
    the rebuild the run already has."""
    from tools.net_report import print_report

    net = {"event_classes": {
        "timer": 11, "packet": 67, "app": 22, "total": 100,
        "timer_share": 0.11, "packet_share": 0.67,
    }}
    print_report({"wheel": {
        "slots": 4, "occupancy_hwm": 2, "spilled": 0, "dropped": 0,
    }}, net)
    out = capsys.readouterr().out
    assert "ride the device wheel" in out
    assert "occupancy hwm 2/4 slots" in out
    print_report({}, net)
    out2 = capsys.readouterr().out
    assert "experimental.timer_wheel" in out2


# --------------------------------------------------------------------------
# 3. checkpoint round-trip (subprocess-isolated: compiled Simulation
#    sequences are the documented corruption magnet on this box)
# --------------------------------------------------------------------------

_CKPT_CHILD = r"""
import json, os, sys
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)

from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.core.checkpoint import (
    CheckpointError, load_checkpoint, save_checkpoint,
)
from shadow_tpu.sim import Simulation

tmp = sys.argv[1]

def build(wheel_slots, stop_s=2, extra=None):
    d = {
        "general": {"stop_time": f"{stop_s} s", "seed": 7,
                     "progress": False,
                     "data_directory": os.path.join(tmp, "out")},
        "network": {"graph": {"type": "1_gbit_switch"}},
        "hosts": {
            "h": {"count": 6, "network_node_id": 0,
                   "processes": [{"model": "phold",
                                  "model_args": {"mean_delay": "40 ms",
                                                 "population": 3}}]},
        },
        "experimental": {"timer_wheel": wheel_slots,
                          "rounds_per_chunk": 4, **(extra or {})},
    }
    return Simulation(ConfigOptions.from_dict(d))

# uninterrupted reference
sim_ref = build(6)
sim_ref.run()
ref = sim_ref.stats_report()

# interrupted: run a few chunks, checkpoint, resume in a FRESH sim
sim_a = build(6)
for _ in range(3):
    sim_a.state = sim_a.engine.run_chunk(sim_a.state, sim_a.params)
path = save_checkpoint(os.path.join(tmp, "ck"), sim_a)

sim_b = build(6)
load_checkpoint(path, sim_b)
sim_b.run()
got = sim_b.stats_report()
assert got["determinism_digest"] == ref["determinism_digest"], (
    got["determinism_digest"], ref["determinism_digest"])
assert got["events_processed"] == ref["events_processed"]

# cross-slot migration restore: resume the same checkpoint at S'=12
sim_c = build(12)
load_checkpoint(path, sim_c)
sim_c.run()
got_c = sim_c.stats_report()
assert got_c["determinism_digest"] == ref["determinism_digest"], (
    got_c["determinism_digest"], ref["determinism_digest"])

# wheel on/off cross-restore refuses loudly
sim_d = build(0)
try:
    load_checkpoint(path, sim_d)
except CheckpointError:
    pass
else:
    raise AssertionError("wheel->no-wheel restore did not refuse")

print("CKPT_OK")
"""


@pytest.mark.slow
def test_wheel_checkpoint_roundtrip(tmp_path):
    from tests.subproc import run_isolated

    proc = run_isolated(_CKPT_CHILD, str(tmp_path), timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CKPT_OK" in proc.stdout
