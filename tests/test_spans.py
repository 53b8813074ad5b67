"""Program spans and scopes that a profiler trace reads.

Host side: `Simulation.run` opens `shadow.*` profiler annotations around
its driver loop (obs/runtime.py `span_or_null`, `chunk_span`), and
`restore_snapshot` one around its device copies; with a profiler session
running they land on the trace's host plane, on the device ops' clock.
Device side: `jax.named_scope`s mark the barrier (`shadow_window`), the
collectives (`shadow_collective`), the merge's two halves
(`shadow_merge_order`, `shadow_merge_write`), the model handler
(`shadow_handler`) and, inside the tgen-TCP handler, its server lane,
client lane and emissions (`shadow_tcp_server`, `shadow_tcp_client`,
`shadow_tcp_emit`) in the compiled HLO's `op_name` metadata. Neither
changes what the program computes."""

from __future__ import annotations

import functools
import glob
import re

import jax
import numpy as np
import pytest

import __graft_entry__ as ge
from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.core import engine as E
from shadow_tpu.core.checkpoint import restore_snapshot, snapshot_state
from shadow_tpu.obs.runtime import SPAN_NAMES, WallLedger, span_or_null
from shadow_tpu.sim import Simulation

_OPNAME = re.compile(r'op_name="([^"]*)"')


def _phold(hosts=32, stop_s=1, **general):
    cfg = ge._sim_config(hosts, stop_s)
    cfg["general"].update(general)
    return cfg


def _tgen(hosts=8, stop="100 ms"):
    gml = ('graph [ directed 0 node [ id 0 host_bandwidth_down "1 Gbit" '
           'host_bandwidth_up "1 Gbit" ] edge [ source 0 target 0 latency "1 ms" ] ]')
    return {
        "general": {"stop_time": stop, "seed": 5},
        "network": {"graph": {"type": "gml", "inline": gml}},
        "experimental": {"event_queue_capacity": 24, "sends_per_host_round": 24},
        "hosts": {"peer": {"count": hosts, "network_node_id": 0, "processes": [
            {"model": "tgen_tcp", "model_args": {"flow_segs": 40, "flows": 2}}]}},
    }


def _sim(cfg, world=1):
    return Simulation(ConfigOptions.from_dict(cfg), world=world)


def _op_names(hlo_text: str) -> list[str]:
    return _OPNAME.findall(hlo_text)


def _tpu_chunk_hlo(monkeypatch, sim) -> str:
    """The one-chip chunk program compiled on the CPU in its TPU form (the
    engine picks its pops from `jax.default_backend()`; the merge is the
    same on every backend)."""
    fn = functools.partial(E._run_chunk, sim.engine_cfg, sim.engine.model, None)
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        return jax.jit(fn).lower(sim.state, sim.params).compile().as_text()


def test_chunk_hlo_carries_round_scopes(monkeypatch):
    names = _op_names(_tpu_chunk_hlo(monkeypatch, _sim(_phold())))
    for scope in ("shadow_window", "shadow_merge_order", "shadow_merge_bounds",
                  "shadow_merge_window", "shadow_merge_write", "shadow_handler"):
        assert any(scope in n.split("/") for n in names), scope
    # the merge's halves sit inside the existing markers, the handler
    # inside the microstep loop
    def nested(outer, inner):
        return any(
            outer in p and inner in p and p.index(outer) < p.index(inner)
            for p in (n.split("/") for n in names)
        )

    assert nested("shadow_exchange", "shadow_merge")
    assert nested("shadow_merge", "shadow_merge_order")
    assert nested("shadow_merge", "shadow_merge_write")
    assert nested("shadow_merge_order", "shadow_merge_bounds")
    assert nested("shadow_merge_order", "shadow_merge_window")
    assert nested("shadow_microsteps", "shadow_handler")
    # one chip: no collective
    assert not any("shadow_collective" in n for n in names)


def test_tgen_chunk_hlo_splits_the_handler(monkeypatch):
    """The TCP handler's three sections sit inside `shadow_handler`, and
    together they hold most of its ops."""
    names = [n.split("/") for n in _op_names(_tpu_chunk_hlo(monkeypatch, _sim(_tgen())))]
    handler = [p for p in names if "shadow_handler" in p]
    tcp = ("shadow_tcp_server", "shadow_tcp_client", "shadow_tcp_emit")
    for scope in tcp:
        inside = [p for p in handler if scope in p]
        assert inside, scope
        assert all(p.index("shadow_handler") < p.index(scope) for p in inside), scope
    assert not any(scope in p for p in names if p not in handler for scope in tcp)
    assert sum(any(sc in p for sc in tcp) for p in handler) > len(handler) // 2


def test_sharded_chunk_hlo_scopes_every_collective():
    """world 4 over four virtual CPU devices, alltoall exchange: the
    all-to-all and the barrier's all-reduces carry `shadow_collective`."""
    cfg = _phold(hosts=32)
    cfg.setdefault("experimental", {})["exchange"] = "alltoall"
    sim = _sim(cfg, world=4)
    text = sim.engine.run_chunk.lower(sim.state, sim.params).compile().as_text()
    lines = [ln for ln in text.splitlines()
             if re.search(r"\b(all-to-all|all-reduce)(-start)?\(", ln)]
    assert any("all-to-all" in ln for ln in lines)
    assert any("all-reduce" in ln for ln in lines)
    for ln in lines:
        op = _OPNAME.search(ln)
        assert op and "shadow_collective" in op.group(1).split("/"), ln
    assert any("shadow_window" in _OPNAME.search(ln).group(1) for ln in lines)


def _host_spans(trace_dir: str) -> list[tuple[str, int, int]]:
    """(name, start_ns, end_ns) of every `shadow.*` event on a host line."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("shadow."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_run_records_nested_host_spans(tmp_path):
    sim = _sim(_phold(stop_s=2, heartbeat_interval="500 ms"))
    jax.profiler.start_trace(str(tmp_path))
    try:
        sim.run(progress=False)
        sim.state = restore_snapshot(snapshot_state(sim.state))
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    (run,) = by["shadow.run"]
    chunks = by["shadow.chunk"]
    # one span per loop iteration, the last one only the final done-check
    assert len(chunks) == sim._chunks + 1
    assert all(_inside(c, run) for c in chunks)
    for name in ("shadow.dispatch", "shadow.sync", "shadow.heartbeat"):
        assert by[name], name
        assert all(any(_inside(s, c) for c in chunks) for s in by[name]), name
    assert len(by["shadow.dispatch"]) == sim._chunks
    # a chunk's sync waits for its dispatch: dispatch, then sync
    first = chunks[0]
    d = next(s for s in by["shadow.dispatch"] if _inside(s, first))
    assert any(_inside(s, first) and s[1] >= d[2] for s in by["shadow.sync"])
    (restore,) = by["shadow.restore"]
    assert restore[1] >= run[2]


def test_results_identical_with_and_without_a_profiler_session(tmp_path):
    _assert_profiler_session_changes_nothing(tmp_path, _phold(stop_s=2))


def test_tgen_results_identical_with_and_without_a_profiler_session(tmp_path):
    _assert_profiler_session_changes_nothing(tmp_path, _tgen(stop="300 ms"))


def _assert_profiler_session_changes_nothing(tmp_path, cfg):
    plain = _sim(cfg)
    rep_plain = plain.run(progress=False)
    traced = _sim(cfg)
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep_traced = traced.run(progress=False)
    finally:
        jax.profiler.stop_trace()
    for key in ("determinism_digest", "events_processed", "rounds",
                "microsteps", "queue_overflow_dropped"):
        assert rep_traced[key] == rep_plain[key], key
    assert rep_plain["events_processed"] > 0
    np.testing.assert_array_equal(traced.host_digests(), plain.host_digests())


@pytest.mark.parametrize("name", ["run", "sync", "heartbeat", "restore"])
def test_spans_outside_the_ledger_stay_in_host_python(name):
    """A span that is not one of the ledger's SPAN_NAMES adds no key to the
    runtime block: its seconds stay in the host_python residual."""
    assert name not in SPAN_NAMES
    w = WallLedger()
    w.sync_sim(0)
    w.chunk_start()
    with span_or_null(w, name):
        pass
    with span_or_null(w, "dispatch"):
        pass
    w.chunk_end(1)
    assert set(w.totals) == set(SPAN_NAMES)
    assert set(w.chunks[0]["spans"]) <= set(SPAN_NAMES)
