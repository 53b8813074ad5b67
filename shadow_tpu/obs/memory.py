"""HBM & capacity observatory: a memory-accounting plane for the engine.

The reference Shadow heartbeats per-host allocated memory through its
tracker (tracker.c) because capacity-sized structures are the scaling
bottleneck; this port's capacity guards were blind until now — the
pressure plane discovered HBM limits by catching RESOURCE_EXHAUSTED
after a wasted compile+dispatch, and the ensemble `max_replicas` guard
was a comment. This module gives every capacity decision numbers, from
THREE independent sources:

  (a) static byte model — derived from the single-source lane registry
      (core/lanes.py STATE_LANES widths x STATE_LANE_SHAPES formulas):
      per-component bytes per shard and per host for ANY (capacity,
      outbox, gear, K, replicas, trace) shape, without touching a
      device. Components the registry does not cover (model pytree,
      token buckets, CoDel, routing params) are measured EXACTLY from
      pytree leaf metadata (shape x dtype — still no device transfer).

  (b) compiled-program ledger — `Compiled.memory_analysis()` (argument/
      output/temp/generated-code bytes) for every chunk program a run's
      engine holds: the base program plus each (gear x capacity x
      budget) rung `Engine.run_chunk_resized` cached, and the ensemble
      program. XLA's own accounting, so it includes what the model
      cannot see (temporaries, fusion buffers).

  (c) live device sampling — `device.memory_stats()` (bytes_in_use /
      peak_bytes_in_use / bytes_limit) at chunk boundaries, folded into
      a per-shard HBM high-water. CPU backends report no allocator
      stats (memory_stats() is None); the monitor then falls back to
      the MODELED live bytes (source (a)'s exact pytree accounting) so
      per-shard high-water telemetry is never silently zero.

Everything here is an OBSERVER on the host side: no traced code changes
whether the observatory is on or off, so digests, events, and every
drop/pressure counter are bit-identical by construction — and the
default jaxpr fingerprint is byte-unchanged (tests/test_memory.py +
tools/lint/jaxpr_audit.py are the gates). The one feedback path is
deliberate and drop-free-safe: `MemoryGuard` lets the pressure plane
REFUSE a grown rung whose predicted footprint exceeds measured headroom
(x a safety factor) BEFORE dispatch, replacing an OOM round-trip with a
poisoned rung — a refusal can cost a PressureAbort the OOM would have
forced anyway, never a drop.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from shadow_tpu.core import lanes

# replay/migration concurrency: while the pressure plane grows a rung it
# holds the pre-chunk snapshot AND the migrated live state, so admission
# charges every grown byte twice (MemoryGuard.copies)
DEFAULT_GUARD_COPIES = 2
DEFAULT_SAFETY_FACTOR = 1.25


# --------------------------------------------------------------------------
# (a) static byte model — lane registry formulas + exact pytree metadata
# --------------------------------------------------------------------------


def _dtype_bytes(dt: str) -> int:
    """Registry dtype string -> bytes per element (bool is stored as one
    byte even though lanes.BITS counts it as one bit)."""
    return 1 if dt == "bool" else np.dtype(dt).itemsize


def dims_of(
    *,
    hosts_per_shard: int,
    queue_capacity: int,
    queue_block: int = 0,
    send_budget: int = 8,
    trace_rounds: int = 0,
    pressure: bool = False,
    netobs: bool = False,
    flow_records: int = 0,
    integrity: bool = False,
    integrity_dual: bool = False,
    wheel_slots: int = 0,
    wheel_block: int = 0,
    fluid_classes: int = 0,
    fluid_links: int = 0,
    hier: bool = False,
    a2a_ladder: bool = False,
    payload_words: int | None = None,
    trace_cols: int | None = None,
    flow_cols: int | None = None,
) -> dict[str, int]:
    """Resolve the STATE_LANE_SHAPES dimension tokens for one shape.

    `payload_words`/`trace_cols`/`flow_cols` default to the live
    constants (ops.events.EVENT_PAYLOAD_WORDS / len(tracer.TRACE_FIELDS)
    / len(netobs.FLOW_FIELDS)) — pass them explicitly only when modeling
    a foreign layout."""
    if payload_words is None:
        from shadow_tpu.ops.events import EVENT_PAYLOAD_WORDS

        payload_words = EVENT_PAYLOAD_WORDS
    if trace_cols is None:
        from shadow_tpu.obs.tracer import TRACE_COLS

        trace_cols = TRACE_COLS
    if flow_cols is None:
        from shadow_tpu.obs.netobs import FLOW_COLS

        flow_cols = FLOW_COLS
    if wheel_slots:
        from shadow_tpu.ops.wheel import resolve_wheel_block

        wnb = int(wheel_slots) // resolve_wheel_block(wheel_slots, wheel_block)
    else:
        wnb = 0
    return {
        "H": int(hosts_per_shard),
        "C": int(queue_capacity),
        "NB": int(queue_capacity) // queue_block if queue_block else 0,
        "P": int(payload_words),
        "SB": int(send_budget),
        "S": 1,
        "R": int(trace_rounds),
        "F": int(trace_cols),
        "FR": int(flow_records) if netobs else 0,
        "FF": int(flow_cols),
        "WS": int(wheel_slots),
        "WNB": wnb,
        "FK": int(fluid_classes),
        "FN": int(fluid_links) if fluid_classes else 0,
        "pressure": 1 if pressure else 0,
        "netobs": 1 if netobs else 0,
        # hierarchical exchange (core/engine.py _exchange_hierarchical):
        # gates the two-tier byte counters (stats.ici_intra/ici_inter)
        "hier": 1 if hier else 0,
        # alltoall block rungs: gates stats.a2a_pair_hwm
        "a2a_ladder": 1 if a2a_ladder else 0,
        "integrity": 1 if integrity else 0,
        "integrity_dual": 1 if integrity_dual else 0,
    }


def dims_of_config(cfg) -> dict[str, int]:
    """Dimension tokens for an EngineConfig (per-SHARD accounting)."""
    return dims_of(
        hosts_per_shard=cfg.hosts_per_shard,
        queue_capacity=cfg.queue_capacity,
        queue_block=cfg.queue_block,
        send_budget=cfg.sends_per_host_round,
        trace_rounds=cfg.trace_rounds,
        pressure=cfg.pressure_abort,
        netobs=cfg.netobs,
        flow_records=cfg.flow_records,
        integrity=cfg.integrity,
        integrity_dual=cfg.integrity_dual,
        wheel_slots=cfg.wheel_slots,
        wheel_block=cfg.wheel_block,
        fluid_classes=cfg.fluid_classes,
        fluid_links=cfg.fluid_links,
        hier=cfg.hier_active,
        a2a_ladder=cfg.a2a_ladder,
    )


def dims_of_state(cfg, state) -> dict[str, int]:
    """Dimension tokens read off a LIVE state's shapes: under an
    escalate pressure policy the queue/outbox may have been regrown past
    the configured base, and the model must price what is actually in
    HBM (the shapes are the truth — the same rule the pressure
    controller's rewind path follows)."""
    q = state.queue
    cap = int(q.t.shape[-1])
    block = cap // int(q.bt.shape[-1]) if hasattr(q, "bt") else 0
    return dims_of(
        hosts_per_shard=cfg.hosts_per_shard,
        queue_capacity=cap,
        queue_block=block,
        send_budget=int(state.outbox.t.shape[-1]),
        trace_rounds=(
            int(state.trace.rows.shape[-2]) if state.trace is not None else 0
        ),
        pressure=state.stats.pressure is not None,
        netobs=state.stats.ec_timer is not None,
        flow_records=(
            int(state.flows.rows.shape[-2]) if state.flows is not None else 0
        ),
        integrity=state.stats.integrity is not None,
        integrity_dual=state.stats.digest2 is not None,
        wheel_slots=(
            int(state.wheel.t.shape[-1]) if state.wheel is not None else 0
        ),
        wheel_block=(
            int(state.wheel.block) if state.wheel is not None else 0
        ),
        fluid_classes=(
            int(state.fluid.rates.shape[-1])
            if getattr(state, "fluid", None) is not None else 0
        ),
        fluid_links=(
            int(state.fluid.link_util.shape[-1])
            if getattr(state, "fluid", None) is not None else 0
        ),
        hier=state.stats.ici_intra is not None,
        a2a_ladder=state.stats.a2a_pair_hwm is not None,
    )


def lane_plane_bytes(path: str, dims: dict[str, int]) -> int | None:
    """Per-shard bytes of one registered carry plane at `dims`, or None
    when the plane is absent from the carry at this shape (flat queue
    drops the bucket caches, trace_rounds 0 drops the ring, the default
    drop policy carries no stats.pressure)."""
    shape = lanes.STATE_LANE_SHAPES[path]
    if path.startswith("queue.b") and dims["NB"] == 0:
        return None
    if path.startswith("trace.") and dims["R"] == 0:
        return None
    if path == "stats.pressure" and not dims["pressure"]:
        return None
    # integrity-sentinel planes (core/integrity.py): the violation/
    # signature lanes ride the sentinel knob, the dual digest its own
    if path in ("stats.integrity", "stats.iv_mask", "stats.iv_round") and (
        not dims.get("integrity")
    ):
        return None
    if path == "stats.digest2" and not dims.get("integrity_dual"):
        return None
    # network-observatory planes: class/safe-window lanes ride with the
    # knob, flow lanes additionally require an active ledger ring
    if path in (
        "stats.ec_timer", "stats.ec_pkt", "stats.ec_app", "stats.win_bound"
    ) and not dims.get("netobs"):
        return None
    if path in ("stats.fl_done", "stats.fl_bytes", "stats.fl_rtx") and (
        not dims.get("netobs") or dims.get("FR", 0) == 0
    ):
        return None
    if path.startswith("flows.") and dims.get("FR", 0) == 0:
        return None
    # timer-wheel planes (ops/wheel.py): absent unless the wheel is on
    if (
        path.startswith("wheel.")
        or path in ("stats.wheel_spilled", "stats.wheel_occ_hwm")
    ) and dims.get("WS", 0) == 0:
        return None
    # fluid planes (net/fluid.py): absent unless classes are declared
    if (
        path.startswith("fluid.")
        or path in ("stats.fl_bg_bytes", "stats.fl_bg_dropped")
    ) and dims.get("FK", 0) == 0:
        return None
    # hierarchical-exchange tier counters: absent off the hierarchical path
    if path in ("stats.ici_intra", "stats.ici_inter") and not dims.get(
        "hier"
    ):
        return None
    # alltoall block-rung signal: absent unless the block may leave its top
    if path == "stats.a2a_pair_hwm" and not dims.get("a2a_ladder"):
        return None
    n = 1
    for tok in shape:
        n *= tok if isinstance(tok, int) else dims[tok]
    return n * _dtype_bytes(lanes.STATE_LANES[path])


def registered_component_bytes(dims: dict[str, int]) -> dict[str, dict[str, int]]:
    """Per-shard bytes of every registered carry plane, grouped by
    component (the SimState top-level field, with bare paths under
    "scalars"). The single-source static model: widths from STATE_LANES,
    shapes from STATE_LANE_SHAPES, nothing else."""
    out: dict[str, dict[str, int]] = {}
    for path in lanes.STATE_LANES:
        b = lane_plane_bytes(path, dims)
        if b is None:
            continue
        comp = path.split(".")[0] if "." in path else "scalars"
        out.setdefault(comp, {})[path] = b
    return out


def component_totals(comps: dict[str, dict[str, int]]) -> dict[str, int]:
    return {k: sum(v.values()) for k, v in sorted(comps.items())}


def leaf_nbytes(leaf) -> int:
    """Bytes of one pytree leaf from METADATA only (shape x dtype — no
    device transfer; works on jax arrays, numpy arrays, and
    ShapeDtypeStructs alike)."""
    shape = getattr(leaf, "shape", ())
    dtype = getattr(leaf, "dtype", None)
    if dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * np.dtype(dtype).itemsize


def tree_bytes(tree) -> int:
    """Total bytes of a pytree, metadata-only."""
    import jax

    return sum(leaf_nbytes(x) for x in jax.tree_util.tree_leaves(tree))


def modeled_shard_bytes(state, params=None, world: int = 1) -> int:
    """The monitor's modeled-fallback figure: exact metadata bytes of
    the live device pytrees, per shard. The ONE formula every driver
    passes to `MemoryMonitor.sample(modeled_bytes=...)` — metadata-only
    (shape x dtype), so it is safe even on donation-consumed arrays."""
    total = tree_bytes(state)
    if params is not None:
        total += tree_bytes(params)
    return total // max(int(world), 1)


def state_field_bytes(state) -> dict[str, int]:
    """Bytes per top-level field of a NamedTuple state pytree (the exact
    counterpart of the formula model — covers the unregistered planes:
    model state, token buckets, CoDel)."""
    import jax

    out: dict[str, int] = {}
    for name, sub in zip(type(state)._fields, state):
        b = sum(leaf_nbytes(x) for x in jax.tree_util.tree_leaves(sub))
        if b:
            out[name] = b
    return out


def per_host_split(tree, num_hosts: int) -> tuple[int, int]:
    """(per_host_slope_bytes, fixed_bytes): leaves whose LEADING axis is
    the host axis scale with host count; everything else (replicated
    tables, per-shard counters, scalars) is fixed. The capacity
    planner's affine decomposition — heuristic only where a replicated
    table's leading dim happens to equal the host count."""
    import jax

    per_host = fixed = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        b = leaf_nbytes(leaf)
        shape = getattr(leaf, "shape", ())
        if shape and int(shape[0]) == num_hosts:
            per_host += b
        else:
            fixed += b
    return per_host // max(num_hosts, 1), fixed


def static_model(cfg, state=None, params=None, replicas: int = 1) -> dict:
    """The full source-(a) report for one engine shape.

    Registered components come from the lane-registry formulas —
    dimensioned from the STATE's actual shapes when one is provided
    (escalation regrows them past the config's base). `state`/`params`
    (metadata-only) add the exact bytes of the unregistered planes and
    a consistency figure the tests pin: formula bytes == actual
    carry-leaf bytes for every registered component. `replicas` scales
    the per-shard state for the ensemble plane (params broadcast via
    in_axes=None and are NOT scaled)."""
    dims = dims_of_state(cfg, state) if state is not None else (
        dims_of_config(cfg)
    )
    comps = registered_component_bytes(dims)
    totals = component_totals(comps)
    registered = sum(totals.values())
    out: dict[str, Any] = {
        "components": totals,
        "registered_bytes": registered,
        "replicas": int(replicas),
    }
    world = max(int(getattr(cfg, "world", 1)), 1)
    state_shard = registered
    if state is not None:
        fields = state_field_bytes(state)
        measured_total = sum(fields.values())
        covered = {
            "queue", "outbox", "stats", "trace", "flows", "rng", "now",
            "done", "seq", "sent_round", "cpu_busy_until", "min_used_lat",
        }
        unreg = {
            k: v // world for k, v in fields.items() if k not in covered
        }
        out["unregistered"] = unreg
        out["components"] = {**totals, **unreg}
        state_shard = registered + sum(unreg.values())
        out["state_bytes_measured"] = measured_total // world
    out["state_bytes"] = state_shard * int(replicas)
    if params is not None:
        pb = tree_bytes(params)
        out["params_bytes"] = pb // world
        out["total_bytes"] = out["state_bytes"] + pb // world
    else:
        out["total_bytes"] = out["state_bytes"]
    h = dims["H"]
    out["per_host_bytes"] = out["total_bytes"] // max(h, 1)
    return out


def state_bytes_at(cfg, capacity: int, send_budget: int) -> int:
    """Per-shard REGISTERED state bytes at an escalated
    (capacity, send_budget) shape — the pressure plane's pre-dispatch
    footprint predictor (the unregistered planes do not scale with
    either axis, so the delta between two shapes is exact)."""
    dims = dims_of(
        hosts_per_shard=cfg.hosts_per_shard,
        queue_capacity=capacity or cfg.queue_capacity,
        queue_block=cfg.queue_block,
        send_budget=send_budget or cfg.sends_per_host_round,
        trace_rounds=cfg.trace_rounds,
        pressure=cfg.pressure_abort,
        netobs=cfg.netobs,
        flow_records=cfg.flow_records,
        integrity=cfg.integrity,
        integrity_dual=cfg.integrity_dual,
        wheel_slots=cfg.wheel_slots,
        wheel_block=cfg.wheel_block,
        hier=cfg.hier_active,
        a2a_ladder=cfg.a2a_ladder,
    )
    return sum(component_totals(registered_component_bytes(dims)).values())


# --------------------------------------------------------------------------
# (b) compiled-program ledger — XLA's own accounting per cached rung
# --------------------------------------------------------------------------

_MA_FIELDS = (
    "generated_code_size_in_bytes",
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "temp_size_in_bytes",
    "alias_size_in_bytes",
)


def memory_analysis_dict(compiled) -> dict | None:
    """`Compiled.memory_analysis()` -> plain dict, or None when the
    backend provides no analysis. `peak_bytes` is the standard XLA
    decomposition: arguments + outputs + temps + code, minus the
    donation-aliased bytes counted twice."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {
        f.replace("_size_in_bytes", "_bytes"): int(getattr(ma, f))
        for f in _MA_FIELDS
        if hasattr(ma, f)
    }
    if not out:
        return None
    out["peak_bytes"] = (
        out.get("argument_bytes", 0)
        + out.get("output_bytes", 0)
        + out.get("temp_bytes", 0)
        + out.get("generated_code_bytes", 0)
        - out.get("alias_bytes", 0)
    )
    return out


def resized_avals(state, capacity: int, send_budget: int, queue_block: int):
    """ShapeDtypeStruct pytree of `state` re-seated at (capacity,
    send_budget), via the SAME migration ops the pressure plane uses —
    `jax.eval_shape` only, nothing runs."""
    import jax

    from shadow_tpu.core.engine import make_empty_outbox
    from shadow_tpu.ops.events import migrate_queue

    def f(st):
        q, ob = st.queue, st.outbox
        if capacity and capacity != q.t.shape[1]:
            q = migrate_queue(q, capacity, queue_block)
        if send_budget and send_budget != ob.t.shape[1]:
            ob = make_empty_outbox(ob.t.shape[0], send_budget, ob.count)
        return st._replace(queue=q, outbox=ob)

    return jax.eval_shape(f, state)


def ledger_entries(engine) -> dict[str, Any]:
    """key -> EngineConfig for every chunk program this engine's run
    touched: the base program plus each cached gear / resized rung."""
    out = {"base": engine.cfg}
    for g in sorted(engine._gear_chunks):
        out[f"gear={g}"] = dataclasses.replace(engine.cfg, gear_cols=g)
    for (g, c, b, k) in sorted(engine._resized_chunks):
        out[f"cap={c}/box={b}/gear={g}" + (f"/a2a={k}" if k else "")] = (
            engine.resized_cfg(g, c, b, k)
        )
    return out


def compiled_ledger(engine, state, params) -> dict[str, dict]:
    """Source (b): memory_analysis for every program in
    `ledger_entries`. Each entry is lowered against avals at ITS OWN
    shape (resized_avals re-seats the live state's tree), then compiled
    — reading the analysis needs a Compiled object, and jax's jit cache
    does not expose the one the run used, so this recompiles. Cost is
    paid only when the observatory is asked for a ledger (opt-in
    reporting, never the run loop)."""
    out: dict[str, dict] = {}
    for key, cfg in ledger_entries(engine).items():
        try:
            avals = resized_avals(
                state, cfg.queue_capacity, cfg.sends_per_host_round,
                cfg.queue_block,
            )
            compiled = engine._jit_chunk(cfg).lower(avals, params).compile()
        except Exception as e:
            # a rung that cannot lower/compile is a FINDING in the
            # ledger, never a reason to lose the rest of the report
            out[key] = {"error": f"{type(e).__name__}: {e}"}
            continue
        md = memory_analysis_dict(compiled)
        out[key] = md if md is not None else {"unavailable": True}
    return out


# --------------------------------------------------------------------------
# (c) live device sampling — per-shard HBM high-water
# --------------------------------------------------------------------------


def device_memory_stats(device) -> dict | None:
    """`device.memory_stats()`, defensively: CPU backends return None,
    some return {} — both mean "no allocator stats here"."""
    try:
        st = device.memory_stats()
    except Exception:
        return None
    return st or None


def device_capacity_bytes(device=None) -> int | None:
    """Best-known memory capacity of a device: the allocator's
    bytes_limit (TPU/GPU), else — for host-backed devices — the box's
    MemAvailable, else None (capacity unknown; guards that need one
    stay inert)."""
    if device is None:
        import jax

        device = jax.devices()[0]
    st = device_memory_stats(device)
    if st:
        for key in ("bytes_limit", "bytes_reservable_limit"):
            if st.get(key):
                return int(st[key])
    if getattr(device, "platform", None) == "cpu":
        try:
            with open("/proc/meminfo") as f:
                for line in f:
                    if line.startswith("MemAvailable:"):
                        return int(line.split()[1]) * 1024
        except OSError:
            return None
    return None


# bound the retained sample list (one sample per chunk; a week-long run
# must not grow an unbounded Python list) — the hwm fold is unaffected
MAX_SAMPLES = 8192


class MemoryMonitor:
    """Per-shard live HBM telemetry, sampled at chunk boundaries.

    `devices` is the mesh's device list (one entry per shard; world=1
    passes the single device). `stats_fn` injects a fake
    `memory_stats` for tests (the pre-dispatch-refusal gates run
    against synthetic headroom). When no device reports allocator
    stats, `sample(modeled_bytes=...)` falls back to the static model's
    exact live-state accounting so the high-water is honest, not zero —
    `source` says which world the numbers came from."""

    def __init__(self, devices=None, stats_fn=None):
        if devices is None:
            import jax

            devices = [jax.devices()[0]]
        self.devices = list(devices)
        self._stats_fn = stats_fn or device_memory_stats
        n = len(self.devices)
        self.peak = [0] * n  # per-shard high-water (bytes)
        self.last = [0] * n  # per-shard bytes at the last sample
        self.limit_bytes: int | None = None
        self.source: str | None = None
        self.samples: list[tuple[float | None, tuple[int, ...]]] = []
        self.samples_lost = 0
        self.count = 0

    def sample(
        self, *, modeled_bytes: int | None = None, wall_t: float | None = None
    ) -> list[int]:
        """One sample across the shard devices; returns per-shard
        bytes_in_use. `modeled_bytes` is the PER-SHARD modeled live
        total used when a device has no allocator stats."""
        per_shard: list[int] = []
        source = None
        for i, d in enumerate(self.devices):
            st = self._stats_fn(d)
            if st and st.get("bytes_in_use") is not None:
                used = int(st["bytes_in_use"])
                peak = int(st.get("peak_bytes_in_use", used))
                if st.get("bytes_limit"):
                    self.limit_bytes = int(st["bytes_limit"])
                source = source or "device"
            elif modeled_bytes is not None:
                used = peak = int(modeled_bytes)
                source = source or "modeled"
            else:
                used = peak = 0
            per_shard.append(used)
            self.peak[i] = max(self.peak[i], peak, used)
            self.last[i] = used
        if source is not None:
            self.source = self.source or source
        self.count += 1
        if len(self.samples) >= MAX_SAMPLES:
            self.samples_lost += 1
        else:
            self.samples.append((wall_t, tuple(per_shard)))
        return per_shard

    def headroom_bytes(self) -> int | None:
        """Worst-shard headroom against the allocator limit at the last
        sample, or None when no limit is known (the informed guard is
        then inert — there is nothing to refuse against)."""
        if self.limit_bytes is None or self.count == 0:
            return None
        return self.limit_bytes - max(self.last)

    def hwm_bytes(self) -> int:
        """Run high-water across shards (the heartbeat `hbm=` value)."""
        return max(self.peak) if self.peak else 0

    def report(self) -> dict:
        out: dict[str, Any] = {
            "source": self.source,
            "samples": self.count,
            "per_shard_hwm_bytes": list(self.peak),
            "bytes_in_use": list(self.last),
        }
        if self.limit_bytes is not None:
            out["limit_bytes"] = self.limit_bytes
            out["headroom_bytes"] = self.headroom_bytes()
        if self.samples_lost:
            out["samples_dropped"] = self.samples_lost
        return out


class MemoryGuard:
    """Pre-dispatch admission control for the pressure plane's grown
    rungs (threaded into core/pressure.py ResilienceController).

    A candidate (capacity, budget) rung is admitted only when the extra
    bytes it needs — the registered-state delta, charged `copies` times
    for the snapshot+migrated-state concurrency of a replay, times the
    configured safety factor — fit inside the monitor's measured
    headroom. Unknown headroom (no allocator limit: CPU backends, or no
    sample yet) admits everything: the guard exists to SAVE an OOM
    round-trip where measurement exists, never to invent limits where
    it doesn't."""

    def __init__(
        self,
        cfg,
        monitor: MemoryMonitor | None,
        safety_factor: float = DEFAULT_SAFETY_FACTOR,
        copies: int = DEFAULT_GUARD_COPIES,
    ):
        self.cfg = cfg
        self.monitor = monitor
        self.safety_factor = float(safety_factor)
        self.copies = int(copies)

    def predicted_need_bytes(
        self, cur_cap: int, cur_box: int, new_cap: int, new_box: int
    ) -> int:
        delta = state_bytes_at(self.cfg, new_cap, new_box) - state_bytes_at(
            self.cfg, cur_cap, cur_box
        )
        return max(int(delta * self.copies * self.safety_factor), 0)

    def admit(
        self, cur_cap: int, cur_box: int, new_cap: int, new_box: int
    ) -> tuple[bool, int, int | None]:
        """(ok, predicted_need_bytes, headroom_bytes)."""
        need = self.predicted_need_bytes(cur_cap, cur_box, new_cap, new_box)
        headroom = (
            self.monitor.headroom_bytes() if self.monitor is not None else None
        )
        if headroom is None:
            return True, need, None
        return need <= headroom, need, headroom


# --------------------------------------------------------------------------
# capacity planning + driver report assembly
# --------------------------------------------------------------------------


def plan_max_hosts(
    per_host_bytes: float, fixed_bytes: float, hbm_bytes: float,
    safety_factor: float = DEFAULT_SAFETY_FACTOR,
) -> int:
    """Max hosts one device fits: solve
    (fixed + hosts * per_host) * safety <= hbm. The ROADMAP question
    ("given this config, what is max hosts/device before OOM?") in one
    line — callers derive per_host/fixed from the static model plus the
    compiled ledger's temp slope."""
    if per_host_bytes <= 0:
        return 0
    budget = hbm_bytes / max(safety_factor, 1e-9) - fixed_bytes
    return max(int(budget // per_host_bytes), 0)


def observatory_report(
    engine, state, params, monitor: MemoryMonitor | None = None,
    *, replicas: int = 1, ledger: bool = True,
) -> dict:
    """The sim-stats `memory{}` block both drivers and bench share:
    model (a) + ledger (b) + live sampling (c)."""
    out: dict[str, Any] = {
        "model": static_model(engine.cfg, state, params, replicas=replicas),
    }
    if ledger:
        out["ledger"] = compiled_ledger(engine, state, params)
    if monitor is not None:
        out.update(monitor.report())
    return out
