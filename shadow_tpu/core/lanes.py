"""Lane registry: the single source of truth for simulation-lane widths.

Every invariant this engine lives on — bit-identical digests, i64
time/order keys, counter-based RNG purity — depends on lanes keeping
their declared widths. The reference Shadow leans on Rust's type system
for this (SimulationTime is a newtype over u64; a narrowing conversion
does not compile). The JAX port has no static types, so this module
declares the widths once and two enforcement layers read it:

  * shadowlint stage A (tools/lint/astlint.py, rule R2) — pure-AST scan
    of shadow_tpu/core + shadow_tpu/ops + obs/tracer.py flagging
    `.astype(...)` narrowing and implicit-dtype construction of any
    registered lane;
  * the jaxpr audit (tools/lint/jaxpr_audit.py) — traces the round body
    and asserts the actual carry dtypes of `STATE_LANES` match.

The planned SimState "memory diet" (ROADMAP item 1) narrows lanes HERE,
deliberately, and both layers follow — instead of an `astype` somewhere
in the round body silently truncating event times.

IMPORTANT: this module is imported by stage A, which must run without
JAX (the tier-1 pre-stage survives jaxlib corruption that kills compiled
runs). Keep it stdlib-only: names and dtype strings, no jnp.
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# Terminal lane names -> required width, used by the AST narrowing rule (R2).
# A "terminal name" is the last attribute/variable name of an expression
# (`ev.t` -> "t", `ring.cursor[0]` -> "cursor"). Narrowing a 64-bit lane
# below its registered width, or constructing one without an explicit
# dtype, is a lint error.
# ---------------------------------------------------------------------------

# Simulated-time lanes: int64 nanoseconds (reference SimulationTime).
# i32 ns wraps at ~2.1 sim-seconds; i32 ms would break the deterministic
# (time, order) total key. Never narrow.
TIME_LANES = frozenset({
    "t",
    "now",
    "window_start",
    "window_end",
    "cpu_busy_until",
    "busy_until",
    "min_used_lat",
    "down_t",
    "up_t",
    "win_start",
    "win_end",
    "arrive",
    "depart",
    "eg_depart",
    "next_time",
    "exec_t",
    "t_push",
    "t_cand",
    "resume",
    "lat_ns",
    "jitter_ns",
    # bucketed-queue / timer-wheel block-minimum TIME cache (bt): a min
    # over t entries is itself a time — same 64-bit obligation
    "bt",
})

# Event-ordering lanes: int64 packed (locality, src-host, seq) keys
# (ops/events.py pack_order). The packing uses the full 63 bits; any
# narrowing collides order keys and breaks determinism.
ORDER_LANES = frozenset({"order", "seq", "bo"})

# Monotone counter lanes: int64. A long campaign overflows i32 counters
# (events at 10k hosts pass 2^31 in under an hour of sim time), and the
# trace/flow rings' cursor arithmetic assumes no wrap. The network
# observatory's class/flow/safe-window counters are the same species
# (fl_bytes at 10k flows/s of 100 KiB flows passes 2^31 in minutes).
COUNTER_LANES = frozenset({
    "cursor", "rounds", "microsteps", "events",
    "ec_timer", "ec_pkt", "ec_app",
    "fl_done", "fl_bytes", "fl_rtx", "win_bound",
    # integrity sentinel (core/integrity.py): the psum'd violation
    # count, the per-shard invariant bitmask, and the first-violation
    # round index (-1 = none) — i64 like every control-signal lane
    "integrity", "iv_mask", "iv_round",
    # fluid traffic plane (net/fluid.py): cumulative background bytes
    # delivered / DropTail-dropped — bytes at Gbit-scale demand pass
    # 2^31 in seconds of sim time, so i64 like fl_bytes
    "fl_bg_bytes", "fl_bg_dropped",
})

# Fluid-plane f64 lanes (net/fluid.py FluidState): the per-class carried
# rates and per-link offered utilization the round body's forward-Euler
# step maintains. float64 deliberately — the ODE is replicated global
# math whose drift across shards would break the mesh-shape determinism
# gate; f32 accumulation error at Gbit rates over long horizons is a
# real divergence risk. Never narrow.
FLUID_LANES: dict[str, str] = {
    "rates": "float64",
    "link_util": "float64",
}

# Digest lanes: uint64 (FNV-1a fold, core/engine.py _digest_update;
# digest2 is the integrity sentinel's independently-folded dual lane,
# core/engine.py _digest_update2).
DIGEST_LANES = frozenset({"digest", "digest2"})

# Deliberately-32-bit lanes (ids and per-round cursors bounded by
# construction): narrowing TO these widths is fine, narrowing below is
# not. Kept here so the registry names every engine lane, not only the
# wide ones.
NARROW_LANES = {
    "dst": "int32",
    "kind": "int32",
    "payload": "int32",
    "sent_round": "int32",
    # exchange-wire fill accounting (core/engine.py alltoall/hierarchical
    # paths): per-destination-shard valid-row counts and the hierarchical
    # exchange's fill-counter wire vectors, all bounded by block/slot
    # counts (LANE_MIN_WIDTH_BITS states each bound) — i32 on the wire is
    # the lane diet, and riding them at i64 would silently double the
    # counter tier's ICI bytes
    "seg_len": "int32",
    "sent_counts": "int32",
    "recv_counts": "int32",
    # staging/queue fill counters bounded by slot counts: the outbox
    # append cursor (<= H_local x sends_per_host_round) and the bucketed
    # queue's per-block occupancy (<= queue_block)
    "count": "int32",
    "bfill": "int32",
}

# ---------------------------------------------------------------------------
# Lane diet (ISSUE 17): minimum EXACT width in bits per lane — the
# smallest width at which the lane's full value range provably
# round-trips, independent of the width it is registered at. Two uses:
#
#   * shadowlint rule R7 (tools/lint/schema.py check_lane_diet) asserts
#     every EXCHANGE_WIRE_LANES member has an entry here, that no lane is
#     registered NARROWER than its minimum, and that wire lanes whose
#     minimum is <= 32 are actually registered at 32 (the diet is real —
#     a bounded counter riding the wire at i64 is a silent 2x on
#     `stats.ici_inter`), while wire lanes whose minimum is 64 must be
#     time/order/digest lanes (the only species with a genuine 64-bit
#     range).
#   * the bounds below are the PROOF OBLIGATIONS: each entry names the
#     capacity/slot count that caps the lane. Growing one of those caps
#     past 2^31 must come back here first.
#
# Bounds (all static config values, enforced at EngineConfig build time):
#   dst          host id < num_hosts; ops/events.check_order_limits caps
#                num_hosts far below 2^31 (the packed order key budget)
#   kind         model event-kind enum (single-digit cardinality)
#   payload      i32 words by the EVENT_PAYLOAD_WORDS contract
#   sent_round   <= sends_per_host_round (per-round budget)
#   count        <= hosts_per_shard x sends_per_host_round (outbox slots)
#   bfill        <= queue_block (per-block slot count)
#   seg_len      <= hosts_per_shard x sends_per_host_round (local rows)
#   sent_counts  <= hier_block_size (minimum of seg_len and the block)
#   recv_counts  <= hier_block_size (a peer's sent_counts)
#   t, bt        int64 ns — i32 ns wraps at ~2.1 sim-seconds (TIME_LANES)
#   order, bo    full 63-bit packed (locality, src, seq) key (ORDER_LANES)
#   digest(2)    64-bit FNV state by definition (DIGEST_LANES)
# ---------------------------------------------------------------------------

LANE_MIN_WIDTH_BITS: dict[str, int] = {
    "dst": 32,
    "kind": 32,
    "payload": 32,
    "sent_round": 32,
    "count": 32,
    "bfill": 32,
    "seg_len": 32,
    "sent_counts": 32,
    "recv_counts": 32,
    "t": 64,
    "bt": 64,
    "order": 64,
    "bo": 64,
    "digest": 64,
    "digest2": 64,
}

#: lanes that cross an exchange collective in SOME exchange kind: the
#: gather path all_gathers the (sliced) outbox lanes wholesale; the
#: alltoall and hierarchical paths pack (dst, t, order, kind, payload)
#: into wire blocks; the hierarchical counter tier moves
#: sent_counts/recv_counts. R7's wire-width table is derived from this
#: set x LANE_MIN_WIDTH_BITS (docs/architecture.md reproduces it).
EXCHANGE_WIRE_LANES = frozenset({
    "dst", "t", "order", "kind", "payload", "count",
    "sent_counts", "recv_counts",
})

#: terminal lane name -> required dtype string
LANE_WIDTHS: dict[str, str] = {
    **{n: "int64" for n in TIME_LANES},
    **{n: "int64" for n in ORDER_LANES},
    **{n: "int64" for n in COUNTER_LANES},
    **{n: "uint64" for n in DIGEST_LANES},
    **FLUID_LANES,
    **NARROW_LANES,
}

#: ops helpers whose RETURN value is a lane (the AST rule resolves
#: `q_next_time(q).astype(...)` through this map)
FUNC_RETURN_LANES: dict[str, str] = {
    "q_next_time": "t",
    "next_time": "t",
    "bq_next_time": "t",
    "pack_order": "order",
}

BITS = {
    "bool": 1,
    "int8": 8, "uint8": 8,
    "int16": 16, "uint16": 16,
    "int32": 32, "uint32": 32, "float32": 32,
    "int64": 64, "uint64": 64, "float64": 64,
}


def lane_width_bits(name: str) -> int | None:
    """Registered width in bits for a terminal lane name, else None."""
    dt = LANE_WIDTHS.get(name)
    return BITS[dt] if dt else None


# ---------------------------------------------------------------------------
# SimState carry paths -> required dtype, asserted by the jaxpr audit on
# the TRACED round body (jax.eval_shape of core/engine._run_chunk). Paths
# are dotted attribute chains from SimState. Every Stats counter is also
# required to appear here — stage A rule R3 cross-checks the Stats
# NamedTuple against this dict, so adding a stats field without declaring
# its width fails lint.
# ---------------------------------------------------------------------------

_STATS_I64 = (
    "events", "pkts_sent", "pkts_lost", "pkts_unreachable",
    "pkts_codel_dropped", "pkts_delivered", "monotonic_violations",
    "pkts_budget_dropped", "faults_dropped", "faults_delayed",
    "ob_dropped", "a2a_shed", "microsteps", "bq_rebuilds",
    "popk_deferred", "ici_bytes", "q_occ_hwm", "outbox_hwm",
    "gear_shed", "rounds",
    # hierarchical-exchange tier counters (present only when
    # experimental.exchange: hierarchical on a multi-device mesh): byte
    # accumulators like ici_bytes, i64 for the same no-wrap reason
    "ici_intra", "ici_inter",
    # alltoall block-rung signal (present only when the flat alltoall
    # runs on a mesh with its block left auto): a row-count high-water
    "a2a_pair_hwm",
)

STATE_LANES: dict[str, str] = {
    "now": "int64",
    "done": "bool",
    "queue.t": "int64",
    "queue.order": "int64",
    "queue.kind": "int32",
    "queue.payload": "int32",
    "queue.dropped": "int64",
    # bucketed-queue cache planes (present only when queue_block > 0)
    "queue.bt": "int64",
    "queue.bo": "int64",
    "queue.bfill": "int32",
    "rng.s": "uint64",
    "seq": "int64",
    "sent_round": "int32",
    "cpu_busy_until": "int64",
    "min_used_lat": "int64",
    "outbox.dst": "int32",
    "outbox.t": "int64",
    "outbox.order": "int64",
    "outbox.kind": "int32",
    "outbox.payload": "int32",
    "outbox.count": "int32",
    "trace.rows": "int64",
    "trace.cursor": "int64",
    **{f"stats.{f}": "int64" for f in _STATS_I64},
    # pressure-abort signal (present only when the pressure policy is
    # escalate/abort — core/pressure.py; the default drop policy carries
    # None here and traces no pressure code)
    "stats.pressure": "int64",
    # network-observatory lanes (obs/netobs.py; present only when
    # observability.network is on — the fl_*/flows planes additionally
    # require an active flow ledger). Event-class counts, flow-ledger
    # totals, safe-window binder counts, and the ledger ring itself.
    "stats.ec_timer": "int64",
    "stats.ec_pkt": "int64",
    "stats.ec_app": "int64",
    "stats.fl_done": "int64",
    "stats.fl_bytes": "int64",
    "stats.fl_rtx": "int64",
    "stats.win_bound": "int64",
    "flows.rows": "int64",
    "flows.cursor": "int64",
    # integrity-sentinel lanes (core/integrity.py; present only when
    # the `integrity:` block enables the guards — the default program
    # carries None here and traces no sentinel code)
    "stats.integrity": "int64",
    "stats.iv_mask": "int64",
    "stats.iv_round": "int64",
    "stats.digest2": "uint64",
    "stats.digest": "uint64",
    # fluid traffic plane (net/fluid.py; present only when the `fluid:`
    # block declares classes — the default program carries None here and
    # traces no fluid code). The ODE carry lanes are replicated f64; the
    # byte counters are replicated i64 scalars (the ODE is global, so a
    # per-shard lane would multiply the total at export).
    "fluid.rates": "float64",
    "fluid.link_util": "float64",
    "stats.fl_bg_bytes": "int64",
    "stats.fl_bg_dropped": "int64",
    # timer-wheel planes (ops/wheel.py; present only when
    # experimental.timer_wheel > 0). The wheel IS the BucketQueue
    # machinery re-aimed at timers, so every wheel lane mirrors its
    # queue.* counterpart's width — WHEEL_LANE_OF_QUEUE below states the
    # pairing and the shadowlint wheel rule enforces the lockstep.
    "wheel.t": "int64",
    "wheel.order": "int64",
    "wheel.kind": "int32",
    "wheel.payload": "int32",
    "wheel.dropped": "int64",
    "wheel.bt": "int64",
    "wheel.bo": "int64",
    "wheel.bfill": "int32",
    "stats.wheel_spilled": "int64",
    "stats.wheel_occ_hwm": "int64",
}

# ---------------------------------------------------------------------------
# Timer-wheel lane pairing (ops/wheel.py): the wheel reuses the bucketed
# queue's slab + cache machinery verbatim, so each wheel.* lane must keep
# the SAME registered width as the queue.* lane the shared ops read and
# write. Narrowing one side but not the other would make the shared ops
# silently reinterpret bits. shadowlint's wheel rule (tools/lint/schema.py
# check_wheel_registry) asserts this dict is total over the wheel.* paths
# and that every pair agrees; the jaxpr audit pins the traced dtypes.
# ---------------------------------------------------------------------------

WHEEL_LANE_OF_QUEUE: dict[str, str] = {
    "wheel.t": "queue.t",
    "wheel.order": "queue.order",
    "wheel.kind": "queue.kind",
    "wheel.payload": "queue.payload",
    "wheel.dropped": "queue.dropped",
    "wheel.bt": "queue.bt",
    "wheel.bo": "queue.bo",
    "wheel.bfill": "queue.bfill",
}

# ---------------------------------------------------------------------------
# Shape formulas for the registered SimState carry paths, consumed by the
# memory observatory (shadow_tpu/obs/memory.py): dtype widths come from
# STATE_LANES above, shapes from here, so the static HBM byte model has
# exactly ONE source to drift from. Dimension tokens (resolved by the
# observatory against a concrete EngineConfig):
#
#   H   hosts per shard (num_hosts / world)
#   C   queue_capacity (per-host event slots)
#   NB  bucket-cache blocks = C // queue_block (planes absent on flat
#       queues — queue_block == 0 drops the queue.bt/bo/bfill entries)
#   P   EVENT_PAYLOAD_WORDS (ops/events.py)
#   SB  sends_per_host_round (outbox columns)
#   S   the per-shard element of a [world]-sharded plane (always 1)
#   R   trace_rounds (ring rows; plane absent when 0)
#   F   len(TRACE_FIELDS) (obs/tracer.py ring columns)
#   FR  flow_records (flow-ledger ring rows; flows planes absent when 0)
#   FF  len(FLOW_FIELDS) (obs/netobs.py ledger columns)
#   WS  wheel_slots (timer-wheel slots per host; wheel planes absent
#       when 0 — the wheel-off carry has no wheel at all)
#   WNB wheel block-cache blocks = WS // resolved wheel block
#   FK  fluid background-traffic classes (net/fluid.py; fluid planes
#       absent when 0 — the fluid-off carry has no fluid at all)
#   FN  fluid links (graph nodes the per-link ODE state covers)
#
# Integer entries are literal dimensions. Stage A stays jax-free: tokens
# only, no imports. tests/test_memory.py asserts this dict covers
# STATE_LANES exactly and that the formula bytes equal the real carry
# leaves' bytes on built engine states (flat/bucketed x trace x pressure).
# ---------------------------------------------------------------------------

_STATS_PER_HOST = (
    "events", "pkts_sent", "pkts_lost", "pkts_unreachable",
    "pkts_codel_dropped", "pkts_delivered", "monotonic_violations",
    "pkts_budget_dropped", "faults_dropped", "faults_delayed", "q_occ_hwm",
)
_STATS_PER_SHARD = (
    "ob_dropped", "a2a_shed", "microsteps", "bq_rebuilds", "popk_deferred",
    "ici_bytes", "outbox_hwm", "gear_shed", "pressure",
    "ec_timer", "ec_pkt", "ec_app", "fl_done", "fl_bytes", "fl_rtx",
    "win_bound", "integrity", "iv_mask", "iv_round",
    "ici_intra", "ici_inter", "a2a_pair_hwm",
)

STATE_LANE_SHAPES: dict[str, tuple] = {
    "now": (),
    "done": (),
    "queue.t": ("H", "C"),
    "queue.order": ("H", "C"),
    "queue.kind": ("H", "C"),
    "queue.payload": ("H", "C", "P"),
    "queue.dropped": ("H",),
    "queue.bt": ("H", "NB"),
    "queue.bo": ("H", "NB"),
    "queue.bfill": ("H", "NB"),
    "rng.s": ("H", 4),
    "seq": ("H",),
    "sent_round": ("H",),
    "cpu_busy_until": ("H",),
    "min_used_lat": (),
    "outbox.dst": ("H", "SB"),
    "outbox.t": ("H", "SB"),
    "outbox.order": ("H", "SB"),
    "outbox.kind": ("H", "SB"),
    "outbox.payload": ("H", "SB", "P"),
    "outbox.count": ("S",),
    "trace.rows": ("S", "R", "F"),
    "trace.cursor": ("S",),
    "flows.rows": ("S", "FR", "FF"),
    "flows.cursor": ("S",),
    **{f"stats.{f}": ("H",) for f in _STATS_PER_HOST},
    **{f"stats.{f}": ("S",) for f in _STATS_PER_SHARD},
    "stats.digest": ("H",),
    "stats.digest2": ("H",),
    "stats.rounds": (),
    "wheel.t": ("H", "WS"),
    "wheel.order": ("H", "WS"),
    "wheel.kind": ("H", "WS"),
    "wheel.payload": ("H", "WS", "P"),
    "wheel.dropped": ("H",),
    "wheel.bt": ("H", "WNB"),
    "wheel.bo": ("H", "WNB"),
    "wheel.bfill": ("H", "WNB"),
    "stats.wheel_spilled": ("H",),
    "stats.wheel_occ_hwm": ("H",),
    # fluid plane (net/fluid.py): replicated global ODE state + the
    # replicated scalar byte counters (shape () like stats.rounds)
    "fluid.rates": ("FK",),
    "fluid.link_util": ("FN",),
    "stats.fl_bg_bytes": (),
    "stats.fl_bg_dropped": (),
}

# ---------------------------------------------------------------------------
# Stats fields that are deliberately NOT exported in sim-stats.json
# (rule R3 requires every Stats field to be either read by
# shadow_tpu/sim.py stats_report or listed here with a reason).
# ---------------------------------------------------------------------------

_NETOBS_EXPORT_REASON = (
    "exported through the sim-stats network{} block assembled by "
    "obs/netobs.assemble_network_report (the ONE shared helper sim.py, "
    "cosim.py, and bench.py all call — it reads the lane directly so "
    "the block's shape cannot drift between exporters); gated on "
    "observability.network, None otherwise"
)

STATS_EXPORT_EXEMPT: dict[str, str] = {
    **{f: _NETOBS_EXPORT_REASON for f in (
        "ec_timer", "ec_pkt", "ec_app",
        "fl_done", "fl_bytes", "fl_rtx", "win_bound",
    )},
    **{f: (
        "exported through the sim-stats fluid{} block assembled by "
        "net/fluid.assemble_fluid_report (the ONE shared helper sim.py "
        "and bench.py both call — it reads the lane directly so the "
        "block's shape cannot drift between exporters); gated on the "
        "fluid: block declaring classes, None otherwise"
    ) for f in ("fl_bg_bytes", "fl_bg_dropped")},
    "gear_shed": (
        "transient gear-abort control signal: a shedding chunk is "
        "discarded and replayed from its pre-chunk snapshot, so the "
        "counter is structurally zero in any accepted final state; the "
        "gears{} block in sim-stats carries the replay accounting"
    ),
    "a2a_pair_hwm": (
        "per-chunk alltoall block-rung signal (core/gears."
        "BlockRungController): the driver reads and resets it after "
        "every accepted chunk, so it holds only the last chunk's rows; "
        "the a2a_rungs{} block in sim-stats carries the run's high-water"
    ),
    "pressure": (
        "transient pressure-abort control signal (core/pressure.py): a "
        "dropping chunk is discarded and replayed at a grown shape "
        "(escalate) or the run stops (abort), so the counter is "
        "structurally zero in any escalate-accepted final state and "
        "redundant with the per-category drop counters otherwise; the "
        "pressure{} block in sim-stats carries the regrow/replay "
        "accounting"
    ),
    **{f: (
        "transient integrity-abort control signal (core/integrity.py): "
        "a violating chunk is discarded and replayed from its pre-chunk "
        "snapshot (transient SDC) or the run stops (IntegrityAbort), so "
        "the lanes are structurally zero/-1 in any accepted final "
        "state; the integrity{} block in sim-stats carries the "
        "transient/replay accounting and the deterministic-violation "
        "naming"
    ) for f in ("integrity", "iv_mask", "iv_round")},
}

# ---------------------------------------------------------------------------
# Heartbeat-format registry (rule R5). Keys that older emitters produced
# but no current code path emits — the parser must keep matching them so
# recorded logs stay parseable. (`windows=` is still live: the hybrid
# cosim driver emits it.)
# ---------------------------------------------------------------------------

HEARTBEAT_LEGACY_KEYS: frozenset[str] = frozenset()
