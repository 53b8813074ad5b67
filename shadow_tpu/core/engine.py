"""The conservative-PDES round engine, entirely on device.

One *round* advances every host through the safe window
[window_start, window_end):

  1. barrier: global min next-event time over the mesh (`lax.pmin` — the
     device form of the per-thread min reduction at reference
     manager.rs:459-464 + controller.rs:88-112);
  2. window_end = min(global_min + runahead, stop_time) where runahead is the
     minimum network latency, optionally shrinking dynamically
     (reference core/runahead.rs:44-57);
  3. microsteps: while any local host has an event < window_end, every host
     pops its earliest event (deterministic total order) and one vectorized
     model dispatch executes for all active hosts (Host::execute,
     host.rs:809-864). Packet arrivals pass ingress shaping (downlink token
     bucket + CoDel) first; sends pass egress shaping and are staged in the
     shard-local outbox;
  4. exchange: outboxes all-gather across the mesh and merge into destination
     queues with the deterministic sorted scatter (the lock-free replacement
     for worker.rs:644-654's per-host mutex push). Conservative lookahead
     guarantees every cross-host packet arrives >= window_end, which is what
     makes the once-per-round exchange exact, not an approximation.

Microstep loops have NO collectives, so shards run them at their own pace;
rounds are the only synchronization points — exactly the reference's
"hosts are the unit of parallelism" invariant (scheduler/src/lib.rs:3-6).

Determinism: pops follow the packed (time, order) key; RNG advances are
per-host masked; the cross-shard merge sorts by (dst, time, order); integer
scatter-adds are order-free. Result: per-host event digests are bit-identical
across runs AND across mesh shapes (the device analogue of the reference's
determinism gate, src/test/determinism/). Scope note: bit-equality across
*platforms* (TPU vs CPU) holds for the integer engine core and integer-only
models, but models using float transcendentals (e.g. PHOLD's exponential
draw) may diverge across backends — the reference likewise promises identical
re-runs on one machine, not cross-machine equality.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array, lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from shadow_tpu.models.base import (
    HandlerCtx,
    KIND_INGRESS_DONE,
    KIND_MASK,
    KIND_PKT,
    PAYLOAD_SIZE_WORD,
)
from shadow_tpu.net import (
    TBParams,
    TBState,
    codel_init,
    codel_on_packet,
    tb_conforming_remove,
    tb_init,
)
from shadow_tpu.ops import (
    BucketQueue,
    EventQueue,
    ORDER_MAX,
    block_minima,
    bucket_rebuild,
    as_flat,
    check_order_limits,
    merge_flat_events,
    pack_order,
    q_clear_popped,
    q_head,
    q_len,
    q_next_time,
    q_pop_k,
    q_pop_min,
    q_push_many,
)
from shadow_tpu.ops.merge import merge_apply, merge_empty_plan, merge_plan
from shadow_tpu.ops.wheel import (
    wheel_free,
    wheel_next_time,
    wheel_pop_min,
    wheel_push_many,
)
from shadow_tpu.obs.tracer import (
    COL_A2A_SHED,
    COL_BQ_REBUILDS,
    COL_EVENTS,
    COL_GEAR,
    COL_ICI_BYTES,
    COL_MICROSTEPS,
    COL_NEXT_TIME,
    COL_OB_HWM,
    COL_OCC_HWM,
    COL_POPK_DEFERRED,
    COL_ROUND,
    COL_SENDS,
    COL_WINDOW_END,
    COL_WINDOW_START,
    TRACE_COLS,
    TraceRing,
    make_trace_ring,
)
from shadow_tpu.obs.tracer import (
    COL_CAP,
    COL_FAULTS_DELAYED,
    COL_FAULTS_DROPPED,
    COL_HOSTS_DOWN,
)
from shadow_tpu.obs.tracer import (
    COL_BIND_SHARD,
    COL_EC_APP,
    COL_EC_PKT,
    COL_EC_TIMER,
    COL_FLOWS,
    COL_XW_INTER,
    COL_XW_INTRA,
)
from shadow_tpu.obs.netobs import FlowLedger, make_flow_ledger
from shadow_tpu.net.fluid import (
    FluidParams,
    FluidState,
    fluid_advance,
    fluid_host_effects,
    fluid_send_uniform,
    make_fluid_state,
)
from shadow_tpu.ops.events import kind_in
from shadow_tpu.core.faults import (
    FaultParams,
    LAT_SCALE,
    down_and_resume,
    window_effects,
)
from shadow_tpu.ops.events import unpack_order_src
from shadow_tpu.ops.events import EVENT_PAYLOAD_WORDS, Event
from shadow_tpu.ops.rng import RngState, rng_init, rng_uniform
from shadow_tpu.simtime import TIME_MAX

AXIS = "hosts"  # mesh axis name for the host dimension


# the jax<0.5 shard_map shim lives in core/compat.py (shared with the
# co-simulation bridge); the old private name stays importable here
from shadow_tpu.core.compat import shard_map_compat as _shard_map

_FNV_PRIME = jnp.uint64(1099511628211)
_MIX1 = jnp.uint64(0x9E3779B97F4A7C15)
_MIX2 = jnp.uint64(0xC2B2AE3D27D4EB4F)
_FNV_OFFSET = jnp.uint64(0xCBF29CE484222325)
# dual-digest fold constants (integrity sentinel, core/integrity.py):
# no constant shared with the primary fold, and the mix roles of t and
# order are swapped, so the two planes cannot agree by accident
from shadow_tpu.core.integrity import DIGEST2_OFFSET, DIGEST2_PRIME

_DIGEST2_OFFSET = jnp.uint64(DIGEST2_OFFSET)
_DIGEST2_PRIME = jnp.uint64(DIGEST2_PRIME)
_MIX3 = jnp.uint64(0xD6E8FEB86659FD93)
_MIX4 = jnp.uint64(0xA0761D6478BD642F)


class Outbox(NamedTuple):
    """Per-host staging lanes for this round's outgoing packets.

    Layout is [H, B] with B = `sends_per_host_round`: host h's k-th send of
    the round lands in row h, column k (`sent_round` is the column cursor).
    This makes the append a one-hot masked write — no scatter (TPU scatters
    into the old flat [OB] buffer were a measured hot spot) — and makes the
    flattened exchange order host-major, i.e. invariant to mesh shape and
    microstep interleaving by construction."""

    dst: Array  # i32[H, B] global destination host id
    t: Array  # i64[H, B] arrival time (>= window_end); TIME_MAX = empty
    order: Array  # i64[H, B]
    kind: Array  # i32[H, B]
    payload: Array  # i32[H, B, P]
    count: Array  # i32[1] entries appended this round (per shard)


class Stats(NamedTuple):
    """Device-side counters (reference: tracker.c per-host counters +
    sim_stats.rs global counters + the determinism digest)."""

    events: Array  # i64[H] events processed
    pkts_sent: Array  # i64[H]
    pkts_lost: Array  # i64[H] random path loss
    pkts_unreachable: Array  # i64[H] no route to dst
    pkts_codel_dropped: Array  # i64[H] (charged to the receiving host)
    pkts_delivered: Array  # i64[H]
    monotonic_violations: Array  # i64[H] pushes scheduled in the past
    pkts_budget_dropped: Array  # i64[H] over the per-host round send budget
    # fault plane (core/faults.py): events/packets discarded by an injected
    # fault — queue-clear crash drops (charged to the down host) plus
    # link-fault-window packet loss (charged to the sender). Distinct from
    # pkts_lost so a faulty run's excess loss is attributable.
    faults_dropped: Array  # i64[H]
    # events deferred to a crash restart (queue-hold) plus packets whose
    # latency a fault window inflated (charged to the sender)
    faults_delayed: Array  # i64[H]
    ob_dropped: Array  # i64[1] outbox-overflow losses (invariant check: always 0)
    a2a_shed: Array  # i64[1] all-to-all block-overflow losses (size blocks so 0)
    microsteps: Array  # i64[1] total microsteps (per shard)
    bq_rebuilds: Array  # i64[1] wholesale block-cache rebuilds (bucketed queue)
    popk_deferred: Array  # i64[1] K-way batch events peeked but deferred
    ici_bytes: Array  # i64[1] exchange-collective bytes moved per shard
    # per-host queue-occupancy high-water mark, sampled once per round
    # after the exchange merge (the post-merge peak — the fullest the slab
    # gets before the next round's pops drain it). Pure observation: reads
    # the queue, feeds nothing back (tracker.c's per-host gauges analogue).
    q_occ_hwm: Array  # i64[H]
    # outbox-send high-water: the most sends any ONE host staged in a
    # single round (the [H, B] outbox's column high-water), sampled
    # pre-exchange every round. Always on; the gear controller reads it
    # between chunks to pick the next merge gear (and resets it per chunk
    # so the signal tracks recent rounds, not the whole run).
    outbox_hwm: Array  # i64[world]
    # gear-shed detector: cumulative count of sends beyond the active
    # merge gear's column width (psum'd across the mesh inside the
    # exchange, so every shard carries the GLOBAL count and the chunk
    # loop's abort condition stays uniform). Structurally zero at full
    # width. A nonzero per-chunk delta means the sliced merge lost
    # entries: the driver discards the chunk, restores the pre-chunk
    # snapshot, and replays one gear up — accepted chunks always carry a
    # zero delta, which is what keeps gear-ladder runs bit-identical to
    # the full-width engine.
    gear_shed: Array  # i64[world]
    digest: Array  # u64[H] rolling per-host event-order digest
    rounds: Array  # i64[] scheduling rounds completed (replicated)
    # pressure-abort signal (core/pressure.py; None unless the pressure
    # policy is escalate/abort — the default `drop` policy traces no
    # pressure code and keeps the program bit-identical to before the
    # pressure plane existed). Cumulative GLOBAL count of capacity drops
    # (queue-push overflow, merge/a2a/outbox sheds, send-budget drops),
    # psum'd across the mesh inside the round like gear_shed, so the
    # chunk loop's first-drop abort condition is uniform on every shard.
    # Structurally zero in any state an escalate run accepts.
    pressure: Any = None  # i64[world] | None
    # Network observatory lanes (obs/netobs.py; None unless cfg.netobs —
    # the default program carries none of them and stays byte-identical).
    # Event-class accounting: executed events bucketed as timer (the
    # model's declared timer_kinds), packet (KIND_PKT flag), or app (the
    # rest). ec_timer + ec_pkt + ec_app == sum(events) by construction —
    # the reconciliation tests/net_report.py --check pin.
    ec_timer: Any = None  # i64[world] | None
    ec_pkt: Any = None  # i64[world] | None
    ec_app: Any = None  # i64[world] | None
    # Flow-ledger totals (None unless cfg.flow_ledger_active): cumulative
    # completions/bytes/retransmits counted INDEPENDENTLY of the ring
    # cursor path, so ledger-vs-counters reconciliation is a real check
    # and stays exact across ring wraps.
    fl_done: Any = None  # i64[world] | None
    fl_bytes: Any = None  # i64[world] | None
    fl_rtx: Any = None  # i64[world] | None
    # Safe-window telemetry (None unless cfg.netobs): rounds where THIS
    # shard's local min event time bound the all-reduce-min barrier
    # (ties to the lowest shard id) — the critical-path/straggler view.
    win_bound: Any = None  # i64[world] | None
    # Integrity sentinel lanes (core/integrity.py; None unless
    # cfg.integrity — the default program traces zero sentinel code and
    # stays byte-identical). `integrity` is the psum'd GLOBAL cumulative
    # violation count (the chunk loop's mesh-uniform first-violation
    # abort signal, same mechanism as gear_shed/pressure); `iv_mask` is
    # the PER-SHARD bitwise-OR of violated invariant bits (bit positions
    # in core/integrity.IV_NAMES) and `iv_round` the per-shard round
    # index of the first local violation (-1 = none) — together the
    # (shard, round, mask) reproduction signature the replay classifier
    # compares. Structurally zero/-1 in any accepted final state: a
    # violating chunk always aborts and is replayed or the run stops.
    integrity: Any = None  # i64[world] | None
    iv_mask: Any = None  # i64[world] | None
    iv_round: Any = None  # i64[world] | None
    # Dual digest (None unless cfg.integrity_dual): a second,
    # independently-folded per-host event digest sharing NO constants
    # with the primary FNV fold, so a scribble on one digest plane is
    # detectable by cross-checking the two (core/integrity.
    # classify_digest_pair) instead of silently reporting a wrong digest.
    digest2: Any = None  # u64[H] | None
    # Timer-wheel lanes (ops/wheel.py; None unless cfg.wheel_active —
    # the default program carries neither and stays byte-identical).
    # `wheel_spilled` counts timer pushes diverted to the event queue
    # because the wheel was full (spill-to-queue semantics: never a
    # loss, but a sizing signal — sweep tools/bench_wheel.py);
    # `wheel_occ_hwm` is the per-host wheel-occupancy high-water,
    # sampled once per round like q_occ_hwm.
    wheel_spilled: Any = None  # i64[H] | None
    wheel_occ_hwm: Any = None  # i64[H] | None
    # Fluid traffic plane (net/fluid.py; None unless cfg.fluid_active —
    # the default program carries neither and stays byte-identical).
    # Cumulative background bytes the fluid ODE delivered / DropTail-
    # dropped, as REPLICATED i64 scalars (shape (), like stats.rounds):
    # the ODE is global, computed identically on every shard from psum'd
    # inputs, so a per-shard lane would multiply the total at export.
    fl_bg_bytes: Any = None  # i64[] | None
    fl_bg_dropped: Any = None  # i64[] | None
    # Hierarchical-exchange tier accounting (None unless cfg.hier_active —
    # the flat-exchange program carries neither and stays byte-identical).
    # `ici_intra` charges the INTRA-shard compaction tier (the local
    # (dshard, t, order) sort's staging bytes: the gear-sliced outbox rows
    # repacked into per-destination-shard prefixes — HBM traffic, not
    # wire); `ici_inter` charges the INTER-shard tier (the alltoall blocks
    # plus the i32 fill-counter word per peer — the actual ICI wire).
    # `stats.ici_bytes` keeps its meaning ("exchange-collective bytes")
    # and carries only the inter tier on hierarchical runs, so the
    # counter == model x rounds dryrun assertion stays uniform across
    # exchange kinds.
    ici_intra: Any = None  # i64[world] | None
    ici_inter: Any = None  # i64[world] | None
    # Alltoall block-rung signal (None unless cfg.a2a_ladder — gather,
    # hierarchical, world-1 and pinned-`a2a_block` programs carry none):
    # the most rows any (source shard, destination shard) pair sent in
    # one round, this shard's max over its destinations, sampled before
    # the block truncation. The driver's rung controller
    # (core/gears.BlockRungController) reads its max over shards between
    # chunks and resets it per chunk, like outbox_hwm.
    a2a_pair_hwm: Any = None  # i64[world] | None


class SimState(NamedTuple):
    now: Array  # i64[] completed-up-to time (replicated)
    done: Array  # bool[] (replicated)
    queue: EventQueue
    rng: RngState
    seq: Array  # i64[H] per-host emission counter (order-key seq)
    sent_round: Array  # i32[H] sends staged this round (budget accounting)
    cpu_busy_until: Array  # i64[H] CPU model: host busy below this time
    tb_egress: TBState
    tb_ingress: TBState
    codel: Any  # CodelState
    min_used_lat: Array  # i64[] min latency seen (dynamic runahead)
    model: Any  # model state pytree
    outbox: Outbox
    stats: Stats
    # device-resident round tracer (obs/tracer.py): None unless
    # cfg.trace_rounds > 0. The ring is written inside the jitted round
    # loop and drained by the driver at chunk boundaries; it observes the
    # round's own values and feeds nothing back, so enabling it cannot
    # change digests, events, or drop counters.
    trace: Any = None  # TraceRing | None
    # flow-completion ledger (obs/netobs.py): None unless
    # cfg.flow_ledger_active. Same contract as the trace ring — written
    # in-jit at model flow completion (the FlowDone port), drained at
    # chunk boundaries, observes values the handler already computed,
    # feeds nothing back into scheduling.
    flows: Any = None  # FlowLedger | None
    # device-resident timer wheel (ops/wheel.py): None unless
    # cfg.wheel_active. A per-host [H, S] calendar slab (BucketQueue
    # machinery) holding the model's timer events (timer_kinds) so they
    # never occupy event-queue slots or feed the exchange-merge's free
    # ranking; the microstep pops the (time, order) minimum of
    # queue ∪ wheel, so dispatch order is bit-identical to wheel-off
    # (tests/test_wheel.py is the gate).
    wheel: Any = None  # TimerWheel | None
    # fluid traffic plane (net/fluid.py): None unless cfg.fluid_active.
    # The background-flow ODE's carry lanes (per-class carried rates +
    # per-link offered utilization), advanced once per round inside the
    # round body; replicated across the mesh (the ODE is global math
    # over psum'd foreground byte counts, identical on every shard).
    fluid: Any = None  # FluidState | None


class EngineParams(NamedTuple):
    """Immutable per-sim arrays. Sharding: per-host arrays (bucket params,
    model params) shard over the mesh; the routing tables (node_of, lat, loss)
    are replicated — packet sends need arbitrary dst lookups. Dense node×node
    tables bound graph size (~2k nodes ≈ 32 MiB); hosts-per-node is unbounded.
    """

    node_of: Array  # i32[H_total] host -> graph node (replicated)
    lat_ns: Array  # i64[N, N] path latency; <0 = unreachable (replicated)
    loss: Array  # f32[N, N] path loss probability (replicated)
    jitter_ns: Array  # i64[N, N] path jitter amplitude (replicated)
    eg_tb: TBParams  # uplink buckets (sharded per host)
    in_tb: TBParams  # downlink buckets (sharded per host)
    model: Any  # model param pytree (sharded per host)
    # per-host ROW views of the path tables, built by init_state for
    # multi-node graphs (r4, VERDICT r3 weak #1): lat_rows[h] =
    # lat_ns[node_of[h]]. Measured on v5e: data-dependent gathers are the
    # multi-node egress cost and are scalar-core bound (uniform indices
    # time the same as divergent; packing the three tables into one
    # 3-wide slice gather is 2x WORSE). The rows are therefore consumed
    # by a one-hot masked REDUCTION over the node axis — pure vector work
    # on the VPU, no gather at all — leaving node_of[dst] as the single
    # gather per send. Sharded over hosts; None on single-node graphs
    # (the (1,1) broadcast path) where rows would only waste HBM at the
    # 1M-host point.
    lat_rows: Any = None  # i64[H_total, N] | None
    loss_rows: Any = None  # f32[H_total, N] | None
    jit_rows: Any = None  # i64[H_total, N] | None
    # compiled fault schedule (core/faults.py FaultParams): per-host crash
    # windows sharded over the mesh, link-fault windows replicated. None
    # when the `faults:` block is absent — the engine then traces no fault
    # code at all and the program is bit-identical to the fault-free build.
    faults: Any = None  # FaultParams | None
    # compiled fluid schedule (net/fluid.py FluidParams): per-class
    # zones/demand/windows + per-link capacity, all replicated (classes
    # and links are global). None when the `fluid:` block declares no
    # classes — the engine then traces no fluid code at all.
    fluid: Any = None  # FluidParams | None


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static (trace-time) configuration."""

    num_hosts: int
    stop_time: int
    bootstrap_end_time: int = 0
    runahead_floor: int = 1_000_000  # 1 ms (reference runahead.rs default)
    static_min_latency: int = 1_000_000
    use_dynamic_runahead: bool = False
    tb_interval_ns: int = 1_000_000  # token bucket refill quantum (1 ms)
    use_codel: bool = True
    # Static shaping skip: when NO host has a bandwidth limit, unlimited
    # token buckets never delay (depart == arrival) and CoDel's sojourn is
    # always 0 (never drops), so the whole ingress/egress shaping pipeline
    # is an exact no-op — eliding it at trace time removes ~40% of the
    # microstep's ops with bit-identical results (digests unchanged).
    shaping: bool = True
    # Per-packet latency jitter (graph edges carry a `jitter` amplitude):
    # statically elided when no edge has jitter so jitter-free sims draw no
    # extra RNG (digest stability).
    use_jitter: bool = False
    # CPU model (reference host/cpu.rs + host.rs:820-847): every handled
    # event charges `cpu_delay_ns` of simulated CPU time; events that pop
    # while the host CPU is still busy are deferred to busy_until instead of
    # executing. 0 = off (statically elided).
    cpu_delay_ns: int = 0
    queue_capacity: int = 64
    # Two-level bucketed event queue (ops/events.py BucketQueue): split the
    # capacity axis into queue_capacity/queue_block blocks and carry
    # incrementally-maintained per-block (min-time, min-order, fill) caches
    # so the microstep's pop/push reductions run over [H, C/B] + [H, B]
    # instead of the whole [H, C] slab. Bit-identical digests, events, and
    # drop counters to the flat queue by construction (tests/test_bucketq.py
    # is the gate). 0 = flat queue (the B=C degenerate case).
    queue_block: int = 0
    # K-way microstep pop (experimental.microstep_events): fold up to K
    # events per host through the model handler per queue dispatch. The
    # queue slab is read once per microstep (ops/events.py pop_k) and the
    # executed prefix cleared once (clear_popped), so per-event queue cost
    # drops up to K-fold on event-dense hosts — the density ceiling the
    # one-event microstep hits on tgen-TCP (BENCH r5: 20-33 microsteps per
    # round at ~0.5 ms each). Exactness guard: event j+1 of a host's batch
    # executes only if no push emitted so far this microstep landed at an
    # earlier (time, order) key on that host (and, under the CPU model,
    # only while busy_until stays inside the window) — otherwise the rest
    # of the batch stays in the queue untouched and re-pops next
    # microstep. Execution order, digests, event counts, and drop counters
    # are bit-identical to K=1 by construction for both queue layouts
    # (tests/test_popk.py is the gate). 1 = today's exact single-event
    # microstep (the default).
    microstep_events: int = 1
    # Per-HOST send budget per round. Budget-drop decisions depend only on a
    # host's own send count, and the shard outbox is sized hosts_per_shard *
    # budget so aggregate overflow is impossible — this is what keeps drop
    # behavior (hence digests) identical across mesh shapes.
    sends_per_host_round: int = 8
    max_round_inserts: int = 64  # per host per round
    microstep_limit: int = 0  # 0 -> queue_capacity * 2
    rounds_per_chunk: int = 64
    world: int = 1  # mesh size (1 = single device)
    # cross-shard exchange strategy (multi-device only):
    #   "gather"   — all_gather the full outbox to every shard; each shard
    #                filters its rows. Exact, but per-shard ICI bytes and
    #                merge input grow O(world).
    #   "alltoall" — sort the local outbox by destination shard and
    #                lax.all_to_all fixed-width blocks: per-shard ICI bytes
    #                and merge input are O(global sends / world). Blocks
    #                hold `a2a_block` entries per (src, dst-shard) pair;
    #                overflow sheds the LATEST entries per the urgency
    #                contract and counts in stats.a2a_shed (size the block
    #                so it stays 0 — every test asserts it).
    exchange: str = "gather"
    a2a_block: int = 0  # 0 -> auto: 2 * outbox_rows / world, >= 64
    # Active alltoall block rung (auto `a2a_block` only): the per-pair
    # block width a chunk program is compiled at, one rung of the
    # driver's ladder (core/gears.BlockRungController). 0 or any width at
    # or above the auto size is the top rung: today's auto block. Below
    # it, a round in which some pair sends more than the rung counts the
    # excess into stats.gear_shed (psum'd) and the chunk aborts; the
    # driver restores its pre-chunk snapshot and replays at a rung that
    # fits, so accepted chunks are bit-identical to the top rung. Like
    # gear_cols, the driver's EngineConfig always carries 0: rung
    # programs are built via Engine.run_chunk_resized.
    a2a_rung: int = 0
    # Active merge gear (experimental.merge_gears): the number of outbox
    # LANE COLUMNS the exchange flattens, sorts, and merges. The outbox is
    # [H, B] with host h's k-th send of the round in column k, so when no
    # host stages more than `gear_cols` sends in a round the first
    # `gear_cols` columns hold EVERY valid entry and the truncation is
    # exact — the (dst, t, order) sort runs over H x gear_cols rows
    # instead of the worst-case H x B. Sends beyond the width are counted
    # (globally) into stats.gear_shed and the chunk loop aborts; the
    # driver then restores its pre-chunk snapshot and replays one gear up
    # (core/gears.py), so results stay bit-identical to full width on
    # every workload. 0 = full width (byte-identical program to before
    # gears existed). The driver's EngineConfig always carries 0 here —
    # geared chunk programs are built via Engine.run_chunk_gear with a
    # dataclasses.replace'd copy, so checkpoint fingerprints never vary
    # with the transient gear choice.
    gear_cols: int = 0
    # Device-resident round tracer (observability.trace): capacity of the
    # in-scan trace ring in rounds. 0 = off (no ring in the carry, no row
    # writes — the traced program is byte-identical to before the tracer
    # existed). The drivers size it to rounds_per_chunk so a drain per
    # chunk can never wrap. Rows are observations of values each round
    # already computes; scheduling never reads them, so digests, events,
    # and drop counters are bit-identical on or off (tests/test_tracer.py).
    trace_rounds: int = 0
    # Fault plane statics (core/faults.py; config `faults:`). The ARRAYS
    # live in EngineParams.faults; these are the trace-time shape/policy
    # knobs the round body specializes on. All 0/False = no fault code
    # traced in (the program is bit-identical to the fault-free engine).
    fault_crash_windows: int = 0  # W: max up/down windows per host
    fault_loss_windows: int = 0  # L: link-fault (loss/latency) windows
    # crashed-host queue policy: False = "hold" (pending events defer to
    # the restart time, the CPU-model busy-floor mechanics), True =
    # "clear" (events whose execution time falls in a down window are
    # dropped and counted in stats.faults_dropped)
    fault_queue_clear: bool = False
    # Pressure plane (core/pressure.py; config `pressure:`): when True
    # (policies escalate/abort) the round body maintains the psum'd
    # `stats.pressure` drop total and the chunk while_loop aborts at the
    # first round where ANY host dropped for capacity — the exact
    # detector the escalation/abort drivers replay or stop on. False
    # (policy drop, the default) traces no pressure code at all: the
    # program is bit-identical to the pre-pressure engine.
    pressure_abort: bool = False
    # Network observatory (obs/netobs.py; observability.network): when
    # True the round body classifies every executed event as timer /
    # packet / app into per-shard stats lanes, tracks the shard that
    # bound each round's safe-window barrier, and (with flow_records > 0)
    # appends flow-completion records to a per-shard ledger ring. All of
    # it observes values the round already computes and feeds nothing
    # back, so digests/events/drops are bit-identical on or off; False
    # (the default) traces NO observatory code — the program stays
    # byte-identical to before the observatory existed.
    netobs: bool = False
    # flow-ledger ring capacity in records per shard (0 = no ledger in
    # the carry — models without a flow port, or the observatory off).
    # The drivers size it from observability.network_flows and drain at
    # chunk boundaries; a burst past capacity overwrites the OLDEST
    # records, counted by the FlowCollector (never silent), while the
    # fl_* stats lanes keep exact totals regardless.
    flow_records: int = 0
    # Integrity sentinel (core/integrity.py; config `integrity:`): when
    # True the round body evaluates the per-round invariant guards
    # (conservation laws the state must satisfy regardless of workload)
    # into the psum'd `stats.integrity` violation lane plus the
    # per-shard `iv_mask`/`iv_round` signature lanes, and the chunk
    # while_loop aborts mesh-uniformly at the first violating round —
    # the detector the quarantine-and-replay classifier
    # (core/pressure.ResilienceController) restores and replays on.
    # False (the default) traces ZERO sentinel code: the program is
    # byte-identical to the pre-sentinel engine (the echo/phold jaxpr
    # fingerprints are the gate).
    integrity: bool = False
    # dual-digest lane (requires integrity): maintain the second,
    # independently-folded per-host digest (stats.digest2) so a scribble
    # on the digest plane itself is detectable host-side.
    integrity_dual: bool = False
    # strict window-monotonicity sub-check of IV_TIME (window_end never
    # below the committed now). Unconditional on the pure-device engine
    # under static runahead; the HYBRID bridge legitimately injects
    # CPU-plane packets whose conservative arrival bound (until +
    # min-latency) can sit below the device's last guarded window_end
    # when runahead_floor exceeds the graph's min latency — cosim
    # therefore builds with False and keeps the slab-floor sub-check
    # plus its own host-side bridge guards (cosim._bridge_guard).
    integrity_strict_time: bool = True
    # Device-resident timer wheel (ops/wheel.py; experimental.timer_wheel):
    # per-host calendar slots for the model's declared timer_kinds. 0 = off
    # (no wheel in the carry, no routing/pop-merge code traced — the
    # program stays byte-identical to before the wheel existed). With
    # S > 0, model timer pushes route to the [H, S] wheel (overflow spills
    # to the event queue, counted in stats.wheel_spilled, never silent),
    # wheel heads fold into the round's min-next-event reduction, and the
    # microstep pops the lexicographic (time, order) minimum of
    # queue ∪ wheel — dispatch order, digests, events, and drop counters
    # are bit-identical to the wheel-off path whenever the queue itself
    # does not overflow (the wheel frees queue slots, so a run the off
    # path would overflow can only drop LESS; sized workloads see zero
    # drops either way — tests/test_wheel.py is the gate).
    wheel_slots: int = 0
    # wheel block size (slots per block of the wheel's block-min caches);
    # 0 = auto (a divisor of wheel_slots near sqrt — ops/wheel.py
    # resolve_wheel_block). Must divide wheel_slots.
    wheel_block: int = 0
    # Fluid traffic plane statics (net/fluid.py; config `fluid:`). The
    # ARRAYS live in EngineParams.fluid; these are the trace-time
    # shape/coupling knobs the round body specializes on. fluid_classes
    # = 0 (the default) traces ZERO fluid code — the program is
    # byte-identical to the fluid-free engine (the default jaxpr
    # fingerprints are the gate; `tgen_fluid` pins the gated surface).
    fluid_classes: int = 0  # K background traffic classes
    fluid_links: int = 0  # N links (graph nodes) the ODE state covers
    fluid_tau_ns: int = 50_000_000  # rate-relaxation time constant
    fluid_util_threshold: float = 0.7  # coupling ramp start (RED min-th)
    fluid_loss_max: float = 0.0  # extra fg loss prob at full overload
    fluid_lat_max_x1000: int = 2000  # fg latency multiplier cap (x1000)
    fluid_seed: int = 1  # the counter-based loss-draw hash seed
    # Trace-time affine-routing constant, set by Engine.init_state when the
    # host->node map is uniform contiguous blocks (node_of[h] == h // g, the
    # shape every `count:`-group config produces): the per-send node lookup
    # becomes an integer divide on the VPU instead of a 10k-descriptor
    # gather (measured 83 us per microstep per send port at H=10k). 0 = map
    # is irregular, gather stays.
    hosts_per_node: int = 0

    def __post_init__(self):
        check_order_limits(self.num_hosts)
        if self.num_hosts % self.world != 0:
            raise ValueError(
                f"num_hosts={self.num_hosts} must divide evenly over "
                f"world={self.world} mesh devices"
            )
        if self.exchange not in ("gather", "alltoall", "hierarchical"):
            raise ValueError(
                f"exchange must be gather|alltoall|hierarchical, got "
                f"{self.exchange!r}"
            )
        if self.a2a_block < 0:
            raise ValueError(
                f"a2a_block must be >= 0 (0 = auto), got {self.a2a_block}"
            )
        if self.a2a_rung < 0:
            raise ValueError(
                f"a2a_rung must be >= 0 (0 = top rung), got {self.a2a_rung}"
            )
        if self.queue_block < 0 or (
            self.queue_block and self.queue_capacity % self.queue_block
        ):
            raise ValueError(
                f"queue_block={self.queue_block} must be 0 (flat) or divide "
                f"queue_capacity={self.queue_capacity} evenly"
            )
        if self.microstep_events < 1:
            raise ValueError(
                f"microstep_events={self.microstep_events} must be >= 1"
            )
        if self.trace_rounds < 0:
            raise ValueError(
                f"trace_rounds={self.trace_rounds} must be >= 0 (0 = off)"
            )
        if self.gear_cols < 0 or self.gear_cols > self.sends_per_host_round:
            raise ValueError(
                f"gear_cols={self.gear_cols} must be in "
                f"[0, sends_per_host_round={self.sends_per_host_round}] "
                f"(0 = full width)"
            )
        if self.fault_crash_windows < 0 or self.fault_loss_windows < 0:
            raise ValueError(
                f"fault window counts must be >= 0, got crash="
                f"{self.fault_crash_windows} loss={self.fault_loss_windows}"
            )
        if self.flow_records < 0:
            raise ValueError(
                f"flow_records={self.flow_records} must be >= 0 (0 = no "
                f"ledger)"
            )
        if self.flow_records and not self.netobs:
            raise ValueError(
                "flow_records > 0 requires netobs=True (the flow ledger "
                "is a network-observatory instrument)"
            )
        if self.integrity_dual and not self.integrity:
            raise ValueError(
                "integrity_dual requires integrity=True (the dual digest "
                "is an integrity-sentinel lane)"
            )
        if self.wheel_slots < 0:
            raise ValueError(
                f"wheel_slots={self.wheel_slots} must be >= 0 (0 = off)"
            )
        if self.wheel_block < 0 or (
            self.wheel_slots and self.wheel_block
            and self.wheel_slots % self.wheel_block
        ):
            raise ValueError(
                f"wheel_block={self.wheel_block} must be 0 (auto) or divide "
                f"wheel_slots={self.wheel_slots} evenly"
            )
        if self.fluid_classes < 0 or self.fluid_links < 0:
            raise ValueError(
                f"fluid dims must be >= 0, got classes="
                f"{self.fluid_classes} links={self.fluid_links}"
            )
        if self.fluid_classes and self.fluid_links < 1:
            raise ValueError(
                "fluid_classes > 0 requires fluid_links >= 1 (the ODE "
                "needs at least one link to cover)"
            )
        if self.fluid_classes:
            if self.fluid_tau_ns <= 0:
                raise ValueError(
                    f"fluid_tau_ns must be > 0, got {self.fluid_tau_ns}"
                )
            if not 0.0 <= self.fluid_util_threshold < 1.0:
                raise ValueError(
                    f"fluid_util_threshold must be in [0, 1), got "
                    f"{self.fluid_util_threshold}"
                )
            if not 0.0 <= self.fluid_loss_max <= 1.0:
                raise ValueError(
                    f"fluid_loss_max must be in [0, 1], got "
                    f"{self.fluid_loss_max}"
                )
            if self.fluid_lat_max_x1000 < 1000:
                raise ValueError(
                    f"fluid_lat_max_x1000 must be >= 1000 (inflation "
                    f"only — the conservative-lookahead bound), got "
                    f"{self.fluid_lat_max_x1000}"
                )
        if self.wheel_slots and self.microstep_events > 1:
            raise ValueError(
                "unsupported knob pair: experimental.timer_wheel (wheel_"
                f"slots={self.wheel_slots}) x experimental.microstep_events="
                f"{self.microstep_events} — the wheel's pop path merges ONE "
                "wheel candidate against the queue head per microstep, and "
                "the K-way fold would need a merged 2K-candidate batch with "
                "split clear/reserve accounting to stay exact. ROADMAP item "
                "1 tracks that follow-up. Until it lands, drop one knob: "
                "run the wheel with microstep_events=1 (the measured CPU "
                "winner) or keep the wheel off (docs/usage.md 'Timer "
                "wheel')."
            )

    @property
    def a2a_auto_block(self) -> int:
        """The auto alltoall block: 2 x the outbox's rows / world, at
        least 64, at most the outbox's rows. The top of the rung ladder."""
        rows = self.hosts_per_shard * self.sends_per_host_round
        return min(rows, max(64, 2 * rows // max(self.world, 1)))

    @property
    def a2a_block_size(self) -> int:
        if self.a2a_block:
            return self.a2a_block
        if self.a2a_rung_active:
            return self.a2a_rung
        return self.a2a_auto_block

    @property
    def a2a_ladder(self) -> bool:
        """True iff the alltoall block may run below its auto size: the
        flat alltoall on a mesh with the block left auto. Only then does
        the carry hold `stats.a2a_pair_hwm` (the rung controller's
        signal); an explicit `a2a_block` pins the block and the ladder."""
        return (
            self.exchange == "alltoall" and self.world > 1
            and not self.a2a_block
        )

    @property
    def a2a_rung_active(self) -> bool:
        """True iff this program's alltoall block is a rung below the
        auto size (block-overflow abort traced in, like `gear_active`)."""
        return self.a2a_ladder and 0 < self.a2a_rung < self.a2a_auto_block

    @property
    def hosts_per_shard(self) -> int:
        return self.num_hosts // self.world

    @property
    def effective_microstep_limit(self) -> int:
        """The per-round safety valve. For K=1 it bounds microsteps (and so
        events per host per round); for K>1 the round loop carries a
        PER-HOST executed-event vector and stops when any host's count
        reaches this value, so the same number keeps denominating an event
        budget — microsteps needed shrink up to K-fold when batches fold
        fully, while a deferral-heavy microstep charges a host only what
        it actually retired. Dividing the valve by K instead would bind
        EARLIER than K=1 under bursty-push deferral, and a global
        sum-of-dispatch-maxima charge would overcharge multi-host rounds;
        the per-host vector can only bind in rounds where some host
        genuinely retires `limit` events — exactly the K=1 livelock
        condition — and never cuts short a round K=1 would finish (a
        host's count before its final dispatch is at most total - 1 <
        limit). It is a livelock valve, not a scheduler."""
        return self.microstep_limit or 2 * self.queue_capacity

    @property
    def effective_microstep_events(self) -> int:
        """K clamped to the queue capacity (popping more than C events in
        one batch is impossible by construction)."""
        return min(self.microstep_events, self.queue_capacity)

    @property
    def effective_gear_cols(self) -> int:
        """The merge width actually in force (0 resolves to full width)."""
        return self.gear_cols or self.sends_per_host_round

    @property
    def faults_active(self) -> bool:
        """True iff any fault plumbing is traced into the round body."""
        return self.fault_crash_windows > 0 or self.fault_loss_windows > 0

    @property
    def fault_hold(self) -> bool:
        """Crash windows with queue-HOLD semantics: down hosts' events
        defer to the restart time (execution-time floor)."""
        return self.fault_crash_windows > 0 and not self.fault_queue_clear

    @property
    def fault_clear(self) -> bool:
        """Crash windows with queue-CLEAR semantics: events executing
        while down are popped and dropped (stats.faults_dropped)."""
        return self.fault_crash_windows > 0 and self.fault_queue_clear

    @property
    def flow_ledger_active(self) -> bool:
        """True iff the flow-completion ledger is traced into the round
        body (network observatory on AND a ring capacity declared)."""
        return self.netobs and self.flow_records > 0

    @property
    def wheel_active(self) -> bool:
        """True iff the timer wheel is traced into the round body (the
        wheel carry, push routing, and merged pops exist only then —
        the wheel-off program stays byte-identical)."""
        return self.wheel_slots > 0

    @property
    def fluid_active(self) -> bool:
        """True iff the fluid traffic plane is traced into the round
        body (the ODE carry, the per-round advance, the outbox byte
        fold, and the coupling factors exist only then — the fluid-off
        program stays byte-identical)."""
        return self.fluid_classes > 0

    @property
    def gear_active(self) -> bool:
        """True iff this program runs a TRUNCATED merge (shed detection,
        gear-abort chunk condition, and the sliced exchange are traced in
        only then — the full-width program stays byte-identical)."""
        return 0 < self.gear_cols < self.sends_per_host_round

    @property
    def hier_active(self) -> bool:
        """True iff the two-tier hierarchical exchange is traced into the
        round body (the tier counters ici_intra/ici_inter exist only then;
        a world-1 'hierarchical' config degenerates to the local gather
        path like every other exchange kind and carries neither)."""
        return self.exchange == "hierarchical" and self.world > 1

    @property
    def hier_block_size(self) -> int:
        """Inter-shard block width of the hierarchical exchange (rows per
        destination shard per round). Same shape law as `a2a_block_size`
        but derived from the GEAR-SLICED row count: the intra-shard
        compaction tier sorts only hosts_per_shard x effective_gear_cols
        rows, so the blocks the wire carries shrink with the merge gear
        instead of staying sized to the full [H, B] outbox — that delta is
        the hierarchical path's wire-byte win (`stats.ici_inter` vs the
        flat alltoall model). An explicit `a2a_block` wins here too, so
        one knob pins both exchange kinds' block math in A/B runs."""
        if self.a2a_block:
            return self.a2a_block
        rows_g = self.hosts_per_shard * self.effective_gear_cols
        return min(rows_g, max(64, 2 * rows_g // max(self.world, 1)))

    @property
    def effective_rounds_per_chunk(self) -> int:
        """The chunk loop's iteration bound actually traced into
        `_run_chunk`/`_run_guarded_chunk`.

        Below ~524k hosts this is `rounds_per_chunk` unchanged. Above it,
        the bound is clamped to the microstep valve (2 x queue_capacity
        when unset): the XLA while-loop pathology documented in
        `config/options.resolve_shapes` (BASELINE.md r3 — per-CALL cost of
        the jitted loop grows superlinearly with the trip bound at >= 1M
        lanes; rpc=64 took 13.5 s where rpc=8 took 0.36 s for the same 30
        rounds) makes a large constant bound poison EVERY dispatch at that
        scale, while results are invariant to it (the drivers loop chunks
        until `state.done`, so a smaller bound only means more host
        round-trips). The valve reproduces `resolve_shapes`' measured
        auto-tier rpc exactly (tier-3 qcap 4 -> 8, tier-2 qcap 16 -> 32);
        the host-count gate keeps explicitly-tuned small-H configs (e.g.
        bench_config's rpc=512 at 10k hosts) untouched."""
        if self.num_hosts <= 1 << 19:
            return self.rounds_per_chunk
        return min(self.rounds_per_chunk, max(self.effective_microstep_limit, 1))


# --------------------------------------------------------------------------
# state construction (host side)
# --------------------------------------------------------------------------


def _init_stats(cfg: EngineConfig) -> Stats:
    h = cfg.num_hosts

    # distinct buffers per field: the state pytree is donated to the jitted
    # chunk, and donating one buffer through two leaves is an error
    def zi():
        return jnp.zeros((h,), jnp.int64)

    def zw():
        return jnp.zeros((cfg.world,), jnp.int64)

    return Stats(
        events=zi(),
        pkts_sent=zi(),
        pkts_lost=zi(),
        pkts_unreachable=zi(),
        pkts_codel_dropped=zi(),
        pkts_delivered=zi(),
        monotonic_violations=zi(),
        pkts_budget_dropped=zi(),
        faults_dropped=zi(),
        faults_delayed=zi(),
        ob_dropped=jnp.zeros((cfg.world,), jnp.int64),
        a2a_shed=jnp.zeros((cfg.world,), jnp.int64),
        microsteps=jnp.zeros((cfg.world,), jnp.int64),
        bq_rebuilds=jnp.zeros((cfg.world,), jnp.int64),
        popk_deferred=jnp.zeros((cfg.world,), jnp.int64),
        ici_bytes=jnp.zeros((cfg.world,), jnp.int64),
        q_occ_hwm=zi(),
        outbox_hwm=jnp.zeros((cfg.world,), jnp.int64),
        gear_shed=jnp.zeros((cfg.world,), jnp.int64),
        digest=jnp.full((h,), _FNV_OFFSET, jnp.uint64),  # FNV offset basis
        rounds=jnp.zeros((), jnp.int64),
        pressure=(
            jnp.zeros((cfg.world,), jnp.int64) if cfg.pressure_abort
            else None
        ),
        # network-observatory lanes: absent (None) unless the observatory
        # is traced in — a distinct buffer per field (donation rule above)
        ec_timer=zw() if cfg.netobs else None,
        ec_pkt=zw() if cfg.netobs else None,
        ec_app=zw() if cfg.netobs else None,
        fl_done=zw() if cfg.flow_ledger_active else None,
        fl_bytes=zw() if cfg.flow_ledger_active else None,
        fl_rtx=zw() if cfg.flow_ledger_active else None,
        win_bound=zw() if cfg.netobs else None,
        # integrity sentinel lanes (core/integrity.py): absent unless
        # the sentinel is traced in; iv_round's -1 = "no violation yet"
        integrity=zw() if cfg.integrity else None,
        iv_mask=zw() if cfg.integrity else None,
        iv_round=(
            jnp.full((cfg.world,), -1, jnp.int64) if cfg.integrity
            else None
        ),
        digest2=(
            jnp.full((h,), _DIGEST2_OFFSET, jnp.uint64)
            if cfg.integrity_dual else None
        ),
        # timer-wheel lanes (ops/wheel.py): absent unless the wheel is
        # traced in — distinct buffers per field (donation rule above)
        wheel_spilled=zi() if cfg.wheel_active else None,
        wheel_occ_hwm=zi() if cfg.wheel_active else None,
        # fluid-plane byte counters (net/fluid.py): replicated scalars,
        # absent unless the fluid ODE is traced in
        fl_bg_bytes=(
            jnp.zeros((), jnp.int64) if cfg.fluid_active else None
        ),
        fl_bg_dropped=(
            jnp.zeros((), jnp.int64) if cfg.fluid_active else None
        ),
        # hierarchical-exchange tier counters: absent unless the two-tier
        # exchange is traced in — distinct buffers (donation rule above)
        ici_intra=zw() if cfg.hier_active else None,
        ici_inter=zw() if cfg.hier_active else None,
        a2a_pair_hwm=zw() if cfg.a2a_ladder else None,
    )


def make_empty_outbox(num_hosts: int, send_budget: int, count) -> Outbox:
    """A fresh (empty) [H, B] staging outbox. The single source of the
    empty layout — the engine build, the pressure plane's outbox
    migration, and the checkpoint restore paths all construct through
    here so a new Outbox field or sentinel change cannot silently
    diverge between them. `count` provides the per-shard count word's
    shape/sharding (zeroed)."""
    h, b = num_hosts, send_budget
    return Outbox(
        dst=jnp.zeros((h, b), jnp.int32),
        t=jnp.full((h, b), TIME_MAX, jnp.int64),
        order=jnp.zeros((h, b), jnp.int64),
        kind=jnp.zeros((h, b), jnp.int32),
        payload=jnp.zeros((h, b, EVENT_PAYLOAD_WORDS), jnp.int32),
        count=jnp.zeros_like(count),
    )


def _init_outbox(cfg: EngineConfig) -> Outbox:
    return make_empty_outbox(
        cfg.num_hosts, cfg.sends_per_host_round,
        jnp.zeros((cfg.world,), jnp.int32),
    )


def seed_queue(
    cfg: EngineConfig, initial_events: list[tuple[int, int, int, tuple]]
) -> tuple[EventQueue, Array]:
    """Build the t=0 queue from (host_id, t_ns, kind, payload) events — the
    boot round (reference manager.rs:357-367 / host.rs:392 add_application).

    Returns (queue, seq[H]) with per-host seq counters advanced past the
    seeded events so later emissions keep globally unique order keys.
    """
    queue, _, seq = _seed_slabs(cfg, initial_events, ())
    return queue, seq


def seed_queue_wheel(
    cfg: EngineConfig,
    initial_events: list[tuple[int, int, int, tuple]],
    timer_kinds: tuple[int, ...],
) -> tuple[EventQueue, Any, Array]:
    """`seed_queue` for wheel-active programs: seeded TIMER events (model
    kind in `timer_kinds`) land in the wheel slab, everything else in the
    queue — the boot-time form of the runtime push routing, so a
    timer-dominant boot population (the 1M-lane phold/tgen seeds) never
    constrains the queue capacity. A full wheel spills seeds back to the
    queue (same contract as the runtime route); order keys advance in
    event-list order regardless of destination, so the (time, order)
    total order — hence dispatch and digests — is identical to seeding
    everything into one queue. Returns (queue, wheel_slabs, seq) with
    wheel_slabs the flat (t, order, kind, payload) numpy planes (the
    caller wraps them via bucket_rebuild)."""
    return _seed_slabs(cfg, initial_events, tuple(timer_kinds))


def _seed_slabs(
    cfg: EngineConfig,
    initial_events: list[tuple[int, int, int, tuple]],
    timer_kinds: tuple[int, ...],
):
    h, c = cfg.num_hosts, cfg.queue_capacity
    s = cfg.wheel_slots if timer_kinds else 0
    t = np.full((h, c), TIME_MAX, np.int64)
    order = np.full((h, c), ORDER_MAX, np.int64)
    kind = np.zeros((h, c), np.int32)
    payload = np.zeros((h, c, EVENT_PAYLOAD_WORDS), np.int32)
    fill = np.zeros((h,), np.int32)
    if s:
        wt = np.full((h, s), TIME_MAX, np.int64)
        worder = np.full((h, s), ORDER_MAX, np.int64)
        wkind = np.zeros((h, s), np.int32)
        wpayload = np.zeros((h, s, EVENT_PAYLOAD_WORDS), np.int32)
        wfill = np.zeros((h,), np.int32)
    seq = np.zeros((h,), np.int64)
    # order keys are packed in numpy for the whole batch: calling the
    # (jax) pack_order per event built three traced scalars per call and
    # dominated 1M-host builds (~290 s of a 318 s construction)
    from shadow_tpu.ops.events import _LOCAL_SHIFT, _SRC_SHIFT, SEQ_MASK

    for host, t_ns, k, pl in initial_events:
        okey = (
            (np.int64(1) << _LOCAL_SHIFT)
            | (np.int64(host) << _SRC_SHIFT)
            | (np.int64(seq[host]) & SEQ_MASK)
        )
        seq[host] += 1
        if s and k in timer_kinds and wfill[host] < s:
            slot = wfill[host]
            wt[host, slot] = t_ns
            worder[host, slot] = okey
            wkind[host, slot] = k
            wpayload[host, slot, : len(pl)] = pl
            wfill[host] += 1
            continue
        slot = fill[host]
        if slot >= c:
            raise ValueError(
                f"host {host}: {slot + 1} initial events exceed queue capacity {c}"
            )
        t[host, slot] = t_ns
        order[host, slot] = okey
        kind[host, slot] = k
        payload[host, slot, : len(pl)] = pl
        fill[host] += 1
    queue = EventQueue(
        t=jnp.asarray(t, jnp.int64),
        order=jnp.asarray(order, jnp.int64),
        kind=jnp.asarray(kind, jnp.int32),
        payload=jnp.asarray(payload, jnp.int32),
        dropped=jnp.zeros((h,), jnp.int64),
    )
    wheel = None
    if s:
        wheel = EventQueue(
            t=jnp.asarray(wt, jnp.int64),
            order=jnp.asarray(worder, jnp.int64),
            kind=jnp.asarray(wkind, jnp.int32),
            payload=jnp.asarray(wpayload, jnp.int32),
            dropped=jnp.zeros((h,), jnp.int64),
        )
    return queue, wheel, jnp.asarray(seq, jnp.int64)


# --------------------------------------------------------------------------
# device-side helpers
# --------------------------------------------------------------------------


def _digest_update(digest, active, t, kind, order):
    x = t.astype(jnp.uint64) * _MIX1
    x = x ^ (kind.astype(jnp.uint64) * _MIX2)
    x = x ^ order.astype(jnp.uint64)
    return jnp.where(active, (digest ^ x) * _FNV_PRIME, digest)


def _digest_update2(digest2, active, t, kind, order):
    """The integrity sentinel's SECOND per-host fold: same inputs, no
    shared constants, and order (not t) carries the first multiplier —
    a scribble flipping bits on one digest plane cannot land on a value
    consistent with the other (core/integrity.classify_digest_pair)."""
    x = order.astype(jnp.uint64) * _MIX3
    x = x ^ (t.astype(jnp.uint64) * _MIX4)
    x = x ^ kind.astype(jnp.uint64)
    return jnp.where(active, (digest2 ^ x) * _DIGEST2_PRIME, digest2)


def _outbox_append(ob: Outbox, mask, col, dst, t, order, kind, payload):
    """Write each masked host's entry into its own lane at column `col`
    (the host's `sent_round` cursor). One-hot masked writes only; `mask`
    implies `col < B` (the send budget is checked upstream), so `n_lost` is
    structurally zero — but it is computed, not assumed, so `ob_dropped`
    remains a real invariant check against future call sites."""
    b = ob.t.shape[1]
    oh = mask[:, None] & (jnp.arange(b, dtype=jnp.int32)[None, :] == col[:, None])
    n_lost = jnp.sum(mask & (col >= b), dtype=jnp.int64)
    new = Outbox(
        dst=jnp.where(oh, dst.astype(jnp.int32)[:, None], ob.dst),
        t=jnp.where(oh, t[:, None], ob.t),
        order=jnp.where(oh, order[:, None], ob.order),
        kind=jnp.where(oh, kind.astype(jnp.int32)[:, None], ob.kind),
        payload=jnp.where(
            oh[:, :, None], jnp.asarray(payload, jnp.int32)[:, None, :], ob.payload
        ),
        count=ob.count + jnp.sum(mask, dtype=jnp.int32)[None],
    )
    return new, n_lost


def _outbox_append_multi(ob: Outbox, entries):
    """Apply ALL of a microstep's outbox appends in one slab pass.

    `entries` is a list of (mask, col, dst, t, order, kind, payload) with
    per-host [H] arrays; columns are cursor-assigned upstream so at most one
    entry targets any (host, col). Applying them as a chained one-hot write
    (no reductions interleaved between the [H, B] selects) lets XLA fuse the
    whole append into a single read+write of the outbox — the per-port
    `_outbox_append` chain materialized the full slab once per port, which
    was the measured cost of multi-port TCP bursts. Overflow (`col >= B`) is
    counted, never silent, exactly as in `_outbox_append`."""
    b = ob.t.shape[1]
    h = ob.t.shape[0]
    cols = jnp.arange(b, dtype=jnp.int32)[None, :]
    dst_n, t_n, order_n = ob.dst, ob.t, ob.order
    kind_n, payload_n = ob.kind, ob.payload
    # reductions are accumulated ELEMENTWISE in the loop and summed once at
    # the end: a jnp.sum between the one-hot selects is a fusion fence that
    # re-materializes the whole [H, B] slab per entry (measured: 8-entry
    # bursts ran ~25% slower with in-loop sums)
    lost_acc = jnp.zeros((h,), jnp.int64)
    total_acc = jnp.zeros((h,), jnp.int32)
    for mask, col, dst, t, order, kind, payload in entries:
        oh = mask[:, None] & (cols == col[:, None])
        dst_n = jnp.where(oh, dst.astype(jnp.int32)[:, None], dst_n)
        t_n = jnp.where(oh, t[:, None], t_n)
        order_n = jnp.where(oh, order[:, None], order_n)
        kind_n = jnp.where(oh, kind.astype(jnp.int32)[:, None], kind_n)
        payload_n = jnp.where(
            oh[:, :, None], jnp.asarray(payload, jnp.int32)[:, None, :],
            payload_n,
        )
        lost_acc = lost_acc + (mask & (col >= b))
        total_acc = total_acc + mask
    return (
        Outbox(
            dst=dst_n, t=t_n, order=order_n, kind=kind_n, payload=payload_n,
            count=ob.count + jnp.sum(total_acc, dtype=jnp.int32)[None],
        ),
        jnp.sum(lost_acc, dtype=jnp.int64),
    )


class Engine:
    """Builds and runs the jitted round loop for a fixed (config, model).

    Single-device: `run_chunk(state, params)`. Multi-device: the same function
    wrapped in shard_map over a 1-D mesh of `cfg.world` devices. The Python
    driver loop (`shadow_tpu.sim`) calls chunks until `state.done`.
    """

    def __init__(self, cfg: EngineConfig, model, mesh: Mesh | None = None):
        if (mesh is None) != (cfg.world == 1):
            raise ValueError("mesh must be provided iff cfg.world > 1")
        if cfg.wheel_active and not tuple(getattr(model, "timer_kinds", ())):
            raise ValueError(
                f"timer wheel enabled (wheel_slots={cfg.wheel_slots}) but "
                f"model {getattr(model, 'name', model)!r} declares no "
                f"timer_kinds — nothing would ever route to the wheel; "
                f"drop experimental.timer_wheel or use a model with timers"
            )
        self.cfg = cfg
        self.model = model
        self.mesh = mesh
        # the chunk program at the selected alltoall block rung
        # (`select_a2a_rung`); the top-rung program until a driver selects
        # a lower one. Built by init_state (needs model pytree shapes).
        self.run_chunk = None
        self._top_chunk = None  # the base-shape, full-width, top-rung program
        self.a2a_rung = 0  # the rung `run_chunk` runs at (0 = top)
        self._gear_chunks: dict[int, Any] = {}  # gear_cols -> jitted chunk
        # (gear_cols, queue_capacity, send_budget, a2a_rung) -> jitted
        # chunk: the pressure plane's escalated programs
        # (core/pressure.py) and the alltoall block rungs. Bounded by the
        # ladders (a handful of rungs per axis).
        self._resized_chunks: dict[tuple, Any] = {}
        self._summary_fn = None  # jitted `chunk_summary` (any shape)
        # runtime observatory (obs/runtime.CompileLedger): when attached,
        # every cached chunk program is wrapped so its first (compiling)
        # call is recorded with its trigger. HOST-SIDE only — wrapping a
        # jitted callable cannot change the traced program.
        self.compile_ledger = None

    def attach_compile_ledger(self, ledger):
        """Attach an `obs.runtime.CompileLedger` so cache misses in the
        chunk-program caches record their compile walls. Safe before OR
        after `init_state` (jit compiles lazily — a not-yet-called
        program still records on its first call); attach before the
        first dispatch or the base program's compile goes unrecorded."""
        self.compile_ledger = ledger
        if self._top_chunk is not None and ledger is not None:
            self._top_chunk = ledger.instrument(
                "chunk", "base", "cold_start", self._top_chunk
            )
            self.select_a2a_rung(self.a2a_rung)

    def _jit_chunk(self, cfg: EngineConfig):
        """Build one jitted chunk program for `cfg` — shared by the
        full-width build and every gear variant so specs/donation can
        never diverge between them."""
        axis = AXIS if self.mesh is not None else None
        chunk = functools.partial(_run_chunk, cfg, self.model, axis)
        if self.mesh is not None:
            state_spec = self.state_specs()
            chunk = _shard_map(
                chunk, self.mesh, (state_spec, self.param_specs()), state_spec
            )
        return jax.jit(chunk, donate_argnums=0)

    def _build_run_chunk(self):
        fn = self._jit_chunk(self.cfg)
        if self.compile_ledger is not None:
            fn = self.compile_ledger.instrument(
                "chunk", "base", "cold_start", fn
            )
        self._top_chunk = fn
        self.run_chunk = fn
        self.a2a_rung = 0

    def select_a2a_rung(self, a2a_rung: int):
        """Point `run_chunk` at the base-shape, full-width program of
        alltoall block rung `a2a_rung` (0 = top). The drivers call it with
        the rung their next chunk runs at, so `run_chunk` (and its HLO) is
        the program in force. Below the top rung a chunk stops at its
        first overflowing round with `stats.gear_shed` grown, and only the
        snapshot-replay seam (core/pressure.ResilienceController) may
        accept its result."""
        self.a2a_rung = int(a2a_rung)
        self.run_chunk = self._chunk_program(0, 0, 0, self.a2a_rung)

    def chunk_summary(self, state):
        """Dispatch the summary of a chunk's output (core/pipeline.py): a
        row of int64 boundary numbers per shard, reduced on the shard, so
        the host reads one small array a chunk and the device runs no
        collective for it. Does not donate `state`: the drivers dispatch
        it before the next chunk consumes that state. Returns a
        `PendingSummary`; its `read()` blocks."""
        from shadow_tpu.core.pipeline import (
            PendingSummary, summary_layout, summary_row,
        )

        if self._summary_fn is None:
            row = summary_row
            if self.mesh is not None:
                row = _shard_map(row, self.mesh, (self.state_specs(),), P(AXIS))
            self._summary_fn = jax.jit(row)
        return PendingSummary(summary_layout(state), self._summary_fn(state))

    def run_chunk_gear(self, state: SimState, params: EngineParams, gear_cols: int):
        """Run one chunk at a merge gear (`gear_cols` outbox columns in the
        exchange sort). Gear programs are jitted lazily and cached per
        width — the ladder is small (<= 4 gears), so at most a handful of
        compiles per run. `gear_cols` of 0 or the full send budget routes
        to the ordinary `run_chunk` (the byte-identical full-width
        program). Callable only after `init_state` (like `run_chunk`).

        State shapes are IDENTICAL across gears (the outbox stays [H, B];
        only the slice the exchange sorts changes), so the pre-chunk
        snapshot/replay loop in the drivers can hand the same pytree to
        any gear."""
        return self._gear_program(gear_cols)(state, params)

    def _gear_program(self, gear_cols: int):
        """The cached top-rung, base-shape program at a merge gear."""
        if gear_cols <= 0 or gear_cols >= self.cfg.sends_per_host_round:
            return self._top_chunk
        fn = self._gear_chunks.get(gear_cols)
        if fn is None:
            fn = self._jit_chunk(
                dataclasses.replace(self.cfg, gear_cols=gear_cols)
            )
            if self.compile_ledger is not None:
                fn = self.compile_ledger.instrument(
                    "chunk", f"gear={gear_cols}", "gear_shift", fn
                )
            self._gear_chunks[gear_cols] = fn
        return fn

    def run_chunk_resized(
        self, state: SimState, params: EngineParams, gear_cols: int,
        queue_capacity: int, send_budget: int, a2a_rung: int = 0,
    ):
        """Run one chunk at an escalated shape: `queue_capacity` slots per
        host and a `send_budget`-wide outbox (the pressure plane's
        regrown programs, core/pressure.py), at merge gear `gear_cols`
        (0 = full width) and alltoall block rung `a2a_rung` (0 = top).
        Base shapes at the top rung route to the gear/full-width cache.

        The resized config pins the knobs that would otherwise drift
        with capacity, so the escalated trajectory stays bit-identical
        to a run LAUNCHED at the final shape with the same pins:
          - `microstep_limit` is fixed at the BASE config's effective
            valve (the valve is a livelock bound, not a scheduler, but
            letting it scale with capacity could cut a pathological
            round at a different microstep across rungs);
          - `max_round_inserts` scales with capacity only when the base
            left it auto-sized (== base capacity), matching what the
            driver would derive at the bigger shape.
        Callable only after `init_state` (like `run_chunk`). A
        `queue_capacity`/`send_budget` of 0 means the base shape (the
        gears-only controller passes 0s — it never reads the state's
        shapes), exactly like `gear_cols` 0 means full width."""
        fn = self._chunk_program(
            gear_cols, queue_capacity, send_budget, a2a_rung
        )
        return fn(state, params)

    def _chunk_program(
        self, gear_cols: int, queue_capacity: int, send_budget: int,
        a2a_rung: int,
    ):
        """The cached jitted chunk program `run_chunk_resized` runs."""
        base = self.cfg
        cap = int(queue_capacity) or base.queue_capacity
        budget = int(send_budget) or base.sends_per_host_round
        cfg = self.resized_cfg(gear_cols, cap, budget, a2a_rung)
        if (cap, budget, cfg.a2a_rung) == (
            base.queue_capacity, base.sends_per_host_round, 0
        ):
            return self._gear_program(cfg.gear_cols)
        key = (cfg.gear_cols, cap, budget, cfg.a2a_rung)
        fn = self._resized_chunks.get(key)
        if fn is None:
            fn = self._jit_chunk(cfg)
            if self.compile_ledger is not None:
                fn = self.compile_ledger.instrument(
                    "chunk",
                    f"cap={cap}/box={budget}/gear={cfg.gear_cols}"
                    + (f"/a2a={cfg.a2a_rung}" if cfg.a2a_rung else ""),
                    "pressure_regrow"
                    if (cap, budget) != (
                        base.queue_capacity, base.sends_per_host_round
                    ) else "a2a_rung",
                    fn,
                )
            self._resized_chunks[key] = fn
        return fn

    def resized_cfg(
        self, gear_cols: int, queue_capacity: int, send_budget: int,
        a2a_rung: int = 0,
    ) -> EngineConfig:
        """The escalated EngineConfig `run_chunk_resized` compiles (shared
        so tests can assert the pinning rules). `a2a_rung` is kept only
        where it is a rung below the auto block at this send budget."""
        base = self.cfg
        cfg = dataclasses.replace(
            base,
            queue_capacity=queue_capacity,
            sends_per_host_round=send_budget,
            gear_cols=gear_cols if 0 < gear_cols < send_budget else 0,
            a2a_rung=int(a2a_rung),
            microstep_limit=base.effective_microstep_limit,
            max_round_inserts=(
                queue_capacity
                if base.max_round_inserts == base.queue_capacity
                else base.max_round_inserts
            ),
        )
        if cfg.a2a_rung and not cfg.a2a_rung_active:
            cfg = dataclasses.replace(cfg, a2a_rung=0)
        return cfg

    def build_capture_step(self):
        """Jitted single round returning (state, sent-outbox) for pcap
        synthesis; built on demand (capture trades speed for observability)."""
        axis = AXIS if self.mesh is not None else None
        step = functools.partial(_round_step_capture, self.cfg, self.model, axis)
        if self.mesh is not None:
            state_spec = self.state_specs()
            sh = P(AXIS)
            ob_spec = Outbox(dst=sh, t=sh, order=sh, kind=sh, payload=sh, count=sh)
            step = _shard_map(
                step, self.mesh, (state_spec, self.param_specs()),
                (state_spec, ob_spec),
            )
        return jax.jit(step)

    # ---- sharding specs ----------------------------------------------------

    def _model_specs(self, tree):
        """Model pytree sharding: host-dim sharded, EXCEPT dict keys named
        `global_*`, which stay replicated — cross-host lookup tables a lane
        must gather by GLOBAL host id (e.g. the mixed model's plane map;
        same role as the engine's replicated node_of)."""

        def walk(t):
            if isinstance(t, dict):
                return {
                    k: (jax.tree.map(lambda _: P(), v)
                        if k.startswith("global_") else walk(v))
                    for k, v in t.items()
                }
            return jax.tree.map(lambda _: P(AXIS), t)

        return walk(tree)

    def state_specs(self):
        sh, rep = P(AXIS), P()
        if self.cfg.queue_block:
            qspec = BucketQueue(
                t=sh, order=sh, kind=sh, payload=sh, dropped=sh,
                bt=sh, bo=sh, bfill=sh,
            )
        else:
            qspec = EventQueue(t=sh, order=sh, kind=sh, payload=sh, dropped=sh)
        return SimState(
            now=rep,
            done=rep,
            queue=qspec,
            rng=RngState(s=sh),
            seq=sh,
            sent_round=sh,
            cpu_busy_until=sh,
            tb_egress=TBState(tokens=sh, last_itv=sh),
            tb_ingress=TBState(tokens=sh, last_itv=sh),
            codel=jax.tree.map(lambda _: sh, codel_init(1)),
            min_used_lat=rep,
            model=self._model_state_spec_tree,
            outbox=Outbox(dst=sh, t=sh, order=sh, kind=sh, payload=sh, count=sh),
            stats=Stats(
                events=sh,
                pkts_sent=sh,
                pkts_lost=sh,
                pkts_unreachable=sh,
                pkts_codel_dropped=sh,
                pkts_delivered=sh,
                monotonic_violations=sh,
                pkts_budget_dropped=sh,
                faults_dropped=sh,
                faults_delayed=sh,
                ob_dropped=sh,
                a2a_shed=sh,
                microsteps=sh,
                bq_rebuilds=sh,
                popk_deferred=sh,
                ici_bytes=sh,
                q_occ_hwm=sh,
                outbox_hwm=sh,
                gear_shed=sh,
                digest=sh,
                rounds=rep,
                pressure=sh if self.cfg.pressure_abort else None,
                ec_timer=sh if self.cfg.netobs else None,
                ec_pkt=sh if self.cfg.netobs else None,
                ec_app=sh if self.cfg.netobs else None,
                fl_done=sh if self.cfg.flow_ledger_active else None,
                fl_bytes=sh if self.cfg.flow_ledger_active else None,
                fl_rtx=sh if self.cfg.flow_ledger_active else None,
                win_bound=sh if self.cfg.netobs else None,
                integrity=sh if self.cfg.integrity else None,
                iv_mask=sh if self.cfg.integrity else None,
                iv_round=sh if self.cfg.integrity else None,
                digest2=sh if self.cfg.integrity_dual else None,
                wheel_spilled=sh if self.cfg.wheel_active else None,
                wheel_occ_hwm=sh if self.cfg.wheel_active else None,
                fl_bg_bytes=rep if self.cfg.fluid_active else None,
                fl_bg_dropped=rep if self.cfg.fluid_active else None,
                ici_intra=sh if self.cfg.hier_active else None,
                ici_inter=sh if self.cfg.hier_active else None,
                a2a_pair_hwm=sh if self.cfg.a2a_ladder else None,
            ),
            trace=(
                TraceRing(rows=sh, cursor=sh) if self.cfg.trace_rounds
                else None
            ),
            flows=(
                FlowLedger(rows=sh, cursor=sh)
                if self.cfg.flow_ledger_active else None
            ),
            wheel=(
                BucketQueue(
                    t=sh, order=sh, kind=sh, payload=sh, dropped=sh,
                    bt=sh, bo=sh, bfill=sh,
                )
                if self.cfg.wheel_active else None
            ),
            fluid=(
                FluidState(rates=rep, link_util=rep)
                if self.cfg.fluid_active else None
            ),
        )

    def param_specs(self):
        sh, rep = P(AXIS), P()
        rows = sh if getattr(self, "_has_rows", False) else None
        # fault schedule: crash windows are per-host (sharded), the
        # link-fault windows are global (replicated). Mirrors the None
        # structure of EngineParams.faults exactly.
        faults = None
        if self.cfg.faults_active:
            cw = self.cfg.fault_crash_windows > 0
            lw = self.cfg.fault_loss_windows > 0
            faults = FaultParams(
                down_t=sh if cw else None,
                up_t=sh if cw else None,
                win_start=rep if lw else None,
                win_end=rep if lw else None,
                win_loss=rep if lw else None,
                win_lat=rep if lw else None,
            )
        # fluid schedule: classes and links are global — replicated,
        # mirroring the None structure of EngineParams.fluid exactly
        fluid = None
        if self.cfg.fluid_active:
            fluid = FluidParams(
                src_zone=rep, dst_zone=rep, demand=rep,
                win_start=rep, win_end=rep, capacity=rep,
            )
        return EngineParams(
            node_of=rep,
            lat_ns=rep,
            loss=rep,
            jitter_ns=rep,
            eg_tb=TBParams(capacity=sh, refill=sh),
            in_tb=TBParams(capacity=sh, refill=sh),
            model=self._model_param_spec_tree,
            lat_rows=rows,
            loss_rows=rows,
            jit_rows=rows,
            faults=faults,
            fluid=fluid,
        )

    # ---- initialization ----------------------------------------------------

    def init_state(
        self,
        params: EngineParams,
        model_state,
        initial_events: list[tuple[int, int, int, tuple]],
        seed: int,
    ) -> tuple[SimState, EngineParams]:
        """Returns (state, params) — params come back re-device_put with the
        mesh sharding when running multi-device; always use the returned pair."""
        cfg = self.cfg
        if (params.faults is not None) != cfg.faults_active:
            raise ValueError(
                "EngineParams.faults must be provided iff the EngineConfig "
                "declares fault windows (fault_crash_windows/"
                "fault_loss_windows) — build both from one FaultSchedule "
                "(core/faults.compile_faults)"
            )
        if (params.fluid is not None) != cfg.fluid_active:
            raise ValueError(
                "EngineParams.fluid must be provided iff the EngineConfig "
                "declares fluid classes (fluid_classes > 0) — build both "
                "from one FluidSchedule (net/fluid.compile_fluid)"
            )
        self._model_state_spec_tree = self._model_specs(model_state)
        self._model_param_spec_tree = self._model_specs(params.model)
        n_nodes = params.lat_ns.shape[0]
        # rows cost H x N x 20 bytes of HBM and the reduction reads them
        # per send: cap the product (beyond it the 2-D gather path is the
        # lesser evil — e.g. 100k hosts on a 2k-node graph)
        import os as _os  # experiment gate, see BASELINE.md routing notes

        rows_ok = cfg.num_hosts * n_nodes <= 32 << 20 and not _os.environ.get(
            "SHADOW_TPU_FORCE_GATHER_ROUTING"
        )
        # affine host->node detection (see EngineConfig.hosts_per_node)
        if n_nodes > 1:
            node_np = np.asarray(params.node_of)
            counts = np.bincount(node_np, minlength=n_nodes)
            g = int(counts[0])
            if (
                g > 0
                and (counts == g).all()
                and (node_np == np.arange(node_np.shape[0]) // g).all()
            ):
                self.cfg = cfg = dataclasses.replace(cfg, hosts_per_node=g)
        if params.lat_ns.shape != (1, 1) and rows_ok and params.lat_rows is None:
            # materialize the per-host routing rows (see EngineParams)
            node = np.asarray(params.node_of)
            params = params._replace(
                lat_rows=jnp.asarray(np.asarray(params.lat_ns)[node]),
                loss_rows=jnp.asarray(np.asarray(params.loss)[node]),
                jit_rows=jnp.asarray(np.asarray(params.jitter_ns)[node]),
            )
        self._has_rows = params.lat_rows is not None
        self._build_run_chunk()
        if cfg.wheel_active:
            # seeded timer events boot straight into the wheel —
            # same routing as runtime pushes, so a timer-dominant
            # boot population never constrains queue capacity
            from shadow_tpu.ops.wheel import resolve_wheel_block

            queue, wheel_flat, seq = seed_queue_wheel(
                cfg, initial_events,
                tuple(getattr(self.model, "timer_kinds", ())),
            )
            wheel = bucket_rebuild(
                wheel_flat,
                resolve_wheel_block(cfg.wheel_slots, cfg.wheel_block),
            )
        else:
            queue, seq = seed_queue(cfg, initial_events)
            wheel = None
        if cfg.queue_block:
            queue = bucket_rebuild(queue, cfg.queue_block)
        state = SimState(
            now=jnp.zeros((), jnp.int64),
            done=jnp.zeros((), bool),
            queue=queue,
            rng=rng_init(cfg.num_hosts, seed),
            seq=seq,
            sent_round=jnp.zeros((cfg.num_hosts,), jnp.int32),
            cpu_busy_until=jnp.zeros((cfg.num_hosts,), jnp.int64),
            tb_egress=tb_init(params.eg_tb),
            tb_ingress=tb_init(params.in_tb),
            codel=codel_init(cfg.num_hosts),
            min_used_lat=jnp.asarray(cfg.static_min_latency, jnp.int64),
            model=model_state,
            outbox=_init_outbox(cfg),
            stats=_init_stats(cfg),
            trace=(
                make_trace_ring(cfg.world, cfg.trace_rounds)
                if cfg.trace_rounds
                else None
            ),
            flows=(
                make_flow_ledger(cfg.world, cfg.flow_records)
                if cfg.flow_ledger_active
                else None
            ),
            wheel=wheel,
            fluid=(
                make_fluid_state(cfg.fluid_classes, cfg.fluid_links)
                if cfg.fluid_active
                else None
            ),
        )
        if self.mesh is not None:
            state = jax.device_put(
                state,
                jax.tree.map(
                    lambda s: NamedSharding(self.mesh, s), self.state_specs()
                ),
            )
            params = jax.device_put(
                params,
                jax.tree.map(
                    lambda s: NamedSharding(self.mesh, s), self.param_specs()
                ),
            )
        else:
            dev = jax.devices()[0]
            state = jax.device_put(state, dev)
            params = jax.device_put(params, dev)
        return state, params


# --------------------------------------------------------------------------
# the round loop (pure function of (cfg, model, axis); shard-local arrays)
# --------------------------------------------------------------------------


def _pmin(x, axis):
    """Global min over the mesh. The TPU lowers no 64-bit min all-reduce
    (its compiler refuses it: "Supported lowering only of Sum all
    reduce"), so an i64 min runs as two 32-bit ones: the signed high
    words, then the unsigned low words of the shards that hold that high
    word — the int64 order exactly."""
    if not axis:
        return x
    with jax.named_scope("shadow_collective"):
        if x.dtype != jnp.int64:
            return lax.pmin(x, axis)
        hi = (x >> 32).astype(jnp.int32)
        lo = (x & 0xFFFFFFFF).astype(jnp.uint32)
        g_hi = lax.pmin(hi, axis)
        g_lo = lax.pmin(
            jnp.where(hi == g_hi, lo, jnp.uint32(0xFFFFFFFF)), axis
        )
        return (g_hi.astype(jnp.int64) << 32) | g_lo.astype(jnp.int64)


def _psum(x, axis):
    """Sum over the mesh; `x` itself off the mesh."""
    if not axis:
        return x
    with jax.named_scope("shadow_collective"):
        return lax.psum(x, axis)


def _run_chunk(cfg: EngineConfig, model, axis, state: SimState, params: EngineParams):
    # gear-abort: once a round's sliced exchange (or a below-top alltoall
    # block rung) sheds, every further round of this chunk is wasted work
    # (the driver will discard the result and replay from its snapshot one
    # gear or rung up), so the loop stops at the first shed. gear_shed
    # carries the psum'd GLOBAL count, so the condition is uniform across
    # shards and the mesh exits together. The pressure
    # plane's first-drop abort (cfg.pressure_abort) is the same mechanism
    # on the psum'd capacity-drop total: the driver either regrows and
    # replays (escalate) or stops with honest artifacts (abort).
    shed0 = (
        state.stats.gear_shed[0]
        if cfg.gear_active or cfg.a2a_rung_active else None
    )
    press0 = state.stats.pressure[0] if cfg.pressure_abort else None
    # integrity sentinel: stop at the first violating round — every
    # further round of this chunk would run on state a guard already
    # called corrupt, and the driver's classifier discards the attempt
    # and replays from its pre-chunk snapshot anyway. `stats.integrity`
    # is psum'd, so the condition is uniform across the mesh.
    iv0 = state.stats.integrity[0] if cfg.integrity else None

    def cond(carry):
        st, i = carry
        # effective_rounds_per_chunk, not rounds_per_chunk: at million-host
        # scale the valve-clamped bound sidesteps the XLA while-loop
        # pathology (the property's docstring has the numbers)
        ok = (~st.done) & (i < cfg.effective_rounds_per_chunk)
        if shed0 is not None:
            ok = ok & (st.stats.gear_shed[0] <= shed0)
        if press0 is not None:
            ok = ok & (st.stats.pressure[0] <= press0)
        if iv0 is not None:
            ok = ok & (st.stats.integrity[0] <= iv0)
        return ok

    def body(carry):
        st, i = carry
        st = _round_step(cfg, model, axis, st, params)
        return st, i + 1

    state, _ = lax.while_loop(cond, body, (state, jnp.zeros((), jnp.int64)))
    return state


def _run_guarded_chunk(
    cfg: EngineConfig, model, axis, stop_probe, st: SimState,
    params: EngineParams, until,
):
    """Run rounds while the global min event time stays below `until` AND
    `stop_probe(model_state)` is False. The co-simulation bridge uses this
    to batch many device rounds into one dispatch while the CPU plane is
    idle, exiting as soon as a round produces host-bound deliveries (the
    probe) so the CPU plane can react — conservative lookahead stays exact
    because the CPU plane's earliest possible influence is `until` +
    min-latency (SURVEY.md §7 hard parts 5-6).

    Runs at whatever merge gear `cfg.gear_cols` selects, with the same
    first-shed abort as `_run_chunk` (the hybrid driver snapshots before
    the dispatch and replays one gear up on a shed), and the same
    first-drop pressure abort when `cfg.pressure_abort` is set."""
    shed0 = (
        st.stats.gear_shed[0]
        if cfg.gear_active or cfg.a2a_rung_active else None
    )
    press0 = st.stats.pressure[0] if cfg.pressure_abort else None
    iv0 = st.stats.integrity[0] if cfg.integrity else None

    def cond(carry):
        stc, i = carry
        gmin = _pmin(
            jnp.min(_effective_next(cfg, stc, _hold_faults(cfg, params))), axis
        )
        probe = stop_probe(stc.model)
        if axis:
            # the probe sees only the LOCAL shard's model state; the loop
            # decision must be global or shards exit at different rounds and
            # the survivors deadlock in the next round's collectives
            with jax.named_scope("shadow_collective"):
                probe = lax.pmax(probe.astype(jnp.int32), axis) > 0
        ok = (
            (~stc.done)
            & (i < cfg.effective_rounds_per_chunk)
            & (gmin < until)
            & (~probe)
        )
        if shed0 is not None:
            ok = ok & (stc.stats.gear_shed[0] <= shed0)
        if press0 is not None:
            ok = ok & (stc.stats.pressure[0] <= press0)
        if iv0 is not None:
            # first-violation stop, same mechanism as the pressure abort
            # (the hybrid driver raises IntegrityAbort on it — the CPU
            # plane cannot roll back, so no replay classification there)
            ok = ok & (stc.stats.integrity[0] <= iv0)
        return ok

    def body(carry):
        stc, i = carry
        stc = _round_step(cfg, model, axis, stc, params)
        return stc, i + 1

    state, _ = lax.while_loop(cond, body, (st, jnp.zeros((), jnp.int64)))
    return state


def _compute_window(cfg: EngineConfig, axis, st: SimState, faults=None):
    """Barrier + window (controller.rs:88-112): (window_end, done)."""
    lmin = jnp.min(_effective_next(cfg, st, faults))
    gmin = _pmin(lmin, axis)
    done = gmin >= cfg.stop_time  # TIME_MAX (empty everywhere) implies done
    gmin_safe = jnp.minimum(gmin, cfg.stop_time)
    runahead = (
        jnp.maximum(jnp.asarray(cfg.runahead_floor, jnp.int64), st.min_used_lat)
        if cfg.use_dynamic_runahead
        else jnp.asarray(max(cfg.runahead_floor, cfg.static_min_latency), jnp.int64)
    )
    window_end = jnp.minimum(gmin_safe + jnp.maximum(runahead, 1), cfg.stop_time)
    return window_end, done


def _round_step(cfg: EngineConfig, model, axis, st: SimState, params: EngineParams):
    with jax.named_scope("shadow_window"):
        window_end, done = _compute_window(
            cfg, axis, st, _hold_faults(cfg, params)
        )
    return _window_step(cfg, model, axis, st, params, window_end, done)


def _round_step_capture(
    cfg: EngineConfig, model, axis, st: SimState, params: EngineParams
):
    """One round that ALSO returns the pre-exchange outbox — the packets
    sent this round, for host-side pcap synthesis (the modeled-sim analogue
    of the reference's per-interface capture, network_interface.c). One
    dispatch per round: capture runs trade throughput for observability."""
    with jax.named_scope("shadow_window"):
        window_end, done = _compute_window(
            cfg, axis, st, _hold_faults(cfg, params)
        )
    return _window_step(
        cfg, model, axis, st, params, window_end, done, capture=True
    )


def _window_step(
    cfg: EngineConfig, model, axis, st: SimState, params: EngineParams,
    window_end, done, capture: bool = False,
):
    """Execute one scheduling window [*, window_end): microsteps + exchange.

    Split out of `_round_step` so the co-simulation bridge
    (`shadow_tpu.cosim`) can drive lockstep windows whose end is computed
    jointly with the CPU host plane instead of from the device queues alone.
    """
    h_local = st.queue.t.shape[0]
    shard_start = (
        lax.axis_index(axis).astype(jnp.int64) * h_local if axis else jnp.int64(0)
    )
    host_gid = shard_start + jnp.arange(h_local, dtype=jnp.int64)

    # ---- fluid traffic plane (net/fluid.py): the fluid->packet half of
    # the conservative coupling, computed ONCE per round from the
    # PREVIOUS round's ODE state (the round's committed window has not
    # run yet — using last round's utilization keeps the factors
    # loop-invariant across this round's microsteps). Per-host extra
    # loss probability and latency multiplier (>= 1.0 by construction:
    # inflation only, so the conservative-lookahead bound — which uses
    # the pre-inflation minimum — stays valid; the safe-window psum is
    # untouched). Zero background load yields loss 0.0 / multiplier
    # exactly 1.0x on every host — value-identical to fluid-off.
    fluid_fx = None
    if cfg.fluid_active:
        fluid_fx = fluid_host_effects(
            cfg, params.fluid, st.fluid, _host_nodes(cfg, params, host_gid)
        )

    # ---- safe-window telemetry (network observatory): which shard's
    # local min event time bound this round's all-reduce-min barrier —
    # the critical-path shard (ties to the lowest shard id, so the value
    # is deterministic and identical on every shard). One extra local
    # min + pmin per round, traced only when the observatory is on.
    bind_shard = None
    if cfg.netobs:
        nb_lmin = jnp.min(
            _effective_next(cfg, st, _hold_faults(cfg, params))
        )
        if axis:
            nb_gmin = _pmin(nb_lmin, axis)
            me = lax.axis_index(axis).astype(jnp.int64)
            bind_shard = _pmin(
                jnp.where(nb_lmin == nb_gmin, me, jnp.int64(cfg.world)),
                axis,
            )
        else:
            me = jnp.int64(0)
            bind_shard = jnp.int64(0)

    # ---- 3: microsteps (no collectives inside — shards proceed independently)
    if cfg.effective_microstep_events > 1:
        # K-way fold: the valve is a PER-HOST executed-event vector, bound
        # by its max — not a global sum of per-dispatch maxima, which
        # could overcharge (dispatch 1 charges host A's fold of 8 while
        # host B retired 1) and bind EARLIER than K=1. Per-host, a host
        # that would finish its round under K=1's limit always finishes
        # here too: its count before its last dispatch is at most
        # total - 1 < limit, so the strict < never cuts a non-pathological
        # round short — see EngineConfig.effective_microstep_limit.
        # `steps` keeps counting real dispatches for stats. Progress is
        # still guaranteed (batch index 0 can never defer, so every
        # dispatch with the cond held retires >= 1 event on some host).
        h_local = st.queue.t.shape[0]

        def micro_cond(carry):
            stc, valve, steps = carry
            return jnp.any(
                _effective_next(cfg, stc, _hold_faults(cfg, params))
                < window_end
            ) & (jnp.max(valve) < cfg.effective_microstep_limit)

        def micro_body(carry):
            stc, valve, steps = carry
            stc, executed = _microstep_k(
                cfg, model, stc, params, host_gid, window_end, fluid_fx
            )
            return stc, valve + executed.astype(jnp.int64), steps + 1

        with jax.named_scope("shadow_microsteps"):
            st_m, _, steps = lax.while_loop(
                micro_cond,
                micro_body,
                (st, jnp.zeros((h_local,), jnp.int64), jnp.zeros((), jnp.int64)),
            )
    else:
        def micro_cond(carry):
            stc, steps = carry
            return jnp.any(
                _effective_next(cfg, stc, _hold_faults(cfg, params))
                < window_end
            ) & (steps < cfg.effective_microstep_limit)

        def micro_body(carry):
            stc, steps = carry
            stc = _microstep(
                cfg, model, stc, params, host_gid, window_end, fluid_fx
            )
            return stc, steps + 1

        with jax.named_scope("shadow_microsteps"):
            st_m, steps = lax.while_loop(
                micro_cond, micro_body, (st, jnp.zeros((), jnp.int64))
            )

    # ---- 4: exchange staged packets across the mesh
    with jax.named_scope("shadow_exchange"):
        st_x = _exchange(cfg, axis, st_m)

    # queue-occupancy high-water, sampled at the post-merge peak (cheap:
    # the bucketed queue reads its bfill caches; flat pays one [H, C]
    # compare+sum per ROUND, noise next to the microsteps it follows)
    occ = q_len(st_x.queue).astype(jnp.int64)
    # outbox-send high-water: the most sends any one host staged THIS
    # round (pre-exchange cursor max — the gear controller's signal).
    # Always on: one [H] max per round, noise next to the occ pass above.
    ob_hwm = jnp.max(st_m.sent_round).astype(jnp.int64)
    stats = st_x.stats._replace(
        rounds=st_x.stats.rounds + jnp.where(done, 0, 1),
        microsteps=st_x.stats.microsteps + steps[None],
        q_occ_hwm=jnp.maximum(st_x.stats.q_occ_hwm, occ),
        outbox_hwm=jnp.maximum(st_x.stats.outbox_hwm, ob_hwm[None]),
    )
    if cfg.wheel_active:
        # wheel-occupancy high-water, same cadence as q_occ_hwm (cheap:
        # the wheel always reads its bfill caches). The exchange never
        # touches the wheel, so the post-exchange sample is the round's
        # post-push peak.
        w_occ = q_len(st_x.wheel).astype(jnp.int64)
        stats = stats._replace(
            wheel_occ_hwm=jnp.maximum(stats.wheel_occ_hwm, w_occ)
        )
    if cfg.netobs:
        # this shard bound the barrier this round (done-rounds are not
        # scheduling rounds and do not count, mirroring stats.rounds)
        stats = stats._replace(
            win_bound=stats.win_bound
            + jnp.where(done | (me != bind_shard), 0, 1)[None]
        )
    if cfg.pressure_abort:
        # pressure signal: the shard-local capacity-drop total (queue-push
        # overflow + merge sheds in queue.dropped, alltoall
        # block sheds, outbox overflow, per-host send-budget drops),
        # psum'd so every shard carries the GLOBAL cumulative count and
        # the chunk loop's first-drop abort stays mesh-uniform. Two [H]
        # sums + one psum per round — noise next to the occ pass above.
        local = (
            jnp.sum(st_x.queue.dropped)
            + jnp.sum(stats.pkts_budget_dropped)
            + stats.a2a_shed[0]
            + stats.ob_dropped[0]
        )
        total = _psum(local, axis)
        stats = stats._replace(pressure=total[None])
    if cfg.integrity:
        # integrity sentinel (core/integrity.py): evaluate the per-round
        # invariant guards on the post-exchange state. The count is
        # psum'd so the chunk loop's first-violation abort is uniform
        # across the mesh; the (shard, round, mask) signature lanes stay
        # per-shard so the replay classifier can name the violating
        # shard. The final done-round is not a scheduling round and is
        # never judged (mirrors stats.rounds).
        iv_viol, iv_m = _integrity_round_check(
            cfg, axis, st, st_m, st_x, stats, window_end, done, ob_hwm
        )
        iv_total = _psum(iv_viol, axis)
        stats = stats._replace(
            integrity=stats.integrity + iv_total[None],
            iv_mask=stats.iv_mask | iv_m[None],
            iv_round=jnp.where(
                (stats.iv_round < 0) & (iv_m != 0),
                st.stats.rounds,
                stats.iv_round,
            ),
        )
    fluid_new = None
    if cfg.fluid_active:
        # packet->fluid half of the coupling + the ODE advance: the
        # pre-exchange outbox's bytes per link (psum'd — every shard
        # sees the GLOBAL count) subtract from fluid capacity, then one
        # forward-Euler step over the committed window updates the
        # replicated rate/utilization lanes and the background byte
        # counters. Runs on the post-microstep outbox (st_m), BEFORE the
        # exchange cleared it.
        fg_link = _fluid_fg_link_bytes(cfg, axis, st_m.outbox, params,
                                       host_gid)
        fluid_new, bg_dlv, bg_drp = fluid_advance(
            cfg, params.fluid, st.fluid, fg_link, st.now, window_end, done
        )
        stats = stats._replace(
            fl_bg_bytes=stats.fl_bg_bytes + bg_dlv,
            fl_bg_dropped=stats.fl_bg_dropped + bg_drp,
        )
    with jax.named_scope("shadow_window"):
        # the next window's lookahead: the barrier's other all-reduce
        min_used = _pmin(st_x.min_used_lat, axis)
    out = st_x._replace(
        now=jnp.where(done, st.now, window_end),
        done=done,
        min_used_lat=min_used,
        stats=stats,
    )
    if cfg.fluid_active:
        out = out._replace(fluid=fluid_new)
    if cfg.trace_rounds:
        out = out._replace(
            trace=_trace_round(
                cfg, st, st_m, st_x, window_end, done, steps, occ, ob_hwm,
                params.faults, bind_shard=bind_shard,
            )
        )
    if capture:
        return out, st_m.outbox  # this round's sends, pre-exchange
    return out


def _trace_round(
    cfg: EngineConfig, st0: SimState, st_m: SimState, st_x: SimState,
    window_end, done, steps, occ, ob_hwm, faults=None, bind_shard=None,
):
    """Append this round's record to the in-scan trace ring.

    Strictly an observer: every value is either already computed by the
    round (window bounds, steps, occ) or a difference of counters the
    round maintains anyway — nothing downstream reads the ring, so the
    scheduling dataflow is untouched and digests/events/drops stay
    bit-identical with tracing on or off. The final done-round (which
    does not count in stats.rounds) is skipped the same way.

    `st0` is the round-entry state (for counter deltas), `st_m` the
    post-microstep state (for the pre-exchange outbox count), `st_x` the
    post-exchange state."""
    ring: TraceRing = st_x.trace

    def delta(get):
        return (get(st_x.stats) - get(st0.stats))[0]

    vals = [jnp.zeros((), jnp.int64)] * TRACE_COLS
    vals[COL_ROUND] = st0.stats.rounds
    vals[COL_WINDOW_START] = st0.now
    vals[COL_WINDOW_END] = window_end
    vals[COL_EVENTS] = jnp.sum(st_x.stats.events - st0.stats.events)
    vals[COL_MICROSTEPS] = steps
    vals[COL_POPK_DEFERRED] = delta(lambda s: s.popk_deferred)
    vals[COL_BQ_REBUILDS] = delta(lambda s: s.bq_rebuilds)
    vals[COL_ICI_BYTES] = delta(lambda s: s.ici_bytes)
    vals[COL_SENDS] = st_m.outbox.count[0].astype(jnp.int64)
    vals[COL_A2A_SHED] = delta(lambda s: s.a2a_shed)
    vals[COL_OCC_HWM] = jnp.max(occ)
    vals[COL_NEXT_TIME] = jnp.min(q_next_time(st_x.queue))
    vals[COL_OB_HWM] = ob_hwm
    vals[COL_GEAR] = jnp.asarray(cfg.effective_gear_cols, jnp.int64)
    vals[COL_CAP] = jnp.asarray(cfg.queue_capacity, jnp.int64)
    if cfg.faults_active:
        vals[COL_FAULTS_DROPPED] = jnp.sum(
            st_x.stats.faults_dropped - st0.stats.faults_dropped
        )
        vals[COL_FAULTS_DELAYED] = jnp.sum(
            st_x.stats.faults_delayed - st0.stats.faults_delayed
        )
    if cfg.fault_crash_windows and faults is not None:
        h = st_x.queue.t.shape[0]
        down, _ = down_and_resume(
            faults, jnp.broadcast_to(window_end, (h,))
        )
        vals[COL_HOSTS_DOWN] = jnp.sum(down, dtype=jnp.int64)
    if cfg.netobs:
        # network-observatory columns (netobs-off traced runs keep zeros
        # here — the columns exist so recorded traces stay positional)
        vals[COL_EC_TIMER] = delta(lambda s: s.ec_timer)
        vals[COL_EC_PKT] = delta(lambda s: s.ec_pkt)
        vals[COL_EC_APP] = delta(lambda s: s.ec_app)
        if cfg.flow_ledger_active:
            vals[COL_FLOWS] = delta(lambda s: s.fl_done)
        if bind_shard is not None:
            vals[COL_BIND_SHARD] = bind_shard
    if cfg.hier_active:
        # hierarchical-exchange tier columns (flat-exchange traced runs
        # keep zeros here — positional like the netobs columns)
        vals[COL_XW_INTRA] = delta(lambda s: s.ici_intra)
        vals[COL_XW_INTER] = delta(lambda s: s.ici_inter)
    row = jnp.stack([jnp.asarray(v, jnp.int64) for v in vals])
    # the cursor is a registered i64 lane (core/lanes.py); the slice index
    # stays i64 rather than narrowing the lane value (shadowlint R2)
    idx = ring.cursor[0] % cfg.trace_rounds
    written = lax.dynamic_update_slice(
        ring.rows, row[None, None, :], (jnp.int64(0), idx, jnp.int64(0))
    )
    # the done-round is not a scheduling round: no row, no cursor bump
    return TraceRing(
        rows=jnp.where(done, ring.rows, written),
        cursor=ring.cursor + jnp.where(done, 0, 1),
    )


def _integrity_round_check(
    cfg: EngineConfig, axis, st0: SimState, st_m: SimState, st_x: SimState,
    stats: Stats, window_end, done, ob_hwm,
):
    """The integrity sentinel's per-round invariant guards
    (core/integrity.py names the bits). Returns (local violation count
    i64, local invariant bitmask i64), both zeroed on the done-round.

    Every check below is UNCONDITIONAL — satisfied by construction on
    every legal engine trajectory, so a trip always means corrupted
    state (or a real engine bug, which the replay classifier
    distinguishes). The derivations:

      IV_TIME (a) window monotonicity: window_end = min(gmin_eff + ra,
        stop) with gmin_eff >= committed now (leftover events are >= the
        previous window end, floor-held events' effective time is their
        restart/busy horizon >= now) and stop >= now — so a regressing
        window means a past-time value appeared in the time plane. The
        one legal exception is a valve-bound round (the livelock
        condition leaves in-window events behind) combined with DYNAMIC
        runahead shrink, so (a) is traced only under static runahead.
      IV_TIME (b) slab floor: every event present at round entry is
        >= the entry's global raw minimum; pops remove, pushes carry
        t >= the executing event's time >= that minimum, and merged
        arrivals are >= window_end > it — so no post-round slot may
        hold a smaller time (catches in-flight scribbles on the time
        plane within the round, any runahead mode).
      IV_EC: ec_timer/ec_pkt/ec_app bucket the exact `active` mask
        stats.events counts (`_event_body`), so the class sums equal
        the event total per shard — the netobs reconciliation CHECK
        promoted to a hard guard (traced only when the observatory is).
      IV_QFILL: the bucketed queue's per-block fill caches are
        incrementally maintained to equal the slab's true occupancy
        (tests/test_bucketq.py gates the ops); one [H, C] compare+sum
        re-derives the truth (bucketed layouts only).
      IV_COUNTER: every event/drop/fault counter only ever adds
        non-negative masks — deltas are >= 0 and values never negative.
      IV_OUTBOX: sent_round increments by booleans gated on the budget,
        so no host's round cursor exceeds sends_per_host_round, cursors
        stay non-negative, and the count word stays in [0, H x B].
      IV_DIGEST: a host with zero executed events has never passed
        through `_digest_update`, so both digest lanes still carry
        their initial offsets (the dual lane shares no constants with
        the primary — core/integrity.classify_digest_pair)."""
    from shadow_tpu.core.integrity import (
        IV_COUNTER,
        IV_DIGEST,
        IV_EC,
        IV_OUTBOX,
        IV_QFILL,
        IV_TIME,
    )

    checks: list[tuple[int, Any]] = []
    entry_min = jnp.min(st0.queue.t)
    post_min = jnp.min(st_x.queue.t)
    if cfg.wheel_active:
        # the wheel's time plane is part of the same slab-floor law:
        # pending timers obey the identical >= entry-minimum argument
        entry_min = jnp.minimum(entry_min, jnp.min(st0.wheel.t))
        post_min = jnp.minimum(post_min, jnp.min(st_x.wheel.t))
    gmin_raw = _pmin(entry_min, axis)
    t_bad = post_min < gmin_raw
    if cfg.integrity_strict_time and not cfg.use_dynamic_runahead:
        # see the IV_TIME (a) derivation above: valve-bound rounds under
        # DYNAMIC runahead (shrinking ra) and the hybrid bridge
        # (cfg.integrity_strict_time False) are the two legal exceptions
        t_bad = t_bad | (window_end < st0.now)
    checks.append((IV_TIME, t_bad))
    if cfg.netobs:
        ec_sum = stats.ec_timer[0] + stats.ec_pkt[0] + stats.ec_app[0]
        checks.append((IV_EC, ec_sum != jnp.sum(stats.events)))
    if cfg.queue_block:
        # judged PRE-exchange (st_m): the merge rebuilds the caches
        # wholesale whenever any shard sent, which would erase a
        # divergence before a post-exchange read could see it — the
        # incrementally-maintained pre-merge caches carry one through
        occ_true = jnp.sum(
            st_m.queue.t != TIME_MAX, axis=1, dtype=jnp.int32
        )
        checks.append((
            IV_QFILL,
            jnp.any(occ_true != jnp.sum(st_m.queue.bfill, axis=1)),
        ))
    if cfg.wheel_active:
        # the wheel's fill caches obey the same incremental-maintenance
        # invariant (it IS the BucketQueue machinery; no merge rebuild
        # ever masks a divergence, so post-exchange is equally valid)
        w_occ_true = jnp.sum(
            st_m.wheel.t != TIME_MAX, axis=1, dtype=jnp.int32
        )
        checks.append((
            IV_QFILL,
            jnp.any(w_occ_true != jnp.sum(st_m.wheel.bfill, axis=1)),
        ))
    c_bad = jnp.any(st_x.queue.dropped < st0.queue.dropped) | jnp.any(
        st_x.queue.dropped < 0
    )
    if cfg.wheel_active:
        # spill routing pre-empts every wheel overflow: a nonzero wheel
        # drop counter means the free accounting (or the slab) is corrupt
        c_bad = c_bad | jnp.any(st_x.wheel.dropped != 0)
        c_bad = c_bad | jnp.any(
            stats.wheel_spilled < st0.stats.wheel_spilled
        ) | jnp.any(stats.wheel_spilled < 0)
    for get in (
        lambda s: s.events,
        lambda s: s.pkts_sent,
        lambda s: s.pkts_lost,
        lambda s: s.pkts_unreachable,
        lambda s: s.pkts_codel_dropped,
        lambda s: s.pkts_delivered,
        lambda s: s.pkts_budget_dropped,
        lambda s: s.faults_dropped,
        lambda s: s.faults_delayed,
    ):
        post, pre = get(stats), get(st0.stats)
        c_bad = c_bad | jnp.any(post < pre) | jnp.any(post < 0)
    checks.append((IV_COUNTER, c_bad))
    b = cfg.sends_per_host_round
    count = st_m.outbox.count[0]
    checks.append((
        IV_OUTBOX,
        (ob_hwm > b)
        | (jnp.min(st_m.sent_round) < 0)
        | (count < 0)
        | (count > st_m.outbox.t.shape[0] * b),
    ))
    virgin = stats.digest != _FNV_OFFSET
    if cfg.integrity_dual:
        virgin = virgin | (stats.digest2 != _DIGEST2_OFFSET)
    checks.append((IV_DIGEST, jnp.any((stats.events == 0) & virgin)))

    mask = jnp.zeros((), jnp.int64)
    viol = jnp.zeros((), jnp.int64)
    for bit, bad in checks:
        bad = bad & ~done
        mask = mask | jnp.where(bad, jnp.int64(1 << bit), jnp.int64(0))
        viol = viol + bad.astype(jnp.int64)
    return viol, mask


def _host_nodes(cfg: EngineConfig, params: EngineParams, host_gid):
    """Per-host graph-node index (the fluid plane's link id): the affine
    divide when init_state detected the uniform-blocks map, else one
    gather from the replicated node_of table — the same two routes the
    send path's destination lookup takes."""
    if cfg.hosts_per_node > 0:
        return (host_gid // cfg.hosts_per_node).astype(jnp.int32)
    return params.node_of[host_gid].astype(jnp.int32)


def _fluid_fg_link_bytes(cfg: EngineConfig, axis, ob: Outbox,
                         params: EngineParams, host_gid):
    """The packet->fluid half of the coupling: this round's foreground
    bytes per fluid link, folded from the pre-exchange outbox — uplink
    bytes charge each sender's access link, downlink bytes the
    destination's. INTEGER scatter-adds only (order-free, so the fold
    is bit-deterministic), psum'd across the mesh so every shard sees
    the GLOBAL count and the replicated ODE stays identical on every
    shard and across mesh shapes."""
    n = cfg.fluid_links
    valid = ob.t != TIME_MAX
    size = jnp.where(
        valid, ob.payload[:, :, PAYLOAD_SIZE_WORD].astype(jnp.int64),
        jnp.int64(0),
    )
    src_node = jnp.clip(_host_nodes(cfg, params, host_gid), 0, n - 1)
    up = jnp.zeros((n,), jnp.int64).at[src_node].add(
        jnp.sum(size, axis=1)
    )
    dst_f = jnp.clip(
        ob.dst.reshape(-1).astype(jnp.int64), 0, cfg.num_hosts - 1
    )
    if cfg.hosts_per_node > 0:
        dnode = (dst_f // cfg.hosts_per_node).astype(jnp.int32)
    else:
        dnode = params.node_of[dst_f].astype(jnp.int32)
    down = jnp.zeros((n,), jnp.int64).at[jnp.clip(dnode, 0, n - 1)].add(
        size.reshape(-1)
    )
    tot = up + down
    return _psum(tot, axis)


def _hold_faults(cfg: EngineConfig, params: EngineParams):
    """The fault schedule iff queue-HOLD crash semantics are in force —
    the only fault mode that floors next-event times (clear mode drops at
    pop and never defers)."""
    return params.faults if cfg.fault_hold else None


def _effective_next(cfg: EngineConfig, st: SimState, faults=None):
    """Per-host next *executable* time: queue head, floored by the CPU
    model's busy horizon (a busy host keeps its events queued — order
    intact — and resumes at busy_until, exactly the reference's CPU-delay
    rescheduling, host.rs:820-847) and, under queue-hold crash faults, by
    the host's restart time (a down host's events defer to its up_t —
    same mechanics, different clock)."""
    nt = q_next_time(st.queue)
    if cfg.wheel_active:
        # the timer wheel's head folds into the same min: a due timer is
        # as executable as a due queue event (TIME_MAX sentinels pass
        # through the minimum unchanged)
        nt = jnp.minimum(nt, wheel_next_time(st.wheel))
    if cfg.cpu_delay_ns > 0:
        nt = jnp.where(nt == TIME_MAX, nt, jnp.maximum(nt, st.cpu_busy_until))
    if faults is not None:
        _, resume = down_and_resume(faults, nt)
        nt = jnp.where(nt == TIME_MAX, nt, jnp.maximum(nt, resume))
    return nt


class _EvCarry(NamedTuple):
    """The state threads an executed event reads/writes — everything a
    microstep touches EXCEPT the queue and the outbox, which the two
    microstep shapes (single-event vs K-way fold) apply differently:
    K=1 applies pushes/appends immediately; the K-way fold accumulates
    them across the batch and applies each in ONE fused pass."""

    stats: Stats
    rng: RngState
    seq: Array
    sent_round: Array
    tb_egress: TBState
    tb_ingress: TBState
    codel: Any
    model: Any


def _event_body(cfg, model, c: _EvCarry, params, host_gid, window_end, ev,
                active, fluid_fx=None):
    """Execute one event per `active` host: digest, ingress shaping, model
    dispatch, and egress staging. Returns (carry', push_list, ob_entries,
    used_lats): queue pushes and outbox appends are RETURNED, not applied —
    dataflow-identical for the K=1 caller (pure functions; application
    order does not change any value) and the enabler for the K-way fold's
    amortized single-pass application."""
    stats = c.stats
    stats = stats._replace(
        events=stats.events + active,
        digest=_digest_update(stats.digest, active, ev.t, ev.kind, ev.order),
    )
    if cfg.integrity_dual:
        stats = stats._replace(
            digest2=_digest_update2(
                stats.digest2, active, ev.t, ev.kind, ev.order
            )
        )

    is_pkt = (ev.kind & KIND_PKT) != 0

    if cfg.netobs:
        # event-class accounting (obs/netobs.py): every EXECUTED event —
        # the same `active` mask stats.events counts, so the class sums
        # reconcile exactly with the event total — buckets as packet
        # (engine KIND_PKT flag), timer (the model's declared
        # timer_kinds), or app (the rest). Three [H] masks + sums per
        # event; traced only when the observatory is on.
        cls_timer = active & ~is_pkt & kind_in(
            ev.kind & KIND_MASK, tuple(getattr(model, "timer_kinds", ()))
        )
        stats = stats._replace(
            ec_timer=stats.ec_timer
            + jnp.sum(cls_timer, dtype=jnp.int64)[None],
            ec_pkt=stats.ec_pkt
            + jnp.sum(active & is_pkt, dtype=jnp.int64)[None],
            ec_app=stats.ec_app
            + jnp.sum(active & ~is_pkt & ~cls_timer, dtype=jnp.int64)[None],
        )

    if cfg.shaping:
        needs_ingress = active & is_pkt & ((ev.kind & KIND_INGRESS_DONE) == 0)

        # ---- ingress pipeline: CoDel at the router queue, then the downlink
        # token bucket. The law sees the delay the packet WOULD experience,
        # and only survivors consume bandwidth (reference: the relay pulls
        # from the CoDel queue, so dropped packets are never charged;
        # router/mod.rs:47-62).
        size_bits = jnp.asarray(ev.payload[:, PAYLOAD_SIZE_WORD], jnp.int64) * 8
        no_mask = jnp.zeros_like(needs_ingress)
        _, depart_probe = tb_conforming_remove(
            c.tb_ingress, params.in_tb, cfg.tb_interval_ns, ev.t, size_bits, no_mask
        )
        sojourn = depart_probe - ev.t
        if cfg.use_codel:
            codel, codel_drop = codel_on_packet(c.codel, ev.t, sojourn, needs_ingress)
        else:
            codel, codel_drop = c.codel, jnp.zeros_like(needs_ingress)
        tb_in, depart = tb_conforming_remove(
            c.tb_ingress,
            params.in_tb,
            cfg.tb_interval_ns,
            ev.t,
            size_bits,
            needs_ingress & ~codel_drop,
        )
        delay = needs_ingress & ~codel_drop & (depart > ev.t)
        # the requeue (bucket-delayed packet goes back in the queue past
        # shaping) is deferred into the fused push pass below. It used to
        # be a lax.cond-guarded push_one "optimization" — the profiler
        # showed the conditional itself costing ~40% of the microstep at
        # 10k hosts x capacity 64: an XLA cond is a fusion barrier that
        # copies the full queue slab at the branch boundary every
        # microstep, far more than the one-hot write it was skipping.
        requeue = (delay, depart, ev.order, ev.kind | KIND_INGRESS_DONE,
                   ev.payload)
        stats = stats._replace(
            pkts_codel_dropped=stats.pkts_codel_dropped + codel_drop
        )
        dispatch = active & ~(needs_ingress & (codel_drop | delay))
    else:
        codel, tb_in = c.codel, c.tb_ingress
        requeue = None
        dispatch = active

    # ---- model dispatch (Host::execute -> TaskRef::execute / packet receive)
    stats = stats._replace(pkts_delivered=stats.pkts_delivered + (dispatch & is_pkt))
    ctx = HandlerCtx(
        t=ev.t,
        window_end=window_end,
        kind=ev.kind & KIND_MASK,
        payload=ev.payload,
        active=dispatch,
        is_packet=is_pkt,
        src=unpack_order_src(ev.order),
        host_id=host_gid,
        state=c.model,
        params=params.model,
        rng=c.rng,
    )
    with jax.named_scope("shadow_handler"):
        out = model.handle(ctx)
    rng, model_state = out.rng, out.state
    seq = c.seq
    sent_round = c.sent_round
    tb_eg = c.tb_egress

    # ---- flow-completion port (network observatory): the model's
    # FlowDone record becomes one ledger entry (applied in a fused pass
    # by _finish_microstep) and the fl_* totals advance on an
    # INDEPENDENT path from the ring cursor, so reconciliation between
    # the two is a real check. Not traced unless the ledger is on.
    flow_list = []
    if cfg.flow_ledger_active and out.flow is not None:
        f = out.flow
        fmask = f.mask & dispatch
        fbytes = jnp.asarray(f.bytes, jnp.int64)
        frtx = jnp.asarray(f.retransmits, jnp.int64)
        stats = stats._replace(
            fl_done=stats.fl_done + jnp.sum(fmask, dtype=jnp.int64)[None],
            fl_bytes=stats.fl_bytes
            + jnp.sum(jnp.where(fmask, fbytes, 0))[None],
            fl_rtx=stats.fl_rtx
            + jnp.sum(jnp.where(fmask, frtx, 0))[None],
        )
        flow_list.append((
            fmask,
            jnp.asarray(f.dst, jnp.int64),
            jnp.asarray(f.flow, jnp.int64),
            jnp.asarray(f.t_start, jnp.int64),
            ev.t,  # completion time = this event's execution time
            fbytes,
            frtx,
        ))

    # ---- local pushes (schedule_task_* analogue). All ports are applied
    # in ONE slab pass (push_many): sequential push_one calls each pay a
    # full [H, C] read+write because the free-slot reduction between them
    # fences XLA fusion — measured as a dominant per-microstep cost.
    # the ingress requeue goes FIRST so slot-assignment order matches the
    # golden oracle (its qpush runs during ingress, before model pushes)
    push_list = [requeue] if requeue is not None else []
    for p in out.pushes:
        mask = p.mask & dispatch
        t_req = jnp.asarray(p.t, jnp.int64)
        stats = stats._replace(
            monotonic_violations=stats.monotonic_violations + (mask & (t_req < ev.t))
        )
        t_push = jnp.maximum(t_req, ev.t)
        order = pack_order(1, host_gid, seq)
        seq = seq + mask
        push_list.append((
            mask, t_push, order,
            jnp.asarray(p.kind, jnp.int32) & KIND_MASK, p.payload,
        ))

    # ---- sends: egress pipeline (worker.rs:330-425 send_packet). Each
    # port may carry a BURST (PacketSend.count/count_max): up to count_max
    # packets to one destination, sharing the routing lookup (the H x N
    # table reduction is the per-port cost that made one-packet-per-port
    # TCP windows unaffordable) while each segment keeps its own loss
    # draw, bandwidth charge, order key, and budget slot. Outbox writes
    # are deferred and applied in one slab pass after the loop.
    entries = []  # (send_ok, col, dst, arrive, order, kind, payload)
    used_lats = []
    if cfg.fault_loss_windows:
        # link-fault windows active at this event's time: one [H, L] pass
        # per event, shared by every port/segment below. Loss draws come
        # from the per-host masked-advance RNG lanes (mesh-shape
        # invariant); inflation is integer x1000 math so the arrive time
        # is bit-reproducible. Inflation can only GROW latency
        # (latency_factor >= 1.0 is validated at config parse), so the
        # conservative-lookahead bound — which uses the pre-inflation
        # minimum — stays valid.
        f_loss, f_lat = window_effects(params.faults, ev.t)
        # inflation honors bootstrap_end_time like the loss side of the
        # same window: bootstrap-phase traffic stays undisturbed (and
        # uncounted in faults_delayed)
        f_inflate = (f_lat > LAT_SCALE) & (ev.t >= cfg.bootstrap_end_time)
    if cfg.fluid_active:
        # fluid congestion coupling (net/fluid.py): this round's per-host
        # extra-loss probability and latency multiplier, computed once at
        # round start from the background ODE's utilization. Inflation
        # honors bootstrap_end_time like every loss plane, and the loss
        # draw below is a COUNTER-BASED hash (fluid_send_uniform) that
        # never advances the RNG lanes — at zero background load the
        # factors are exactly (0.0, 1.0x) and every value downstream is
        # bit-identical to the fluid-off program.
        bg_loss, bg_lat = fluid_fx
        bg_inflate = (bg_lat > LAT_SCALE) & (
            ev.t >= cfg.bootstrap_end_time
        )
    for s in out.sends:
        cmax = int(getattr(s, "count_max", 1) or 1)
        mask0 = s.mask & dispatch
        # gate on count is None (the documented contract, mirrored by the
        # golden oracle) — NOT on count_max: count=None with count_max>1 is
        # legal, and an explicit count of 0 must suppress the send
        if getattr(s, "count", None) is None:
            counts = mask0.astype(jnp.int32)
        else:
            counts = jnp.where(mask0, jnp.asarray(s.count, jnp.int32), 0)
        sz = jnp.asarray(s.size_bytes, jnp.int32)
        dst_raw = jnp.asarray(s.dst, jnp.int64)
        bad_dst = mask0 & ((dst_raw < 0) | (dst_raw >= cfg.num_hosts))
        dst = jnp.clip(dst_raw, 0, cfg.num_hosts - 1)  # safe gather only
        if params.lat_ns.shape == (1, 1):
            # single graph node (e.g. the 1-gbit-switch topology): the path
            # lookup is a constant — elide the node_of/table gathers, which
            # are a measured per-microstep hot spot on TPU
            lat = jnp.broadcast_to(params.lat_ns[0, 0], dst.shape)
            lossp = jnp.broadcast_to(params.loss[0, 0], dst.shape)
            jit = jnp.broadcast_to(params.jitter_ns[0, 0], dst.shape)
        elif params.lat_rows is not None:
            # node lookup (then a one-hot masked reduction over the node
            # axis for each table — vector work on the VPU instead of
            # scalar-core gathers, see EngineParams.lat_rows). With an
            # affine host->node map even the lookup's gather disappears
            # into a VPU divide (EngineConfig.hosts_per_node).
            if cfg.hosts_per_node > 0:
                dst_node = (dst // cfg.hosts_per_node).astype(jnp.int32)
            else:
                dst_node = params.node_of[dst].astype(jnp.int32)
            n_nodes = params.lat_rows.shape[1]
            eq = (
                jnp.arange(n_nodes, dtype=jnp.int32)[None, :]
                == dst_node[:, None]
            )
            lat = jnp.sum(jnp.where(eq, params.lat_rows, 0), axis=1)
            lossp = jnp.sum(jnp.where(eq, params.loss_rows, 0.0), axis=1)
            jit = jnp.sum(jnp.where(eq, params.jit_rows, 0), axis=1)
        else:
            if cfg.hosts_per_node > 0:
                src_node = host_gid // cfg.hosts_per_node
                dst_node = dst // cfg.hosts_per_node
            else:
                src_node = params.node_of[host_gid]
                dst_node = params.node_of[dst]
            lat = params.lat_ns[src_node, dst_node]
            lossp = params.loss[src_node, dst_node]
            jit = params.jitter_ns[src_node, dst_node]
        lat_bound0 = lat  # pre-jitter: the conservative lookahead quantity
        if cfg.use_jitter:
            lat_bound0 = lat_bound0 - jit
        port_kind = jnp.asarray(s.kind, jnp.int32) | KIND_PKT
        for j in range(cmax):
            mask = mask0 & (counts > j)
            # per-host round budget: the drop decision is a function of
            # this host's own sends only, so it cannot vary with mesh
            # shape. Decided BEFORE the bandwidth charge: a budget-dropped
            # packet must be side-effect-free (no debited bits, no
            # borrowed refill intervals).
            over_budget = sent_round >= cfg.sends_per_host_round
            if cfg.shaping:
                tb_eg, eg_depart = tb_conforming_remove(
                    tb_eg,
                    params.eg_tb,
                    cfg.tb_interval_ns,
                    ev.t,
                    sz.astype(jnp.int64) * 8,
                    mask & ~over_budget,
                )
            else:
                eg_depart = ev.t  # unlimited uplink: no serialization delay
            lat_j = lat
            if cfg.use_jitter:
                # uniform in [lat - j, lat + j] (deterministic per-host
                # lane draw, one per segment); the lookahead bound uses
                # lat - j
                rng, uj = rng_uniform(rng, mask)
                lat_j = lat + (
                    (uj * 2.0 - 1.0) * jit.astype(jnp.float32)
                ).astype(jnp.int64)
            # a model emitting an out-of-range dst is a bug: surface it as
            # unreachable rather than silently delivering to a clamped
            # host. Uses the PRE-jitter bound so the predicate is
            # independent of the jitter draw (float32 jitter math could
            # otherwise flip the sign for amplitudes >= 2^24 ns, diverging
            # from golden which tests lat_bound)
            unreachable = mask & ((lat_bound0 < 0) | bad_dst)
            rng, u = rng_uniform(rng, mask)
            lost = mask & (u < lossp) & (ev.t >= cfg.bootstrap_end_time)
            if cfg.fault_loss_windows:
                # fault loss draws AFTER the path-loss draw (stable
                # position in the per-host stream) and honors the same
                # bootstrap gate; precedence: path loss > unreachable >
                # fault loss > budget, each counted exactly once
                rng, uf = rng_uniform(rng, mask)
                flost = (
                    mask & ~lost & ~unreachable & (uf < f_loss)
                    & (ev.t >= cfg.bootstrap_end_time)
                )
                lat_j = jnp.where(
                    f_inflate, (lat_j * f_lat) // LAT_SCALE, lat_j
                )
            else:
                flost = None
            if cfg.fluid_active:
                bglost = None
                if cfg.fluid_loss_max > 0.0:
                    # fluid congestion loss AFTER the path/fault draws
                    # (precedence: path loss > unreachable > fault loss
                    # > fluid loss > budget, each counted exactly once).
                    # The uniform is a pure hash of (fluid seed, global
                    # host id, emission counter) — unique per send,
                    # mesh-shape invariant, and side-effect-free on the
                    # RNG stream. Drops fold into pkts_lost (congestion
                    # loss IS path loss to the protocol; the links fold
                    # attributes it). loss_max is a trace-time static:
                    # latency-only coupling (the default) traces NO draw
                    # — bg_loss would be identically 0.0 yet the hash is
                    # per send segment on the measured dispatch path.
                    ub = fluid_send_uniform(cfg.fluid_seed, host_gid, seq)
                    bglost = (
                        mask & ~lost & ~unreachable & (ub < bg_loss)
                        & (ev.t >= cfg.bootstrap_end_time)
                    )
                    if flost is not None:
                        bglost = bglost & ~flost
                lat_j = jnp.where(
                    bg_inflate, (lat_j * bg_lat) // LAT_SCALE, lat_j
                )
            else:
                bglost = None
            send_ok = mask & ~lost & ~unreachable & ~over_budget
            budget_dropped = mask & ~lost & ~unreachable & over_budget
            if flost is not None:
                send_ok = send_ok & ~flost
                budget_dropped = budget_dropped & ~flost
                stats = stats._replace(
                    faults_dropped=stats.faults_dropped + flost,
                    faults_delayed=stats.faults_delayed
                    + (send_ok & f_inflate),
                )
            if bglost is not None:
                send_ok = send_ok & ~bglost
                budget_dropped = budget_dropped & ~bglost
            ob_col = sent_round  # lane column (cursor pre-increment)
            sent_round = sent_round + send_ok.astype(jnp.int32)
            # conservative-PDES clamp (worker.rs:411-414): never before
            # round end
            arrive = jnp.maximum(eg_depart + jnp.maximum(lat_j, 0), window_end)
            order = pack_order(0, host_gid, seq)
            seq = seq + mask
            payload = s.payload
            if j > 0 and s.payload_inc is not None:
                payload = payload + j * jnp.asarray(s.payload_inc, jnp.int32)
            payload = payload.at[:, PAYLOAD_SIZE_WORD].set(sz)
            entries.append(
                (send_ok, ob_col, dst, arrive, order, port_kind, payload)
            )
            used_lats.append(jnp.where(send_ok, lat_bound0, TIME_MAX))
            stats = stats._replace(
                pkts_sent=stats.pkts_sent + mask,
                # bglost is disjoint from lost by construction (drawn on
                # the ~lost survivors), so the OR is an exact sum
                pkts_lost=stats.pkts_lost
                + (lost if bglost is None else lost | bglost),
                pkts_unreachable=stats.pkts_unreachable + unreachable,
                pkts_budget_dropped=stats.pkts_budget_dropped + budget_dropped,
            )
    return (
        _EvCarry(
            stats=stats, rng=rng, seq=seq, sent_round=sent_round,
            tb_egress=tb_eg, tb_ingress=tb_in, codel=codel, model=model_state,
        ),
        push_list,
        entries,
        used_lats,
        flow_list,
    )


def _ev_carry_of(st: SimState) -> _EvCarry:
    return _EvCarry(
        stats=st.stats, rng=st.rng, seq=st.seq, sent_round=st.sent_round,
        tb_egress=st.tb_egress, tb_ingress=st.tb_ingress, codel=st.codel,
        model=st.model,
    )


def _flow_append(cfg: EngineConfig, ledger: FlowLedger, host_gid, entries):
    """Append a microstep's flow-completion entries to the per-shard
    ledger ring, in chronological entry order with host-major slot
    assignment inside each entry (an exclusive prefix-sum over the mask
    gives every completing host its own slot — no collisions by
    construction). Writes land at `cursor % R`; hosts beyond the mask
    scatter to index R, which `mode="drop"` discards — counted later by
    the FlowCollector against the monotone cursor, never silent."""
    fr = cfg.flow_records
    rows = ledger.rows[0]  # shard-local [R, F] plane
    cur = ledger.cursor[0]
    for mask, dst, fidx, t0, t1, fbytes, frtx in entries:
        m64 = mask.astype(jnp.int64)
        ofs = jnp.cumsum(m64) - m64  # exclusive prefix: per-host slot
        n = jnp.sum(m64)
        slot = (cur + ofs) % fr
        # only the NEWEST fr completions of this entry get a live slot:
        # with more than fr completions in ONE microstep (H > fr shards
        # under synchronized FIN-ACKs) slots would wrap WITHIN a single
        # scatter, and duplicate scatter indices have an unspecified
        # winner — masking ofs < n - fr keeps the indices unique (a
        # window of fr consecutive offsets maps injectively mod fr) and
        # preserves the ring's newest-overwrites-oldest contract. The
        # cursor still advances by n, so the collector counts exactly
        # these drops as wrap losses — nothing silent.
        live = mask & (ofs >= n - fr)
        idx = jnp.where(live, slot, jnp.int64(fr))  # others -> dropped
        row = jnp.stack(
            [host_gid, dst, fidx, t0, t1, fbytes, frtx], axis=1
        )  # [H, FLOW_COLS] i64, netobs.FLOW_FIELDS column order
        rows = rows.at[idx].set(row, mode="drop")
        cur = cur + n
    return FlowLedger(rows=rows[None], cursor=cur[None])


def _finish_microstep(
    cfg: EngineConfig, st: SimState, c: _EvCarry, queue, ob_entries,
    used_lats, flow_entries, host_gid, wheel=None,
):
    """Apply a microstep's accumulated outbox appends (one fused slab pass)
    and flow-ledger appends, fold the used-latency lookahead, and
    reassemble the SimState. `wheel` is the post-pop/post-push timer
    wheel on wheel-active programs (None otherwise — SimState.wheel
    stays None)."""
    outbox = st.outbox
    ob_lost = jnp.zeros((), jnp.int64)
    if ob_entries:
        outbox, n_lost = _outbox_append_multi(outbox, ob_entries)
        ob_lost = ob_lost + n_lost
        st = st._replace(
            min_used_lat=jnp.minimum(
                st.min_used_lat,
                jnp.min(jnp.stack([jnp.min(u) for u in used_lats])),
            )
        )
    if flow_entries:
        st = st._replace(
            flows=_flow_append(cfg, st.flows, host_gid, flow_entries)
        )
    stats = c.stats._replace(ob_dropped=c.stats.ob_dropped + ob_lost[None])
    return st._replace(
        queue=queue,
        wheel=wheel,
        rng=c.rng,
        seq=c.seq,
        sent_round=c.sent_round,
        tb_egress=c.tb_egress,
        tb_ingress=c.tb_ingress,
        codel=c.codel,
        model=c.model,
        outbox=outbox,
        stats=stats,
    )


def _microstep(cfg, model, st: SimState, params, host_gid, window_end,
               fluid_fx=None):
    """The single-event microstep (microstep_events = 1): pop each host's
    earliest event, execute, apply pushes and appends. `fluid_fx` is the
    round's loop-invariant fluid coupling factors (None when the fluid
    plane is off)."""
    # execution-time floor: the CPU model's busy horizon and/or the fault
    # plane's queue-hold restart time. A host floored past the window does
    # not pop at all; events stay in the queue so their (time, order)
    # sequence is preserved verbatim. An event popped while the floor is
    # *within* the window executes at the floor (host.rs:820-847 for the
    # CPU case; a crash restart is the same mechanics on a different
    # clock): rewrite ev.t to the execution time so every downstream
    # consumer (handler ctx, digest, pushes, egress departure) sees the
    # delayed clock, never a stale one. Both the floor and ev.t are
    # < window_end here, so the execution time stays inside the window.
    floor = None
    down_h = None
    if cfg.cpu_delay_ns > 0:
        floor = st.cpu_busy_until
    if cfg.fault_hold:
        # the down check reads the BUSY-FLOORED head time — the candidate
        # execution time — not the raw queue head: a CPU-delayed event
        # whose busy horizon lands inside a down window must defer to the
        # restart exactly as _effective_next (the barrier's view) says it
        # will. TIME_MAX heads stay TIME_MAX through the maximum.
        ht = q_next_time(st.queue)
        if cfg.wheel_active:
            # the candidate execution time is the COMBINED head: a due
            # wheel timer is the next event exactly like a queue head
            ht = jnp.minimum(ht, wheel_next_time(st.wheel))
        if floor is not None:
            ht = jnp.maximum(ht, floor)
        down_h, resume_h = down_and_resume(params.faults, ht)
        floor = resume_h if floor is None else jnp.maximum(floor, resume_h)
    limit = window_end
    if floor is not None:
        limit = jnp.where(floor < window_end, window_end, jnp.int64(0))
    if cfg.wheel_active:
        queue, wheel, ev, active = _pop_min_merged(st.queue, st.wheel, limit)
    else:
        queue, ev, active = q_pop_min(st.queue, limit)
        wheel = st.wheel
    if floor is not None:
        exec_t = jnp.maximum(ev.t, floor)
        ev = ev._replace(t=jnp.where(active, exec_t, ev.t))
        if cfg.cpu_delay_ns > 0:
            st = st._replace(
                cpu_busy_until=jnp.where(
                    active, exec_t + cfg.cpu_delay_ns, st.cpu_busy_until
                )
            )
        if cfg.fault_hold:
            # events executing at a crash restart (the head was inside a
            # down window) count as fault-delayed, charged to the host
            st = st._replace(
                stats=st.stats._replace(
                    faults_delayed=st.stats.faults_delayed + (active & down_h)
                )
            )

    if cfg.fault_clear:
        # queue-clear crash semantics: an event whose execution time falls
        # inside a down window is consumed (popped) but never dispatched —
        # no digest, no pushes, no sends; counted, never silent
        down_e, _ = down_and_resume(params.faults, ev.t)
        fdrop = active & down_e
        active = active & ~fdrop
        st = st._replace(
            stats=st.stats._replace(
                faults_dropped=st.stats.faults_dropped + fdrop
            )
        )

    c, push_list, ob_entries, used_lats, flow_entries = _event_body(
        cfg, model, _ev_carry_of(st), params, host_gid, window_end, ev,
        active, fluid_fx,
    )
    if cfg.wheel_active and push_list:
        # route model timer pushes to the wheel (spill-to-queue when
        # full); everything else — packets, app events, ingress
        # requeues, spills — stays queue-bound. Static pre-filter: the
        # requeue (first entry under shaping) is a packet by
        # construction, and models may declare which push PORTS can
        # carry timers (`timer_push_ports`, e.g. tgen's port_b) — the
        # other entries skip the wheel pass entirely.
        n_req = 1 if cfg.shaping else 0
        tports = getattr(model, "timer_push_ports", None)
        route_mask = [
            i >= n_req and (tports is None or (i - n_req) in tports)
            for i in range(len(push_list))
        ]
        push_list, push_w, spilled = _route_timer_pushes(
            cfg, wheel, push_list,
            tuple(getattr(model, "timer_kinds", ())), route_mask,
        )
        wheel = wheel_push_many(wheel, push_w)
        c = c._replace(
            stats=c.stats._replace(
                wheel_spilled=c.stats.wheel_spilled + spilled
            )
        )
    if push_list:
        queue = q_push_many(queue, push_list)
    return _finish_microstep(
        cfg, st, c, queue, ob_entries, used_lats, flow_entries, host_gid,
        wheel=wheel,
    )


def _lex_less(at, ao, bt, bo):
    """(at, ao) < (bt, bo) on the (time, order) total key."""
    return (at < bt) | ((at == bt) & (ao < bo))


def _pop_min_merged(queue, wheel, limit):
    """Pop each host's earliest event from queue ∪ wheel under the
    (time, order) total key — the wheel integration's dispatch-order
    exactness hinge: the winner is chosen by comparing the two heads
    (cache-cheap for the wheel and bucketed queues), then each structure
    runs its pop masked to the hosts it won, so exactly one event pops
    per active host and it is the same event the wheel-off path would
    pop from its single combined queue. Ties are impossible between live
    events (order keys are globally unique); the all-empty tie on the
    (TIME_MAX, ORDER_MAX) sentinels picks the wheel side, whose pop then
    does nothing (TIME_MAX is never < limit). Returns
    (queue', wheel', event, active)."""
    qt, qo = q_head(queue)
    wt, wo = q_head(wheel)
    q_wins = _lex_less(qt, qo, wt, wo)
    z = jnp.int64(0)
    queue2, ev_q, act_q = q_pop_min(queue, jnp.where(q_wins, limit, z))
    wheel2, ev_w, act_w = wheel_pop_min(wheel, jnp.where(q_wins, z, limit))
    ev = Event(
        t=jnp.where(act_w, ev_w.t, ev_q.t),
        order=jnp.where(act_w, ev_w.order, ev_q.order),
        kind=jnp.where(act_w, ev_w.kind, ev_q.kind),
        payload=jnp.where(act_w[:, None], ev_w.payload, ev_q.payload),
    )
    return queue2, wheel2, ev, act_q | act_w


def _route_timer_pushes(cfg: EngineConfig, wheel, push_list, timer_kinds,
                        route_mask=None):
    """Split a microstep's push list into queue-bound and wheel-bound
    entries. A push routes to the wheel iff it is a model timer event
    (no KIND_PKT flag, model kind in the STATIC timer_kinds tuple — the
    exact predicate the network observatory's ec_timer class uses) AND
    the wheel has a free slot left after this microstep's earlier wheel
    pushes; otherwise it stays queue-bound. Timer pushes that found the
    wheel full SPILL to the queue — behaviorally identical to the
    wheel-off path for that event (the pop merge re-derives the total
    order from wherever events sit), counted per host into
    stats.wheel_spilled, never silent. The running `taken` counter makes
    the free check exact across multiple wheel pushes in one microstep,
    so the wheel itself can never overflow (its `dropped` lane is an
    invariant zero — the sentinel's IV_COUNTER asserts it).

    `route_mask` is a per-entry STATIC list: False entries are known at
    trace time to never carry a timer (the ingress requeue — packets by
    construction — and model ports outside `timer_push_ports`), so they
    skip the classification AND the wheel's one-hot write pass entirely.
    Each skipped entry removes one [H, S]-shaped push pass per
    microstep, which is most of the wheel's routing overhead on models
    with several ports (tgen: 3 pushes, 1 possible timer).

    Returns (queue_pushes, wheel_pushes, spilled i64[H])."""
    free = wheel_free(wheel)  # [H] i32, post-pop occupancy
    taken = jnp.zeros_like(free)
    push_q, push_w = [], []
    spilled = jnp.zeros((free.shape[0],), jnp.int64)
    for i, push in enumerate(push_list):
        mask, t, order, kind, payload = push[:5]
        if route_mask is not None and not route_mask[i]:
            push_q.append(push)
            continue
        kind = jnp.asarray(kind, jnp.int32)
        is_timer = (
            mask
            & ((kind & KIND_PKT) == 0)
            & kind_in(kind & KIND_MASK, timer_kinds)
        )
        fits = is_timer & (taken < free)
        taken = taken + fits.astype(jnp.int32)
        spilled = spilled + (is_timer & ~fits)
        push_w.append((fits, t, order, kind, payload))
        push_q.append((mask & ~fits, t, order, kind, payload))
    return push_q, push_w, spilled


def _microstep_k(cfg, model, st: SimState, params, host_gid, window_end,
                 fluid_fx=None):
    """The K-way microstep (microstep_events = K > 1): peek each host's K
    earliest in-window events in ONE slab pass (`q_pop_k`), fold them
    through the model handler with a `lax.scan` over the batch (one traced
    copy of the handler for any K), then remove the executed prefix and
    apply ALL pushes and outbox appends in one fused pass each. Returns
    (state', executed[H]) — each host's executed count, the round loop's
    per-host event-denominated valve charge.

    Exactness guard (the reason this is bit-identical to K=1 by
    construction): batch event j+1 of a host executes only if no push this
    host emitted so far this microstep (model pushes AND ingress requeues)
    landed at an earlier (time, order) key — in K=1 that pushed event would
    pop before batch event j+1 — and, under the CPU model, only while the
    host's busy horizon stays inside the window (K=1 would stop popping).
    Deferral is monotone (the batch is key-sorted and push keys only
    accumulate), so execution is always a PREFIX of the batch; deferred
    events were only peeked, never removed, and re-pop next microstep in
    their original order.

    Drop exactness: pushes run AFTER the executed prefix is cleared, in
    K=1 chronological order (requeue_0, pushes_0, requeue_1, ...), each
    carrying a RESERVE equal to the number of batch events that executed
    after it (in K=1 those still occupied queue slots when the push
    landed) — see ops/events.py `_push_fields`. Outbox columns are cursor-
    assigned exactly as across separate microsteps."""
    k = cfg.effective_microstep_events
    h = st.queue.t.shape[0]
    if cfg.cpu_delay_ns > 0 or cfg.fault_hold:
        # combined execution floor at the HEAD event: CPU busy horizon
        # and/or crash-restart time (every peeked in-window event of a
        # down host shares the head's down window — the window extends to
        # >= window_end whenever the head is blocked — so head-time
        # gating is exact; within-window restarts are handled per batch
        # event below)
        floor0 = jnp.zeros((h,), jnp.int64)
        if cfg.cpu_delay_ns > 0:
            floor0 = jnp.maximum(floor0, st.cpu_busy_until)
        if cfg.fault_hold:
            # down check at the busy-floored head (the candidate execution
            # time) — same rule as _microstep and _effective_next
            _, resume0 = down_and_resume(
                params.faults,
                jnp.maximum(q_next_time(st.queue), floor0),
            )
            floor0 = jnp.maximum(floor0, resume0)
        limit = jnp.where(floor0 < window_end, window_end, jnp.int64(0))
    else:
        limit = window_end
    popped = q_pop_k(st.queue, limit, k)

    def fold(carry, x):
        # one batch event; traced ONCE for all K (a Python-unrolled batch
        # multiplied the handler's compile cost by K on the TPU)
        c, deferred, pm_t, pm_o, busy, fault_held, fault_drop = carry
        j, ev, active_j = x
        down_j = resume_j = None
        if cfg.fault_hold:
            # evaluated at the busy-floored event time (the candidate
            # execution time) so a mid-batch busy horizon that lands in a
            # down window defers exactly where K=1 would
            t_cand = (
                jnp.maximum(ev.t, busy) if cfg.cpu_delay_ns > 0 else ev.t
            )
            down_j, resume_j = down_and_resume(params.faults, t_cand)
        later = deferred | _lex_less(pm_t, pm_o, ev.t, ev.order)
        if cfg.cpu_delay_ns > 0:
            later = later | (busy >= window_end)
        if cfg.fault_hold:
            # a later batch event entering a down window whose restart
            # is past the horizon: K=1 would stop popping this host
            later = later | (down_j & (resume_j >= window_end))
        deferred = jnp.where(j > 0, later, deferred)
        cons_j = active_j & ~deferred
        if cfg.cpu_delay_ns > 0 or cfg.fault_hold:
            fl = busy if cfg.cpu_delay_ns > 0 else jnp.zeros((h,), jnp.int64)
            if cfg.fault_hold:
                fl = jnp.maximum(fl, jnp.where(down_j, resume_j, 0))
            exec_t = jnp.maximum(ev.t, fl)
            ev = ev._replace(t=jnp.where(cons_j, exec_t, ev.t))
            if cfg.cpu_delay_ns > 0:
                busy = jnp.where(cons_j, exec_t + cfg.cpu_delay_ns, busy)
            if cfg.fault_hold:
                fault_held = fault_held + (cons_j & down_j)
        exec_j = cons_j
        if cfg.fault_clear:
            # consumed but never dispatched — same contract as K=1. The
            # down check reads ev.t AFTER the CPU-busy rewrite above (the
            # EXECUTION time), exactly where the K=1 path evaluates it.
            down_x, _ = down_and_resume(params.faults, ev.t)
            fd = cons_j & down_x
            fault_drop = fault_drop + fd
            exec_j = cons_j & ~fd
        c, push_list, entries, lats, flows_j = _event_body(
            cfg, model, c, params, host_gid, window_end, ev, exec_j,
            fluid_fx,
        )
        # accumulate this event's push keys into the guard minimum AFTER
        # its own execution (an event's pushes cannot defer itself)
        for push in push_list:
            mask, p_t, p_o = push[0], jnp.asarray(push[1], jnp.int64), push[2]
            better = mask & _lex_less(p_t, p_o, pm_t, pm_o)
            pm_t = jnp.where(better, p_t, pm_t)
            pm_o = jnp.where(better, p_o, pm_o)
        out = jax.tree.map(
            jnp.asarray, (cons_j, push_list, entries, lats, flows_j)
        )
        return (c, deferred, pm_t, pm_o, busy, fault_held, fault_drop), out

    carry0 = (
        _ev_carry_of(st),
        jnp.zeros((h,), bool),
        jnp.full((h,), TIME_MAX, jnp.int64),  # earliest push key so far
        jnp.full((h,), ORDER_MAX, jnp.int64),
        st.cpu_busy_until,
        jnp.zeros((h,), jnp.int64),  # hold: events run at restart
        jnp.zeros((h,), jnp.int64),  # clear: events consumed+dropped
    )
    xs = (
        jnp.arange(k, dtype=jnp.int32),
        Event(
            t=popped.t[:, :k].T, order=popped.order[:, :k].T,
            kind=popped.kind[:, :k].T,
            payload=jnp.moveaxis(popped.payload[:, :k], 1, 0),
        ),
        popped.active[:, :k].T,
    )
    (c, _, _, _, busy, fault_held, fault_drop), per_j = lax.scan(
        fold, carry0, xs
    )
    cons = per_j[0]  # [K, H] bool: CONSUMED (cleared from queue)
    # unstack the per-event outputs in K=1 chronological order
    push_lists, ob_entries, used_lats, flow_entries = [], [], [], []
    for j in range(k):
        _, pl, en, la, fl = jax.tree.map(lambda x, j=j: x[j], per_j)
        push_lists.append(pl)
        ob_entries += en
        used_lats += la
        flow_entries += fl

    # consumed prefix length per host, and the per-push reserves
    cons_i32 = cons.astype(jnp.int32)
    m = jnp.sum(cons_i32, axis=0)  # [H] i32
    queue = q_clear_popped(st.queue, popped, m)
    # batch events consumed AFTER event j still held their slots when
    # event j's pushes landed in K=1
    reserves = m[None, :] - jnp.cumsum(cons_i32, axis=0)  # [K, H]
    all_pushes = []
    for j, push_list in enumerate(push_lists):
        all_pushes += [tuple(p) + (reserves[j],) for p in push_list]
    if all_pushes:
        queue = q_push_many(queue, all_pushes)

    n_deferred = jnp.sum((popped.active[:, :k] & ~cons.T).astype(jnp.int64))
    stats = c.stats._replace(
        popk_deferred=c.stats.popk_deferred + n_deferred[None]
    )
    if cfg.fault_hold:
        stats = stats._replace(
            faults_delayed=stats.faults_delayed + fault_held
        )
    if cfg.fault_clear:
        stats = stats._replace(
            faults_dropped=stats.faults_dropped + fault_drop
        )
    c = c._replace(stats=stats)
    if cfg.cpu_delay_ns > 0:
        st = st._replace(cpu_busy_until=busy)
    st = _finish_microstep(
        cfg, st, c, queue, ob_entries, used_lats, flow_entries, host_gid
    )
    return st, m


def exchange_ici_bytes_per_round(cfg: EngineConfig, kind: str | None = None) -> int:
    """Per-shard ICI bytes one exchange moves — the cost model written out
    in `_exchange_alltoall`'s docstring, as a checkable number.

    gather:   every shard RECEIVES the other (W-1) shards' whole outboxes:
              (W-1) x rows_local x row_bytes (+ the 4-byte count word),
              with rows_local = hosts_per_shard x sends_per_host_round and
              row_bytes = dst + t + order + kind + payload words.
    alltoall: every shard sends/receives (W-1) fixed blocks of
              `a2a_block_size` packed rows (1 dst word + the packed event,
              ops/merge._pack_words) — O(global sends / world) once blocks
              are sized to traffic instead of O(world-replicated) like the
              gather.
    hierarchical: the INTER tier of `exchange_tier_bytes_per_round` —
              (W-1) gear-aware blocks of `hier_block_size` packed rows
              plus the 4-byte i32 fill counter per peer. The intra tier
              (local compaction staging) is charged to `stats.ici_intra`
              only, never here: `ici_bytes` stays "bytes the exchange
              COLLECTIVE moves" across all three kinds.

    The engine charges exactly these numbers into `stats.ici_bytes` every
    round (the collectives run unconditionally, empty rounds included), so
    the counter is the model made observable: the multichip dryrun asserts
    counter == model x rounds, and on a real mesh the counter can be held
    against profiler ICI traffic to validate the model itself."""
    kind = kind or cfg.exchange
    if cfg.world <= 1:
        return 0
    # the gather collective moves the SLICED outbox, so a lower merge gear
    # shrinks ICI bytes too; the alltoall's fixed blocks are gear-invariant
    # (the gear trims its local sort input, not the wire format)
    rows_local = cfg.hosts_per_shard * cfg.effective_gear_cols
    row_bytes = 4 + 8 + 8 + 4 + 4 * EVENT_PAYLOAD_WORDS
    if kind == "gather":
        return (cfg.world - 1) * (rows_local * row_bytes + 4)
    if kind == "hierarchical":
        return exchange_tier_bytes_per_round(cfg)[1]
    packed_words = 1 + (2 + 2 + 1 + EVENT_PAYLOAD_WORDS)  # dst + packed event
    return (cfg.world - 1) * cfg.a2a_block_size * packed_words * 4


def exchange_tier_bytes_per_round(cfg: EngineConfig) -> tuple[int, int]:
    """(intra, inter) bytes the hierarchical exchange charges per round,
    per shard — the two-tier cost model as checkable numbers.

    intra: the compaction tier's staging traffic — every gear-sliced local
           outbox row (hosts_per_shard x effective_gear_cols) repacked
           once into the [world, k] block layout at packed width (1 dst
           word + the packed event, ops/merge._pack_words). HBM bytes,
           not wire: charged to `stats.ici_intra` so the weak-scaling
           bench can hold local-compaction work against wire savings.
    inter: the wire tier — (W-1) blocks of `hier_block_size` packed rows
           plus the 4-byte i32 fill counter per peer (the lane-diet wire
           element). Charged to `stats.ici_inter` AND `stats.ici_bytes`.

    Both tiers shrink with the merge gear (the flat alltoall's blocks are
    gear-invariant) — that delta is the hierarchical path's win, and
    `tests/test_hier.py` pins counter == model x rounds for both lanes."""
    if cfg.world <= 1:
        return 0, 0
    packed_words = 1 + (2 + 2 + 1 + EVENT_PAYLOAD_WORDS)
    rows_g = cfg.hosts_per_shard * cfg.effective_gear_cols
    intra = rows_g * packed_words * 4
    inter = (cfg.world - 1) * (cfg.hier_block_size * packed_words + 1) * 4
    return intra, inter


def _gear_sliced_outbox(cfg, axis, ob: Outbox, sent_round):
    """Truncate the outbox to the active merge gear's column width.

    Host h's k-th send of a round lands in lane column k (`_outbox_append`
    cursor layout), so when no host staged more than `gear_cols` sends the
    first `gear_cols` columns hold EVERY valid entry and the slice is
    exact — the downstream (dst, t, order) sort sees the same entry set in
    a host-major order that is monotone in the full-width flattening
    (identical selection even on the cheap-shed index-tiebreak path).
    Sends beyond the width are counted into the returned shed, psum'd so
    every shard carries the global value; the chunk loop aborts on the
    first nonzero delta and the driver replays from its pre-chunk snapshot
    one gear up, so a shed never reaches accepted results.

    Returns (outbox-view, shed | None); None means the full-width program
    (no slicing traced in at all)."""
    if not cfg.gear_active:
        return ob, None
    from shadow_tpu.ops.merge import gear_shed_count

    gc = cfg.gear_cols
    local = gear_shed_count(sent_round, gc)
    shed = _psum(local, axis)
    sliced = Outbox(
        dst=ob.dst[:, :gc],
        t=ob.t[:, :gc],
        order=ob.order[:, :gc],
        kind=ob.kind[:, :gc],
        payload=ob.payload[:, :gc, :],
        count=ob.count,
    )
    return sliced, shed


def _exchange(cfg, axis, st: SimState):
    if axis and cfg.exchange == "alltoall":
        return _exchange_alltoall(cfg, axis, st)
    if axis and cfg.exchange == "hierarchical":
        return _exchange_hierarchical(cfg, axis, st)
    ob_full = st.outbox
    ob, gear_shed = _gear_sliced_outbox(cfg, axis, ob_full, st.sent_round)
    if axis:
        with jax.named_scope("shadow_collective"):
            g = jax.tree.map(
                lambda a: lax.all_gather(a, axis, tiled=True),
                Outbox(ob.dst, ob.t, ob.order, ob.kind, ob.payload, ob.count),
            )
    else:
        g = ob
    h_local = st.queue.t.shape[0]
    shard_start = (
        lax.axis_index(axis).astype(jnp.int32) * h_local if axis else jnp.int32(0)
    )

    # flatten the [H, B] lanes host-major: entry order (and therefore
    # cheap-shed overflow selection) is identical for every mesh shape
    dst_f = g.dst.reshape(-1)
    t_f = g.t.reshape(-1)
    local = dst_f - shard_start
    valid = (t_f != TIME_MAX) & (local >= 0) & (local < h_local)
    flat = (
        local, t_f, g.order.reshape(-1), g.kind.reshape(-1),
        g.payload.reshape(-1, g.payload.shape[-1]), valid,
    )
    has_sends = jnp.sum(g.count) > 0
    with jax.named_scope("shadow_merge"):
        queue = _merge_into_queue(cfg, st.queue, flat, has_sends)
    stats = st.stats
    if gear_shed is not None:
        stats = stats._replace(gear_shed=stats.gear_shed + gear_shed[None])
    if axis:
        stats = stats._replace(
            ici_bytes=stats.ici_bytes
            + jnp.int64(exchange_ici_bytes_per_round(cfg, "gather"))[None]
        )
    if isinstance(st.queue, BucketQueue):
        stats = stats._replace(
            bq_rebuilds=stats.bq_rebuilds + has_sends.astype(jnp.int64)[None]
        )
    return st._replace(
        queue=queue,
        outbox=_fresh_outbox(ob_full),
        sent_round=jnp.zeros_like(st.sent_round),
        stats=stats,
    )


def _fresh_outbox(ob: Outbox) -> Outbox:
    return Outbox(
        dst=jnp.zeros_like(ob.dst),
        t=jnp.full_like(ob.t, TIME_MAX),
        order=jnp.zeros_like(ob.order),
        kind=jnp.zeros_like(ob.kind),
        payload=jnp.zeros_like(ob.payload),
        count=jnp.zeros_like(ob.count),
    )


def _merge_into_queue(cfg, queue0, flat, has_sends):
    """Insert flat (local, t, order, kind, payload, valid) rows, skipping
    the merge in empty rounds.

    A `BucketQueue` merges through its flat slab view and its block caches
    are rebuilt wholesale afterwards — the exchange merge is the one hot-path
    point where incremental maintenance is not worth it (a merge can touch
    every block). The rebuild sits under the same `has_sends` cond as the
    merge plan: its outputs are the small [H, C/B] cache planes, so the
    branch-boundary copies that rule out whole-slab conds do not apply, and
    empty rounds keep their caches for free.

    The merge's sort dominates round cost; rounds where NO shard sent
    anything (timer-heavy workloads, drained phases) skip it entirely —
    `has_sends` is identical on all shards, so the branch is uniform
    across the mesh. At capacity >= 48 the cond wraps only the PLAN
    (`merge_plan`): branches returning the whole queue forced XLA to copy
    every slab at the branch boundary each round — traced at ~55% of the
    PHOLD-torus round cost — while the plan is one [H, C, W] packed block.
    The apply then runs unconditionally as a single where-pass."""
    q_flat = as_flat(queue0)
    if cfg.queue_capacity < 48:
        # Fused merge inside the cond: it wins at SMALL slab capacities
        # (measured in round 5, on the old stack: PHOLD-torus cap 16 ran 40%
        # slower on the v5e with the plan split — the [H, C, W] plan
        # materialization costs more than the small branch-boundary copies
        # it avoids; at cap >= ~48 the copy volume dominates and the split
        # below won). The choice depends on the capacity alone, so every
        # backend runs the program the chip runs.
        merged = lax.cond(
            has_sends,
            lambda queue: merge_flat_events(
                queue, *flat, cfg.max_round_inserts
            ),
            lambda queue: queue,
            q_flat,
        )
    else:
        p_words = flat[4].shape[-1]
        # the cond consumes ONLY the time plane (free-slot source): feeding
        # the whole queue through would add a second consumer per slab and
        # reintroduce the branch-boundary copies this split removes
        take, gw, dropped_add = lax.cond(
            has_sends,
            lambda q_t: merge_plan(q_t, *flat, cfg.max_round_inserts),
            lambda q_t: merge_empty_plan(q_t, p_words),
            q_flat.t,
        )
        merged = merge_apply(q_flat, take, gw, dropped_add)
    if not isinstance(queue0, BucketQueue):
        return merged
    nb = queue0.bt.shape[1]
    bt, bo, bfill = lax.cond(
        has_sends,
        lambda to: block_minima(to[0], to[1], nb),
        lambda _to: (queue0.bt, queue0.bo, queue0.bfill),
        (merged.t, merged.order),
    )
    return BucketQueue(
        merged.t, merged.order, merged.kind, merged.payload, merged.dropped,
        bt, bo, bfill,
    )


def _exchange_alltoall(cfg, axis, st: SimState):
    """Destination-sharded exchange (VERDICT r4 #4): instead of replicating
    the whole outbox to every shard (O(world) ICI bytes and merge input per
    shard), sort the LOCAL outbox by destination shard and move fixed-width
    blocks with `lax.all_to_all`.

    Cost model (written out in BASELINE.md): with S = global sends/round
    and W = shard count, the gather exchange moves (W-1) x rows_local x
    row_bytes per shard over ICI and feeds W x rows_local rows into every
    shard's merge sort; this path moves ~rows_local x row_bytes and feeds
    ~rows_local rows — both O(S / W) for balanced traffic.

    Determinism: each destination shard's block takes its most urgent
    rows by (t, order), so when a block overflows the LATEST entries shed —
    the same contract as the merge — and the final per-queue insertion
    order is re-derived by the merge sort from (dst, t, order), identical
    to the gather path whenever nothing sheds (`stats.a2a_shed` counts
    sheds; size `a2a_block` so it stays zero).

    Block width: with the block left auto, k is the rung the chunk
    program was compiled at (`EngineConfig.a2a_rung`, picked by the
    driver's rung controller from `stats.a2a_pair_hwm`, the rows each
    pair actually sent) and the top rung is the auto size. Below the top
    a block overflow counts into the psum'd `stats.gear_shed`: the chunk
    aborts and is replayed at a rung that fits, so accepted chunks match
    the top rung bit for bit. At the top rung, and with an explicit
    `a2a_block`, overflow sheds into `a2a_shed` as before.

    Merge gears trim the LOCAL dst-shard sort input (the [H, B] lanes
    sliced to gear_cols columns) exactly like the gather path; the
    block width does not follow the gear."""
    ob_full = st.outbox
    ob, gear_shed = _gear_sliced_outbox(cfg, axis, ob_full, st.sent_round)
    h_local = st.queue.t.shape[0]
    world = cfg.world
    k = cfg.a2a_block_size
    my = lax.axis_index(axis).astype(jnp.int32)

    blocks, seg_len, shed = _dshard_pack_blocks(ob, h_local, world, k)

    with jax.named_scope("shadow_collective"):
        recv = lax.all_to_all(blocks, axis, split_axis=0, concat_axis=0)
    flat_rows = recv.reshape(world * k, -1)
    r_dst = flat_rows[:, 0]
    r_t, r_order, r_kind, r_payload = _unpack_words_rows(
        flat_rows[:, 1:], ob.payload.shape[-1]
    )
    local = r_dst - my * h_local
    r_valid = (r_t != TIME_MAX) & (local >= 0) & (local < h_local)
    flat = (local, r_t, r_order, r_kind, r_payload, r_valid)

    has_sends = _psum(jnp.sum(ob.count), axis) > 0
    with jax.named_scope("shadow_merge"):
        queue = _merge_into_queue(cfg, st.queue, flat, has_sends)
    stats = st.stats._replace(
        ici_bytes=st.stats.ici_bytes
        + jnp.int64(exchange_ici_bytes_per_round(cfg, "alltoall"))[None],
    )
    if cfg.a2a_rung_active:
        # below-top rung: overflow rides the abort-and-replay path
        rung_shed = _psum(shed, axis)
        gear_shed = rung_shed if gear_shed is None else gear_shed + rung_shed
    else:
        stats = stats._replace(a2a_shed=stats.a2a_shed + shed[None])
    if cfg.a2a_ladder:
        pair_hwm = jnp.max(seg_len).astype(jnp.int64)
        stats = stats._replace(
            a2a_pair_hwm=jnp.maximum(stats.a2a_pair_hwm, pair_hwm[None])
        )
    if gear_shed is not None:
        stats = stats._replace(gear_shed=stats.gear_shed + gear_shed[None])
    if isinstance(st.queue, BucketQueue):
        stats = stats._replace(
            bq_rebuilds=stats.bq_rebuilds + has_sends.astype(jnp.int64)[None]
        )
    return st._replace(
        queue=queue,
        outbox=_fresh_outbox(ob_full),
        sent_round=jnp.zeros_like(st.sent_round),
        stats=stats,
    )


def _dshard_pack_blocks(ob: Outbox, h_local: int, world: int, k: int):
    """Group the local outbox by destination shard and pack each group's
    `k` most urgent rows into fixed wire blocks.

    The shared front half of the flat alltoall AND the hierarchical
    exchange's intra-shard compaction tier — one definition of "compacted
    per-destination prefix" (ops/merge.dshard_segments does the grouping),
    so the two exchange kinds select bit-identical row sets for any given
    block width. Block j carries group j's k most urgent rows by (t,
    order); later rows shed (counted, never silent).

    Returns (blocks i32[world, k, 1 + packed], seg_len i32[world],
    shed i64[]) — `seg_len` is the per-destination valid-row count before
    truncation (the hierarchical path's fill-counter source), `shed` the
    local count of rows beyond `k`."""
    from shadow_tpu.ops.merge import dshard_segments

    dst_f = ob.dst.reshape(-1)
    t_f = ob.t.reshape(-1)
    order_f = ob.order.reshape(-1)
    kind_f = ob.kind.reshape(-1)
    payload_f = ob.payload.reshape(-1, ob.payload.shape[-1])
    valid = t_f != TIME_MAX
    dshard = jnp.where(valid, dst_f // h_local, world).astype(jnp.int32)

    s_idx, start, seg_len = dshard_segments(dshard, t_f, order_f, world, k)

    # pack rows (dst word + event words)
    words = jnp.concatenate(
        [
            dst_f[:, None].astype(jnp.int32),
            _pack_words_rows(t_f, order_f, kind_f, payload_f),
        ],
        axis=1,
    )

    # block j carries group j's k most urgent rows; later rows shed
    rr = jnp.arange(k, dtype=jnp.int32)
    in_seg = rr[None, :] < jnp.minimum(seg_len, k)[:, None]  # [world, k]
    src_pos = jnp.where(in_seg, start[:, None] + rr[None, :], 0)
    blocks = words[s_idx[src_pos]]  # [world, k, W+1]
    inval = _invalid_row(ob.payload.shape[-1])
    blocks = jnp.where(in_seg[:, :, None], blocks, inval[None, None, :])
    shed = jnp.sum(jnp.maximum(seg_len - k, 0), dtype=jnp.int64)
    return blocks, seg_len, shed


def _exchange_hierarchical(cfg, axis, st: SimState):
    """Two-tier exchange (ROADMAP item 2 — the million-host climb): an
    INTRA-shard compaction tier, then an INTER-shard alltoall that moves
    only the compacted prefixes.

    Tier 1 (intra-shard, no wire): the gear-sliced [H_local, gear] outbox
    is grouped by destination shard — `ops/merge.dshard_segments`, the
    exact machinery the flat alltoall uses — compacting this shard's sends
    into dense per-destination-shard prefixes of their most urgent rows. Charged to
    `stats.ici_intra` (staging-buffer traffic; obs/memory.py prices the
    buffers themselves).

    Tier 2 (inter-shard, the ICI wire): two collectives — the i32
    fill-counter vector `sent_counts` (the lane-diet wire element: bounded
    by `hier_block_size`, so i32 is provably lossless — core/lanes.py
    LANE_MIN_WIDTH_BITS and shadowlint R7 pin the bound) and the
    [world, k] packed blocks with k = `hier_block_size`. Charged to
    `stats.ici_inter` AND `stats.ici_bytes`.

    Where the wire shrinks vs the flat alltoall: `hier_block_size` derives
    from the GEAR-SLICED row count (hosts_per_shard x effective_gear_cols)
    where `a2a_block_size` is fixed at the full [H, B] row count — geared
    runs move proportionally smaller blocks. Gears off, the two block
    sizes coincide and the wire rows are identical.

    Exactness: local sort, urgency-order block selection, and merge input
    are identical to the flat alltoall whenever nothing sheds. Receive
    validity is derived from the counts AND the invalid-row time marker —
    identical truth sets by construction, so a counts-vs-payload drift
    surfaces as dropped rows the digest gate catches rather than phantom
    inserts. Block overflow on a geared run counts into `gear_shed`
    (psum'd): the chunk aborts and replays one gear up, and the TOP gear's
    k equals the flat k, so the ladder always has an exact escape; at full
    width overflow counts into `a2a_shed` exactly like the flat path.
    Digests, events, and every drop counter are therefore bit-identical to
    `alltoall` (tests/test_hier.py is the gate)."""
    ob_full = st.outbox
    ob, gear_shed = _gear_sliced_outbox(cfg, axis, ob_full, st.sent_round)
    h_local = st.queue.t.shape[0]
    world = cfg.world
    k = cfg.hier_block_size
    my = lax.axis_index(axis).astype(jnp.int32)

    with jax.named_scope("shadow_hier_intra"):
        blocks, seg_len, shed = _dshard_pack_blocks(ob, h_local, world, k)
    sent_counts = jnp.minimum(seg_len, k).astype(jnp.int32)

    with jax.named_scope("shadow_hier_inter"), jax.named_scope(
        "shadow_collective"
    ):
        recv_counts = lax.all_to_all(
            sent_counts, axis, split_axis=0, concat_axis=0
        )
        recv = lax.all_to_all(blocks, axis, split_axis=0, concat_axis=0)
    flat_rows = recv.reshape(world * k, -1)
    r_dst = flat_rows[:, 0]
    r_t, r_order, r_kind, r_payload = _unpack_words_rows(
        flat_rows[:, 1:], ob.payload.shape[-1]
    )
    local = r_dst - my * h_local
    rr = jnp.arange(world * k, dtype=jnp.int32)
    by_count = (rr % k) < recv_counts[rr // k]
    r_valid = by_count & (r_t != TIME_MAX) & (local >= 0) & (local < h_local)
    flat = (local, r_t, r_order, r_kind, r_payload, r_valid)

    has_sends = _psum(jnp.sum(ob.count), axis) > 0
    with jax.named_scope("shadow_merge"):
        queue = _merge_into_queue(cfg, st.queue, flat, has_sends)

    intra_b, inter_b = exchange_tier_bytes_per_round(cfg)
    stats = st.stats._replace(
        ici_bytes=st.stats.ici_bytes + jnp.int64(inter_b)[None],
        ici_intra=st.stats.ici_intra + jnp.int64(intra_b)[None],
        ici_inter=st.stats.ici_inter + jnp.int64(inter_b)[None],
    )
    if cfg.gear_active:
        # geared block overflow rides the gear-abort path, not a2a_shed:
        # the driver replays one gear up, whose wider k re-derives the
        # block — the exact-escape contract the docstring argues
        shed_g = _psum(shed, axis)
        stats = stats._replace(
            gear_shed=stats.gear_shed + (gear_shed + shed_g)[None]
        )
    else:
        stats = stats._replace(a2a_shed=stats.a2a_shed + shed[None])
    if isinstance(st.queue, BucketQueue):
        stats = stats._replace(
            bq_rebuilds=stats.bq_rebuilds + has_sends.astype(jnp.int64)[None]
        )
    return st._replace(
        queue=queue,
        outbox=_fresh_outbox(ob_full),
        sent_round=jnp.zeros_like(st.sent_round),
        stats=stats,
    )


def _pack_words_rows(t, order, kind, payload):
    from shadow_tpu.ops.merge import _pack_words

    return _pack_words(t, order, kind.astype(jnp.int32), payload)


def _unpack_words_rows(g, p_words):
    from shadow_tpu.ops.merge import _unpack_words

    return _unpack_words(g, p_words)


def _invalid_row(p_words: int):
    """A packed row whose unpack yields t == TIME_MAX (the empty marker)."""
    t = jnp.full((1,), TIME_MAX, jnp.int64)
    o = jnp.full((1,), ORDER_MAX, jnp.int64)
    row = _pack_words_rows(
        t, o, jnp.zeros((1,), jnp.int32), jnp.zeros((1, p_words), jnp.int32)
    )[0]
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), row])
