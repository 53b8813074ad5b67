"""Simulation driver: config -> graph -> engine -> run loop -> outputs.

This is the analogue of the reference's L5/L6 stack (SURVEY.md §1):
  - `SimConfig::new` (sim_config.rs:47): expand config into per-host specs,
    load the graph, assign IPs, compute routing.
  - `Controller::run` / `Manager::run` (controller.rs:40, manager.rs:219):
    build hosts, seed boot events, drive the round loop, merge stats, write
    `processed-config.yaml` (manager.rs:182-193) and `sim-stats.json`
    (manager.rs:544-546).
  - heartbeat logging (manager.rs:675-717) and the status-bar progress line
    (controller.rs:115-168, utility/status_bar.rs).

The scheduling loop itself is on-device (`core.engine`); this module only
decides how many jitted chunks to run and when to print. The reference's
equivalent of `chunks` is the Manager's `while window` loop — here each chunk
is `rounds_per_chunk` whole scheduling rounds fused into one device program,
which is the batching that amortizes dispatch latency (SURVEY.md §7 hard
part 5).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import yaml

from shadow_tpu.config.options import ConfigError, ConfigOptions
from shadow_tpu.core.engine import Engine, EngineConfig, EngineParams
from shadow_tpu.models.base import get_model
from shadow_tpu.net import TBParams
from shadow_tpu.net.graph import IpAssignment, NetworkGraph, load_graph
from shadow_tpu.simtime import NS_PER_SEC, TIME_MAX

MTU_BITS = 1500 * 8
UNLIMITED_BW = 1 << 40  # token-bucket params for "no bandwidth configured"


@dataclasses.dataclass
class HostSpec:
    """One simulated host after config expansion (reference HostInfo,
    sim_config.rs:168-192)."""

    host_id: int
    name: str
    node_index: int  # index into graph tables (NOT the GML id)
    ip: str
    bw_down_bits: int  # 0 = unlimited
    bw_up_bits: int
    model: str
    model_args: dict[str, Any]
    start_time: int
    shutdown_time: int | None
    pcap_enabled: bool
    pcap_capture_size: int
    # per-host TCP defaults for the CPU plane (reference HostDefaultOptions
    # socket buffer / autotune knobs); None on pure-device hosts
    tcp_cfg: Any = None
    # managed programs (hybrid/co-sim hosts): [{path, args, start_time, ...}]
    programs: list = dataclasses.field(default_factory=list)


class _ModeledPcap:
    """Per-round pcap synthesis for device-modeled sims: each captured
    round's pre-exchange outbox is pulled to the host and written as
    synthesized UDP frames (src host = lane row, arrival timestamp; ports
    are a deterministic synthesis — model events carry no transport
    header). Reference: pcap_writer.rs + network_interface.c capture."""

    def __init__(self, sim: "Simulation"):
        from shadow_tpu.host.sockets import NetPacket
        from shadow_tpu.obs.pcap import PcapWriter

        self._NetPacket = NetPacket
        self.step = sim.engine.build_capture_step()
        self._ips = [h.ip for h in sim.hosts]
        data_dir = sim.cfg.general.data_directory or "shadow_tpu.data"
        self.writers = {}
        for h in sim.hosts:
            if not h.pcap_enabled:
                continue
            host_dir = os.path.join(data_dir, "hosts", h.name)
            os.makedirs(host_dir, exist_ok=True)
            self.writers[h.host_id] = PcapWriter(
                os.path.join(host_dir, "eth0.pcap"), h.pcap_capture_size
            )

    def write_round(self, outbox):
        t = np.asarray(jax.device_get(outbox.t))
        if not (t < TIME_MAX).any():
            return
        dst = np.asarray(jax.device_get(outbox.dst))
        payload = np.asarray(jax.device_get(outbox.payload))
        n_hosts = len(self._ips)
        for src, col in zip(*np.nonzero(t < TIME_MAX)):
            d = int(dst[src, col])
            if not (0 <= d < n_hosts):
                continue
            size = int(payload[src, col, 0])  # PAYLOAD_SIZE_WORD
            pkt = self._NetPacket(
                src_ip=self._ips[int(src)],
                src_port=40000,
                dst_ip=self._ips[d],
                dst_port=40000,
                proto=17,  # synthesized as UDP
                payload=b"\x00" * max(0, min(size, 65000)),
            )
            ts = int(t[src, col])
            w = self.writers.get(int(src))
            if w is not None:
                w.write(ts, pkt)  # egress (timestamped at arrival: the
                # outbox stores only the delivery time)
            w = self.writers.get(d)
            if w is not None:
                w.write(ts, pkt)  # ingress at the destination

    def close(self):
        for w in self.writers.values():
            w.close()


def _resolve_host_basics(cfg: ConfigOptions, graph: NetworkGraph):
    """Shared per-host resolution for both expanders: stable name order,
    manual-IPs-first assignment (graph/mod.rs:370), graph node lookup, and
    graph-bandwidth fallback. Yields (host_id, host_options, node, ip,
    bw_down, bw_up)."""
    ips = IpAssignment()
    ordered = sorted(cfg.hosts, key=lambda h: h.name)
    # the reference requires a self-loop on every graph node
    # (graph/mod.rs:210-216); enforce it where it matters — a node carrying
    # >= 2 hosts with an unreachable diagonal can never route same-node
    # traffic, which is a config error, not per-packet drops
    hosts_per_node: dict[int, int] = {}
    for h in ordered:
        n = graph.node_index(h.network_node_id)
        hosts_per_node[n] = hosts_per_node.get(n, 0) + h.count
    for n, cnt in sorted(hosts_per_node.items()):
        if cnt >= 2 and graph.lat_ns[n, n] < 0:
            raise ConfigError(
                f"graph node {int(graph.node_ids[n])} hosts {cnt} hosts but "
                f"has no self-loop edge: same-node traffic cannot route "
                f"(the reference requires a self-loop per node)"
            )
    for i, h in enumerate(ordered):
        if h.ip_addr is not None:
            ips.assign_manual(i, h.ip_addr)
    for i, h in enumerate(ordered):
        if not h.processes:
            raise ConfigError(f"host {h.name!r} has no processes")
        node = graph.node_index(h.network_node_id)
        if h.ip_addr is None:
            ips.assign(i)
        bw_down = h.bandwidth_down if h.bandwidth_down is not None else int(
            graph.bw_down_bits[node]
        )
        bw_up = h.bandwidth_up if h.bandwidth_up is not None else int(
            graph.bw_up_bits[node]
        )
        yield i, h, node, ips.ip_of(i), bw_down, bw_up


def expand_hosts(cfg: ConfigOptions, graph: NetworkGraph) -> list[HostSpec]:
    """Config hosts -> HostSpecs with IPs and node indices resolved.

    Hosts are sorted by name for a config-order-independent host-id mapping
    (the reference shuffles hosts for scheduler balance, manager.rs:272 —
    sharding here is by contiguous id range, so a stable order is what keeps
    runs reproducible across config reorderings)."""
    specs: list[HostSpec] = []
    for i, h, node, ip, bw_down, bw_up in _resolve_host_basics(cfg, graph):
        dev_models = [p for p in h.processes if p.model is not None]
        if len(dev_models) != 1:
            raise ConfigError(
                f"host {h.name!r}: exactly one device-model process per host "
                f"is supported (got {len(dev_models)})"
            )
        p = dev_models[0]
        specs.append(
            HostSpec(
                host_id=i,
                name=h.name,
                node_index=node,
                ip=ip,
                bw_down_bits=bw_down,
                bw_up_bits=bw_up,
                model=p.model,
                model_args=dict(p.model_args),
                start_time=p.start_time,
                shutdown_time=p.shutdown_time,
                pcap_enabled=h.host_options.pcap_enabled,
                pcap_capture_size=h.host_options.pcap_capture_size,
            )
        )
    return specs


def config_is_hybrid(cfg: ConfigOptions) -> bool:
    """True if any host runs managed programs (`path:`) instead of models."""
    return any(p.path is not None for h in cfg.hosts for p in h.processes)


def expand_hosts_hybrid(cfg: ConfigOptions, graph: NetworkGraph) -> list[HostSpec]:
    """Config -> specs for co-simulation. Program hosts (`path:`) run on
    CpuHosts behind the hybrid device proxy; model hosts (`model:`) run
    fully on device — a MIXED simulation shares one device network between
    both planes (models/mixed.py)."""
    from shadow_tpu.programs import PROGRAM_REGISTRY

    specs: list[HostSpec] = []
    for i, h, node, ip, bw_down, bw_up in _resolve_host_basics(cfg, graph):
        model_procs = [p for p in h.processes if p.model is not None]
        if model_procs:
            if len(h.processes) != 1:
                raise ConfigError(
                    f"host {h.name!r}: a modeled host runs exactly one "
                    f"model process (got {len(h.processes)} processes)"
                )
            p = model_procs[0]
            # loud rejection instead of silent intent-dropping: the mixed
            # plane does not (yet) honor these on modeled lanes
            if p.shutdown_time is not None:
                raise ConfigError(
                    f"host {h.name!r}: shutdown_time on a modeled host in a "
                    f"mixed simulation is not supported"
                )
            if h.host_options.pcap_enabled:
                raise ConfigError(
                    f"host {h.name!r}: pcap on a modeled host in a mixed "
                    f"simulation is not supported (model packets carry no "
                    f"bytes; enable pcap on the program hosts instead)"
                )
            specs.append(
                HostSpec(
                    host_id=i, name=h.name, node_index=node, ip=ip,
                    bw_down_bits=bw_down, bw_up_bits=bw_up,
                    model=p.model, model_args=dict(p.model_args),
                    start_time=p.start_time, shutdown_time=None,
                    pcap_enabled=False,
                    pcap_capture_size=h.host_options.pcap_capture_size,
                    programs=[],
                )
            )
            continue
        for p in h.processes:
            if "/" in p.path:
                # real binary for the native managed-process plane
                if not os.path.exists(p.path):
                    raise ConfigError(
                        f"host {h.name!r}: binary {p.path!r} not found"
                    )
            elif p.path not in PROGRAM_REGISTRY:
                raise ConfigError(
                    f"host {h.name!r}: unknown program {p.path!r}; "
                    f"available: {sorted(PROGRAM_REGISTRY)} "
                    f"(use a path containing '/' for a real binary)"
                )
        specs.append(
            HostSpec(
                host_id=i,
                name=h.name,
                node_index=node,
                ip=ip,
                bw_down_bits=bw_down,
                bw_up_bits=bw_up,
                model="hybrid",
                model_args={},
                start_time=0,
                shutdown_time=None,
                pcap_enabled=h.host_options.pcap_enabled,
                pcap_capture_size=h.host_options.pcap_capture_size,
                tcp_cfg=h.host_options.tcp_config(),
                programs=[
                    {
                        "path": p.path,
                        "args": _program_args(p),
                        "argv_raw": list(p.args),  # verbatim argv (native bins)
                        "environment": dict(p.environment),
                        "start_time": p.start_time,
                        "shutdown_time": p.shutdown_time,
                        "expected_final_state": p.expected_final_state,
                    }
                    for p in h.processes
                ],
            )
        )
    return specs


def _program_args(p) -> dict:
    """Program args: `args: ["key=value", ...]` entries become a dict; the
    reference passes argv strings the same way (ProcessOptions.args)."""
    out: dict[str, Any] = {}
    for a in p.args:
        if "=" in a:
            k, v = a.split("=", 1)
            out[k] = v
        else:
            out.setdefault("_argv", []).append(a)
    out.update({f"env_{k}": v for k, v in p.environment.items()})
    return out


def build_simulation(cfg: ConfigOptions, **kw):
    """Factory: modeled sims -> `Simulation` (device-only, mesh-scalable);
    program sims -> `HybridSimulation` (CPU plane + device network)."""
    if config_is_hybrid(cfg):
        from shadow_tpu.cosim import HybridSimulation

        return HybridSimulation(cfg, **kw)
    return Simulation(cfg, **kw)


def _tb_params(bws: np.ndarray, interval_ns: int) -> TBParams:
    """Bandwidth -> token bucket: refill = bits per interval, burst capacity =
    refill + one MTU (reference relay/token_bucket.rs: 1ms refill quantum with
    an MTU burst allowance, relay/mod.rs:276-319)."""
    unlimited = bws <= 0
    per_itv = np.maximum(bws * interval_ns // NS_PER_SEC, 1)
    refill = np.where(unlimited, UNLIMITED_BW, per_itv).astype(np.int64)
    cap = np.where(unlimited, UNLIMITED_BW, per_itv + MTU_BITS).astype(np.int64)
    return TBParams(capacity=jnp.asarray(cap), refill=jnp.asarray(refill))


def resolve_world(parallelism: int) -> int:
    """0 = all local devices (reference: 0 = all cores, configuration.rs)."""
    avail = jax.device_count()
    if parallelism <= 0:
        return avail
    if parallelism > avail:
        raise ConfigError(
            f"general.parallelism={parallelism} exceeds {avail} available devices"
        )
    return parallelism


def heartbeat_line(
    now_ns: int,
    wall: float,
    events: int,
    microsteps: int,
    rounds: int,
    ici_bytes: int,
    q_hwm: int,
    *,
    xw: tuple[int, int] | None = None,
    fault: tuple[int, int] | None = None,
    gear: int | None = None,
    cap: int | None = None,
    hbm: int | None = None,
    ek: tuple[int, int] | None = None,
    fct: int | None = None,
    bg: tuple[int, int] | None = None,
    iv: tuple[int, int] | None = None,
    rt: float | None = None,
    rep: tuple[int, int] | None = None,
) -> str:
    """The `[heartbeat]` progress line, shared by the Simulation run loop
    and the campaign driver so tools/parse_shadow.py has ONE format to
    track. Optional fields ride along in a fixed order (faults, gear,
    cap, hbm, rep, then ratio); lines without them are byte-identical to
    the older formats, which the parser keeps reading (gated by
    literal-line tests). `cap` is the ACTIVE per-host queue capacity on
    pressure-plane runs (escalation regrows it mid-run); `hbm` is the
    per-shard HBM high-water in bytes (memory observatory runs —
    obs/memory.py, the reference's per-host allocated-memory heartbeat);
    `rep` is (replicas done, total) on ensemble campaign runs; `ek` is
    (timer events, packet events) and `fct` the flows completed so far —
    both only on network-observatory runs (obs/netobs.py); `bg` is
    (background bytes delivered, background bytes dropped) — only on
    fluid-traffic-plane runs (net/fluid.py); `iv` is
    (transient SDC survived, sentinel replays) — only on
    integrity-sentinel runs (core/integrity.py); `rt` is the LAST
    chunk's realtime factor (sim-s/wall-s) — only on runtime-observatory
    runs (obs/runtime.py; unlike `ratio=`, which is the run-cumulative
    average, `rt=` is the fresh per-chunk number the serving posture
    tracks); `xw` is (intra-shard compaction bytes, inter-shard wire
    bytes), cumulative — only on hierarchical-exchange runs
    (core/engine.py _exchange_hierarchical; it rides right after q_hwm=,
    before faults=, matching HEARTBEAT_RE's position anchor)."""
    xw_f = f"xw={xw[0]}/{xw[1]} " if xw is not None else ""
    fault_f = f"faults={fault[0]}/{fault[1]} " if fault is not None else ""
    gear_f = f"gear={gear} " if gear is not None else ""
    cap_f = f"cap={cap} " if cap is not None else ""
    hbm_f = f"hbm={hbm} " if hbm is not None else ""
    ek_f = f"ek={ek[0]}/{ek[1]} " if ek is not None else ""
    fct_f = f"fct={fct} " if fct is not None else ""
    bg_f = f"bg={bg[0]}/{bg[1]} " if bg is not None else ""
    iv_f = f"iv={iv[0]}/{iv[1]} " if iv is not None else ""
    rt_f = f"rt={rt:.2f} " if rt is not None else ""
    rep_f = f"rep={rep[0]}/{rep[1]} " if rep is not None else ""
    return (
        f"[heartbeat] sim_time={now_ns / NS_PER_SEC:.3f}s "
        f"wall={wall:.2f}s events={events} "
        f"rounds={rounds} "
        f"msteps/round={microsteps / max(rounds, 1):.1f} "
        f"ev/mstep={events / max(microsteps, 1):.2f} "
        f"ici_bytes={ici_bytes} q_hwm={q_hwm} "
        f"{xw_f}"
        f"{fault_f}"
        f"{gear_f}"
        f"{cap_f}"
        f"{hbm_f}"
        f"{ek_f}"
        f"{fct_f}"
        f"{bg_f}"
        f"{iv_f}"
        f"{rt_f}"
        f"{rep_f}"
        f"ratio={now_ns / NS_PER_SEC / max(wall, 1e-9):.2f}x "
        f"{resource_heartbeat()}"
    )


class Simulation:
    """Built simulation: engine + host specs + run loop."""

    def __init__(self, cfg: ConfigOptions, *, world: int | None = None):
        self.cfg = cfg
        self.graph = load_graph(cfg.network.graph)
        self.hosts = expand_hosts(cfg, self.graph)
        if not self.hosts:
            raise ConfigError("config defines no hosts")
        models = {h.model for h in self.hosts}
        if len(models) != 1:
            raise ConfigError(
                f"all hosts must run one device model per simulation for "
                f"vectorized dispatch; got {sorted(models)}"
            )
        self.model = get_model(models.pop())()

        ex = cfg.experimental
        world = resolve_world(cfg.general.parallelism) if world is None else world
        # pad the host count to a multiple of the mesh size with inert hosts
        # (empty queues never activate; the digest ignores them)
        self._num_real = len(self.hosts)
        num_hosts = -(-self._num_real // world) * world
        qcap, send_budget, rpc = ex.resolve_shapes(num_hosts)
        # fault plane (core/faults.py): compile the seeded schedule into
        # device arrays + the static dims the round body specializes on.
        # Churn draws run over the real-host prefix only, so the schedule
        # is invariant to mesh padding.
        from shadow_tpu.core.faults import FaultSchedule, compile_faults

        try:
            self._fault_sched = (
                compile_faults(
                    cfg.faults,
                    num_hosts=num_hosts,
                    num_real=self._num_real,
                    stop_time=cfg.general.stop_time,
                    bootstrap_end=cfg.general.bootstrap_end_time,
                    default_seed=cfg.general.seed,
                    name_to_id={h.name: h.host_id for h in self.hosts},
                )
                if cfg.faults.injecting
                else FaultSchedule(0, 0, False, None)
            )
        except ValueError as e:
            raise ConfigError(f"faults: {e}") from e
        if self._fault_sched.active and ex.scheduler == "cpu-reference":
            raise ConfigError(
                "faults: the cpu-reference scheduler does not model the "
                "fault plane; run the tpu scheduler or drop the faults block"
            )
        # fluid traffic plane (net/fluid.py): compile the background
        # classes onto the graph's node space. Zone ids are GML node ids,
        # resolved through the same graph.node_index the hosts use.
        from shadow_tpu.net.fluid import FluidSchedule, compile_fluid

        try:
            self._fluid_sched = (
                compile_fluid(
                    cfg.fluid,
                    num_links=int(self.graph.lat_ns.shape[0]),
                    default_seed=cfg.general.seed,
                    zone_of=self.graph.node_index,
                )
                if cfg.fluid.active
                # inactive: every knob pinned to the EngineConfig
                # DEFAULTS (not general.seed etc.) — a fluid-off config
                # must produce the identical EngineConfig regardless of
                # seed, or ensemble replicas differing only in seed
                # would fail static reconciliation
                else FluidSchedule(0, 0, 50_000_000, 0.7, 0.0, 2000, 1,
                                   None)
            )
        except (ValueError, KeyError) as e:
            raise ConfigError(f"fluid: {e}") from e
        if self._fluid_sched.active and ex.scheduler == "cpu-reference":
            raise ConfigError(
                "fluid: the cpu-reference scheduler does not model the "
                "fluid traffic plane; run the tpu scheduler or drop the "
                "fluid block"
            )
        # pressure plane (core/pressure.py): validated here so every
        # unsupported combination fails at build, not mid-run
        press = cfg.pressure
        if press.active:
            if ex.scheduler == "cpu-reference":
                raise ConfigError(
                    "pressure: the cpu-reference scheduler does not model "
                    "the pressure plane; run the tpu scheduler or keep "
                    "policy: drop"
                )
            if any(h.pcap_enabled for h in self.hosts):
                raise ConfigError(
                    "pressure: escalate/abort are not supported with pcap "
                    "capture (the single-round capture loop has no "
                    "snapshot-replay seam); disable pcap or keep "
                    "policy: drop"
                )
        # integrity sentinel (core/integrity.py): validated at build so
        # unsupported combinations fail loudly, not mid-run
        if cfg.integrity.enabled:
            if ex.scheduler == "cpu-reference":
                raise ConfigError(
                    "integrity: the cpu-reference scheduler does not "
                    "model the sentinel's in-jit guards; run the tpu "
                    "scheduler or disable the integrity block"
                )
            if any(h.pcap_enabled for h in self.hosts):
                raise ConfigError(
                    "integrity: the sentinel is not supported with pcap "
                    "capture (the single-round capture loop has no "
                    "snapshot-replay seam for quarantine-and-replay); "
                    "disable pcap or the integrity block"
                )
        if press.policy == "escalate":
            if ex.a2a_block > 0:
                raise ConfigError(
                    "pressure: escalate cannot cure an explicit "
                    "a2a_block's sheds (the resized programs scale only "
                    "the AUTO block with the send budget) — drop "
                    "a2a_block (auto-sizing follows escalation) or keep "
                    "policy: drop/abort"
                )
            if press.max_capacity and press.max_capacity < qcap:
                raise ConfigError(
                    f"pressure.max_capacity={press.max_capacity} is below "
                    f"the configured queue capacity {qcap}"
                )
            if press.max_outbox and press.max_outbox < send_budget:
                raise ConfigError(
                    f"pressure.max_outbox={press.max_outbox} is below the "
                    f"configured send budget {send_budget}"
                )
        # timer wheel (ops/wheel.py): validated here so a model with no
        # timer_kinds fails at config parse, not engine build
        if ex.timer_wheel and not tuple(
            getattr(self.model, "timer_kinds", ())
        ):
            raise ConfigError(
                f"experimental.timer_wheel: model {self.model.name!r} "
                f"declares no timer_kinds — nothing would route to the "
                f"wheel; drop the knob or use a model with timers"
            )
        self.engine_cfg = EngineConfig(
            num_hosts=num_hosts,
            stop_time=cfg.general.stop_time,
            bootstrap_end_time=cfg.general.bootstrap_end_time,
            runahead_floor=ex.runahead,
            static_min_latency=max(self.graph.min_latency_ns_opt or 0, 1),
            use_jitter=self.graph.has_jitter,
            use_dynamic_runahead=ex.use_dynamic_runahead,
            use_codel=ex.use_codel,
            queue_capacity=qcap,
            queue_block=ex.event_queue_block,
            sends_per_host_round=send_budget,
            max_round_inserts=ex.max_round_inserts or qcap,
            rounds_per_chunk=rpc,
            microstep_limit=ex.microstep_limit,
            microstep_events=ex.microstep_events,
            world=world,
            # exact elision: with no bandwidth limits anywhere, token buckets
            # and CoDel are provable no-ops (see EngineConfig.shaping)
            shaping=any(
                h.bw_up_bits > 0 or h.bw_down_bits > 0 for h in self.hosts
            ),
            cpu_delay_ns=ex.cpu_delay,
            exchange=ex.resolve_exchange(world),
            a2a_block=ex.a2a_block,
            # round tracer ring sized to the chunk length: the run loop
            # drains at every chunk boundary, so the ring can never wrap
            trace_rounds=rpc if cfg.observability.trace else 0,
            # network observatory (obs/netobs.py): event classes + safe
            # window ride the knob; the flow ledger only for models that
            # declare a flow port (tgen) — other models carry no ring
            netobs=cfg.observability.network,
            flow_records=(
                cfg.observability.network_flows
                if cfg.observability.network
                and getattr(self.model, "flow_ledger", False)
                else 0
            ),
            fault_crash_windows=self._fault_sched.crash_windows,
            fault_loss_windows=self._fault_sched.loss_windows,
            fault_queue_clear=self._fault_sched.queue_clear,
            # pressure plane: escalate/abort trace the first-drop abort
            # condition into the chunk loop; drop (default) leaves the
            # program bit-identical to the pre-pressure engine
            pressure_abort=press.active,
            # integrity sentinel: per-round invariant guards + the
            # first-violation abort condition; OFF traces zero sentinel
            # code (the default program stays byte-identical)
            integrity=cfg.integrity.enabled,
            integrity_dual=cfg.integrity.enabled and cfg.integrity.dual_digest,
            # timer wheel (ops/wheel.py): off by default — the default
            # program stays byte-identical (jaxpr fingerprints are the gate)
            wheel_slots=ex.timer_wheel,
            wheel_block=ex.timer_wheel_block,
            # fluid traffic plane (net/fluid.py): zero classes (the
            # default) traces no fluid code — the program stays
            # byte-identical to the fluid-free engine
            fluid_classes=self._fluid_sched.classes,
            fluid_links=self._fluid_sched.links,
            fluid_tau_ns=self._fluid_sched.tau_ns,
            fluid_util_threshold=self._fluid_sched.util_threshold,
            fluid_loss_max=self._fluid_sched.loss_max,
            fluid_lat_max_x1000=self._fluid_sched.lat_max_x1000,
            fluid_seed=self._fluid_sched.seed,
        )
        # occupancy-adaptive merge gears (core/gears.py): resolved against
        # the (possibly auto-sized) send budget; [] = disabled
        from shadow_tpu.core.gears import resolve_gear_ladder

        try:
            self._gear_ladder = resolve_gear_ladder(ex.merge_gears, send_budget)
        except ValueError as e:
            raise ConfigError(f"experimental.merge_gears: {e}") from e
        self._gearctl = None  # built per run()
        # alltoall block rungs (core/gears.BlockRungController): built by
        # the first run() and kept, so later runs start at its rung
        self._rungctl = None
        self._pressctl = None  # ResilienceController when pressure is active
        self._pipeline = None  # the last run()'s core/pipeline.ChunkPipeline
        self._ob_hwm_run = 0  # run-wide outbox high-water (gear runs reset
        # the device counter per chunk, so the run max is tracked here)
        mesh = None
        if world > 1:
            mesh = jax.sharding.Mesh(np.array(jax.devices()[:world]), ("hosts",))
        self.engine = Engine(self.engine_cfg, self.model, mesh)
        # runtime observatory (obs/runtime.py): the compile ledger hooks
        # the engine's program caches BEFORE the first dispatch so the
        # base program's cold compile is recorded. Host-side only.
        self._rt_compiles = None
        if cfg.observability.runtime:
            from shadow_tpu.obs.runtime import CompileLedger

            self._rt_compiles = CompileLedger()
            self.engine.attach_compile_ledger(self._rt_compiles)
        self._build_state()

    # ---- build ------------------------------------------------------------

    def _model_hosts(self) -> list[dict]:
        return [
            {
                "host_id": h.host_id,
                "name": h.name,
                "start_time": h.start_time,
                "shutdown_time": h.shutdown_time,
                "ip": h.ip,
                "model_args": h.model_args,
            }
            for h in self.hosts
        ]

    def _pad(self, tree):
        """Pad model [H_real, ...] arrays to the engine's H_total."""
        pad = self.engine_cfg.num_hosts - self._num_real

        def f(a):
            a = np.asarray(a)
            if pad == 0:
                return jnp.asarray(a)
            width = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
            return jnp.asarray(np.pad(a, width))

        return jax.tree.map(f, tree)

    def _build_state(self):
        cfg, ecfg = self.cfg, self.engine_cfg
        try:
            mparams, mstate, events = self.model.build(
                self._model_hosts(), cfg.general.seed
            )
        except (ValueError, KeyError) as e:
            raise ConfigError(f"model {self.model.name!r}: {e}") from e
        node_of = np.zeros((ecfg.num_hosts,), np.int32)
        bw_up = np.zeros((ecfg.num_hosts,), np.int64)
        bw_down = np.zeros((ecfg.num_hosts,), np.int64)
        for h in self.hosts:
            node_of[h.host_id] = h.node_index
            bw_up[h.host_id] = h.bw_up_bits
            bw_down[h.host_id] = h.bw_down_bits
        params = EngineParams(
            node_of=jnp.asarray(node_of),
            lat_ns=jnp.asarray(self.graph.lat_ns),
            loss=jnp.asarray(self.graph.loss),
            jitter_ns=jnp.asarray(self.graph.jitter_ns),
            eg_tb=_tb_params(bw_up, ecfg.tb_interval_ns),
            in_tb=_tb_params(bw_down, ecfg.tb_interval_ns),
            model=self._pad(mparams),
            faults=self._fault_sched.params,
            fluid=self._fluid_sched.params,
        )
        padded_state = self._pad(mstate)
        # kept for the cpu-reference scheduler path (golden engine inputs)
        self._golden_inputs = (params, padded_state, events)
        self.state, self.params = self.engine.init_state(
            params, padded_state, events, seed=cfg.general.seed
        )

    # ---- run --------------------------------------------------------------

    def run(self, *, progress: bool | None = None, log=sys.stderr) -> dict:
        """Drive chunks until done. Returns the final stats report dict."""
        cfg = self.cfg
        if cfg.experimental.scheduler == "cpu-reference":
            return self._run_golden()
        show_progress = cfg.general.progress if progress is None else progress
        hb_ns = cfg.general.heartbeat_interval
        t0 = time.monotonic()
        next_hb = hb_ns
        capture = self._pcap_capture_begin()
        simlog = None
        if cfg.general.log_file:
            from shadow_tpu.obs import SimLogger

            path = cfg.general.log_file
            if not os.path.isabs(path):
                path = os.path.join(cfg.general.data_directory, path)
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            simlog = SimLogger(path, level=cfg.general.log_level)
        tracer = None
        if self.engine_cfg.trace_rounds:
            from shadow_tpu.obs import RoundTracer

            tracer = RoundTracer(self.engine_cfg.trace_rounds)
            # a restored checkpoint (or a prior run()) leaves rows in the
            # ring; start draining from the current cursor, not zero
            tracer.sync_cursor(self.state.trace)
            self._tracer = tracer
        flowcol = None
        if self.engine_cfg.flow_ledger_active:
            # network observatory's flow ledger (obs/netobs.py): drained
            # at the same chunk boundaries as the trace ring, with the
            # same checkpoint-resume cursor adoption (pre-snapshot
            # records are never replayed as fresh completions)
            from shadow_tpu.obs.netobs import FlowCollector

            flowcol = FlowCollector(self.engine_cfg.flow_records)
            flowcol.sync_cursor(self.state.flows)
            self._flowcol = flowcol

        def _drain_flows():
            if flowcol is not None:
                n = flowcol.drain(self.state.flows)
                if n and tracer is not None:
                    # this drain's records feed the Perfetto flow track
                    tracer.note_flows(flowcol.last_drained)
        profiling = bool(cfg.observability.profile_dir)
        if profiling:
            os.makedirs(cfg.observability.profile_dir, exist_ok=True)
            jax.profiler.start_trace(cfg.observability.profile_dir)
        monitor = None
        if cfg.observability.memory:
            # HBM observatory (obs/memory.py): per-shard live sampling at
            # chunk boundaries. Host-side observer only — the traced
            # programs are byte-identical with this on or off.
            from shadow_tpu.obs.memory import MemoryMonitor, modeled_shard_bytes

            devs = (
                list(self.engine.mesh.devices.flat)
                if self.engine.mesh is not None
                else [jax.devices()[0]]
            )
            monitor = MemoryMonitor(devs)
            self._memmon = monitor
            # the modeled fallback, recomputed per sample: escalation
            # regrows the state's shapes mid-run
            self._modeled_shard_bytes = lambda: modeled_shard_bytes(
                self.state, self.params, self.engine_cfg.world
            )
        # runtime observatory (obs/runtime.py): the wall-clock
        # attribution plane. Per-chunk spans (dispatch / export /
        # snapshot / replay / compile, residual = host_python) plus a
        # per-chunk realtime-factor series feeding the `rt=` heartbeat
        # field. Host-side observer only.
        from shadow_tpu.obs.runtime import chunk_span, span_or_null

        wallled = None
        if cfg.observability.runtime:
            from shadow_tpu.obs.runtime import WallLedger

            wallled = WallLedger()
            wallled.sync_sim(int(self.state.now))
            self._wallled = wallled
            if self._rt_compiles is not None:
                # compiles recorded mid-chunk reattribute their seconds
                # out of the enclosing dispatch span
                self._rt_compiles.wall = wallled
        gearctl = None
        resilience = None
        pressure_on = cfg.pressure.active
        integrity_on = cfg.integrity.enabled
        rungctl = self._block_rungs() if capture is None else None
        if (
            self._gear_ladder or rungctl is not None or pressure_on
            or integrity_on
        ) and capture is None:
            # the shared snapshot-replay seam (core/pressure.py): adaptive
            # merge gears dispatch at the width the controller picked from
            # last chunk's outbox-send high-water — a shed (exact, in-jit)
            # discards the chunk and replays it one gear up — and the
            # pressure plane's escalate policy regrows queue/outbox shapes
            # and replays at the first capacity drop, so accepted chunks
            # are bit-identical to full width at the final shape. The
            # alltoall block rungs ride the same seam. The capture path
            # stays full-width, top-rung and fixed-shape: its single-round
            # dispatches re-sync every round anyway (pressure policies are
            # rejected with capture at build time).
            from shadow_tpu.core.gears import GearController
            from shadow_tpu.core.pressure import ResilienceController

            gearctl = (
                GearController(self._gear_ladder) if self._gear_ladder
                else None
            )
            self._gearctl = gearctl
            reshard = None
            if self.engine.mesh is not None:
                specs = jax.tree.map(
                    lambda s: jax.sharding.NamedSharding(self.engine.mesh, s),
                    self.engine.state_specs(),
                )
                reshard = lambda st: jax.device_put(st, specs)  # noqa: E731
            memguard = None
            if pressure_on and monitor is not None:
                # memory-informed escalation: predicted-vs-measured rung
                # admission BEFORE dispatch (obs/memory.py MemoryGuard;
                # inert until a sample measures an allocator limit)
                from shadow_tpu.obs.memory import MemoryGuard

                memguard = MemoryGuard(
                    self.engine_cfg, monitor,
                    safety_factor=cfg.pressure.memory_safety_factor,
                )
            resilience = ResilienceController(
                gearctl=gearctl,
                rungctl=rungctl,
                rung_top=lambda b: dataclasses.replace(
                    self.engine_cfg, sends_per_host_round=b
                ).a2a_auto_block,
                pressure=cfg.pressure if pressure_on else None,
                integrity=cfg.integrity if integrity_on else None,
                queue_block=self.engine_cfg.queue_block,
                reshard=reshard,
                log=log,
                memory=memguard,
                wall=wallled,
            )
            self._pressctl = resilience if pressure_on else None
            self._resil = resilience
            # test-only SDC-injection seam (tests/test_integrity.py):
            # a hook set on the Simulation before run() rides into the
            # controller's post-snapshot/pre-dispatch slot
            resilience.test_scribble = getattr(
                self, "_integrity_test_scribble", None
            )
        sup = None
        if cfg.faults.supervisor.enabled and capture is None:
            # crash-resilient supervisor (core/supervisor.py): periodic
            # device snapshots + retry-with-backoff on dispatch failure +
            # graceful abort that still exports the completed prefix. The
            # capture path keeps its single-round dispatches unsupervised
            # (pcap writes are host-side effects a replay would duplicate).
            from shadow_tpu.core.checkpoint import save_checkpoint
            from shadow_tpu.core.supervisor import ChunkSupervisor

            so = cfg.faults.supervisor
            ckpt = so.checkpoint_file
            if ckpt is not None:
                if not os.path.isabs(ckpt):
                    ckpt = os.path.join(cfg.general.data_directory, ckpt)
                os.makedirs(os.path.dirname(ckpt) or ".", exist_ok=True)

            def _save(path, snap_state):
                # save_checkpoint dumps sim.state: point it at the
                # supervisor's snapshot for the write, then restore the
                # binding (the old reference may hold donated buffers)
                prev = self.state
                self.state = snap_state
                try:
                    return save_checkpoint(path, self)
                finally:
                    self.state = prev

            sup = ChunkSupervisor(
                snapshot_every_chunks=so.snapshot_every_chunks,
                max_retries=so.max_retries,
                backoff_base_s=so.backoff_base_ms / 1000.0,
                checkpoint_path=ckpt,
                save_fn=_save if ckpt else None,
                log=log,
                memory=monitor,
                memory_modeled_fn=(
                    self._modeled_shard_bytes if monitor is not None
                    else None
                ),
                wall=wallled,
            )
            self._supervisor = sup
            sup.note_state(self.state)
        chunks = 0
        from shadow_tpu.core.pipeline import ChunkPipeline

        if resilience is not None:
            def dispatch(s, g, c, b, k=0):
                return self.engine.run_chunk_resized(s, self.params, g, c, b, k)
        else:
            def dispatch(s):
                return self.engine.run_chunk(s, self.params)
        # lag 1 (the next chunk queued before this one's summary is
        # read) unless something between chunks reads the whole state:
        # then lag 0, the same loop with the read before the dispatch
        whole_state = (
            capture is not None or sup is not None or tracer is not None
            or flowcol is not None or monitor is not None
            or wallled is not None
            or (pressure_on and cfg.pressure.policy == "abort")
        )
        pipe = ChunkPipeline(
            dispatch, self.engine.chunk_summary, seam=resilience,
            lag=0 if whole_state else 1,
        )
        self._pipeline = pipe

        def _policy_abort(e, t_chunk, kind="pressure"):
            # a policy stopped the run. Pressure-abort exports the
            # dropping state itself (the honest record — the drop is in
            # the counters); escalate-cornered and integrity-abort
            # export the last good pre-chunk snapshot (an integrity
            # violation's state is by definition corrupt — exporting it
            # would be the poison this plane exists to catch; the
            # report names the violated invariant instead). Either way
            # the artifacts cover exactly what the exported state saw.
            print(f"[{kind}] aborting run: {e}", file=log)
            good = resilience.abort_export_state()
            if good is not None:
                self.state = good
            if tracer is not None:
                jax.block_until_ready(self.state)
                tracer.drain(
                    self.state.trace,
                    wall_t0=t_chunk, wall_t1=time.monotonic(),
                )
                tracer.truncate_to_round(int(self.state.stats.rounds))
            if flowcol is not None:
                # drained records beyond the exported state's own ledger
                # cursor cover rounds the artifacts do not — drop them
                # (FlowCollector.truncate_to_cursor docs this), then
                # re-seat the trace's flow track on the kept record set
                jax.block_until_ready(self.state)
                _drain_flows()
                flowcol.truncate_to_cursor(
                    np.asarray(jax.device_get(self.state.flows.cursor))
                )
                if tracer is not None:
                    tracer.reset_flows(flowcol.records())
            if kind == "integrity":
                self._integrity_aborted = True
                if tracer is not None and resilience is not None and (
                    resilience.iv_deterministic is not None
                ):
                    tracer.note_violation(resilience.iv_deterministic)
            else:
                self._pressure_aborted = True

        from shadow_tpu.core.integrity import IntegrityAbort
        from shadow_tpu.core.pressure import PressureAbort

        def _heartbeat(s, wall):
            # every number but the host's own comes from the accepted
            # chunk's summary, read once for the whole boundary
            ecfg = self.engine_cfg
            # faults= rides along only when the fault plane is
            # active, gear= only on adaptive runs (old-format
            # lines stay byte-identical; parse_shadow reads both)
            # xw= rides along only on hierarchical-exchange runs:
            # cumulative (intra compaction, inter wire) tier bytes
            xw = (s.ici_intra, s.ici_inter) if ecfg.hier_active else None
            fault = (
                (s.faults_dropped, s.faults_delayed)
                if ecfg.faults_active else None
            )
            # cap= rides along only on pressure-plane runs (the
            # ACTIVE capacity — escalation regrows it mid-run)
            cap = self.state.queue.t.shape[1] if pressure_on else None
            # hbm= rides along only on memory-observatory runs:
            # the per-shard HBM high-water so far (bytes)
            hbm = (
                monitor.hwm_bytes() if monitor is not None else None
            )
            # ek= (timer/packet event counts) and fct= (flows
            # completed) ride along only on network-observatory
            # runs (fct only when a flow ledger is active)
            ek = (s.ec_timer, s.ec_pkt) if ecfg.netobs else None
            fct = s.fl_done if ecfg.flow_ledger_active else None
            # bg= rides along only on fluid-traffic-plane runs:
            # cumulative background bytes delivered/dropped
            bg = (
                (s.fl_bg_bytes, s.fl_bg_dropped)
                if ecfg.fluid_active else None
            )
            # iv= rides along only on integrity-sentinel runs:
            # (transient SDC survived, sentinel replays) so far
            iv = (
                (resilience.iv_transients, resilience.iv_replays)
                if integrity_on and resilience is not None else None
            )
            # rt= rides along only on runtime-observatory runs:
            # the LAST chunk's realtime factor (sim-s/wall-s)
            rt = (
                wallled.rt_last if wallled is not None else None
            )
            gear = pipe.gear if gearctl is not None else None
            print(
                heartbeat_line(
                    s.now, wall, s.events, s.microsteps, s.rounds,
                    s.ici_bytes, s.q_occ_hwm,
                    xw=xw, fault=fault, gear=gear, cap=cap,
                    hbm=hbm, ek=ek, fct=fct, bg=bg, iv=iv, rt=rt,
                ),
                file=log,
            )
            if simlog is not None:
                simlog.info(
                    s.now, "manager",
                    f"heartbeat events={s.events} rounds={s.rounds}",
                )

        s = None  # the summary of the last accepted chunk
        try:
            with span_or_null(wallled, "run"):
                while True:
                    with chunk_span(chunks):
                        if s is None:
                            s = pipe.start(self.state)
                        if s.done:
                            break
                        t_chunk = time.monotonic()
                        if wallled is not None:
                            wallled.chunk_start()
                        if capture is not None:
                            with span_or_null(wallled, "dispatch"):
                                self.state, sent = capture.step(
                                    self.state, self.params
                                )
                                if wallled is not None:
                                    jax.block_until_ready(self.state)
                            with span_or_null(wallled, "export"):
                                capture.write_round(sent)
                            s = pipe.start(self.state)
                        elif sup is not None:
                            from shadow_tpu.core.supervisor import (
                                SupervisorAbort,
                            )

                            try:
                                with span_or_null(wallled, "dispatch"):
                                    self.state = sup.run_chunk(
                                        self.state, pipe.run_one
                                    )
                                s = pipe.last
                            except IntegrityAbort as e:
                                _policy_abort(e, t_chunk, kind="integrity")
                                break
                            except PressureAbort as e:
                                _policy_abort(e, t_chunk)
                                break
                            except SupervisorAbort as e:
                                # graceful abort: export the completed
                                # prefix from the supervisor's snapshot,
                                # not the in-hand state (abort_export_state
                                # docs the poisoned/donation rationale)
                                print(
                                    f"[supervisor] aborting run: {e}",
                                    file=log,
                                )
                                good = sup.abort_export_state()
                                if good is not None:
                                    self.state = good
                                    if tracer is not None:
                                        # chunks that succeeded AFTER the
                                        # snapshot were already drained;
                                        # drop their rows so the trace
                                        # covers exactly the exported
                                        # prefix (truncate_to_round docs
                                        # this)
                                        tracer.truncate_to_round(
                                            int(self.state.stats.rounds)
                                        )
                                    if flowcol is not None:
                                        # same contract for flow records:
                                        # the exported state's ledger
                                        # cursor is the truth of what its
                                        # prefix completed — and the
                                        # trace's flow track re-seats on
                                        # the kept record set
                                        flowcol.truncate_to_cursor(
                                            np.asarray(jax.device_get(
                                                self.state.flows.cursor
                                            ))
                                        )
                                        if tracer is not None:
                                            tracer.reset_flows(
                                                flowcol.records()
                                            )
                                self._aborted = True
                                break
                        else:
                            try:
                                with span_or_null(wallled, "dispatch"):
                                    pipe.launch()
                                    if wallled is not None:
                                        # the observatory's dispatch span
                                        # holds the chunk's device time
                                        # and the seam's replays (lag 0)
                                        s = pipe.settle()
                                        jax.block_until_ready(pipe.state)
                                if wallled is None:
                                    s = pipe.settle()
                                self.state = pipe.state
                            except IntegrityAbort as e:
                                _policy_abort(e, t_chunk, kind="integrity")
                                break
                            except PressureAbort as e:
                                _policy_abort(e, t_chunk)
                                break
                        if resilience is not None:
                            self._ob_hwm_run = max(
                                self._ob_hwm_run, resilience.ob_hwm_run
                            )
                            if rungctl is not None:
                                self.engine.select_a2a_rung(rungctl.rung)
                        with span_or_null(wallled, "export"):
                            if tracer is not None:
                                # pair the drained rounds with the true
                                # wall span of this dispatch (block: async
                                # dispatch would pin the span to enqueue
                                # time, not device time)
                                jax.block_until_ready(self.state)
                                tracer.drain(
                                    self.state.trace,
                                    wall_t0=t_chunk, wall_t1=time.monotonic(),
                                )
                            if flowcol is not None:
                                jax.block_until_ready(self.state)
                                _drain_flows()
                            if monitor is not None:
                                t_s = time.monotonic()
                                shard_bytes = monitor.sample(
                                    modeled_bytes=(
                                        self._modeled_shard_bytes()
                                    ),
                                    wall_t=t_s,
                                )
                                if tracer is not None:
                                    tracer.note_memory(t_s, shard_bytes)
                        chunks += 1
                        now_ns = s.now
                        if wallled is not None:
                            # close the chunk (heartbeat/progress
                            # printing below lands in the NEXT chunk's
                            # host_python residual)
                            wallled.chunk_end(now_ns)
                        wall = time.monotonic() - t0
                        if hb_ns and now_ns >= next_hb:
                            with span_or_null(wallled, "heartbeat"):
                                _heartbeat(s, wall)
                            next_hb = (now_ns // hb_ns + 1) * hb_ns
                        if show_progress:
                            pct = min(100.0, 100.0 * now_ns / max(
                                cfg.general.stop_time, 1))
                            print(f"\rprogress: {pct:5.1f}% ", end="",
                                  file=log, flush=True)
        finally:
            if profiling:
                jax.profiler.stop_trace()
        if show_progress:
            print(file=log)
        if capture is not None:
            capture.close()
        self._wall_seconds = time.monotonic() - t0
        self._chunks = chunks
        if simlog is not None:
            simlog.info(
                int(self.state.now), "manager",
                f"simulation done chunks={chunks}",
            )
            simlog.close()
        return self.stats_report()

    def _block_rungs(self):
        """The alltoall block-rung controller for this run, seated on the
        current state, or None where the block cannot leave its top rung
        (no flat alltoall on a mesh, an explicit `a2a_block`, or a ladder
        with no rung below the auto size)."""
        ecfg = self.engine_cfg
        if not ecfg.a2a_ladder:
            return None
        if self._rungctl is None:
            from shadow_tpu.core.gears import BlockRungController

            ctl = BlockRungController(ecfg.a2a_auto_block)
            if len(ctl.ladder) < 2:
                return None
            self._rungctl = ctl
        # pending events on the fullest shard, spread over the world: what
        # one pair can be asked to carry in the run's first round
        pending = jnp.sum(
            (self.state.queue.t < TIME_MAX).reshape(ecfg.world, -1), axis=1
        )
        fullest = int(np.asarray(jax.device_get(pending)).max())
        per_pair = -(-fullest // ecfg.world)
        self.engine.select_a2a_rung(self._rungctl.begin_run(per_pair))
        return self._rungctl

    def _pcap_capture_begin(self):
        """When any host has pcap_enabled, switch the run loop to captured
        single-round dispatches and open per-host eth0.pcap writers (the
        modeled-sim analogue of the reference's per-interface capture; the
        co-sim plane captures real packets, here frames are synthesized
        from packet events). Returns None when no host captures."""
        specs = [h for h in self.hosts if h.pcap_enabled]
        if not specs:
            return None
        return _ModeledPcap(self)

    def _run_golden(self) -> dict:
        """`experimental.scheduler: cpu-reference` — run the independent
        pure-Python golden engine instead of the device engine (the
        reference's two-scheduler determinism capability, src/test/
        determinism 2a/2b vs 2c: scheduler choice must not change results).
        """
        from shadow_tpu.core.golden import run_golden

        params, mstate, events = self._golden_inputs
        t0 = time.monotonic()
        gold = run_golden(
            self.engine_cfg, self.model, params, mstate, events,
            seed=self.cfg.general.seed,
        )
        self._wall_seconds = time.monotonic() - t0
        self._chunks = 0
        self._golden = gold
        n = self._num_real
        sim_s = gold.now / NS_PER_SEC
        self._golden_report = {
            "simulated_seconds": sim_s,
            "wall_seconds": self._wall_seconds,
            "sim_wall_ratio": sim_s / max(self._wall_seconds, 1e-9),
            "scheduler": "cpu-reference",
            "rounds": gold.rounds,
            "microsteps": gold.microsteps,
            "events_processed": int(gold.stats["events"][:n].sum()),
            "packets_sent": int(gold.stats["pkts_sent"][:n].sum()),
            "packets_delivered": int(gold.stats["pkts_delivered"][:n].sum()),
            "packets_lost": int(gold.stats["pkts_lost"][:n].sum()),
            "packets_unreachable": int(gold.stats["pkts_unreachable"][:n].sum()),
            "packets_codel_dropped": int(
                gold.stats["pkts_codel_dropped"][:n].sum()
            ),
            "queue_overflow_dropped": int(gold.stats["dropped"][:n].sum()),
            "packets_budget_dropped": int(
                gold.stats["pkts_budget_dropped"][:n].sum()
            ),
            "outbox_overflow_dropped": 0,  # golden has no staging outbox
            "monotonic_violations": int(
                gold.stats["monotonic_violations"][:n].sum()
            ),
            "determinism_digest": f"{int(np.bitwise_xor.reduce(gold.digests[:n])):016x}",
            "model_report": self.model.report(
                jax.tree.map(lambda a: np.asarray(a)[:n], gold.model_state),
                self._model_hosts(),
            ),
        }
        return self._golden_report

    # ---- outputs ----------------------------------------------------------

    def stats_report(self) -> dict:
        """sim-stats content (reference sim_stats.rs counters + tracker.c)."""
        if getattr(self, "_golden_report", None) is not None:
            return self._golden_report  # cpu-reference run: device state unused
        s = jax.device_get(self.state.stats)
        n = self._num_real
        wall = getattr(self, "_wall_seconds", None)
        sim_s = int(self.state.now) / NS_PER_SEC
        report = {
            "simulated_seconds": sim_s,
            "wall_seconds": wall,
            "sim_wall_ratio": (sim_s / wall) if wall else None,
            "rounds": int(s.rounds),
            "microsteps": int(np.asarray(s.microsteps).sum()),
            "events_processed": int(s.events[:n].sum()),
            "packets_sent": int(s.pkts_sent[:n].sum()),
            "packets_delivered": int(s.pkts_delivered[:n].sum()),
            "packets_lost": int(s.pkts_lost[:n].sum()),
            "packets_unreachable": int(s.pkts_unreachable[:n].sum()),
            "packets_codel_dropped": int(s.pkts_codel_dropped[:n].sum()),
            "queue_overflow_dropped": int(
                np.asarray(jax.device_get(self.state.queue.dropped))[:n].sum()
            ),
            "packets_budget_dropped": int(s.pkts_budget_dropped[:n].sum()),
            "faults_dropped": int(s.faults_dropped[:n].sum()),
            "faults_delayed": int(s.faults_delayed[:n].sum()),
            "outbox_overflow_dropped": int(np.asarray(s.ob_dropped).sum()),
            # alltoall block-overflow sheds: structurally zero when
            # a2a_block is sized right — exported so a mis-sized block is
            # visible in sim-stats, not only in test asserts
            "alltoall_shed_dropped": int(np.asarray(s.a2a_shed).sum()),
            "bucket_cache_rebuilds": int(np.asarray(s.bq_rebuilds).sum()),
            "popk_deferred": int(np.asarray(s.popk_deferred).sum()),
            "ici_bytes": int(np.asarray(s.ici_bytes).sum()),
            "queue_occupancy_hwm": int(s.q_occ_hwm[:n].max()) if n else 0,
            # always-on: the most sends any one host staged in a round.
            # Gear runs reset the device counter per chunk (the controller
            # needs a fresh signal), so fold in the Python-tracked run max.
            "outbox_send_hwm": max(
                int(np.asarray(s.outbox_hwm).max()), self._ob_hwm_run
            ),
            "monotonic_violations": int(s.monotonic_violations[:n].sum()),
            "determinism_digest": f"{int(np.bitwise_xor.reduce(s.digest[:n])):016x}",
            "model_report": self.model.report(
                jax.tree.map(lambda a: np.asarray(a)[:n], jax.device_get(self.state.model)),
                self._model_hosts(),
            ),
        }
        if self.engine_cfg.hier_active:
            # hierarchical-exchange block (core/engine.py
            # _exchange_hierarchical): the two-tier byte split. intra is
            # compaction staging traffic (stays on-shard, HBM-side);
            # inter is what actually crossed the ICI — the same number
            # ici_bytes above carries, broken out so trend tooling
            # (bench rows, tools/bench_compare.py) can guard the
            # inter-shard tier against regressing toward the flat cost.
            report["exchange"] = {
                "kind": "hierarchical",
                "block": self.engine_cfg.hier_block_size,
                "ici_intra_bytes": int(np.asarray(s.ici_intra).sum()),
                "ici_inter_bytes": int(np.asarray(s.ici_inter).sum()),
            }
        if self.engine_cfg.wheel_active:
            # timer-wheel block (ops/wheel.py): occupancy high-water +
            # spill count — the slot-sizing signal (tools/bench_wheel.py
            # sweeps S; tools/net_report.py breaks this out in its
            # verdict). wheel_dropped is an invariant zero (spill
            # routing pre-empts overflow; the sentinel guards it).
            report["wheel"] = {
                "slots": self.engine_cfg.wheel_slots,
                "block": self.state.wheel.block,
                "occupancy_hwm": int(s.wheel_occ_hwm[:n].max()) if n else 0,
                "spilled": int(s.wheel_spilled[:n].sum()),
                "dropped": int(
                    np.asarray(
                        jax.device_get(self.state.wheel.dropped)
                    )[:n].sum()
                ),
            }
        if self._pipeline is not None:
            # the driver loop's chunks, how many of them had their
            # successor queued before they were read (lag 1), and the
            # chunks in flight thrown away for a replay
            report["chunk_pipeline"] = self._pipeline.report()
        if self._gearctl is not None:
            report["gears"] = self._gearctl.report()
        if self._rungctl is not None:
            # the run's chunks per alltoall block rung, its replays, and
            # the most rows one (source, destination) shard pair sent in a
            # round
            report["a2a_rungs"] = self._rungctl.report()
        if self._pressctl is not None:
            rc = self._pressctl
            report["pressure"] = {
                **rc.report(),
                # the shapes the run ENDED at (escalation regrows them;
                # fixed-shape runs echo the configured values)
                "capacity": self.state.queue.t.shape[1],
                "outbox": self.state.outbox.t.shape[1],
                "base_capacity": self.engine_cfg.queue_capacity,
                "base_outbox": self.engine_cfg.sends_per_host_round,
            }
            # flat counters for trend tooling (bench rows, parse_shadow
            # consumers) — same numbers as the block above
            report["pressure_regrows"] = (
                rc.regrows + rc.proactive_regrows
            )
            report["pressure_replays"] = rc.replays
            if getattr(self, "_pressure_aborted", False):
                report["pressure_aborted"] = True
                report["aborted"] = True
        if self.engine_cfg.integrity:
            # integrity sentinel block (core/integrity.py): the
            # transient/replay accounting — the documented scribble
            # waves as counted, survived events — plus the second
            # digest fold (the dual lane that makes a scribble on the
            # digest plane itself classifiable,
            # core/integrity.classify_digest_pair) and, after an
            # IntegrityAbort, the deterministic violation's naming.
            rc = getattr(self, "_resil", None)
            block: dict[str, Any] = (
                rc.integrity_report() if rc is not None
                else {
                    "transients": 0,
                    "replays": 0,
                    "max_replays": self.cfg.integrity.max_replays,
                }
            )
            if self.engine_cfg.integrity_dual:
                block["determinism_digest2"] = (
                    f"{int(np.bitwise_xor.reduce(np.asarray(s.digest2)[:n])):016x}"
                )
            report["integrity"] = block
            if getattr(self, "_integrity_aborted", False):
                report["integrity_aborted"] = True
                report["aborted"] = True
        if self.engine_cfg.netobs:
            # network observatory block (obs/netobs.py): event classes,
            # safe-window critical path, flow ledger, per-link fold —
            # assembled by the ONE shared helper (bench rows and the
            # hybrid driver use the same one, so the block's shape
            # cannot drift between exporters). The gated stats lanes
            # (ec_* / fl_* / win_bound) are read inside it and listed in
            # lanes.STATS_EXPORT_EXEMPT with that export path recorded.
            from shadow_tpu.obs.netobs import (
                assemble_network_report, node_map,
            )

            report["network"] = assemble_network_report(
                stats=s,
                num_real=n,
                rounds=int(s.rounds),
                node_of=node_map(self.hosts, n),
                model=self.model,
                model_state=self._model_host_view(),
                flow_ledger=self.engine_cfg.flow_ledger_active,
                collector=getattr(self, "_flowcol", None),
            )
        if self.engine_cfg.fluid_active:
            # fluid traffic plane block (net/fluid.py): the background
            # byte accounting and final link-utilization view, assembled
            # by the ONE shared helper (bench rows use the same one, so
            # the block's shape cannot drift between exporters). The
            # gated fl_bg_* stats lanes are read inside it and listed in
            # lanes.STATS_EXPORT_EXEMPT with that export path recorded.
            from shadow_tpu.net.fluid import assemble_fluid_report

            report["fluid"] = assemble_fluid_report(
                stats=s,
                fluid_state=jax.device_get(self.state.fluid),
                cfg=self.engine_cfg,
            )
        memmon = getattr(self, "_memmon", None)
        if memmon is not None:
            # HBM observatory block (obs/memory.py): static byte model +
            # per-rung compiled ledger + per-shard live high-water
            from shadow_tpu.obs.memory import observatory_report

            report["memory"] = observatory_report(
                self.engine, self.state, self.params, memmon,
                ledger=self.cfg.observability.memory_ledger,
            )
        if self.cfg.observability.runtime:
            # runtime observatory block (obs/runtime.py): per-span
            # wall attribution + realtime-factor series + the compile
            # ledger — assembled by the ONE shared helper (the hybrid
            # driver and bench rows use the same one, so the block's
            # shape cannot drift between exporters)
            from shadow_tpu.obs.runtime import assemble_runtime_report

            report["runtime"] = assemble_runtime_report(
                wall=getattr(self, "_wallled", None),
                compiles=getattr(self, "_rt_compiles", None),
                total_wall_s=wall,
            )
        sup = getattr(self, "_supervisor", None)
        if sup is not None:
            report["supervisor"] = sup.report()
        if getattr(self, "_aborted", False):
            # bounded retries exhausted: everything above describes the
            # COMPLETED prefix (the supervisor's last good snapshot) —
            # unless the snapshot was poisoned, which the top-level flag
            # makes impossible to miss
            report["aborted"] = True
            if sup is not None and sup.poisoned:
                report["poisoned"] = True
        tracer = getattr(self, "_tracer", None)
        if tracer is not None:
            # tracing opted in: the per-host planes are cheap relative to
            # the trace itself, so the full vectors ride along (gated —
            # a 1M-host untraced sim must not grow MB-scale JSON)
            report["trace"] = tracer.summary()
            report["per_host"] = {
                "events_processed": [int(x) for x in s.events[:n]],
                "queue_occupancy_hwm": [int(x) for x in s.q_occ_hwm[:n]],
            }
        return report

    def _model_host_view(self):
        """The model state as a host-side tree sliced to the real hosts,
        fetched ONCE per device state (memoized on the state's model
        pytree identity): stats_report's network block and
        write_outputs' host-stats extras both read it, and the transfer
        is the whole model state — at the million-host scale, paying it
        twice per report is real traffic."""
        st = self.state.model
        cached = getattr(self, "_model_view_cache", None)
        if cached is not None and cached[0] is st:
            return cached[1]
        view = jax.tree.map(
            lambda a: np.asarray(a)[: self._num_real], jax.device_get(st)
        )
        self._model_view_cache = (st, view)
        return view

    def host_digests(self) -> np.ndarray:
        return np.asarray(jax.device_get(self.state.stats.digest))[: self._num_real]

    def write_outputs(
        self, data_dir: str | None = None, report: dict | None = None
    ) -> str:
        """Write the data directory (reference data-dir layout:
        processed-config.yaml, sim-stats.json, hosts/<name>/). Pass the report
        from run() to avoid recomputing the device->host stats transfer."""
        data_dir = data_dir or self.cfg.general.data_directory
        os.makedirs(data_dir, exist_ok=True)
        with open(os.path.join(data_dir, "processed-config.yaml"), "w") as f:
            yaml.safe_dump(self.cfg.to_dict(), f, sort_keys=False)
        if report is None:
            report = self.stats_report()
        with open(os.path.join(data_dir, "sim-stats.json"), "w") as f:
            json.dump(report, f, indent=2)
        gold = getattr(self, "_golden", None)
        if gold is not None:
            events_c, sent_c = gold.stats["events"], gold.stats["pkts_sent"]
            deliv_c, lost_c = gold.stats["pkts_delivered"], gold.stats["pkts_lost"]
            digests = gold.digests
            occ_c = None  # the golden oracle does not track occupancy
        else:
            s = jax.device_get(self.state.stats)
            events_c, sent_c = s.events, s.pkts_sent
            deliv_c, lost_c = s.pkts_delivered, s.pkts_lost
            digests = self.host_digests()
            occ_c = s.q_occ_hwm
        # network observatory: per-host network counters ride into
        # host-stats.json on gated runs (engine drop lanes by cause +
        # the model's per-host hook — bytes/retransmits on tgen)
        net_ph: dict[str, Any] = {}
        if getattr(self.engine_cfg, "netobs", False) and gold is None:
            net_ph = {
                "packets_codel_dropped": s.pkts_codel_dropped,
                "packets_budget_dropped": s.pkts_budget_dropped,
                "packets_unreachable": s.pkts_unreachable,
            }
            if hasattr(self.model, "per_host_network"):
                for k, v in self.model.per_host_network(
                    self._model_host_view()
                ).items():
                    net_ph[k] = v
        for h in self.hosts:
            hd = os.path.join(data_dir, "hosts", h.name)
            os.makedirs(hd, exist_ok=True)
            with open(os.path.join(hd, "host-stats.json"), "w") as f:
                json.dump(
                    {
                        "name": h.name,
                        "ip": h.ip,
                        "events_processed": int(events_c[h.host_id]),
                        "packets_sent": int(sent_c[h.host_id]),
                        "packets_delivered": int(deliv_c[h.host_id]),
                        "packets_lost": int(lost_c[h.host_id]),
                        **(
                            {"queue_occupancy_hwm": int(occ_c[h.host_id])}
                            if occ_c is not None
                            else {}
                        ),
                        **{
                            k: int(np.asarray(v)[h.host_id])
                            for k, v in net_ph.items()
                            if h.host_id < len(np.asarray(v))
                        },
                        "determinism_digest": f"{int(digests[h.host_id]):016x}",
                    },
                    f,
                    indent=2,
                )
        self._write_trace_outputs(data_dir, report)
        return data_dir

    def _write_trace_outputs(self, data_dir: str, report: dict | None):
        """Export the round tracer's artifacts (Chrome trace + Prometheus
        metrics) into the data dir. No-op unless `observability.trace` ran."""
        tracer = getattr(self, "_tracer", None)
        if tracer is not None:
            compiles = getattr(self, "_rt_compiles", None)
            if compiles is not None:
                # runtime observatory: the compile track (one X event
                # per recorded program compile on the wall-clock
                # timeline, obs/runtime.CompileLedger.events)
                tracer.note_compiles(compiles.events())
            tracer.write_artifacts(data_dir, self.cfg.observability, report)


def resource_heartbeat() -> str:
    """Process-resource snippet for heartbeat lines (the reference logs
    getrusage + /proc/meminfo every interval in a tornettools-parseable
    format, manager.rs:675-717)."""
    try:
        import resource

        ru = resource.getrusage(resource.RUSAGE_SELF)
        # ru_maxrss is KiB on Linux but bytes on macOS
        rss_div = (1 << 30) if sys.platform == "darwin" else (1 << 20)
        rss_gib = ru.ru_maxrss / rss_div
        mem_avail = ""
        try:
            with open("/proc/meminfo") as f:
                for line in f:
                    if line.startswith("MemAvailable:"):
                        mem_avail = f" mem_avail_gib={int(line.split()[1]) / (1 << 20):.1f}"
                        break
        except OSError:
            pass
        return (
            f"rss_gib={rss_gib:.2f} utime_min={ru.ru_utime / 60:.1f} "
            f"stime_min={ru.ru_stime / 60:.1f}{mem_avail}"
        )
    except Exception:
        return ""


def run_simulation(cfg: ConfigOptions, **kw) -> tuple[Simulation, dict]:
    sim = Simulation(cfg, **kw)
    report = sim.run()
    return sim, report
