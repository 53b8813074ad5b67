"""Co-simulation bridge: CPU-emulated hosts over the device network plane.

The reference couples managed Linux processes to its simulated network
through shared-memory syscall channels (SURVEY.md §3.3-3.4, §5.8). The TPU
recast replaces that hop with per-window host↔device staging (SURVEY.md §7
hard part 6):

  every window [start, end):
    1. joint barrier: t_next = min(CPU plane, device plane) next event time;
       window_end = min(t_next + runahead, stop)  (controller.rs:88-112)
    2. CPU hosts run their event loops to window_end; socket egress is
       *staged* — (src, t, dst, size, key) — with the real bytes parked
       host-side in a by-(src, key) store
    3. one jitted `prepare` op: reset capture rings + merge the staged
       send-requests into the device queues (sorted deterministic scatter)
    4. one jitted guarded round loop: engine rounds — microsteps + the full
       egress pipeline (budget, token bucket, loss, latency, clamp) +
       exchange — run back to back on device until a round captures
       host-bound deliveries (the CPU plane must react) or the device
       catches up to the CPU plane's next event
    5. drain capture rings; map (src, key) back to bytes; schedule socket
       delivery on each destination CPU host at the captured arrival time

  Conservative lookahead makes this exact: every cross-host arrival lands
  at >= window_end, so a packet staged in window N is always delivered into
  window N+1 or later on both planes.

Multi-device: with `general.parallelism > 1` the device plane is
shard-mapped over the mesh exactly like `Engine.run_chunk` — staged sends
arrive replicated, each shard merges only its own hosts' rows, and capture
rings are gathered back (mesh-invariance: `tests/test_cosim.py`,
`tests/test_mixed.py`). The CPU plane stays one Python process; its
parallelism is `experimental.host_workers`. The alltoall exchange keeps
its top rung here: a fixed block of the auto size (or the explicit
`a2a_block`), as before the rung ladder. The ladder's abort-and-replay
needs a chunk it can discard, and a window that the CPU plane has
answered cannot be discarded.
"""

from __future__ import annotations

import functools
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
from shadow_tpu.core import engine as eng
from shadow_tpu.core.engine import Engine, EngineParams
from shadow_tpu.core.integrity import IntegrityAbort
from shadow_tpu.core.pressure import PressureAbort
from shadow_tpu.core.supervisor import SupervisorAbort
from shadow_tpu.host import CpuHost, HostConfig
from shadow_tpu.host.sockets import NetPacket
from shadow_tpu.models.hybrid import (
    HybridModel,
    KIND_SENDREQ,
    PW_DST_OR_SRC,
    PW_KEY,
    PW_SIZE,
)
from shadow_tpu.net.dns import Dns
from shadow_tpu.obs import PcapWriter, PerfTimers, SimLogger, StraceLogger
from shadow_tpu.ops import merge_flat_events, pack_order, q_next_time
from shadow_tpu.programs import get_program
from shadow_tpu.simtime import NS_PER_SEC, TIME_MAX
from shadow_tpu import sim as simmod

_BYTES_GC_WINDOWS = 1024  # sweep horizon for lost-packet payloads

# magic value in the hybrid payload's flags word marking "the byte store
# holds this key" — the echo-reconstruction in _drain_captures must fire
# ONLY for payloads that originated as bridge send-requests (a model's own
# payload words can collide with small live keys)
BYTES_KEY_MAGIC = 0x53484457  # "SHDW"


def _pad_tree(tree, pad: int):
    """Zero-pad every [H_real, ...] leaf to [H_real + pad, ...] (the mixed
    plane's analogue of sim.Simulation._pad)."""

    def f(a):
        a = np.asarray(a)
        if pad == 0:
            return jnp.asarray(a)
        width = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return jnp.asarray(np.pad(a, width))

    return jax.tree.map(f, tree)


class HybridSimulation:
    """Config-driven co-simulation (CLI-compatible with `Simulation`)."""

    def __init__(
        self,
        cfg: ConfigOptions,
        *,
        staging_cap: int = 4096,
        world: int | None = None,
    ):
        self.cfg = cfg
        self.graph = simmod.load_graph(cfg.network.graph)
        self.specs = simmod.expand_hosts_hybrid(cfg, self.graph)
        if not self.specs:
            raise ConfigError("config defines no hosts")
        if cfg.fluid.active:
            # the fluid plane's coupling rides the device engine's send
            # path; the CPU host plane's packets never see it, so a
            # hybrid run would model background congestion for HALF the
            # traffic — reject loudly instead of silently under-coupling
            raise ConfigError(
                "fluid: the hybrid (managed-process) driver does not "
                "support the fluid traffic plane yet — the CPU plane's "
                "packets would bypass the background coupling; run a "
                "pure device-model sim or drop the fluid block"
            )
        self.staging_cap = staging_cap
        # mixed simulations: any spec carrying a device model makes the
        # lane plane heterogeneous (models/mixed.py); pure-program configs
        # keep the plain hybrid proxy
        model_names = {s.model for s in self.specs if s.model != "hybrid"}
        if model_names:
            from shadow_tpu.models.base import get_model
            from shadow_tpu.models.mixed import MixedModel

            if len(model_names) > 1:
                raise ConfigError(
                    f"mixed simulation supports one device model, got "
                    f"{sorted(model_names)}"
                )
            inner_name = model_names.pop()
            self.model = MixedModel(get_model(inner_name)(), inner_name)
        else:
            self.model = HybridModel()
        ex = cfg.experimental
        world = (
            simmod.resolve_world(cfg.general.parallelism)
            if world is None
            else world
        )
        # device plane pads the host count to a multiple of the mesh size
        # with inert hosts (same scheme as the modeled sim); the CPU plane
        # only ever touches the real prefix
        self._num_real = len(self.specs)
        num_hosts = -(-self._num_real // world) * world
        # emulated TCP bursts land many events per host per window; keep the
        # per-host slab roomy (overflow is counted, never silent — see
        # stats_report queue_overflow_dropped)
        auto_qcap, auto_budget, auto_rpc = ex.resolve_shapes(num_hosts)
        qcap = max(auto_qcap, 256)
        # fault plane: link-fault (loss/latency) windows act below the
        # bridge — in the device engine's egress pipeline — so they ride
        # along on hybrid sims unchanged. Host crashes do NOT: the CPU
        # plane's processes have live Python/native state no up/down mask
        # can pause, so a crash schedule here is a config error, not a
        # silent no-op.
        from shadow_tpu.core.faults import FaultSchedule, compile_faults

        if cfg.faults.crashes or (
            cfg.faults.host_churn is not None and cfg.faults.host_churn.prob > 0
        ):
            raise ConfigError(
                "faults: host crashes/churn are not supported on hybrid "
                "(program) simulations — the CPU plane cannot pause live "
                "processes; use loss_windows, or model the hosts"
            )
        # pressure plane: the hybrid driver supports drop (default) and
        # abort (first-drop stop with honest artifacts). Escalate is
        # rejected loudly — the bridge's injection programs and byte
        # stores are compiled/keyed against the device queue shape, and a
        # mid-window capacity migration cannot re-seat the CPU plane's
        # staged state; model the hosts (the Simulation driver escalates)
        # or size the hybrid slab up front (it already auto-rooms to
        # >= 256 slots).
        if cfg.pressure.policy == "escalate":
            raise ConfigError(
                "pressure: escalate is not supported on hybrid (program) "
                "simulations — the CPU bridge cannot migrate staged state "
                "across queue shapes; use policy drop/abort or model the "
                "hosts"
            )
        if ex.timer_wheel:
            # the hybrid device plane runs the bridge model, which has no
            # timer_kinds (timers live in the real CPU processes) — a
            # wheel would be dead HBM; reject loudly rather than carry it
            raise ConfigError(
                "experimental.timer_wheel is not supported on hybrid "
                "(program) simulations — the bridge model declares no "
                "timer_kinds; drop the knob or model the hosts"
            )
        if (cfg.faults.supervisor.enabled
                and cfg.faults.supervisor.checkpoint_file is not None):
            # same principle as crashes above: the hybrid supervisor runs
            # per-dispatch snapshots only (the CPU plane's live processes
            # cannot be restored from an on-disk device checkpoint), so a
            # durability knob it cannot honor is a config error, not a
            # silent drop the user discovers at crash time
            raise ConfigError(
                "faults.supervisor.checkpoint_file is not supported on "
                "hybrid (program) simulations — the CPU plane cannot "
                "resume from a device checkpoint; remove it or model the "
                "hosts"
            )
        try:
            self._fault_sched = (
                compile_faults(
                    cfg.faults,
                    num_hosts=num_hosts,
                    num_real=self._num_real,
                    stop_time=cfg.general.stop_time,
                    bootstrap_end=cfg.general.bootstrap_end_time,
                    default_seed=cfg.general.seed,
                )
                if cfg.faults.injecting
                else FaultSchedule(0, 0, False, None)
            )
        except ValueError as e:
            raise ConfigError(f"faults: {e}") from e
        self.engine_cfg = eng.EngineConfig(
            num_hosts=num_hosts,
            stop_time=cfg.general.stop_time,
            bootstrap_end_time=cfg.general.bootstrap_end_time,
            runahead_floor=ex.runahead,
            static_min_latency=max(self.graph.min_latency_ns_opt or 0, 1),
            use_jitter=self.graph.has_jitter,
            use_dynamic_runahead=False,
            use_codel=ex.use_codel,
            queue_capacity=qcap,
            # the bucketed queue rides along on hybrid sims too (merge and
            # pop/push dispatch on queue type); a block that does not divide
            # the roomier hybrid capacity fails loudly in EngineConfig
            queue_block=ex.event_queue_block,
            sends_per_host_round=max(auto_budget, 32),
            max_round_inserts=ex.max_round_inserts or qcap,
            # bounds the guarded round loop — the ONLY device execution path,
            # so it must be >= 1 or nothing would ever advance
            rounds_per_chunk=max(auto_rpc, 1),
            # round tracer ring sized to the guarded chunk bound; drained
            # after every guarded dispatch so it can never wrap
            trace_rounds=(
                max(auto_rpc, 1) if cfg.observability.trace else 0
            ),
            # network observatory: event-class + safe-window accounting
            # ride along on the hybrid device plane (the hybrid model has
            # no flow port, so no flow ledger here)
            netobs=cfg.observability.network,
            microstep_limit=ex.microstep_limit,
            # the K-way fold and the flipped multi-device exchange default
            # ride along on hybrid sims: both act below the bridge (the
            # microstep loop / the cross-shard merge), so the CPU plane
            # sees identical deliveries either way
            microstep_events=ex.microstep_events,
            exchange=ex.resolve_exchange(world),
            a2a_block=ex.a2a_block,
            world=world,
            shaping=any(
                s.bw_up_bits > 0 or s.bw_down_bits > 0 for s in self.specs
            ),
            fault_loss_windows=self._fault_sched.loss_windows,
            # pressure plane: abort policy traces the first-drop stop
            # into the guarded loop (escalate was rejected above)
            pressure_abort=cfg.pressure.active,
            # integrity sentinel: device-plane guards ride along on the
            # hybrid plane, with the first-violation stop in the guarded
            # loop. The bridge cannot roll the CPU plane back, so there
            # is no quarantine-and-replay classification here — a
            # violation raises IntegrityAbort directly (treated
            # deterministic; see _device_rounds). The strict
            # window-monotonicity sub-check is relaxed: CPU-plane
            # injections' conservative arrival bound can legally sit
            # below the device's last guarded window_end (the
            # EngineConfig.integrity_strict_time docstring derives
            # this); the host-side _bridge_guard covers the bridge's
            # own clock/staging invariants instead.
            integrity=cfg.integrity.enabled,
            integrity_dual=(
                cfg.integrity.enabled and cfg.integrity.dual_digest
            ),
            integrity_strict_time=False,
        )
        self.mesh = None
        if world > 1:
            self.mesh = jax.sharding.Mesh(
                np.array(jax.devices()[:world]), (eng.AXIS,)
            )
        self.engine = Engine(self.engine_cfg, self.model, self.mesh)
        self._build()

    # ---- build -------------------------------------------------------------

    def _build(self):
        cfg, ecfg = self.cfg, self.engine_cfg
        # device side (reuses the modeled-sim param construction)
        node_of = np.zeros((ecfg.num_hosts,), np.int32)
        bw_up = np.zeros((ecfg.num_hosts,), np.int64)
        bw_down = np.zeros((ecfg.num_hosts,), np.int64)
        for h in self.specs:
            node_of[h.host_id] = h.node_index
            bw_up[h.host_id] = h.bw_up_bits
            bw_down[h.host_id] = h.bw_down_bits
        from shadow_tpu.models.mixed import MixedModel

        if isinstance(self.model, MixedModel):
            # build over the REAL lanes only, then zero-pad to the mesh
            # size (exactly sim.py's _pad): building the inner model at the
            # padded width would make results world-dependent — phold's
            # num_hosts and gossip's neighbor draws change with H
            lane_hosts = [
                {
                    "host_id": s.host_id,
                    "name": s.name,
                    "plane": "native" if s.programs else "model",
                    "model_args": dict(s.model_args) if not s.programs else {},
                    "start_time": s.start_time,
                }
                for s in self.specs
            ]
        else:
            lane_hosts = [{"host_id": i} for i in range(self._num_real)]
        mparams, mstate, initial_events = self.model.build(
            lane_hosts, cfg.general.seed
        )
        mparams = _pad_tree(mparams, ecfg.num_hosts - self._num_real)
        mstate = _pad_tree(mstate, ecfg.num_hosts - self._num_real)
        params = EngineParams(
            node_of=jnp.asarray(node_of),
            lat_ns=jnp.asarray(self.graph.lat_ns),
            loss=jnp.asarray(self.graph.loss),
            jitter_ns=jnp.asarray(self.graph.jitter_ns),
            eg_tb=simmod._tb_params(bw_up, ecfg.tb_interval_ns),
            in_tb=simmod._tb_params(bw_down, ecfg.tb_interval_ns),
            model=jax.tree.map(jnp.asarray, mparams),
            faults=self._fault_sched.params,
        )
        mstate_dev = jax.tree.map(jnp.asarray, mstate)
        self.state, self.params = self.engine.init_state(
            params, mstate_dev, initial_events, seed=cfg.general.seed
        )

        # CPU side: one CpuHost per PROGRAM spec; modeled specs live only
        # on device (but are registered in DNS and the IP map, so real
        # processes can address them by name or IP)
        self.ip_to_gid: dict[str, int] = {}
        self.dns = Dns()
        for s in self.specs:
            self.dns.register(s.name, s.ip)
            self.ip_to_gid[s.ip] = s.host_id
        self.native_specs = [s for s in self.specs if s.programs]
        self._model_gids = {s.host_id for s in self.specs if not s.programs}
        self.hosts: list[CpuHost] = []
        self._host_by_gid: dict[int, CpuHost] = {}
        for s in self.native_specs:
            h = CpuHost(
                HostConfig(
                    name=s.name,
                    ip=s.ip,
                    seed=cfg.general.seed,
                    host_id=s.host_id,
                    model_unblocked_latency=cfg.general.model_unblocked_syscall_latency,
                    tcp=s.tcp_cfg,
                    breadcrumbs=cfg.experimental.packet_breadcrumbs,
                )
            )
            h.egress = self._stage_send
            h.resolver = self.dns.resolve
            h.rev_resolver = self.dns.reverse
            self.hosts.append(h)
            self._host_by_gid[s.host_id] = h
        self.procs = []
        for s, h in zip(self.native_specs, self.hosts):
            for p in s.programs:
                args = dict(p.get("args") or {})
                if "/" in p["path"]:
                    # real binary under the C++ shim (native plane)
                    from shadow_tpu.native_plane import ensure_built, spawn_native

                    if not ensure_built():
                        raise ConfigError(
                            f"native plane unavailable (no C++ toolchain?) "
                            f"for binary {p['path']!r}"
                        )
                    proc = spawn_native(
                        h,
                        [p["path"], *p.get("argv_raw", [])],
                        start_time=p.get("start_time", 0),
                        env=p.get("environment") or {},
                    )
                else:
                    prog = get_program(p["path"])
                    proc = h.spawn(
                        prog,
                        name=p["path"],
                        args=args,
                        start_time=p.get("start_time", 0),
                    )
                proc.expected_final_state = p.get("expected_final_state", "running")
                if p.get("shutdown_time") is not None:
                    h.schedule(p["shutdown_time"], proc.kill)
                self.procs.append(proc)

        # observability (reference §5.1: pcap per interface, strace per
        # process, perf timers around the hot phases; §5.5: async
        # sim-time-stamped logger, shadow_logger.rs:17-60)
        self.perf = PerfTimers()
        self._tracer = None
        if self.engine_cfg.trace_rounds:
            from shadow_tpu.obs import RoundTracer

            self._tracer = RoundTracer(self.engine_cfg.trace_rounds)
        # runtime observatory (obs/runtime.py): per-window bridge-stall
        # split (ROADMAP item 4's before/after instrument) + the compile
        # ledger over the bridge's jitted programs. Host-side only —
        # must exist before the jitted ops below are built so their
        # cold compiles are recorded.
        self._bridge_rt = None
        self._rt_compiles = None
        if cfg.observability.runtime:
            from shadow_tpu.obs.runtime import BridgeTelemetry, CompileLedger

            self._bridge_rt = BridgeTelemetry()
            self._rt_compiles = CompileLedger()
        self._pcaps = []
        self._strace_files = []
        data_dir = cfg.general.data_directory
        self.log = None
        if cfg.general.log_file:
            path = cfg.general.log_file
            if not os.path.isabs(path):
                path = os.path.join(data_dir, path)
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            self.log = SimLogger(path, level=cfg.general.log_level)
            for s, h in zip(self.native_specs, self.hosts):
                h.on_process_exit = functools.partial(
                    _log_process_exit, self.log, h
                )
        strace_mode = cfg.experimental.strace_logging_mode
        for s, h in zip(self.native_specs, self.hosts):
            host_dir = os.path.join(data_dir, "hosts", s.name)
            if s.pcap_enabled:
                os.makedirs(host_dir, exist_ok=True)
                h.pcap_lo = PcapWriter(
                    os.path.join(host_dir, "lo.pcap"), s.pcap_capture_size
                )
                h.pcap_eth = PcapWriter(
                    os.path.join(host_dir, "eth0.pcap"), s.pcap_capture_size
                )
                self._pcaps += [h.pcap_lo, h.pcap_eth]
            if strace_mode != "off":
                os.makedirs(host_dir, exist_ok=True)
                for p in h.processes.values():
                    f = open(
                        os.path.join(host_dir, f"{p.name}.{p.pid}.strace"), "w"
                    )
                    self._strace_files.append(f)
                    p.strace = StraceLogger(f, strace_mode)

        # staging + payload store; tuples are (src, t, dst, size, key, sock).
        # Sends land in PER-SOURCE buffers (each written only by its own
        # host, so window execution can be parallel) and are flushed into
        # `_staged` in host-id order — identical to serial execution order.
        self._staged: list[tuple[int, int, int, int, int, int]] = []
        self._stage_buf: list[list] = [[] for _ in self.specs]
        self._qdisc = cfg.experimental.interface_qdisc
        self._send_seq = np.zeros((ecfg.num_hosts,), np.int64)
        self._bytes: list[dict[int, tuple[int, NetPacket]]] = [
            {} for _ in self.specs
        ]
        self._window_idx = 0
        self._unreach = [0] * len(self.specs)
        self._model_pkts_unrouted = 0  # model->native with no UDP listener
        # parallel CPU host plane (reference thread_per_core.rs; see
        # CpuNetwork for the staging argument). GIL caveat: pure-Python
        # hosts serialize; native hosts block in futex waits off-GIL.
        self._host_pool = None
        ex = cfg.experimental
        if ex.host_workers > 1 or ex.host_scheduler == "per-host":
            from shadow_tpu.host import affinity
            from shadow_tpu.host.scheduler import make_pool

            pin = affinity.assign(ex.host_workers) if ex.use_cpu_pinning else None
            self._host_pool = make_pool(ex.host_scheduler, ex.host_workers, pin)

        # jitted ops (shard-mapped over the mesh when world > 1, exactly
        # like Engine.run_chunk — staged-send arrays ride in replicated and
        # each shard merges only its own hosts' rows)
        axis = eng.AXIS if self.mesh is not None else None
        prepare = functools.partial(
            _prepare_window, self.engine_cfg, self.model, axis
        )
        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            rep = P()
            from shadow_tpu.core.compat import shard_map_compat as _shard_map

            prepare = _shard_map(
                prepare, self.mesh,
                (self.engine.state_specs(), rep, rep, rep, rep, rep, rep),
                self.engine.state_specs(),
            )
        self._prepare = jax.jit(prepare, donate_argnums=0)
        if self._rt_compiles is not None:
            self._prepare = self._rt_compiles.instrument(
                "prepare", "base", "cold_start", self._prepare
            )

        def _mk_guarded(ecfg):
            """The guarded round loop jitted at one engine config —
            called once for the full-width program and lazily per merge
            gear (`dataclasses.replace(cfg, gear_cols=g)`: same state
            shapes, truncated exchange sort + first-shed abort)."""
            g = functools.partial(
                eng._run_guarded_chunk,
                ecfg,
                self.model,
                axis,
                lambda ms: jnp.any(ms["cap_n"] > 0),
            )
            if self.mesh is not None:
                from jax.sharding import PartitionSpec as P

                from shadow_tpu.core.compat import shard_map_compat as _shard_map

                state_spec = self.engine.state_specs()
                g = _shard_map(
                    g, self.mesh,
                    (state_spec, self.engine.param_specs(), P()), state_spec,
                )
            fn = jax.jit(g, donate_argnums=0)
            if self._rt_compiles is not None:
                gear = getattr(ecfg, "gear_cols", 0)
                fn = self._rt_compiles.instrument(
                    "guarded",
                    f"gear={gear}" if gear else "base",
                    "gear_shift" if gear else "cold_start", fn,
                )
            return fn

        self._mk_guarded = _mk_guarded
        self._guarded = _mk_guarded(self.engine_cfg)
        self._guarded_gears: dict[int, Any] = {}
        # occupancy-adaptive merge gears on the device plane (core/gears.py;
        # the bridge's CPU plane is unaffected — gears act below it, in the
        # exchange merge, and accepted chunks are bit-identical to full
        # width by the shed-exact replay)
        from shadow_tpu.core.gears import GearController, resolve_gear_ladder

        try:
            ladder = resolve_gear_ladder(
                cfg.experimental.merge_gears,
                self.engine_cfg.sends_per_host_round,
            )
        except ValueError as e:
            raise ConfigError(f"experimental.merge_gears: {e}") from e
        self._gearctl = GearController(ladder) if ladder else None
        self._last_gear = None
        self._ob_hwm_run = 0
        # HBM observatory (obs/memory.py): per-shard live sampling after
        # each guarded device dispatch. Host-side observer only — the
        # traced programs are byte-identical with this on or off.
        self._memmon = None
        if cfg.observability.memory:
            from shadow_tpu.obs.memory import MemoryMonitor

            devs = (
                list(self.mesh.devices.flat) if self.mesh is not None
                else [jax.devices()[0]]
            )
            self._memmon = MemoryMonitor(devs)
        self._clear_caps = jax.jit(_clear_caps, donate_argnums=0)
        # crash-resilient supervisor, per-dispatch mode: the CPU plane
        # advances between device dispatches and cannot roll back, so
        # every guarded dispatch snapshots the DEVICE state first and only
        # the failing dispatch retries (no cross-window replay, no on-disk
        # checkpoint — hybrid durable checkpoints keep their own
        # end-of-run constraints, core/checkpoint.save_checkpoint_hybrid)
        self._supervisor = None
        self._aborted = False
        self._pressure_aborted = False
        # integrity sentinel: the committed joint time horizon the
        # host-side bridge guards check against (str detail once aborted)
        self._integrity_aborted: str | None = None
        self._iv_horizon = 0
        if cfg.faults.supervisor.enabled:
            from shadow_tpu.core.supervisor import ChunkSupervisor

            self._supervisor = ChunkSupervisor(
                snapshot_every_chunks=1,
                max_retries=cfg.faults.supervisor.max_retries,
                backoff_base_s=cfg.faults.supervisor.backoff_base_ms / 1000.0,
                pre_dispatch_snapshot=True,
                log=sys.stderr,
                memory=self._memmon,
                memory_modeled_fn=(
                    self._modeled_shard_bytes if self._memmon is not None
                    else None
                ),
            )

    # ---- egress staging ----------------------------------------------------

    def _stage_send(self, host: CpuHost, pkt: NetPacket):
        gid = host.host_id
        dst_gid = self.ip_to_gid.get(pkt.dst_ip)
        if dst_gid is None:
            self._unreach[gid] += 1
            host.drop_packet(pkt, "inet_no_route")
            return
        key = int(self._send_seq[gid] % (1 << 31))
        self._send_seq[gid] += 1
        self._bytes[gid][key] = (self._window_idx, pkt)
        sock = (int(pkt.proto) << 16) | (int(pkt.src_port) & 0xFFFF)
        self._stage_buf[gid].append(
            (gid, host.now(), dst_gid, pkt.size_bytes, key, sock)
        )

    def _flush_stage_buf(self):
        """Move per-source buffers into the flat staging list in host-id
        order (the deterministic merge point; worker.rs:644-654 analogue)."""
        for buf in self._stage_buf:
            if buf:
                self._staged.extend(buf)
                buf.clear()

    def _execute_hosts(self, until: int):
        if self._host_pool is not None:
            self._host_pool.run(self.hosts, lambda h: h.execute(until))
        else:
            for h in self.hosts:  # deterministic host order
                h.execute(until)
        self._flush_stage_buf()

    # ---- window loop -------------------------------------------------------

    def _cpu_min_next(self) -> int:
        return min(
            (h.next_event_time() for h in self.hosts), default=TIME_MAX
        )

    def run(self, *, progress: bool | None = None, log=sys.stderr) -> dict:
        try:
            return self._run(progress=progress, log=log)
        finally:
            # flush observability artifacts even when a window raises, so a
            # determinism byte-compare never sees a truncated file
            for w in self._pcaps:
                w.close()
            for f in self._strace_files:
                if not f.closed:
                    f.close()
            if self.log is not None:
                self.log.close()

    def _run(self, *, progress: bool | None = None, log=sys.stderr) -> dict:
        cfg = self.cfg
        stop = cfg.general.stop_time
        show_progress = cfg.general.progress if progress is None else progress
        hb_ns = cfg.general.heartbeat_interval
        next_hb = hb_ns or 0
        if self._tracer is not None and not (
            self._tracer.rounds or self._tracer.lost
        ):
            # nothing drained yet: adopt the ring's current cursor so a
            # state restored from a hybrid checkpoint is not replayed
            self._tracer.sync_cursor(self.state.trace)
        profiling = bool(cfg.observability.profile_dir)
        if profiling:
            os.makedirs(cfg.observability.profile_dir, exist_ok=True)
            jax.profiler.start_trace(cfg.observability.profile_dir)
        # wall clock starts AFTER observability setup so _wall_seconds and
        # heartbeat ratios measure the simulation, not the trace session
        t0 = time.monotonic()
        try:
            windows = self._window_loop(
                stop, show_progress, t0, hb_ns, next_hb, log
            )
        finally:
            if profiling:
                jax.profiler.stop_trace()
        self._execute_hosts(stop)
        if self._host_pool is not None:
            self._host_pool.shutdown()
            self._host_pool = None
        # snapshot final states BEFORE reaping: a daemon alive at stop_time
        # satisfies expected_final_state: running even though shutdown kills
        # it (reference free_all_applications semantics, host.rs:791-807)
        for p in self.procs:
            p.state_at_stop = getattr(p.state, "value", p.state)
        for h in self.hosts:  # reap live processes + native IPC resources
            h.shutdown()
        if show_progress:
            print(file=log)
        self._wall_seconds = time.monotonic() - t0
        self._windows = windows
        return self.stats_report()

    def _window_loop(self, stop, show_progress, t0, hb_ns, next_hb, log):
        cfg = self.cfg
        runahead = max(
            self.engine_cfg.runahead_floor, self.engine_cfg.static_min_latency, 1
        )
        windows = 0
        bt = self._bridge_rt  # obs/runtime.BridgeTelemetry | None
        while True:
            if bt is not None:
                # the joint-barrier computation below is bridge work:
                # it lands in the window's bridge residual
                bt.window_start()
            dev_min = int(jnp.min(q_next_time(self.state.queue)))
            t_next = min(self._cpu_min_next(), dev_min)
            if t_next >= stop:
                break
            window_end = min(t_next + runahead, stop)
            try:
                if self.engine_cfg.integrity:
                    self._bridge_guard_clock(t_next)
                t_host = time.monotonic()
                with self.perf.time("host_plane"):
                    self._execute_hosts(window_end)
                if bt is not None:
                    bt.note("cpu_plane", time.monotonic() - t_host)
                if self.engine_cfg.integrity:
                    # judged while the window's staged sends actually
                    # EXIST (post host execution, pre injection) — at
                    # the top of the loop the previous window's inject
                    # loop has always drained the staging list
                    self._bridge_guard_staging()
            except IntegrityAbort as e:
                print(f"[integrity] aborting run: {e}", file=log)
                self._integrity_aborted = str(e)
                break
            # inject ALL staged sends (multiple merges under staging-cap
            # overflow — BEFORE any device rounds run, so a tiny cap only
            # costs extra merge dispatches and cannot shift packet timing),
            # then run device rounds until the first round that captures
            # host-bound deliveries (the CPU plane must react) or the
            # device catches up to the CPU plane's next event.
            with self.perf.time("device_inject"):
                if bt is None:
                    self.state = self._inject()
                    while self._staged:
                        self.state = self._inject()
                    # settle the staged merge BEFORE the timer stops: jax
                    # dispatch is async, so without the block this phase
                    # timed only the enqueue and the device work leaked
                    # into whichever phase synced first — perf.report()
                    # under-reported the device plane (the reference's
                    # perf_timers wrap the actual work, host.rs:721-729)
                    jax.block_until_ready(self.state)
                else:
                    # per-syscall-batch latency: each staged merge is one
                    # batch, blocked individually so its histogram entry
                    # is a true round-trip latency (the instrument's cost
                    # when on; the off path above keeps one block total)
                    while True:
                        n_batch = min(len(self._staged), self.staging_cap)
                        t_b = time.monotonic()
                        self.state = self._inject()
                        jax.block_until_ready(self.state)
                        if n_batch > 0:
                            bt.note_batch(time.monotonic() - t_b, n_batch)
                        else:
                            # the off path's unconditional first _inject
                            # on an empty staging list: bridge wall, but
                            # NOT a syscall batch — an empty merge in the
                            # histogram would dilute the round-trip
                            # latencies ROADMAP item 4 reads
                            bt.note("bridge", time.monotonic() - t_b)
                        if not self._staged:
                            break
            until = min(self._cpu_min_next(), stop)
            t_rounds = time.monotonic()
            try:
                with self.perf.time("device_rounds"):
                    self._device_rounds(
                        jnp.asarray(max(until, window_end), jnp.int64)
                    )
            except SupervisorAbort as e:
                # graceful abort: export the completed prefix from the
                # pre-dispatch device snapshot, not the in-hand state
                # (abort_export_state docs the poisoned/donation rationale)
                print(f"[supervisor] aborting run: {e}", file=log)
                good = self._supervisor.abort_export_state()
                if good is not None:
                    self.state = good
                self._aborted = True
                break
            except PressureAbort as e:
                # pressure abort policy: the in-hand state IS the honest
                # record (the guarded loop stopped at the dropping round;
                # the drop is in the exported counters)
                print(f"[pressure] aborting run: {e}", file=log)
                if self._tracer is not None:
                    self._tracer.drain(
                        self.state.trace,
                        wall_t0=t_rounds, wall_t1=time.monotonic(),
                    )
                self._pressure_aborted = True
                break
            except IntegrityAbort as e:
                # an in-jit invariant tripped on the device plane. The
                # CPU plane cannot roll back, so there is no replay
                # classification — the run stops loudly, the report
                # names the invariant/round/shard, and the artifacts
                # carry `integrity_aborted` so the violating state's
                # counters never read as a trustworthy record.
                print(f"[integrity] aborting run: {e}", file=log)
                if self._tracer is not None:
                    self._tracer.drain(
                        self.state.trace,
                        wall_t0=t_rounds, wall_t1=time.monotonic(),
                    )
                self._integrity_aborted = str(e)
                break
            if bt is not None:
                bt.note("device_plane", time.monotonic() - t_rounds)
            if self._tracer is not None:
                self._tracer.drain(
                    self.state.trace,
                    wall_t0=t_rounds, wall_t1=time.monotonic(),
                )
            if self._memmon is not None:
                t_s = time.monotonic()
                shard_bytes = self._memmon.sample(
                    modeled_bytes=self._modeled_shard_bytes(), wall_t=t_s
                )
                if self._tracer is not None:
                    self._tracer.note_memory(t_s, shard_bytes)
            t_drain = time.monotonic()
            with self.perf.time("drain_captures"):
                self._drain_captures()
            if bt is not None:
                # capture draining is bridge marshalling, like staging
                bt.note("bridge", time.monotonic() - t_drain)
                bt.window_end(window_end)
            windows += 1
            if self.log is not None and hb_ns and window_end >= next_hb:
                self.log.info(
                    window_end, "manager",
                    f"heartbeat windows={windows}",
                )
            if hb_ns and window_end >= next_hb:
                wall = time.monotonic() - t0
                gear_f = (
                    f"gear={self._last_gear} "
                    if self._last_gear is not None else ""
                )
                fault_f = ""
                if self.engine_cfg.faults_active:
                    _s = self.state.stats
                    fault_f = (
                        f"faults="
                        f"{int(np.asarray(_s.faults_dropped).sum())}/"
                        f"{int(np.asarray(_s.faults_delayed).sum())} "
                    )
                hbm_f = (
                    f"hbm={self._memmon.hwm_bytes()} "
                    if self._memmon is not None else ""
                )
                ek_f = ""
                if self.engine_cfg.netobs:
                    _s = self.state.stats
                    ek_f = (
                        f"ek={int(np.asarray(_s.ec_timer).sum())}/"
                        f"{int(np.asarray(_s.ec_pkt).sum())} "
                    )
                # rt= rides along only on runtime-observatory runs: the
                # LAST window's realtime factor (sim-s/wall-s)
                rt_f = (
                    f"rt={bt.rt_last:.2f} "
                    if bt is not None and bt.rt_last is not None else ""
                )
                print(
                    f"[heartbeat] sim_time={window_end / NS_PER_SEC:.3f}s "
                    f"wall={wall:.2f}s windows={windows} "
                    f"{fault_f}"
                    f"{gear_f}"
                    f"{hbm_f}"
                    f"{ek_f}"
                    f"{rt_f}"
                    f"ratio={window_end / NS_PER_SEC / max(wall, 1e-9):.2f}x "
                    f"{simmod.resource_heartbeat()}",
                    file=log,
                )
                # per-host tracker interval (per-socket/per-interface
                # deltas, reference tracker.c heartbeats)
                for h in self.hosts:
                    h.record_heartbeat(window_end)
                next_hb = (window_end // hb_ns + 1) * hb_ns
            if show_progress:
                pct = min(100.0, 100.0 * window_end / max(stop, 1))
                print(f"\rprogress: {pct:5.1f}% ", end="", file=log, flush=True)
            if self._window_idx % 256 == 0:
                self._gc_bytes()
        return windows

    def _bridge_guard_clock(self, t_next: int):
        """Host-side bridge-clock invariant (the integrity sentinel's
        hybrid half — the in-jit guards cover the device plane, these
        cover the clock/staging state only Python can see): the
        (CPU plane, device plane) joint next-event time never regresses
        below the previously committed horizon — both planes completed
        everything under it, and every new event (CPU injection,
        conservative arrival bound) lands at or above it by the
        lookahead argument, so a regression means a scribbled
        queue/time value. Raises IntegrityAbort (no replay
        classification on the bridge)."""
        horizon = self._iv_horizon
        if t_next < horizon:
            raise IntegrityAbort(
                f"integrity: bridge clock regressed — joint next-event "
                f"time {t_next} fell below the committed horizon "
                f"{horizon} (a scribbled queue/time plane, or an engine "
                f"bug breaking conservative lookahead)"
            )
        self._iv_horizon = t_next

    def _bridge_guard_staging(self):
        """Staging-floor invariant, judged POST host execution while the
        window's staged sends exist (the top-of-loop point always sees
        an empty list — the previous window's inject loop drains it):
        no staged send's event time sits below the committed horizon —
        its originating host already executed past it."""
        horizon = self._iv_horizon
        below = [s for s in self._staged if s[1] < horizon]
        if below:
            raise IntegrityAbort(
                f"integrity: bridge staging holds {len(below)} "
                f"send(s) below the committed horizon {horizon} "
                f"(earliest t={min(s[1] for s in below)}) — staged "
                f"state corrupted"
            )

    def _guarded_at(self, gear: int):
        """The guarded-chunk program for a merge gear (lazily jitted and
        cached, exactly like Engine.run_chunk_gear)."""
        if gear <= 0 or gear >= self.engine_cfg.sends_per_host_round:
            return self._guarded
        fn = self._guarded_gears.get(gear)
        if fn is None:
            import dataclasses

            fn = self._mk_guarded(
                dataclasses.replace(self.engine_cfg, gear_cols=gear)
            )
            self._guarded_gears[gear] = fn
        return fn

    def _device_rounds(self, until_arr):
        """One guarded device dispatch — at the adaptive merge gear with
        shed-exact replay when gears are on, the plain full-width program
        otherwise; wrapped in the supervisor's per-dispatch retry when
        `faults.supervisor` is enabled. The block_until_ready keeps the
        perf phase honest (jax dispatch is async; see the device_inject
        comment above).

        Cost note: below the top gear every window pays a device-side
        SimState copy (the replay snapshot). Guarded windows can be a
        handful of rounds, so on CPU-plane-chatty workloads at large H
        the copy can eat the narrower sort's savings — merge gears on
        hybrid sims are for device-dominant phases; leave the knob off
        when the CPU plane sets the pace."""

        def run(st):
            if self._gearctl is None:
                st = self._guarded(st, self.params, until_arr)
                jax.block_until_ready(st)
                return st
            from shadow_tpu.core.gears import run_adaptive_chunk

            def dispatch(s, gear):
                s = self._guarded_at(gear)(s, self.params, until_arr)
                jax.block_until_ready(s)
                return s

            # rounds0: a guarded window can legitimately retire ZERO
            # rounds (probe fires immediately / device already at the
            # horizon) — such windows must not feed the controller an
            # hwm of 0
            st, self._last_gear, hwm = run_adaptive_chunk(
                self._gearctl, st, dispatch,
                rounds0=int(st.stats.rounds),
            )
            self._ob_hwm_run = max(self._ob_hwm_run, hwm)
            return st

        if self._supervisor is None:
            self.state = run(self.state)
        else:
            self.state = self._supervisor.run_chunk(self.state, run)
        if self.engine_cfg.integrity:
            # integrity sentinel, checked BEFORE the pressure read: a
            # violating attempt's other counters may themselves be
            # scribbled. The guarded loop stopped at the first violating
            # round; the bridge cannot replay-classify (the CPU plane
            # advanced), so any violation is a loud stop.
            from shadow_tpu.core.integrity import raise_if_violated

            raise_if_violated(
                self.state,
                context="hybrid device plane (unclassifiable — the CPU "
                "plane cannot roll back for a replay)",
            )
        if self.cfg.pressure.active:
            # abort policy (the only active pressure policy the hybrid
            # driver admits): the guarded loop stopped at the first
            # dropping round — stop the run with the drop in the record
            # (the shared formatter keeps the two drivers' reports equal)
            from shadow_tpu.core.pressure import ResilienceController

            ResilienceController.raise_if_dropped(self.state)

    def _order_seq(self, gid: int) -> int:
        """Fresh per-host order counter for qdisc-reordered injections."""
        v = int(self._send_seq[gid] % (1 << 31))
        self._send_seq[gid] += 1
        return v

    def _inject(self):
        """Merge up to staging_cap staged sends into the device queues (and
        clear the capture rings); the guarded round loop computes its own
        windows from the queue contents."""
        cap = self.staging_cap
        staged = self._staged[:cap]
        overflow = self._staged[cap:]
        self._staged = overflow  # carried to next window (bounded staging)
        if self._qdisc == "round-robin":
            staged = _rr_reorder(staged)
        n = cap
        src = np.zeros((n,), np.int64)
        t = np.full((n,), TIME_MAX, np.int64)
        dstw = np.zeros((n,), np.int32)
        order = np.zeros((n,), np.int64)
        kind = np.zeros((n,), np.int32)
        payload = np.zeros((n, 4), np.int32)
        valid = np.zeros((n,), bool)
        # order keys are packed in NUMPY for the whole batch: the jax
        # pack_order builds traced scalars and int() forces a sync PER
        # PACKET — profiled at ~4 s of a 21 s tor-minimal run (the same
        # per-event-jax pathology seed_queue hit at 1M hosts)
        from shadow_tpu.ops.events import _LOCAL_SHIFT, _SRC_SHIFT, SEQ_MASK

        for i, (gid, t_ns, dst_gid, size, key, _sock) in enumerate(staged):
            src[i] = gid
            t[i] = t_ns
            dstw[i] = gid  # send-request is a LOCAL event on the source host
            # flags word: marks "bytes stored under (src, key)" so the echo
            # reconstruction can trust the key (see BYTES_KEY_MAGIC)
            payload[i, 3] = BYTES_KEY_MAGIC
            # key doubles as the order tiebreak: under round-robin the list
            # order changed, so re-sequence (the payload keeps the original
            # key for the byte-store lookup)
            seq = key if self._qdisc == "fifo" else self._order_seq(gid)
            order[i] = (
                (np.int64(1) << _LOCAL_SHIFT)
                | (np.int64(gid) << _SRC_SHIFT)
                | (np.int64(seq) & SEQ_MASK)
            )
            kind[i] = KIND_SENDREQ
            payload[i, PW_SIZE] = size
            payload[i, PW_DST_OR_SRC] = dst_gid
            payload[i, PW_KEY] = key
            valid[i] = True
        self._window_idx += 1
        return self._prepare(
            self.state,
            jnp.asarray(dstw),
            jnp.asarray(t),
            jnp.asarray(order),
            jnp.asarray(kind),
            jnp.asarray(payload),
            jnp.asarray(valid),
        )

    def _drain_captures(self):
        # cheap guard first: the count vector is H ints vs the full rings
        # being H x cap x 4 words — most windows deliver nothing
        cap_n = np.asarray(jax.device_get(self.state.model["cap_n"]))
        if not cap_n.any():
            return
        m = self.state.model
        ms = dict(
            zip(
                ("cap_t", "cap_src", "cap_key", "cap_size", "cap_flags"),
                jax.device_get(
                    (m["cap_t"], m["cap_src"], m["cap_key"], m["cap_size"],
                     m["cap_flags"])
                ),
            )
        )
        # rings are drained: clear the device-side counters so the guarded
        # batch's probe sees a clean slate and nothing is delivered twice
        self.state = self._clear_caps(self.state)
        for gid in np.nonzero(cap_n > 0)[0]:
            host = self._host_by_gid.get(int(gid))
            if host is None:
                continue  # modeled or mesh-padding lane: no CPU plane

            for j in range(int(cap_n[gid])):
                t = int(ms["cap_t"][gid, j])
                src = int(ms["cap_src"][gid, j])
                key = int(ms["cap_key"][gid, j])
                pkt = None
                if src in self._model_gids:
                    # model-plane origin: there is no byte store. If the
                    # payload still carries our send-request magic, the
                    # modeled peer ECHOED our request payload verbatim
                    # (udp_echo does): reconstruct the endpoint-swapped
                    # reply from our own bytes — exact echo semantics
                    # including ports. Without the magic, the key is just a
                    # model payload word (possibly colliding with a live
                    # key): synthesize a zero-filled datagram instead.
                    echoed = int(ms["cap_flags"][gid, j]) == BYTES_KEY_MAGIC
                    own = self._bytes[gid].pop(key, None) if echoed else None
                    src_ip = self.specs[src].ip
                    if own is not None:
                        q = own[1]
                        pkt = NetPacket(
                            src_ip=src_ip, src_port=q.dst_port,
                            dst_ip=q.src_ip, dst_port=q.src_port,
                            proto=q.proto, payload=q.payload,
                        )
                    else:
                        # no byte store for model-plane origins: synthesize
                        # a zero-filled datagram. Aim it at the host's
                        # lowest bound UDP port (deterministic) so modeled-
                        # initiated traffic actually reaches a native
                        # listener; with none bound, fall back to 40000 and
                        # count it (visible in stats, not a silent drop)
                        size = max(int(ms["cap_size"][gid, j]), 0)
                        # LISTENERS only: explicit binds below the ephemeral
                        # range and not connected to a peer (a connected
                        # client socket would filter our src anyway, and an
                        # autobound client port is not a service endpoint)
                        from shadow_tpu.host.netns import EPHEMERAL_START

                        udp_ports = sorted(
                            port
                            for (proto, port), s in host.netns._ports.items()
                            if proto == 17 and port < EPHEMERAL_START
                            and getattr(s, "peer_ip", None) is None
                        )
                        if udp_ports:
                            dst_port = udp_ports[0]
                        else:
                            dst_port = 40000
                            self._model_pkts_unrouted += 1
                        pkt = NetPacket(
                            src_ip=src_ip, src_port=40000,
                            dst_ip=host.ip, dst_port=dst_port,
                            proto=17, payload=b"\0" * size,
                        )
                else:
                    entry = (
                        self._bytes[src].pop(key, None)
                        if 0 <= src < len(self._bytes)
                        else None
                    )
                    if entry is None:
                        continue  # duplicate capture (cannot happen) or GC'd
                    pkt = entry[1]
                host.schedule(t, functools.partial(host.deliver_packet, pkt))

    def _gc_bytes(self):
        horizon = self._window_idx - _BYTES_GC_WINDOWS
        if horizon <= 0:
            return
        for store in self._bytes:
            dead = [k for k, (w, _) in store.items() if w < horizon]
            for k in dead:  # lost to device-side drop (loss/budget/codel)
                del store[k]

    def _modeled_shard_bytes(self) -> int:
        """The memory monitor's modeled fallback where the backend
        reports no allocator stats (obs/memory.py owns the formula)."""
        from shadow_tpu.obs.memory import modeled_shard_bytes

        return modeled_shard_bytes(
            self.state, self.params, self.engine_cfg.world
        )

    # ---- outputs -----------------------------------------------------------

    def stats_report(self) -> dict:
        s = jax.device_get(self.state.stats)
        n = self._num_real  # exclude mesh-padding hosts
        wall = getattr(self, "_wall_seconds", None)
        sim_s = self.cfg.general.stop_time / NS_PER_SEC
        def pstate(p):  # coroutine procs use ProcState, native procs a str
            snap = getattr(p, "state_at_stop", None)
            return snap if snap is not None else getattr(p.state, "value", p.state)

        runtime_block: dict[str, Any] = {}
        if self.cfg.observability.runtime:
            # runtime observatory block (obs/runtime.py): the bridge-
            # stall split + compile ledger, assembled by the ONE shared
            # helper the modeled driver and bench rows use
            from shadow_tpu.obs.runtime import assemble_runtime_report

            runtime_block = {
                "runtime": assemble_runtime_report(
                    bridge=getattr(self, "_bridge_rt", None),
                    compiles=getattr(self, "_rt_compiles", None),
                    total_wall_s=wall,
                )
            }
        zombies = [p for p in self.procs if pstate(p) == "zombie"]
        failures = sum(
            1
            for p in self.procs
            if (p.expected_final_state == "running" and pstate(p) == "zombie")
            or (
                isinstance(p.expected_final_state, dict)
                and p.expected_final_state.get("exited") is not None
                and p.exit_code != p.expected_final_state["exited"]
            )
        )
        return {
            "simulated_seconds": sim_s,
            "wall_seconds": wall,
            "sim_wall_ratio": (sim_s / wall) if wall else None,
            "windows": getattr(self, "_windows", 0),
            "device_rounds": int(s.rounds),
            "events_processed": int(s.events[:n].sum())
            + sum(h.counters["events"] for h in self.hosts),
            "packets_sent": int(s.pkts_sent[:n].sum()),
            "packets_delivered": int(s.pkts_delivered[:n].sum()),
            "packets_lost": int(s.pkts_lost[:n].sum()),
            "packets_budget_dropped": int(s.pkts_budget_dropped[:n].sum()),
            "packets_codel_dropped": int(s.pkts_codel_dropped[:n].sum()),
            "faults_dropped": int(s.faults_dropped[:n].sum()),
            "faults_delayed": int(s.faults_delayed[:n].sum()),
            "queue_overflow_dropped": int(
                np.asarray(jax.device_get(self.state.queue.dropped))[:n].sum()
            ),
            "queue_occupancy_hwm": int(np.asarray(s.q_occ_hwm)[:n].max())
            if n
            else 0,
            "outbox_send_hwm": max(
                int(np.asarray(s.outbox_hwm).max()), self._ob_hwm_run
            ),
            "unreachable_ips": sum(self._unreach),
            "model_pkts_unrouted": self._model_pkts_unrouted,
            "syscalls": sum(h.counters["syscalls"] for h in self.hosts),
            "process_failures": failures,
            "processes_exited": len(zombies),
            "determinism_digest": f"{int(np.bitwise_xor.reduce(jax.device_get(self.state.stats.digest)[:n])):016x}",
            "perf": self.perf.report(),
            **runtime_block,
            "model_report": self.model.report(
                jax.device_get(self.state.model), None
            ),
            **(
                {"gears": self._gearctl.report()}
                if self._gearctl is not None
                else {}
            ),
            **(
                {"supervisor": self._supervisor.report()}
                if self._supervisor is not None
                else {}
            ),
            **({"aborted": True} if self._aborted else {}),
            **(
                {
                    "pressure": {
                        "policy": self.cfg.pressure.policy,
                        "capacity": self.state.queue.t.shape[1],
                        "outbox": self.state.outbox.t.shape[1],
                        **(
                            {"aborted": True}
                            if self._pressure_aborted else {}
                        ),
                    },
                    "pressure_regrows": 0,
                    "pressure_replays": 0,
                }
                if self.cfg.pressure.active
                else {}
            ),
            **(
                {"pressure_aborted": True, "aborted": True}
                if self._pressure_aborted else {}
            ),
            # integrity sentinel block (core/integrity.py): the hybrid
            # plane has no replay classifier, so the block carries the
            # dual digest fold plus — after an abort — the violation's
            # naming; integrity_aborted keeps a violating state's
            # counters from reading as a trustworthy record
            **(
                {
                    "integrity": {
                        "transients": 0,
                        "replays": 0,
                        "max_replays": self.cfg.integrity.max_replays,
                        **(
                            {"deterministic": {
                                "detail": self._integrity_aborted,
                            }}
                            if self._integrity_aborted else {}
                        ),
                        **(
                            {"determinism_digest2": (
                                f"{int(np.bitwise_xor.reduce(jax.device_get(self.state.stats.digest2)[:n])):016x}"
                            )}
                            if self.engine_cfg.integrity_dual else {}
                        ),
                    },
                }
                if self.engine_cfg.integrity else {}
            ),
            **(
                {"integrity_aborted": True, "aborted": True}
                if self._integrity_aborted else {}
            ),
            **(
                {"poisoned": True}
                if self._supervisor is not None and self._supervisor.poisoned
                else {}
            ),
            **(
                {"trace": self._tracer.summary()}
                if self._tracer is not None
                else {}
            ),
            **(
                {"network": self._network_report(s, n)}
                if self.engine_cfg.netobs
                else {}
            ),
            **(
                {"memory": self._memory_report()}
                if self._memmon is not None
                else {}
            ),
        }

    def _network_report(self, s, n: int) -> dict:
        """Network-observatory block for the hybrid device plane: event
        classes + safe-window telemetry + the per-link fold over the
        modeled lanes (the CPU plane's per-socket/interface counters
        already live in host-stats.json). The hybrid model carries no
        flow port and no per-host hook, so no ledger/model fields."""
        from shadow_tpu.obs.netobs import assemble_network_report, node_map

        return assemble_network_report(
            stats=s,
            num_real=n,
            rounds=int(s.rounds),
            node_of=node_map(self.specs, n),
        )

    def _memory_report(self) -> dict:
        from shadow_tpu.obs.memory import observatory_report

        return observatory_report(
            self.engine, self.state, self.params, self._memmon,
            ledger=self.cfg.observability.memory_ledger,
        )

    def write_outputs(self, data_dir: str | None = None, report: dict | None = None) -> str:
        data_dir = data_dir or self.cfg.general.data_directory
        os.makedirs(data_dir, exist_ok=True)
        with open(os.path.join(data_dir, "processed-config.yaml"), "w") as f:
            yaml.safe_dump(self.cfg.to_dict(), f, sort_keys=False)
        with open(os.path.join(data_dir, "sim-stats.json"), "w") as f:
            json.dump(report or self.stats_report(), f, indent=2)
        with open(os.path.join(data_dir, "hosts.txt"), "w") as f:
            f.write(self.dns.hosts_file())  # reference per-host hostname files
        for spec, host in zip(self.native_specs, self.hosts):
            hd = os.path.join(data_dir, "hosts", spec.name)
            os.makedirs(hd, exist_ok=True)
            for p in host.processes.values():
                base = os.path.join(hd, f"{p.name}.{p.pid}")
                with open(base + ".stdout", "wb") as f:
                    f.write(b"".join(p.stdout))
                with open(base + ".stderr", "wb") as f:
                    f.write(b"".join(p.stderr))
            with open(os.path.join(hd, "host-stats.json"), "w") as f:
                json.dump(
                    {
                        "name": spec.name,
                        "ip": spec.ip,
                        **host.counters,
                        # tracker.c:24-80 analogue: cumulative per-interface
                        # + per-socket wire counters and the per-heartbeat
                        # interval deltas recorded during the run
                        "interfaces": host.if_counters,
                        "sockets": host.socket_stats(),
                        "heartbeats": host.heartbeats,
                        **(
                            {"packet_drops": host.packet_drops}
                            if host.cfg.breadcrumbs
                            else {}
                        ),
                    },
                    f,
                )
        if self._tracer is not None:
            if self._rt_compiles is not None:
                self._tracer.note_compiles(self._rt_compiles.events())
            self._tracer.write_artifacts(
                data_dir, self.cfg.observability, report
            )
        return data_dir


def _log_process_exit(log: SimLogger, host, proc):
    """Per-host process-lifecycle record (the reference stamps every log
    line with sim time + host context; process exits are the load-bearing
    events when debugging a failed expected_final_state)."""
    code = getattr(proc, "exit_code", None)
    sig = getattr(proc, "term_signal", None)
    how = f"signal {sig}" if sig else f"code {code}"
    log.info(
        host.now(), host.name,
        f"process {getattr(proc, 'name', '?')} (pid {proc.pid}) exited with {how}",
    )


def _rr_reorder(staged):
    """Round-robin qdisc (reference QDiscMode::RoundRobin wired into
    network_interface.c): within each source host, interleave this window's
    packets one per originating socket (sockets in first-seen order) instead
    of strict emit-FIFO. Deterministic: depends only on the staged list."""
    by_host: dict[int, dict[int, list]] = {}
    host_order: list[int] = []
    for e in staged:
        gid, sock = e[0], e[5]
        if gid not in by_host:
            by_host[gid] = {}
            host_order.append(gid)
        by_host[gid].setdefault(sock, []).append(e)
    out = []
    for gid in host_order:
        socks = list(by_host[gid].values())
        while any(socks):
            for q in socks:
                if q:
                    out.append(q.pop(0))
    return out


def _clear_caps(state):
    ms = dict(state.model)
    ms["cap_n"] = jnp.zeros_like(ms["cap_n"])
    return state._replace(model=ms)


def _prepare_window(cfg, model, axis, state, dst, t, order, kind, payload, valid):
    """Jitted: clear capture rings + merge staged send-requests. Under a
    mesh the staged arrays arrive replicated with GLOBAL host ids; each
    shard keeps only its own rows and rebases them to shard-local ids."""
    state = _clear_caps(state)
    if axis:
        import jax.lax as _lax

        h_local = state.queue.t.shape[0]
        start = _lax.axis_index(axis).astype(jnp.int64) * h_local
        mine = (dst >= start) & (dst < start + h_local)
        valid = valid & mine
        dst = jnp.clip(dst - start, 0, h_local - 1)
    queue = merge_flat_events(
        state.queue, dst, t, order, kind, payload, valid, cfg.max_round_inserts
    )
    return state._replace(queue=queue)


def run_hybrid(cfg: ConfigOptions, **kw) -> tuple[HybridSimulation, dict]:
    sim = HybridSimulation(cfg, **kw)
    report = sim.run()
    return sim, report
