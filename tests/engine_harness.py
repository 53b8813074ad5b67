"""Shared test harness: build and run small sims directly against the engine
(the config->sim builder layer has its own tests)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu.core import Engine, EngineConfig, EngineParams
from shadow_tpu.models import get_model
from shadow_tpu.net import TBParams


def build_sim(
    model_name: str,
    hosts: list[dict],
    stop: int,
    world: int = 1,
    latency: int = 50_000_000,
    loss: float = 0.0,
    bw_bits: int = 0,
    qcap: int = 32,
    sends_budget: int = 8,
    max_round_inserts: int = 0,
    seed: int = 1,
    runahead_floor: int = 1_000_000,
    use_codel: bool = True,
    cpu_delay_ns: int = 0,
    jitter: int = 0,
    exchange: str = "gather",
    queue_block: int = 0,
    microstep_events: int = 1,
    trace_rounds: int = 0,
    netobs: bool = False,
    flow_records: int = 0,
    integrity: bool = False,
    integrity_dual: bool | None = None,
    faults: dict | None = None,
    bootstrap_end: int = 0,
    rounds_per_chunk: int = 64,
    microstep_limit: int = 0,
    wheel_slots: int = 0,
    wheel_block: int = 0,
    fluid: dict | None = None,
):
    """(cfg, model, params, model_state, initial_events) — shared between the
    device engine runner and the golden reference runner so both see byte-
    identical inputs. `faults` is a `faults:` config dict (FaultOptions
    schema) compiled through the same core/faults path the drivers use;
    `fluid` likewise a `fluid:` config dict (FluidOptions schema) compiled
    through net/fluid.compile_fluid onto the harness's single-node graph
    (every zone id must be 0)."""
    h = len(hosts)
    fault_sched = None
    fault_kw = {}
    if faults:
        from shadow_tpu.config.options import FaultOptions
        from shadow_tpu.core.faults import compile_faults

        fault_sched = compile_faults(
            FaultOptions.from_dict(faults),
            num_hosts=h, stop_time=stop, default_seed=seed,
            bootstrap_end=bootstrap_end,
            name_to_id={d.get("name", f"h{i}"): i
                        for i, d in enumerate(hosts)},
        )
        fault_kw = dict(
            fault_crash_windows=fault_sched.crash_windows,
            fault_loss_windows=fault_sched.loss_windows,
            fault_queue_clear=fault_sched.queue_clear,
        )
    fluid_sched = None
    fluid_kw = {}
    if fluid:
        from shadow_tpu.config.options import FluidOptions
        from shadow_tpu.net.fluid import compile_fluid

        fluid_sched = compile_fluid(
            FluidOptions.from_dict(fluid),
            num_links=1, default_seed=seed,
        )
        if fluid_sched.active:
            fluid_kw = dict(
                fluid_classes=fluid_sched.classes,
                fluid_links=fluid_sched.links,
                fluid_tau_ns=fluid_sched.tau_ns,
                fluid_util_threshold=fluid_sched.util_threshold,
                fluid_loss_max=fluid_sched.loss_max,
                fluid_lat_max_x1000=fluid_sched.lat_max_x1000,
                fluid_seed=fluid_sched.seed,
            )
    cfg = EngineConfig(
        num_hosts=h,
        stop_time=stop,
        bootstrap_end_time=bootstrap_end,
        runahead_floor=runahead_floor,
        static_min_latency=latency,
        queue_capacity=qcap,
        queue_block=queue_block,
        sends_per_host_round=sends_budget,
        max_round_inserts=max_round_inserts or qcap,
        rounds_per_chunk=rounds_per_chunk,
        microstep_limit=microstep_limit,
        world=world,
        use_codel=use_codel,
        cpu_delay_ns=cpu_delay_ns,
        use_jitter=jitter > 0,
        exchange=exchange,
        microstep_events=microstep_events,
        trace_rounds=trace_rounds,
        netobs=netobs,
        flow_records=flow_records,
        # integrity sentinel: dual digest rides along by default when the
        # guards are on (the drivers' IntegrityOptions.dual_digest default)
        integrity=integrity,
        integrity_dual=(
            integrity if integrity_dual is None else integrity_dual
        ),
        wheel_slots=wheel_slots,
        wheel_block=wheel_block,
        **fault_kw,
        **fluid_kw,
    )
    model = get_model(model_name)()
    mparams, mstate, events = model.build(hosts, seed=seed)
    params = EngineParams(
        node_of=jnp.zeros((h,), jnp.int32),
        lat_ns=jnp.full((1, 1), latency, jnp.int64),
        loss=jnp.full((1, 1), loss, jnp.float32),
        jitter_ns=jnp.full((1, 1), jitter, jnp.int64),
        eg_tb=TBParams(
            capacity=jnp.full((h,), 30_000, jnp.int64),
            refill=jnp.full((h,), bw_bits // 1000, jnp.int64),
        ),
        in_tb=TBParams(
            capacity=jnp.full((h,), 30_000, jnp.int64),
            refill=jnp.full((h,), bw_bits // 1000, jnp.int64),
        ),
        model=mparams,
        faults=fault_sched.params if fault_sched is not None else None,
        fluid=(
            fluid_sched.params
            if fluid_sched is not None and fluid_sched.active else None
        ),
    )
    return cfg, model, params, mstate, events


def run_golden_sim(model_name: str, hosts: list[dict], stop: int, seed: int = 1, **kw):
    from shadow_tpu.core.golden import run_golden

    cfg, model, params, mstate, events = build_sim(
        model_name, hosts, stop, world=1, seed=seed, **kw
    )
    return run_golden(cfg, model, params, mstate, events, seed=seed)


def run_sim(
    model_name: str,
    hosts: list[dict],
    stop: int,
    world: int = 1,
    seed: int = 1,
    **kw,
):
    cfg, model, params, mstate, events = build_sim(
        model_name, hosts, stop, world=world, seed=seed, **kw
    )
    mesh = None
    if world > 1:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:world]), ("hosts",))
    eng = Engine(cfg, model, mesh)
    state, params = eng.init_state(params, mstate, events, seed=seed)
    chunks = 0
    while not bool(state.done):
        state = eng.run_chunk(state, params)
        chunks += 1
        assert chunks < 500, "simulation failed to terminate"
    stats = jax.device_get(state.stats)
    report = model.report(jax.device_get(state.model), hosts)
    return state, stats, report


def mk_hosts(n: int, model_args=None, **extra) -> list[dict]:
    return [
        {
            "host_id": i,
            "name": f"h{i}",
            "start_time": 0,
            "model_args": dict(model_args or {}),
            **extra,
        }
        for i in range(n)
    ]
