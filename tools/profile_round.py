"""Knockout profile of the 10k-host PHOLD round: where do the 46 ms go?

Times the full chunk, then variants with the exchange merge stubbed out and
with shaping off, to attribute round cost. The round-1 claim 'sort = 85%'
came from operand-slimming experiments, not a measured knockout — a
round-1 microbenchmark timed the 60k 3-key sort at ~40 us, which cannot be
85% of a 46 ms round.
"""

import sys, pathlib
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import time

import jax

from bench import bench_config
from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.sim import Simulation


def time_chunks(sim, n=4):
    state, params, engine = sim.state, sim.params, sim.engine
    state = engine.run_chunk(state, params)
    jax.block_until_ready(state)
    now0 = int(state.now)
    r0 = int(state.stats.rounds)
    t0 = time.perf_counter()
    for _ in range(n):
        state = engine.run_chunk(state, params)
        jax.block_until_ready(state)  # per-chunk timing
    dt = (time.perf_counter() - t0) / n
    sim_advanced = (int(state.now) - now0) / 1e9
    rounds = max(1, (int(state.stats.rounds) - r0) // n)
    print(f"  sim advanced {sim_advanced:.2f}s over {n} chunks "
          f"({sim_advanced / max(dt * n, 1e-9):.2f} sim-s/wall-s)")
    return dt, dt / rounds * 1e3, state


def build(mutate=None):
    d = bench_config(10_000, 100)
    if mutate:
        mutate(d)
    cfg = ConfigOptions.from_dict(d)
    return Simulation(cfg, world=1)


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "base"

    if which == "nomerge":
        import shadow_tpu.core.engine as e
        from shadow_tpu.ops.merge import merge_empty_plan

        def fake_merge(q, dst, t, order, kind, payload, valid, max_inserts):
            return q

        def fake_plan(q_t, dst, t, order, kind, payload, valid, max_inserts):
            return merge_empty_plan(q_t, payload.shape[-1])

        # the engine fuses the merge below capacity 48 and splits it above
        e.merge_flat_events = fake_merge
        e.merge_plan = fake_plan
        sim = build()
    elif which == "noshaping":
        # strip host bandwidths from the GML -> Simulation auto-elides the
        # whole shaping pipeline (provable no-op path)
        def strip_bw(d):
            d["network"]["graph"]["inline"] = (
                d["network"]["graph"]["inline"]
                .replace('host_bandwidth_down "1 Gbit"', "")
                .replace('host_bandwidth_up "1 Gbit"', "")
            )
        sim = build(strip_bw)
    elif which == "nocodel":
        sim = build(lambda d: d["experimental"].update({"use_codel": False}))
    elif which == "micro1":
        sim = build(lambda d: d["experimental"].update({"microstep_limit": 1}))
    elif which == "cap8":
        sim = build(lambda d: d["experimental"].update({"event_queue_capacity": 8}))
    elif which == "chunk1":
        sim = build(lambda d: d["experimental"].update({"rounds_per_chunk": 1}))
    elif which == "chunk128":
        sim = build(lambda d: d["experimental"].update({"rounds_per_chunk": 128}))
    elif which == "sends2":
        sim = build(lambda d: d["experimental"].update({"sends_per_host_round": 2}))
    else:
        sim = build()

    dt, per_round, state = time_chunks(sim)
    print(f"{which}: chunk={dt*1e3:.1f} ms  per-round={per_round:.2f} ms "
          f"rounds={int(state.stats.rounds)} microsteps={int(state.stats.microsteps[0])} "
          f"events={int(jax.numpy.sum(state.stats.events))}")


if __name__ == "__main__":
    main()
