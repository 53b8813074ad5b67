"""Headline benchmark: simulated-seconds per wall-second on the north-star
workload — 10k-host tgen-TCP all-to-all on a 2D torus (BASELINE.json's
"10k-host tgen all-to-all"; bulk Reno TCP flows between every host pair).

The reference publishes no benchmark tables (SURVEY.md §6) and its scheduler
cannot run here (it requires real managed Linux processes), so `vs_baseline`
is the TPU engine's ratio over the SAME engine executed on the host CPU —
the stand-in for the reference's thread-per-core CPU scheduler that the
north star targets (>=10x on v5e).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "events",
"phold_10k_sim_s_per_wall_s"} — the last key keeps the PHOLD headline
tracked since round 1 as a secondary continuity metric.

Usage: python bench.py                    (full: TPU + CPU-subprocess baseline)
       python bench.py --config N [--cpu] (one BASELINE config, 1-12)
       python bench.py --self [--cpu]     (bare PHOLD ratio, prints a float)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

SMALL = bool(os.environ.get("SHADOW_TPU_BENCH_SMALL"))
NUM_HOSTS = 512 if SMALL else 10_000
# long enough that the steady-state rate dominates: PHOLD's initial burst
# (population x hosts maturing within ~1 sim-s) and the compile chunk are
# excluded, but a short stop_time would still truncate measurement to a
# couple of chunks. Both legs are wall-budget-bounded either way.
SIM_S = 2 if SMALL else 120
CPU_SIM_S = 1 if SMALL else 60  # ratio is time-normalized; budget-bounded


# The reference's PHOLD topology (src/test/phold/phold.yaml: one graph node,
# 50 ms latency, 1 Gbit): the 50 ms lookahead is what makes PHOLD a fair
# PDES benchmark — windows span 50 ms of simulated time per barrier.
PHOLD_GML = """
graph [
  directed 0
  node [
    id 0
    host_bandwidth_down "1 Gbit"
    host_bandwidth_up "1 Gbit"
  ]
  edge [ source 0 target 0 latency "50 ms" packet_loss 0.0 ]
]
"""


def bench_config(num_hosts: int, stop_s: int, rounds_per_chunk: int = 512) -> dict:
    # PHOLD (SURVEY.md §4.4: the reference's in-repo PDES workload) scaled to
    # the 10k-host point: every host holds jobs, matures them after an
    # exponential delay, and forwards to a uniform-random peer — pure
    # steady-state round-loop + cross-shard exchange stress.
    return {
        "general": {"stop_time": f"{stop_s} s", "seed": 1},
        "network": {"graph": {"type": "gml", "inline": PHOLD_GML}},
        "experimental": {
            # static shapes sized to the workload: Poisson(~0.5) events per
            # host per 50 ms window, budgeted with head-room
            "event_queue_capacity": 16,
            "sends_per_host_round": 6,
            # many rounds per dispatch, so the fixed per-chunk dispatch and
            # host round-trip cost is amortized over many rounds. The CPU
            # baseline leg picks its own setting — the knob tunes dispatch
            # amortization, not simulation semantics.
            "rounds_per_chunk": rounds_per_chunk,
        },
        "hosts": {
            "node": {
                "count": num_hosts,
                "network_node_id": 0,
                "processes": [
                    {
                        "model": "phold",
                        "model_args": {
                            "population": 2,
                            "mean_delay": "200 ms",
                            "size_bytes": 64,
                        },
                    }
                ],
            }
        },
    }


def torus_gml(side: int, lat_ms: int = 10) -> str:
    """2D torus of side x side nodes (BASELINE config 2). Every node also
    carries a self-loop at the SAME latency so same-node host pairs route
    and the conservative lookahead stays at `lat_ms` (runahead = min path
    latency — a faster self-loop would shrink every window)."""
    lines = ["graph [", "  directed 0"]
    for i in range(side * side):
        lines.append(
            f'  node [ id {i} host_bandwidth_down "1 Gbit" '
            f'host_bandwidth_up "1 Gbit" ]'
        )
    for r in range(side):
        for c in range(side):
            i = r * side + c
            right = r * side + (c + 1) % side
            down = ((r + 1) % side) * side + c
            lines.append(f'  edge [ source {i} target {i} latency "{lat_ms} ms" ]')
            if right != i:
                lines.append(
                    f'  edge [ source {i} target {right} latency "{lat_ms} ms" ]'
                )
            if down != i:
                lines.append(
                    f'  edge [ source {i} target {down} latency "{lat_ms} ms" ]'
                )
    lines.append("]")
    return "\n".join(lines)


def baseline_config(n: int, small: bool) -> tuple[dict, str, int]:
    """BASELINE.json benchmark configs; returns (config, metric_name, stop_s).

    1: 1k-host udp-echo on the basic graph        (tgen-echo analogue)
    2: 10k-host PHOLD all-to-all on a 2D torus    (routing-gather stress)
    3: 100k-host gossip flood, sparse adjacency   (CSR-in-HBM stress)
    4: 5k-relay Tor-like circuit mix              (packets + continuations)
    5: 1M-host timer-only                         (sort + barrier stress)
    6: 10k-host tgen-TCP all-to-all on the torus  (THE north-star workload:
       bulk Reno TCP flows between every host pair, BASELINE.json target)
    7: PHOLD under host churn + a lossy window    (fault-plane robustness:
       crash/restart masks, fault loss draws, and the run supervisor's
       periodic snapshots all inside the measured loop)
    8: R-replica PHOLD seed sweep (ensemble plane) (one vmapped program
       advances R replicas per dispatch; the row reports aggregate
       replica-rounds/s and the wall-clock ratio vs R sequential solo
       runs — the dispatch-amortization evidence for core/ensemble.py)
    """
    if n == 1:
        hosts = 64 if small else 1000
        cfg = {
            "general": {"stop_time": "60 s", "seed": 1},
            "network": {"graph": {"type": "1_gbit_switch"}},
            "experimental": {"event_queue_capacity": 16,
                             "rounds_per_chunk": 512},
            "hosts": {
                "server": {
                    "network_node_id": 0,
                    "processes": [{"model": "udp_echo",
                                   "model_args": {"role": "server"}}],
                },
                "cli": {
                    "count": hosts - 1,
                    "network_node_id": 0,
                    "processes": [{
                        "model": "udp_echo",
                        "model_args": {"role": "client", "peer": "server",
                                       "interval": "100 ms",
                                       "size_bytes": 512},
                    }],
                },
            },
        }
        return cfg, "echo_1k_sim_seconds_per_wall_second", 60
    if n == 2:
        side = 4 if small else 10
        per_node = 8 if small else 100  # 10k hosts on 100 nodes
        host_groups = {
            f"n{i:03d}": {
                "count": per_node,
                "network_node_id": i,
                "processes": [{
                    "model": "phold",
                    "model_args": {"population": 2, "mean_delay": "200 ms",
                                   "size_bytes": 64},
                }],
            }
            for i in range(side * side)
        }
        # 50 ms edges to match the single-node PHOLD lookahead: the rate
        # delta vs config 0 then isolates the routing-gather cost instead of
        # being dominated by 5x more barrier rounds per simulated second
        cfg = {
            "general": {"stop_time": "120 s", "seed": 1},
            "network": {"graph": {"type": "gml",
                                  "inline": torus_gml(side, lat_ms=50)}},
            "experimental": {"event_queue_capacity": 16,
                             "sends_per_host_round": 6,
                             "rounds_per_chunk": 512,
                             # adaptive merge gears (PR 4): PHOLD at
                             # population 2 stages ~1 send per host per
                             # 50 ms window against a 6-wide budget, so
                             # most chunks should run well below full
                             # merge width — the BENCH row's gear
                             # histogram (counters.gears/gear_rounds) is
                             # the low-occupancy evidence; digests stay
                             # bit-identical by the shed-exact replay
                             "merge_gears": "auto"},
            "hosts": host_groups,
        }
        return cfg, "phold_10k_torus_sim_seconds_per_wall_second", 120
    if n == 3:
        hosts = 2048 if small else 100_000
        cfg = {
            "general": {"stop_time": "30 s", "seed": 1},
            "network": {"graph": {"type": "gml", "inline": PHOLD_GML}},
            "experimental": {"event_queue_capacity": 32,
                             "sends_per_host_round": 10,
                             "rounds_per_chunk": 64},
            "hosts": {
                "pub": {
                    "network_node_id": 0,
                    # repeated floods: a fresh generation every 2 s makes
                    # this a steady-state pubsub measurement instead of a
                    # compile-dominated one-shot
                    "processes": [{"model": "gossip",
                                   "model_args": {"fanout": 8,
                                                  "publisher": True,
                                                  "publish_interval": "2 s"}}],
                },
                "sub": {
                    "count": hosts - 1,
                    "network_node_id": 0,
                    "processes": [{"model": "gossip",
                                   "model_args": {"fanout": 8}}],
                },
            },
        }
        return cfg, "gossip_100k_events_per_wall_second", 30
    if n == 4:
        n_relays = 64 if small else 5000
        n_clients = 32 if small else 2500
        cfg = {
            "general": {"stop_time": "60 s", "seed": 1},
            "network": {"graph": {"type": "gml", "inline": PHOLD_GML}},
            "experimental": {"event_queue_capacity": 32,
                             "sends_per_host_round": 8,
                             "rounds_per_chunk": 256},
            "hosts": {
                "relay": {
                    "count": n_relays,
                    "network_node_id": 0,
                    "processes": [{"model": "circuit",
                                   "model_args": {"role": "relay"}}],
                },
                "cli": {
                    "count": n_clients,
                    "network_node_id": 0,
                    "processes": [{"model": "circuit",
                                   "model_args": {"role": "client",
                                                  "interval": "400 ms"}}],
                },
            },
        }
        return cfg, "circuit_5k_relay_sim_seconds_per_wall_second", 60
    if n == 5:
        hosts = 4096 if small else 1_000_000
        # NO static-shape overrides (r4, VERDICT r3 weak #9): capacities/
        # budget/chunk length auto-size from the host count
        # (ExperimentalOptions.resolve_shapes) — at 1M lanes that derives
        # the measured-good 4/1/8 (HBM fit + the XLA while-loop pathology
        # documented in BASELINE.md) from a plain config. merge_gears is
        # not a shape: it picks among programs of identical state shapes.
        cfg = {
            "general": {"stop_time": "30 s", "seed": 1},
            "network": {"graph": {"type": "gml", "inline": PHOLD_GML}},
            # adaptive merge gears on THE low-occupancy workload: timers
            # never send, so every chunk's outbox high-water is 0 and the
            # controller settles at the bottom gear — the BENCH row's gear
            # histogram (counters.gears) is the "majority of chunks below
            # full merge width" evidence. At the true 1M point the auto
            # send budget is 1, the ladder collapses, and gears self-
            # disable (resolve_gear_ladder returns []) — exactly right,
            # there is no width to shed there.
            "experimental": {"merge_gears": "auto"},
            "hosts": {
                "t": {
                    "count": hosts,
                    "network_node_id": 0,
                    # the small leg ticks 10x faster so the run spans
                    # several chunks (30 rounds is ONE 64-round chunk —
                    # the gear controller, which starts at the top and
                    # downshifts after two low chunks, would never move)
                    "processes": [{"model": "timer",
                                   "model_args": {"interval": (
                                       "100 ms" if small else "1 s")}}],
                },
            },
        }
        return cfg, "timer_1m_sim_seconds_per_wall_second", 30
    if n == 6:
        side = 4 if small else 10
        per_node = 8 if small else 100  # 10k hosts on 100 torus nodes
        # K-way microstep fold (r6): swept with tools/bench_popk.py. On
        # the CPU backend the e2e winner is K=1 — the microstep loop is
        # HANDLER-dispatch bound there (decomposed in BASELINE.md r6:
        # ~15 ms handler vs ~3 ms queue work per microstep at 10k hosts),
        # and folding grows the full-width handler-dispatch count. On TPU
        # the r5 on-chip trace shows the opposite balance (slab passes
        # dominate the ~0.5 ms microstep), which is the regime the fold
        # amortizes — K=4 is the r5-trace-predicted winner there, to be
        # measured the next time a chip is reachable. Digests are
        # bit-identical either way (tests/test_popk.py), so this knob is
        # purely a perf lever and the trajectory stays comparable.
        import jax as _jax

        microstep_events = 1 if _jax.default_backend() == "cpu" else 4
        host_groups = {
            f"n{i:03d}": {
                "count": per_node,
                "network_node_id": i,
                "processes": [{
                    "model": "tgen_tcp",
                    # enough flow cycles to keep every client busy for the
                    # whole horizon (a drained sim would fast-forward and
                    # inflate the rate); cwnd_cap stands in for the peer's
                    # advertised window (models/tgen.py divergence notes)
                    "model_args": {"flows": 8 if small else 64,
                                   "flow_segs": 20 if small else 100,
                                   "cwnd_cap": 16, "mss": 1460,
                                   "flow_gap": "50 ms",
                                   # scanned 1/2/3/4/8 on v5e: 2 is the
                                   # sweet spot between TX-event count and
                                   # per-segment engine work
                                   "tx_batch": 2},
                }],
            }
            for i in range(side * side)
        }
        cfg = {
            "general": {"stop_time": "120 s", "seed": 1},
            "network": {"graph": {"type": "gml",
                                  "inline": torus_gml(side, lat_ms=50)}},
            "experimental": {
                # Every slab pass and the merge sort scale with cap x H and
                # B x H, so both are tuned to the measured drop cliff plus
                # ~15% margin: cap 24 / B 20 drop (cap 26 is margin-free);
                # 28/24 runs the FULL 120 sim-s with zero queue/budget
                # drops and digests identical to the roomy 64/40 shapes,
                # at 10.3 vs 18.1 ms/round. Retune against the drop
                # counters if the workload changes (drops act as loss —
                # protocol-visible).
                "event_queue_capacity": 28,
                # two-level bucketed queue: 4 blocks of 7 slots (B ~ sqrt(C)
                # balances the [H, C/B] + [H, B] levels). Digests are
                # bit-identical to the flat queue (tests/test_bucketq.py);
                # the microstep pop/push pair stops paying full-capacity
                # reductions — see tools/bench_bucketq.py for the sweep.
                "event_queue_block": 7,
                "microstep_events": microstep_events,
                "sends_per_host_round": 24,
                "rounds_per_chunk": 256,
                # no post-sort row cut: measured on this workload (66k
                # sends/round avg, >121k peaks) a 196k truncation was
                # behavior-clean but 2 ms/round SLOWER than the full 410k
                # permute — an XLA scheduling artifact, A/B-verified twice.
                # 128k and below shed (protocol-visible). See BASELINE.md.
            },
            "hosts": host_groups,
        }
        return cfg, "tgen_tcp_10k_torus_sim_seconds_per_wall_second", 120
    if n == 7:
        # fault-plane bench (PR 5): the PHOLD workload with ~30% of hosts
        # crash-restarting mid-run (queue-hold), a mid-run lossy/slow
        # window, and the crash-resilient supervisor snapshotting every 4
        # chunks. Measures what robustness costs on the steady-state round
        # loop: the up/down mask adds one [H, W] pass per microstep, the
        # fault window one draw per send, the supervisor one device copy
        # per 4 chunks. BENCH counters carry faults_dropped/faults_delayed
        # + the supervisor's snapshot/retry counts.
        hosts = 256 if small else 4096
        cfg = {
            "general": {"stop_time": "30 s", "seed": 1},
            "network": {"graph": {"type": "gml", "inline": PHOLD_GML}},
            "experimental": {"event_queue_capacity": 16,
                             "sends_per_host_round": 6,
                             "rounds_per_chunk": 128},
            "faults": {
                "seed": 7,
                "restart_queue": "hold",
                "host_churn": {"prob": 0.3, "mean_downtime": "2 s"},
                "loss_windows": [{"start": "10 s", "end": "15 s",
                                  "loss": 0.2, "latency_factor": 1.5}],
                "supervisor": {"snapshot_every_chunks": 4},
            },
            "hosts": {
                "node": {
                    "count": hosts,
                    "network_node_id": 0,
                    "processes": [{
                        "model": "phold",
                        "model_args": {"population": 2,
                                       "mean_delay": "200 ms",
                                       "size_bytes": 64},
                    }],
                }
            },
        }
        return cfg, "phold_churn_sim_seconds_per_wall_second", 30
    if n == 8:
        # ensemble-plane bench (PR 6): R=4 PHOLD replicas differing only
        # in seed, advanced by ONE vmapped chunk program. The comparison
        # leg runs the same four scenarios as sequential solo runs — the
        # delta is pure dispatch/fixed-cost amortization (BASELINE.md r6:
        # ~83% of the CPU microstep is full-width handler dispatch,
        # identical work per replica).
        # small leg H=8: the scenario-SCREENING shape, where per-replica
        # work is small enough for the fixed dispatch cost to dominate.
        # Measured on this box (per-chunk walls, compile chunk excluded,
        # 12-chunk runs): R=4 ensemble 15.4-16.1k replica-rounds/s vs
        # 12.5k solo => 1.24-1.29x; R=8 reaches ~17k (~1.35x). The win
        # SHRINKS as per-replica work grows — 1.12x at H=12, parity at
        # H=40, and at H>=64 the CPU backend is data-bound (ops scale
        # linearly with R) and solo runs win. Same honest posture as the
        # K-way fold (config 6): the CPU crossover is documented; the TPU
        # regime, where a dispatch costs far more than a small replica's
        # work, is the predicted winner, not measured on a chip yet.
        hosts = 8 if small else 4096
        stop_s = 40 if small else 30
        cfg = {
            "general": {"stop_time": f"{stop_s} s", "seed": 1},
            "network": {"graph": {"type": "gml", "inline": PHOLD_GML}},
            "experimental": {"event_queue_capacity": 16,
                             "sends_per_host_round": 6,
                             "rounds_per_chunk": 64},
            "campaign": {"seeds": [1, 2, 3, 4], "ledger_file": None},
            "hosts": {
                "node": {
                    "count": hosts,
                    "network_node_id": 0,
                    "processes": [{
                        "model": "phold",
                        "model_args": {"population": 2,
                                       "mean_delay": "200 ms",
                                       "size_bytes": 64},
                    }],
                }
            },
        }
        return cfg, "phold_seed_sweep_replica_rounds_per_second", stop_s
    if n == 9:
        # pressure-plane bench (PR 8): PHOLD with a DELIBERATELY
        # undersized queue capacity (population 6 against 8 slots — the
        # seed shapes would shed silently) under `pressure: escalate`.
        # Measures what drop-free operation costs: the first pressured
        # chunk aborts in-jit, replays once at a grown slab, and the
        # proactive headroom check absorbs further growth at chunk
        # boundaries — the BENCH row carries the regrow/replay counters
        # (counters.pressure) plus the zero drop totals that prove the
        # escalation did its job.
        hosts = 256 if small else 4096
        cfg = {
            "general": {"stop_time": "30 s", "seed": 1},
            "network": {"graph": {"type": "gml", "inline": PHOLD_GML}},
            "experimental": {"event_queue_capacity": 8,
                             "sends_per_host_round": 6,
                             "rounds_per_chunk": 128},
            "pressure": {"policy": "escalate", "max_capacity": 64},
            "hosts": {
                "node": {
                    "count": hosts,
                    "network_node_id": 0,
                    "processes": [{
                        "model": "phold",
                        "model_args": {"population": 6,
                                       "mean_delay": "200 ms",
                                       "size_bytes": 64},
                    }],
                }
            },
        }
        return cfg, "phold_pressure_sim_seconds_per_wall_second", 30
    if n == 10:
        # integrity-sentinel bench (PR 11): the flagship tgen-TCP torus
        # shapes (config 6) with the in-jit invariant guards ON — what
        # always-on SDC detection costs on the north-star workload. The
        # guards are a handful of reductions per ROUND (one [H, C]
        # compare for the slab floor + per-lane monotonicity compares),
        # amortized over the round's microsteps; the BENCH row carries
        # the integrity{transients,replays} counters so a box's scribble
        # waves show up as counted, survived events instead of silent
        # poison, and tools/bench_compare.py fails the diff if a
        # deterministic violation ever appears.
        cfg, _, stop_s = baseline_config(6, small)
        cfg["integrity"] = {"enabled": True}
        return cfg, "tgen_tcp_integrity_sim_seconds_per_wall_second", stop_s
    if n == 11:
        # timer-wheel bench: the flagship tgen-TCP torus (config 6)
        # with the device timer wheel ON. What moves and why:
        #   - RTO/DELACK timers (10.9% of small-leg events, dominant at
        #     1M-flow scale per tools/net_report.py) leave the event
        #     queue for the [H, S] wheel, so every [H, C] slab pass
        #     (pop reductions, push free-ranking, merge free-ranking)
        #     runs at a SMALLER C — the queue no longer has to hold
        #     pending timers: capacity drops 28 -> 14 (the measured
        #     no-drop high-water 13 + 1 margin, same tuning rule as
        #     config 6's drop cliff; digests identical to the roomy
        #     shapes).
        # Measured on this box (CPU small leg, 3 paired subprocess runs,
        # digests bit-identical to config 6's 28/7 trajectory, zero
        # drops): base 28/7 median 13.24 sim-s/wall-s vs wheel-4 +
        # cap 14/7 median 13.87 — the wheel wins every paired rep
        # (+1.2/+6.4/+3.7%) because the queue runs at HALF the slab
        # capacity (q_occ_hwm 13 with timers off-queue; cap 14 = the
        # measured no-drop high-water + 1, and a drop would be loud:
        # counted counters + bench_compare FCT gates). Wheel slots 4 =
        # measured occupancy hwm (1-2) with margin; spills are exact
        # and counted.
        # microstep_events pins 1 (the wheel's K-fold composition is
        # follow-up work; K=1 is also config 6's measured CPU winner).
        cfg, _, stop_s = baseline_config(6, small)
        ex = cfg["experimental"]
        ex["timer_wheel"] = 4
        ex["event_queue_capacity"] = 14
        ex["event_queue_block"] = 7
        ex["microstep_events"] = 1
        cfg["observability"] = {"network": True}
        return cfg, "tgen_tcp_wheel_sim_seconds_per_wall_second", stop_s
    if n == 12:
        # fluid-traffic-plane bench (PR 13): the flagship tgen-TCP torus
        # (config 6) as the packet-exact FOREGROUND plus a flash-crowd
        # BACKGROUND schedule on the fluid plane — the first ISP/CDN-
        # scale scenario shape the pure packet engine cannot reach
        # (emulating the crowd packet-exactly would blow the event
        # budget). Four staggered background classes converge on torus
        # node 0 from t=5s (the flash ramp — EARLY, inside the
        # foreground's active phase: the fluid plane is passive, it
        # generates no events, so a drained foreground ends the sim
        # regardless of pending background windows), each demanding most
        # of a 2 Gbit access link, so background bytes dwarf the tgen
        # foreground byte volume while
        # the DropTail clip and the >= 1.0x latency coupling stay
        # honest: coupling is latency-only here (loss_max 0), so the
        # foreground sees congestion as inflated RTTs — zero unexplained
        # drops — and the FCT distribution (network{} block) quantifies
        # the foreground cost against config 6's fluid-off calibration.
        # The fluid{} block carries bg_bytes/bg_dropped for
        # tools/bench_compare.py's coverage gates.
        cfg, _, stop_s = baseline_config(6, small)
        cfg["observability"] = {"network": True}
        # shorter chunks than config 6's 256: on this box the documented
        # jaxlib-0.4.37 corruption (docs/corruption.md) hits the
        # inflated-RTT execution profile's LONG single dispatches at a
        # very high per-attempt rate (rpc=256 aborted ~9/10 attempts
        # with glibc "corrupted double-linked list"; rpc<=128 completes
        # with BIT-IDENTICAL results — bg/digest equal across every
        # surviving rpc, so this is dispatch-length exposure, not a
        # results change). 128 keeps the leg inside the classify-then-
        # retry posture's budget.
        cfg["experimental"]["rounds_per_chunk"] = 128
        cfg["fluid"] = {
            "link_capacity": "2 Gbit",
            "latency_factor_max": 1.5,
            "util_threshold": 0.5,
            "classes": [
                {"name": f"crowd{i}", "src_zone": z, "dst_zone": 0,
                 "rate": "1500 Mbit", "start": f"{5 + i} s"}
                for i, z in enumerate((1, 2, 3, 5))
            ],
        }
        return cfg, "tgen_tcp_fluid_sim_seconds_per_wall_second", stop_s
    raise SystemExit(f"unknown --config {n} (1-12 supported)")


def _campaign_worker(leg: str, small: bool, wall_budget_s: float) -> dict:
    """One bench-8 measurement leg, run in a FRESH subprocess (see
    measure_campaign for why): per-chunk walls so the parent can exclude
    the compile chunk without an extra warmup dispatch. `leg` is
    "ensemble" (the whole R-replica vmapped campaign) or "solo:<i>" (ONE
    replica built and run exactly as a solo simulation)."""
    import jax
    import numpy as _np

    from tools.campaign import build_campaign, expand_replicas, replica_config_dict
    from shadow_tpu.config.options import ConfigOptions
    from shadow_tpu.sim import Simulation

    from shadow_tpu.obs.runtime import CompileLedger

    cfg_dict, _, _ = baseline_config(8, small)
    rpc = cfg_dict["experimental"]["rounds_per_chunk"]
    t_build = time.monotonic()
    # runtime observatory: the compile ledger records each leg's
    # program compiles precisely (jax.monitoring), so the parent's
    # runtime{} block carries measured compile wall, not an estimate
    rt_compiles = CompileLedger()
    if leg == "ensemble":
        c = build_campaign(cfg_dict)
        c.engine.attach_compile_ledger(rt_compiles)
        state, params = c.state, None
        run_chunk = c.engine.run_chunk
        r_count = c.num_replicas

        def _done(st):
            return bool(_np.asarray(jax.device_get(st.done)).all())
    else:
        idx = int(leg.split(":", 1)[1])
        spec = expand_replicas(ConfigOptions.from_dict(cfg_dict))[idx]
        sim = Simulation(
            ConfigOptions.from_dict(replica_config_dict(cfg_dict, spec)),
            world=1,
        )
        sim.engine.attach_compile_ledger(rt_compiles)
        state, params = sim.state, sim.params
        run_chunk = sim.engine.run_chunk
        r_count = 1

        def _done(st):
            return bool(st.done)
    build_s = time.monotonic() - t_build
    walls: list[float] = []
    t_run = time.monotonic()
    while not _done(state):
        t0 = time.monotonic()
        state = (
            run_chunk(state) if params is None else run_chunk(state, params)
        )
        jax.block_until_ready(state)
        walls.append(time.monotonic() - t0)
        # budget the post-compile window (walls[0] carries the compile)
        if time.monotonic() - t_run - walls[0] >= wall_budget_s:
            break
    s = jax.device_get(state.stats)
    # per-replica digests and rounds: the parent's poison gate. This
    # box's documented corruption can scribble device state WITHOUT
    # crashing (tools/soak.py classifies the same mode) — a poisoned
    # solo run yields wrong dynamics and a garbage rate, so the parent
    # accepts a solo leg only when its digest/rounds equal its ensemble
    # lane's (the vmap-vs-solo bit-identity property makes the ensemble
    # leg the free ground truth).
    digests = _np.asarray(s.digest).reshape(r_count, -1)
    rounds_arr = _np.asarray(s.rounds).reshape(r_count)
    # memory observatory: one post-run sample (modeled fallback on CPU)
    # so the config-8 BENCH row carries an hbm block like every other.
    # The ensemble leg's params live on the campaign engine (the solo
    # leg passes them explicitly).
    from shadow_tpu.obs.memory import (
        MemoryMonitor, modeled_shard_bytes, tree_bytes,
    )

    live_params = params if params is not None else c.engine._params
    memmon = MemoryMonitor([jax.devices()[0]])
    memmon.sample(modeled_bytes=modeled_shard_bytes(state, live_params))
    return {
        "hbm": memmon.report(),
        "state_bytes": tree_bytes(state),
        "leg": leg,
        "replicas": r_count,
        "rpc": rpc,
        # runtime observatory: measured compile walls + the sim horizon
        # the leg reached (feeds the parent row's runtime{} block)
        "compiles": rt_compiles.summary(),
        "sim_ns": int(_np.asarray(jax.device_get(state.now)).max()),
        "walls": [round(w, 5) for w in walls],
        "rounds": int(_np.asarray(s.rounds).sum()),
        "replica_rounds": [int(r) for r in rounds_arr],
        "replica_digests": [
            f"{int(_np.bitwise_xor.reduce(d)):016x}" for d in digests
        ],
        "events": int(_np.asarray(s.events).sum()),
        "done": _done(state),
        "build_s": round(build_s, 2),
        "queue_occupancy_hwm": int(_np.asarray(s.q_occ_hwm).max()),
        "outbox_send_hwm": int(_np.asarray(s.outbox_hwm).max()),
    }


def _corruption_rcs() -> tuple[int, ...]:
    """Worker exit signatures of this box's documented jaxlib-0.4.37
    compiled-run corruption (CHANGES.md env notes). tools/corruption.py
    owns the canonical catalog (stdlib-only — no test infra, no JAX)."""
    from tools.corruption import HEAP_CORRUPTION_RCS

    return HEAP_CORRUPTION_RCS


def _run_campaign_leg(leg: str, small: bool, wall_budget_s: float,
                      attempts: int = 6, timeout_s: float = 420.0,
                      validate=None) -> dict:
    """Spawn `_campaign_worker(leg)` in a fresh subprocess, retrying the
    known corruption signatures AND results `validate` rejects (the
    silent-scribble flavor: a worker that completes with poisoned device
    state — validate returns a reason string, or None to accept).
    Returns the worker's JSON dict, or {"skipped": reason} when every
    attempt died or was rejected."""
    cmd = [sys.executable, os.path.abspath(__file__),
           "--campaign-worker", leg,
           "--campaign-budget", str(wall_budget_s)]
    if small:
        cmd.append("--small")
    last = None
    for attempt in range(attempts):
        if attempt:
            time.sleep(3)  # the corruption is phase-y; spacing helps
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=timeout_s,
            )
        except subprocess.TimeoutExpired:
            last = "timeout"
            continue
        out = proc.stdout.strip()
        if proc.returncode == 0 and out:
            result = json.loads(out.splitlines()[-1])
            reason = validate(result) if validate is not None else None
            if reason is None:
                return result
            last = f"poisoned: {reason}"
            continue
        if proc.returncode in _corruption_rcs():
            last = f"rc={proc.returncode}"
            continue
        # any other failure is a REAL bug in the worker path (ConfigError,
        # ImportError, ...) — fail loudly, never classify it as the
        # environment
        raise RuntimeError(
            f"campaign worker {leg} failed rc={proc.returncode}: "
            f"{proc.stderr[-500:]}"
        )
    return {"leg": leg, "skipped":
            f"{attempts} attempts died of the known corruption ({last})"}


def post_compile_stats(
    walls: list[float], rounds: int | None = None, rpc: int = 0,
    replicas: int = 1,
) -> tuple[float, int | None]:
    """THE shared compile-chunk exclusion rule (runtime-observatory
    satellite): given per-chunk walls, return (post-compile wall,
    post-compile rounds). walls[0] carries the jit compile, and its
    chunk always retires the full rounds_per_chunk x replicas (no
    replica can finish before its first chunk ends), so both are
    excluded exactly. Every bench path routes its exclusion through
    here — `measure` (the --self PHOLD legs), `measure_campaign` (the
    config-8 subprocess legs this rule generalizes), and the
    runtime{} block's ex-compile rates — so sim-s/wall-s never
    silently folds a cold compile in. When the whole run fit inside
    the compile chunk, that chunk IS the measurement (counted)."""
    if len(walls) < 2:
        return max(sum(walls), 1e-9), rounds
    w = max(sum(walls[1:]), 1e-9)
    if rounds is None:
        return w, None
    return w, rounds - rpc * replicas


def _leg_run_stats(w: dict) -> tuple[float, int]:
    """(post-compile wall, post-compile rounds) for one config-8 worker
    result, via the shared `post_compile_stats` rule."""
    wall, rounds = post_compile_stats(
        w["walls"], w["rounds"], w["rpc"], w["replicas"]
    )
    return wall, rounds


def measure_campaign(small: bool, wall_budget_s: float = 120.0) -> dict:
    """BASELINE config 8: the ensemble-plane leg. Runs the R-replica
    vmapped campaign AND R sequential solo runs, each leg in a FRESH
    subprocess (this box's documented jaxlib-0.4.37 corruption targets
    exactly the solo small-dispatch pattern — tools/soak.py posture:
    retry the signature, classify honestly, never let a poisoned process
    fabricate a number), with per-chunk walls so the compile chunk drops
    out of both legs identically. Reports aggregate replica-rounds per
    wall-second and the solo/ensemble rate ratio."""
    cfg_dict, metric, stop_s = baseline_config(8, small)
    r_count = len(cfg_dict["campaign"]["seeds"])
    ens = _run_campaign_leg("ensemble", small, wall_budget_s,
                            timeout_s=wall_budget_s + 300)
    if "skipped" in ens:
        # no ensemble measurement = no metric AND no ground truth for the
        # solo poison gate — skip the solo legs entirely (each would cost
        # up to `attempts` full subprocess runs) and report the
        # classification instead of a number (soak.py SKIP posture)
        return {
            "metric": metric,
            "unit": "replica_rounds/wall_s",
            "sim_seconds": stop_s,
            "counters": {"replicas": r_count},
            "value": None,
            "skipped": ens["skipped"],
        }

    def _solo_gate(i):
        # accept a solo worker only when it reproduced its ensemble
        # lane bit-exactly (digest + rounds) — both legs done. A
        # budget-truncated leg can't be digest-checked; accept it.
        def check(w):
            if not (w["done"] and ens["done"]):
                return None
            if w["replica_rounds"][0] != ens["replica_rounds"][i]:
                return (f"rounds {w['replica_rounds'][0]} != ensemble "
                        f"lane {ens['replica_rounds'][i]}")
            if w["replica_digests"][0] != ens["replica_digests"][i]:
                return "digest mismatch vs ensemble lane"
            return None
        return check

    solos = [
        _run_campaign_leg(f"solo:{i}", small, wall_budget_s,
                          timeout_s=wall_budget_s + 300,
                          validate=_solo_gate(i))
        for i in range(r_count)
    ]
    row = {
        "metric": metric,
        "unit": "replica_rounds/wall_s",
        "sim_seconds": stop_s,
        "counters": {"replicas": r_count},
    }
    wall_ens, rounds_ens = _leg_run_stats(ens)
    row.update({
        "value": round(rounds_ens / wall_ens, 3),
        "events": ens["events"],
        "wall_seconds_ensemble": round(wall_ens, 4),
        "first_chunk_s": round(ens["walls"][0], 1),
        "build_s": ens["build_s"],
    })
    row["counters"].update({
        "rounds": ens["rounds"],
        "chunks": len(ens["walls"]),
        "queue_occupancy_hwm": ens["queue_occupancy_hwm"],
        "outbox_send_hwm": ens["outbox_send_hwm"],
    })
    if "hbm" in ens:
        # R replicas multiply the state. Deliberately NOT under the
        # `total_bytes` key other rows use (their figure is per-shard
        # state+params from static_model; this one is the stacked
        # replica-state total) — a shared key with different semantics
        # would poison cross-row diffs in tools/bench_compare.py.
        row["hbm"] = {
            **ens["hbm"],
            "model": {
                "stacked_state_bytes": ens.get("state_bytes"),
                "per_replica_state_bytes": (
                    ens.get("state_bytes", 0) // max(r_count, 1)
                ),
                "replicas": r_count,
            },
        }
    # runtime{} block (runtime observatory): the worker's compile
    # ledger + the leg's realtime factor, with the ex-compile rate so
    # sim-s/wall-s never silently folds a cold compile in (the shares
    # split needs the driver's WallLedger, which the minimal worker
    # loop does not carry — per-phase shares live on configs 1-12)
    comp = ens.get("compiles") or {}
    total_wall = max(sum(ens["walls"]), 1e-9)
    sim_s = ens.get("sim_ns", 0) / 1e9
    cw = comp.get("compile_wall_s", 0.0)
    row["runtime"] = {
        "compile_wall_s": cw,
        # the whole leg is the measured window here, so every compile
        # the worker's ledger recorded landed inside it — the factor
        # below folds them in, the ex-compile factor is the clean one
        # (bench_runtime_block semantics: window == the measured span)
        "compile_in_window_s": cw,
        "compile_programs": comp.get("programs", 0),
        "cache_hits": comp.get("cache_hits", 0),
        "realtime_factor": round(sim_s / total_wall, 4),
        "realtime_factor_ex_compile": round(
            sim_s / max(total_wall - cw, 1e-9), 4,
        ),
    }
    ok_solos = [w for w in solos if "skipped" not in w]
    if ok_solos:
        # rate ratio over the measured solos (fair even when some solo
        # workers died: rates, not raw walls, so a missing replica does
        # not deflate the solo side)
        wall_solo = sum(_leg_run_stats(w)[0] for w in ok_solos)
        rounds_solo = sum(_leg_run_stats(w)[1] for w in ok_solos)
        solo_rate = rounds_solo / wall_solo
        row.update({
            "wall_seconds_solo_total": round(wall_solo, 4),
            "solo_replicas_measured": len(ok_solos),
            "solo_replica_rounds_per_s": round(solo_rate, 3),
            "solo_over_ensemble": round(row["value"] / solo_rate, 3),
        })
    else:
        row["solo_leg_skipped"] = solos[0].get("skipped", "no solo results")
    return row


def _bench_network(sim, state, s, netcol) -> dict:
    """The BENCH row's compact network{} block: the SAME shared assembly
    sim-stats uses (obs/netobs.assemble_network_report), compacted to
    the diffable bench shape — rows cannot drift from sim-stats."""
    import numpy as _np

    from shadow_tpu.obs.netobs import (
        assemble_network_report, bench_network_block, node_map,
    )

    n = sim._num_real
    import jax as _jax

    model_view = _jax.tree.map(
        lambda a: _np.asarray(a)[:n], _jax.device_get(state.model)
    )
    return bench_network_block(assemble_network_report(
        stats=s,
        num_real=n,
        rounds=int(s.rounds),
        node_of=node_map(sim.hosts, n),
        model=sim.model,
        model_state=model_view,
        flow_ledger=sim.engine_cfg.flow_ledger_active,
        collector=netcol,
    ))


def _bench_fluid(sim, state, s) -> dict:
    """The BENCH row's compact fluid{} block: the SAME shared assembly
    sim-stats uses (net/fluid.assemble_fluid_report), compacted to the
    diffable bench shape — rows cannot drift from sim-stats."""
    import jax as _jax

    from shadow_tpu.net.fluid import assemble_fluid_report, bench_fluid_block

    return bench_fluid_block(assemble_fluid_report(
        stats=s,
        fluid_state=_jax.device_get(state.fluid),
        cfg=sim.engine_cfg,
    ))


def measure_config(n: int, small: bool, wall_budget_s: float = 120.0) -> dict:
    """Run one BASELINE config; returns the JSON-able result row."""
    if n == 8:
        # the ensemble leg has its own two-leg harness (vmapped campaign
        # vs sequential solos) — everything below assumes one Simulation
        return measure_campaign(small, wall_budget_s)
    import jax

    from shadow_tpu.config.options import ConfigOptions
    from shadow_tpu.sim import Simulation

    from shadow_tpu.obs import RoundTracer

    cfg_dict, metric, stop_s = baseline_config(n, small)
    # the round tracer rides along (PR 3 observability): digests and event
    # counts are bit-identical with it on (tests/test_tracer.py), and the
    # drained ring hands future perf PRs the per-round decomposition the
    # first two PRs had to reconstruct by hand (BASELINE.md r5/r6).
    # Measurement note: tracing is now part of the measured configuration
    # (BENCH rows from this round on include it). Its cost inside the wall
    # window is one extra row write per round in-jit plus a per-chunk
    # device_get of the [1, R, F] i64 ring (F = tracer.TRACE_COLS,
    # ~tens of KB against a
    # multi-second 256-512-round chunk; the block_until_ready was already
    # there) — well under the run-to-run noise floor.
    cfg_dict.setdefault("observability", {})["trace"] = True
    # network observatory (PR 10): measured-in like the tracer — digests
    # are bit-identical with it on (tests/test_netobs.py), its in-jit
    # cost is a handful of [H] masks+sums per event, and the BENCH row
    # gains the network{} block (timer-event share, FCT p50/p99, link
    # hot-spot) tools/bench_compare.py diffs for flow-behavior
    # regressions, not just wall-clock ones.
    cfg_dict["observability"]["network"] = True
    cfg = ConfigOptions.from_dict(cfg_dict)
    t_build = time.monotonic()
    sim = Simulation(cfg, world=1)
    state, params, engine = sim.state, sim.params, sim.engine
    # runtime observatory (obs/runtime.py): measured in like the tracer
    # and the network observatory — host-side only, digest-identical by
    # the same gates. The compile ledger records every program the run
    # compiles (base + gear variants + pressure rungs), the WallLedger
    # splits each chunk's wall into spans, and the row gains the
    # runtime{} block tools/bench_compare.py diffs (realtime-factor
    # drop or compile-wall growth = regression).
    from shadow_tpu.obs.runtime import (
        CompileLedger, WallLedger, bench_runtime_block,
    )

    rt_compiles = CompileLedger()
    engine.attach_compile_ledger(rt_compiles)
    wallled = WallLedger()
    rt_compiles.wall = wallled
    tracer = RoundTracer(sim.engine_cfg.rounds_per_chunk)
    from shadow_tpu.obs.netobs import FlowCollector

    netcol = (
        FlowCollector(sim.engine_cfg.flow_records)
        if sim.engine_cfg.flow_ledger_active else None
    )
    # adaptive merge gears (PR 4): when the config opts in, drive chunks
    # through the same shed-exact controller loop the Simulation driver
    # uses — the BENCH row then carries the gear histogram (chunks per
    # gear + rounds per gear from the trace ring)
    from shadow_tpu.core.gears import GearController
    from shadow_tpu.core.pressure import PressureAbort, ResilienceController
    from shadow_tpu.core.supervisor import SupervisorAbort

    # HBM observatory (obs/memory.py): per-shard live sampling folded
    # into the BENCH row's `hbm` block — peak bytes per shard, the
    # static model's predicted bytes, and headroom where the backend
    # has an allocator limit (CPU backends fall back to the exact
    # modeled live bytes, so the high-water is honest, never zero).
    # Sampling is one memory_stats call + a metadata pytree walk per
    # chunk — noise-floor cost; the per-rung compiled ledger is NOT
    # computed here (it recompiles programs, which would perturb the
    # measured window).
    from shadow_tpu.obs.memory import (
        MemoryMonitor, modeled_shard_bytes, static_model,
    )

    memmon = MemoryMonitor([jax.devices()[0]])

    def _sample_memory(st):
        memmon.sample(modeled_bytes=modeled_shard_bytes(
            st, params, sim.engine_cfg.world
        ))

    gearctl = GearController(sim._gear_ladder) if sim._gear_ladder else None
    # the shared snapshot-replay loop (core/pressure.py): gears and/or
    # pressure escalation, exactly as the Simulation driver wires it —
    # config 9's BENCH row measures drop-free-under-pressure end to end
    resil = None
    if gearctl is not None or cfg.pressure.active or cfg.integrity.enabled:
        resil = ResilienceController(
            gearctl=gearctl,
            pressure=cfg.pressure if cfg.pressure.active else None,
            integrity=cfg.integrity if cfg.integrity.enabled else None,
            queue_block=sim.engine_cfg.queue_block,
            wall=wallled,
        )
    ob_hwm_run = 0  # run-wide outbox high-water (gear runs reset the
    # device counter per chunk, so the run max is folded host-side)
    # crash-resilient supervisor (PR 5): when the config opts in, chunks
    # dispatch through the same snapshot/retry loop the Simulation driver
    # uses, so the BENCH row measures robustness-on (and carries the
    # snapshot/retry counts in `counters.supervisor`)
    sup = None
    if cfg.faults.supervisor.enabled:
        from shadow_tpu.core.supervisor import ChunkSupervisor

        sup = ChunkSupervisor(
            snapshot_every_chunks=cfg.faults.supervisor.snapshot_every_chunks,
            max_retries=cfg.faults.supervisor.max_retries,
            backoff_base_s=cfg.faults.supervisor.backoff_base_ms / 1000.0,
            wall=wallled,
        )
        sup.note_state(state)

    def _step_raw(state):
        nonlocal ob_hwm_run
        if resil is None:
            state = engine.run_chunk(state, params)
            jax.block_until_ready(state)
            return state

        def dispatch(st, gear, cap, budget):
            return engine.run_chunk_resized(st, params, gear, cap, budget)

        state, _, hwm = resil.run_chunk(state, dispatch)
        ob_hwm_run = max(ob_hwm_run, hwm)
        return state

    sup_aborted = False
    press_aborted = False
    integ_aborted = False

    from shadow_tpu.core.integrity import IntegrityAbort

    def step(state):
        nonlocal sup_aborted, press_aborted, integ_aborted
        try:
            if sup is None:
                return _step_raw(state)
            return sup.run_chunk(state, _step_raw)
        except IntegrityAbort as e:
            # deterministic violation (or a persistently non-reproducing
            # one): export the last good pre-chunk snapshot — the
            # violating attempt's state is by definition corrupt — and
            # let the row carry the abort naming for bench_compare
            print(f"[integrity] aborting bench run: {e}", file=sys.stderr)
            integ_aborted = True
            sup_aborted = True  # stops the measurement loops
            good = resil.abort_export_state()
            return good if good is not None else state
        except PressureAbort as e:
            # same honest-artifacts posture as the drivers: abort policy
            # exports the dropping state, escalate-cornered the last
            # good pre-chunk snapshot (abort_export_state docs this)
            print(f"[pressure] aborting bench run: {e}", file=sys.stderr)
            press_aborted = True
            sup_aborted = True  # stops the measurement loops
            good = resil.abort_export_state()
            return good if good is not None else state
        except SupervisorAbort as e:
            # same graceful-abort contract as the drivers: the BENCH row
            # carries the completed prefix's counters, exported from the
            # supervisor's snapshot (abort_export_state docs the
            # poisoned/donation rationale; supervisor.aborted flags it)
            print(f"[supervisor] aborting bench run: {e}", file=sys.stderr)
            sup_aborted = True
            good = sup.abort_export_state()
            return good if good is not None else state

    t0 = time.monotonic()
    build_s = t0 - t_build  # capture BEFORE t0 is reused for measurement
    wallled.sync_sim(int(state.now))
    wallled.chunk_start()
    with wallled.span("dispatch"):
        state = step(state)  # compile + first chunk (controller at top)
    compile_s = time.monotonic() - t0
    with wallled.span("export"):
        tracer.drain(state.trace, wall_t0=t0, wall_t1=time.monotonic())
        if netcol is not None:
            netcol.drain(state.flows)
        _sample_memory(state)
    wallled.chunk_end(int(state.now))
    if gearctl is not None:
        # pre-warm the LOWER gear programs outside the timed window: the
        # controller reaches them only a few chunks in, and their
        # first-call jit compile would otherwise land inside the measured
        # loop and be charged to sim-s/wall-s (each runs one chunk on a
        # throwaway snapshot copy — the real state is untouched)
        from shadow_tpu.core.checkpoint import snapshot_state

        for g in sim._gear_ladder[:-1]:
            jax.block_until_ready(
                engine.run_chunk_gear(snapshot_state(state), params, g)
            )
    sim0 = int(state.now)
    ev0 = int(jax.device_get(state.stats.events).sum())
    t0 = time.monotonic()
    while not bool(state.done) and not sup_aborted:
        t_c = time.monotonic()
        wallled.chunk_start()
        with wallled.span("dispatch"):
            state = step(state)
        with wallled.span("export"):
            tracer.drain(
                state.trace, wall_t0=t_c, wall_t1=time.monotonic()
            )
            if netcol is not None:
                netcol.drain(state.flows)
            _sample_memory(state)
        wallled.chunk_end(int(state.now))
        if time.monotonic() - t0 >= wall_budget_s:
            break
    wall = max(time.monotonic() - t0, 1e-9)
    sim_adv = (int(state.now) - sim0) / 1e9
    ev_adv = int(jax.device_get(state.stats.events).sum()) - ev0
    if sim_adv <= 0 and ev_adv <= 0 and not sup_aborted:
        # whole sim fit inside the compile chunk: rebuild FRESH STATE but
        # drive it with the ALREADY-COMPILED engine (a new Engine would
        # build a new jit closure and silently recompile — the "clean"
        # run would time a second compile, which is exactly the artifact
        # this branch exists to exclude)
        sim2 = Simulation(cfg, world=1)
        state = sim2.state
        tracer = RoundTracer(sim.engine_cfg.rounds_per_chunk)  # fresh cursor
        if netcol is not None:
            netcol = FlowCollector(sim.engine_cfg.flow_records)
        if sup is not None:
            # re-arm on the FRESH state: without this, a dispatch failure
            # in the rerun loop would restore the finished first run's
            # near-done snapshot and the row would report its totals over
            # the rerun's tiny wall time
            sup.note_state(state)
        wallled.sync_sim(int(state.now))
        t0 = time.monotonic()
        while not bool(state.done) and not sup_aborted:
            t_c = time.monotonic()
            wallled.chunk_start()
            with wallled.span("dispatch"):
                state = step(state)
            with wallled.span("export"):
                tracer.drain(
                    state.trace, wall_t0=t_c, wall_t1=time.monotonic()
                )
                if netcol is not None:
                    netcol.drain(state.flows)
                _sample_memory(state)
            wallled.chunk_end(int(state.now))
        wall = max(time.monotonic() - t0, 1e-9)
        sim_adv = int(state.now) / 1e9
        ev_adv = int(jax.device_get(state.stats.events).sum())
    if sup_aborted:
        # chunks that succeeded after the supervisor's snapshot were
        # already drained, but the exported state rewound past them —
        # drop their rows so the row's trace-derived numbers cover
        # exactly the rewound prefix (truncate_to_round docs this); the
        # flow collector follows the same contract against the rewound
        # state's OWN ledger cursor, or the row's network{} block would
        # report flows the exported prefix never completed
        tracer.truncate_to_round(int(state.stats.rounds))
        if netcol is not None:
            import numpy as _np_t

            netcol.truncate_to_cursor(
                _np_t.asarray(jax.device_get(state.flows.cursor))
            )
    value = (ev_adv / wall) if "events_per" in metric else (sim_adv / wall)
    # event-density telemetry (the K-way microstep's target): how many
    # dispatches a round serializes into, and how many events each
    # dispatch retires — tracked in the BENCH trajectory from round 6 on
    import numpy as _np

    s = jax.device_get(state.stats)
    msteps = int(_np.asarray(s.microsteps).sum())
    rounds = int(s.rounds)
    events_total = int(_np.asarray(s.events).sum())
    return {
        "metric": metric,
        "value": round(value, 3),
        "unit": "events/wall_s" if "events_per" in metric else "sim_s/wall_s",
        "sim_seconds": round(sim_adv, 3),
        "events": ev_adv,
        "microsteps_per_round": round(msteps / max(rounds, 1), 2),
        "events_per_microstep": round(events_total / max(msteps, 1), 2),
        # counters snapshot (PR 3): the decomposition future perf PRs read
        # straight from the BENCH row instead of re-deriving by hand —
        # rounds_per_chunk comes from the drained trace ring (wall-paired
        # chunk records), the rest from the device counters
        "counters": {
            "rounds": rounds,
            "ici_bytes": int(_np.asarray(s.ici_bytes).sum()),
            "bq_rebuilds": int(_np.asarray(s.bq_rebuilds).sum()),
            "popk_deferred": int(_np.asarray(s.popk_deferred).sum()),
            "queue_occupancy_hwm": int(_np.asarray(s.q_occ_hwm).max()),
            "outbox_send_hwm": max(
                int(_np.asarray(s.outbox_hwm).max()), ob_hwm_run
            ),
            "rounds_per_chunk": tracer.summary()["rounds_per_chunk"],
            # fault-plane counters (PR 5): zero on fault-free configs,
            # the robustness evidence on config 7
            "faults_dropped": int(_np.asarray(s.faults_dropped).sum()),
            "faults_delayed": int(_np.asarray(s.faults_delayed).sum()),
            # pressure-plane counters (PR 8): config 9's evidence — the
            # regrow/replay accounting plus the drop totals escalation
            # kept at zero (and the capacity the run ended at)
            **(
                {
                    "pressure": {
                        **resil.report(),
                        "capacity": state.queue.t.shape[1],
                        "outbox": state.outbox.t.shape[1],
                    },
                    "pressure_regrows": (
                        resil.regrows + resil.proactive_regrows
                    ),
                    "pressure_replays": resil.replays,
                    "queue_overflow_dropped": int(
                        _np.asarray(
                            jax.device_get(state.queue.dropped)
                        ).sum()
                    ),
                }
                if resil is not None and cfg.pressure.active else {}
            ),
            # integrity-sentinel counters (PR 11): config 10's evidence
            # — transient SDC survived + sentinel replays (zero on a
            # clean box), and the deterministic-violation naming when
            # the sentinel aborted the run
            **(
                {"integrity": resil.integrity_report()}
                if resil is not None and cfg.integrity.enabled else {}
            ),
            **(
                {"supervisor": sup.report()} if sup is not None else {}
            ),
            # timer-wheel counters (PR 12): config 11's evidence — the
            # occupancy high-water + spill count (the slot-sizing
            # signal; spills are exact, never a loss) and the invariant-
            # zero wheel drop total
            **(
                {"wheel": {
                    "slots": sim.engine_cfg.wheel_slots,
                    "occupancy_hwm": int(
                        _np.asarray(s.wheel_occ_hwm).max()
                    ),
                    "spilled": int(_np.asarray(s.wheel_spilled).sum()),
                    "dropped": int(_np.asarray(
                        jax.device_get(state.wheel.dropped)
                    ).sum()),
                }}
                if sim.engine_cfg.wheel_active else {}
            ),
            # hierarchical-exchange tier counters (PR 17): the two-tier
            # byte split — intra is on-shard compaction staging, inter is
            # the wire tier ici_bytes above carries; tools/bench_compare.py
            # gates the inter tier against regressing toward the flat
            # alltoall cost
            **(
                {"exchange": {
                    "kind": "hierarchical",
                    "block": sim.engine_cfg.hier_block_size,
                    "ici_intra_bytes": int(
                        _np.asarray(s.ici_intra).sum()
                    ),
                    "ici_inter_bytes": int(
                        _np.asarray(s.ici_inter).sum()
                    ),
                }}
                if sim.engine_cfg.hier_active else {}
            ),
            # gear histogram (adaptive-exchange runs): accepted chunks per
            # gear from the controller, rounds per gear from the trace
            # ring — the low-occupancy acceptance evidence
            **(
                {"gears": gearctl.report(),
                 "gear_rounds": {str(g): n for g, n
                                 in tracer.gear_histogram().items()}}
                if gearctl is not None else {}
            ),
        },
        "first_chunk_s": round(compile_s, 1),
        "build_s": round(build_s, 1),
        # runtime block (runtime observatory, PR 14): measured compile
        # wall (ledger-precise, incl. mid-run pressure-rung compiles
        # inside the measured window), per-phase shares, and the
        # realtime factor with in-window compiles excluded — diffed by
        # tools/bench_compare.py (rt drop / compile-wall growth =
        # regression, lost block = coverage warning)
        "runtime": bench_runtime_block(
            rt_compiles, wallled, sim_adv, wall, window=(t0, t0 + wall)
        ),
        # fluid block (fluid traffic plane, PR 13): the background
        # byte/drop accounting and hot-link utilization — diffed by
        # tools/bench_compare.py as background-coverage gates (the
        # foreground cost shows up in the network{} FCT gates)
        **(
            {"fluid": _bench_fluid(sim, state, s)}
            if sim.engine_cfg.fluid_active else {}
        ),
        # network block (network observatory, PR 10): the timer-vs-packet
        # event share ROADMAP item 2's timer-wheel decision gates on, the
        # FCT distribution, and the per-link hot-spot — diffed by
        # tools/bench_compare.py so flow-behavior regressions fail the
        # comparison even when wall-clock holds
        "network": _bench_network(sim, state, s, netcol),
        # HBM block (memory observatory): per-shard peak bytes + the
        # static model's prediction + headroom — the BENCH/MULTICHIP
        # telemetry ROADMAP item 1 asks for; tools/bench_compare.py
        # diffs it across rounds
        "hbm": {
            **memmon.report(),
            "model": {
                k: v
                for k, v in static_model(
                    sim.engine_cfg, state, params
                ).items()
                if k in ("components", "state_bytes", "params_bytes",
                         "total_bytes", "per_host_bytes")
            },
        },
        # the row-level integrity block (like network/hbm): what
        # tools/bench_compare.py diffs — a deterministic violation
        # appearing in NEW is a regression, transient growth a warning
        **(
            {"integrity": resil.integrity_report()}
            if resil is not None and cfg.integrity.enabled else {}
        ),
        **({"aborted": True} if sup_aborted else {}),
        **({"pressure_aborted": True} if press_aborted else {}),
        **({"integrity_aborted": True} if integ_aborted else {}),
    }


def measure(
    num_hosts: int,
    stop_s: int,
    wall_budget_s: float = 90.0,
    rounds_per_chunk: int = 512,
) -> float:
    """sim-seconds advanced per wall-second, excluding the compile chunk.

    Bounded by `wall_budget_s` of measurement wall time so the bench always
    terminates regardless of platform speed — the rate is the metric, so a
    partial run measures the same quantity."""
    import jax

    from shadow_tpu.config.options import ConfigOptions
    from shadow_tpu.sim import Simulation

    cfg = ConfigOptions.from_dict(
        bench_config(num_hosts, stop_s, rounds_per_chunk)
    )
    sim = Simulation(cfg, world=1)
    state, params, engine = sim.state, sim.params, sim.engine
    walls: list[float] = []
    t_c = time.monotonic()
    state = engine.run_chunk(state, params)  # compile + first chunk
    jax.block_until_ready(state)
    walls.append(time.monotonic() - t_c)
    if bool(state.done):
        # whole sim fit in the compile chunk: rebuild (compile is cached)
        # and time a clean full run
        sim = Simulation(cfg, world=1)
        t0 = time.monotonic()
        state = sim.state
        while not bool(state.done):
            state = sim.engine.run_chunk(state, sim.params)
            jax.block_until_ready(state)
        return stop_s / max(time.monotonic() - t0, 1e-9)
    sim0 = int(state.now)
    t0 = time.monotonic()
    t_c = t0
    while not bool(state.done):
        state = engine.run_chunk(state, params)
        jax.block_until_ready(state)
        now = time.monotonic()
        walls.append(now - t_c)
        t_c = now
        if now - t0 >= wall_budget_s:
            break
    # the shared compile-exclusion rule (post_compile_stats): the same
    # walls[0]-carries-the-compile convention every bench path uses
    wall, _ = post_compile_stats(walls)
    sim_advanced_s = (int(state.now) - sim0) / 1e9
    return sim_advanced_s / wall


def main() -> int:
    if "--campaign-worker" in sys.argv:
        # hidden: one subprocess-isolated config-8 measurement leg
        import jax

        jax.config.update("jax_platforms", "cpu")
        leg = sys.argv[sys.argv.index("--campaign-worker") + 1]
        budget = (
            float(sys.argv[sys.argv.index("--campaign-budget") + 1])
            if "--campaign-budget" in sys.argv else 120.0
        )
        print(json.dumps(_campaign_worker(
            leg, SMALL or "--small" in sys.argv, wall_budget_s=budget
        )))
        return 0
    if "--config" in sys.argv:
        n = int(sys.argv[sys.argv.index("--config") + 1])
        if "--cpu" in sys.argv:
            import jax

            jax.config.update("jax_platforms", "cpu")
            # baseline leg: print the bare rate for the parent to consume
            print(measure_config(n, SMALL or "--small" in sys.argv,
                                 wall_budget_s=60.0)["value"])
            return 0
        print(json.dumps(measure_config(n, SMALL or "--small" in sys.argv)))
        return 0
    if "--self" in sys.argv:
        if "--cpu" in sys.argv:
            import jax

            jax.config.update("jax_platforms", "cpu")
            print(
                measure(
                    NUM_HOSTS, CPU_SIM_S, wall_budget_s=60.0, rounds_per_chunk=128
                )
            )
        else:
            print(measure(NUM_HOSTS, SIM_S))
        return 0

    # Primary metric (round 5, VERDICT r4 #2): the north-star workload —
    # 10k-host tgen-TCP all-to-all on the 2D torus. vs_baseline is the SAME
    # engine + workload on this box's (one-core) CPU backend, as before.
    res = measure_config(6, SMALL)
    value = res["value"]
    vs = 1.0
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--config", "6", "--cpu"],
            capture_output=True,
            text=True,
            timeout=1200,  # covers CPU-backend compile + first chunk too
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        cpu_ratio = float(out.stdout.strip().splitlines()[-1])
        if cpu_ratio > 0:
            vs = value / cpu_ratio
    except Exception as e:  # baseline leg is best-effort; headline still valid
        print(f"# cpu baseline failed: {e}", file=sys.stderr)
    # secondary: the PHOLD headline tracked since round 1 (continuity)
    phold = None
    try:
        phold = round(measure(NUM_HOSTS, SIM_S, wall_budget_s=60.0), 3)
    except Exception as e:
        print(f"# phold secondary failed: {e}", file=sys.stderr)
    print(
        json.dumps(
            {
                "metric": "tgen_tcp_10k_torus_sim_seconds_per_wall_second",
                "value": round(value, 3),
                "unit": "sim_s/wall_s",
                "vs_baseline": round(vs, 3),
                "events": res.get("events"),
                "microsteps_per_round": res.get("microsteps_per_round"),
                "events_per_microstep": res.get("events_per_microstep"),
                "counters": res.get("counters"),
                "phold_10k_sim_s_per_wall_s": phold,
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
