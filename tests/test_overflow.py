"""Pressure tests at every fixed capacity (VERDICT r2 weak #6): each limit
must degrade counted-and-sane — bounded loss with a visible counter, or a
clean errno — never a wedge or silent corruption. Reference analogue: the
determinism suite + resource watchdogs (manager.rs:447-454)."""

from __future__ import annotations

import os

import pytest

from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.cosim import HybridSimulation

MS = 1_000_000
SEC = 1_000_000_000
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from tests.subproc import native_plane_skip_reason

# toolchain-unavailable OR the shim-cannot-load (exit-97) container
# (tests/subproc.py native_plane_skip_reason classifies the signature)
_native_skip = native_plane_skip_reason()


@pytest.mark.skipif(_native_skip is not None, reason=str(_native_skip))
def test_thread_slot_exhaustion_is_eagain_and_recovers():
    """IPC_MAX_THREADS (32) bounds concurrent managed threads: the excess
    pthread_create calls fail with EAGAIN, and creation works again after
    slots recycle — no wedge, no crash."""
    from shadow_tpu.host import CpuHost, HostConfig
    from shadow_tpu.host.network import CpuNetwork
    from shadow_tpu.native_plane import IPC_MAX_THREADS, spawn_native

    host = CpuHost(HostConfig(name="h0", ip="10.0.0.1", seed=3, host_id=0))
    CpuNetwork([host], latency_ns=lambda s, d: MS)
    p = spawn_native(
        host,
        [os.path.join(REPO, "native", "build", "test_many_threads"), "40"],
    )
    host.execute(30 * SEC)
    assert p.exit_code == 0, b"".join(p.stderr)
    out = b"".join(p.stdout).decode()
    # main thread holds slot 0: 31 concurrent workers fit, 9 get EAGAIN
    assert f"created={IPC_MAX_THREADS - 1} eagain=9 other=0" in out
    assert "post-join create ok" in out


def _flood_cfg(n_clients: int, extra_exp: dict | None = None):
    return ConfigOptions.from_dict(
        {
            "general": {"stop_time": "4 s", "seed": 5},
            "network": {"graph": {"type": "1_gbit_switch"}},
            "experimental": extra_exp or {},
            "hosts": {
                "server": {
                    "network_node_id": 0,
                    "processes": [
                        {"path": "udp_echo_server", "args": ["port=9000"]}
                    ],
                },
                "client": {
                    "network_node_id": 0,
                    "count": n_clients,
                    "processes": [
                        {
                            # no expected_final_state: under ring overflow
                            # some clients legitimately never finish
                            "path": "udp_ping",
                            "args": ["server=server", "port=9000", "count=2"],
                            "expected_final_state": "running",
                        }
                    ],
                },
            },
        }
    )


def test_staging_cap_overflow_carries_no_loss():
    """More sends per window than the staging buffer holds: the bridge
    loops injection until drained, so a tiny cap loses NOTHING (it only
    costs extra inject dispatches) and results match a roomy cap."""

    def once(cap):
        sim = HybridSimulation(_flood_cfg(12), staging_cap=cap, world=1)
        r = sim.run()
        outs = {
            spec.name: b"".join(
                b"".join(p.stdout) for p in host.processes.values()
            )
            for spec, host in zip(sim.specs, sim.hosts)
        }
        return (
            r["determinism_digest"], r["packets_sent"],
            r["packets_delivered"], outs,
        )

    small = once(4)
    big = once(4096)
    assert small == big


def test_capture_ring_overflow_is_counted():
    """More same-window deliveries to one host than its capture ring holds:
    the excess is dropped AND counted (model_report capture_overflow_lost);
    the simulation still terminates cleanly."""
    from shadow_tpu.models.hybrid import HybridModel

    n = 150  # > capture_cap (128) arrivals at the server in one window
    sim = HybridSimulation(_flood_cfg(n), world=1)
    assert sim.model.capture_cap == 128
    r = sim.run()
    lost = r["model_report"]["capture_overflow_lost"]
    assert lost > 0
    # the shortfall is visible (not silent): fewer pings complete than were
    # sent, and the run still reaches stop_time
    assert r["packets_delivered"] < r["packets_sent"] or lost > 0
    assert r["simulated_seconds"] == 4.0


def test_event_queue_shed_policies_run_clean():
    """Tiny per-host event queues under flood: overflow sheds by urgency
    (the one shed policy) and the run terminates without monotonic
    violations."""
    sim = HybridSimulation(
        _flood_cfg(16, {"event_queue_capacity": 256}), world=1
    )
    r = sim.run()
    assert r["packets_sent"] > 0
    # no wedge: the run reached stop_time and reported
    assert r["simulated_seconds"] == 4.0
