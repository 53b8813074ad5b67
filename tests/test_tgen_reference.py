"""The tgen_tcp model against the benchmark's plain numpy reference.

`benchmark/reference/tgen_tcp.py` is written from the model's documented
TCP semantics and runs through `benchmark/reference/engine.py`; it imports
nothing of the program. On the CPU the program's `Simulation` and the
reference must agree exactly on every host's event-order digest and event
count, the seven packet counters, every model state lane and the round
count: what decides `correct` in the benchmark's
`tgen_10k_1gbit_switch.bulk` cell, here at a small size. The cases run on
Shadow's 1 Gbit switch (one node, 1 ms self-loop); the last two cut the
per-round send budget below its high-water, so budget drops act as loss and
drive the recovery paths through both."""

from __future__ import annotations

import copy
import importlib.util
import os
import sys

import jax
import numpy as np
import pytest

from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.sim import Simulation

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)  # the reference imports `reference.engine`
_spec = importlib.util.spec_from_file_location(
    "tgen_tcp_reference", os.path.join(BENCH, "reference", "tgen_tcp.py"))
ref_tgen = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref_tgen)

SWITCH = (
    'graph [\n  directed 0\n'
    '  node [ id 0 host_bandwidth_down "1 Gbit" host_bandwidth_up "1 Gbit" ]\n'
    '  edge [ source 0 target 0 latency "1 ms" ]\n]'
)
CELL_ARGS = {"flow_segs": 685, "mss": 1460, "flows": 16, "cwnd_cap": 16}
COUNTERS = (
    "pkts_sent", "pkts_delivered", "pkts_lost", "pkts_unreachable",
    "pkts_codel_dropped", "pkts_budget_dropped", "monotonic_violations",
)


def _config(hosts, stop, budget, **args):
    return {
        "general": {"stop_time": stop, "seed": 3_000_000_017},
        "network": {"graph": {"type": "gml", "inline": SWITCH}},
        "experimental": {"event_queue_capacity": 24, "sends_per_host_round": budget},
        "hosts": {"peer": {"count": hosts, "network_node_id": 0, "processes": [
            {"model": "tgen_tcp", "model_args": {**CELL_ARGS, **args}}]}},
    }


# (hosts, horizon, send budget, model args); the send high-water with room
# to spare is 16 in each (cwnd_cap 16 plus what the server acks)
CASES = {
    "cell_args": (32, "100 ms", 24, {}),
    "one_segment_bursts_no_delack": (7, "500 ms", 24, {"tx_batch": 1, "delack": "0 ms"}),
    # one below the high-water: single drops, fast retransmit, and tail
    # drops that only the RTO recovers
    "budget_one_below": (16, "1.5 s", 15, {"delack": "0 ms"}),
    # two below: several drops in one window, so NewReno's partial ACKs
    "budget_two_below": (16, "1 s", 14, {}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_program_equals_reference(case):
    hosts, stop, budget, args = CASES[case]
    cfg = _config(hosts, stop, budget, **args)
    sim = Simulation(ConfigOptions.from_dict(copy.deepcopy(cfg)), world=1)
    report = sim.run(progress=False)
    ref = ref_tgen.reference(copy.deepcopy(cfg)).run()

    st = jax.device_get(sim.state)
    n = sim._num_real
    np.testing.assert_array_equal(np.asarray(st.stats.digest)[:n], ref.digest)
    np.testing.assert_array_equal(np.asarray(st.stats.events)[:n], ref.events)
    for k in COUNTERS:
        np.testing.assert_array_equal(np.asarray(getattr(st.stats, k))[:n], ref.counters[k],
                                      err_msg=k)
    assert set(st.model) == set(ref.model)
    for k, v in st.model.items():
        np.testing.assert_array_equal(np.asarray(v)[:n], ref.model[k], err_msg=k)
    assert int(st.stats.rounds) == ref.rounds
    assert int(ref.dropped.sum()) == report["queue_overflow_dropped"] == 0

    # each case reaches the paths it is there for
    m = report["model_report"]
    assert m["data_segments_sent"] > 0
    drops = int(ref.counters["pkts_budget_dropped"].sum())
    if case == "budget_one_below":
        assert drops > 0 and m["fast_retransmits"] > 0 and m["timeouts"] > 0
    elif case == "budget_two_below":
        # retransmits that neither three duplicate ACKs nor an RTO sent
        assert drops > 0 and m["retransmits"] > m["fast_retransmits"] + m["timeouts"]
    else:
        assert drops == 0 and m["retransmits"] == 0
