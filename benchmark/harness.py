"""The benchmark's library: cells found by name, set-up, the replayed window.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own, found by the name `BENCHMARK.json` gives:

    benchmark/configs/<config>.json     the configuration as it is run
    benchmark/workloads/<cell>.json     the cell's traffic parameters
    benchmark/reference/<model>.py      the plain reference of the model
    benchmark/metrics/<metric>.py       a reader: read(ctx) -> number | None

A reader is handed a `TraceContext`: the trace's `Reduction` (scopes,
every `shadow_*` marker, every `shadow.*` host span, busy time), the
traced window's seconds, segments, rounds and microsteps, each traced
segment's full report from `Simulation.run` (`model_report` included),
the resolved `EngineConfig` the chunk program was compiled with, and the
device kind's row of `peaks.json`. A `per_layer` entry with a `workloads`
list is read in those cells only.

The timed entry is a replayed segment: set-up builds one `Simulation`,
keeps a device copy of its initial state, and runs one whole segment to
compile and warm the one chunk program. The window then restores a fresh
copy and calls `Simulation.run` to the cell's horizon, again and again,
for whole segments only.
"""

from __future__ import annotations

import copy
import dataclasses
import importlib.util
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# every segment must end with these at zero (the configurations' stated
# guarantee: no acknowledged packet is lost to a full queue or budget)
DROP_KEYS = (
    "queue_overflow_dropped",
    "packets_budget_dropped",
    "outbox_overflow_dropped",
    "alltoall_shed_dropped",
)


# per-host packet counters the reference keeps too
COUNTERS = (
    "pkts_sent", "pkts_delivered", "pkts_lost", "pkts_unreachable",
    "pkts_codel_dropped", "pkts_budget_dropped", "monotonic_violations",
)


class BenchError(Exception):
    """The run cannot give a result (no chip, unknown cell, bad file)."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def bench_file(root: str, *parts: str) -> str:
    return os.path.join(root, "benchmark", *parts)


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    model: str
    horizon_ns: int
    shadow: dict  # the program's configuration dict, seed applied


def _parse_seconds(value: str) -> float:
    num, unit = value.split()
    scale = {"s": 1.0, "ms": 1e-3}[unit]
    return float(num) * scale


def load_cell(name: str, seed: int, root: str = ROOT) -> Cell:
    """The cell's configuration with its traffic file and the seed applied.

    A workload file may set `horizon` (the replayed segment's end),
    `model_args` (merged into every host's model process) and
    `experimental` (merged into the configuration's capacity shapes)."""
    m = manifest(root)
    entry = find(m["workloads"], name, "workload")
    cfg_entry = find(m["configs"], entry["config"], "config")
    conf = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(bench_file(root, "workloads", f"{name}.json"))
    if traffic["config"] != entry["config"]:
        raise BenchError(
            f"workload file {name}.json names config {traffic['config']!r},"
            f" BENCHMARK.json {entry['config']!r}"
        )
    shadow = copy.deepcopy(conf["shadow"])
    if "horizon" in traffic:
        shadow["general"]["stop_time"] = traffic["horizon"]
    shadow["general"]["seed"] = int(seed)
    shadow.setdefault("experimental", {}).update(traffic.get("experimental", {}))
    for host in shadow["hosts"].values():
        for proc in host["processes"]:
            proc["model_args"].update(traffic.get("model_args", {}))
    horizon_ns = round(_parse_seconds(shadow["general"]["stop_time"]) * 1e9)
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        model=conf["model"],
        horizon_ns=horizon_ns,
        shadow=shadow,
    )


def load_module(path: str, name: str):
    """Import a file of the benchmark by path (metric names hold dots, so
    the files are not package modules)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_readers(root: str = ROOT) -> list[tuple[dict, object]]:
    """(manifest entry, read function) of each per-layer metric; a reader
    that finds nothing to read in a cell returns None."""
    out = []
    for entry in manifest(root)["per_layer"]:
        path = bench_file(root, "metrics", f"{entry['name']}.py")
        out.append((entry, load_module(path, f"metric_{entry['name']}").read))
    return out


def reference_module(model: str):
    """benchmark/reference/<model>.py: `reference(shadow) -> Reference`."""
    return importlib.import_module(f"reference.{model}")


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------


class CompileClock:
    """Backend compiles (persistent-cache loads included) as JAX reports
    them: a count and seconds (copied from chip_smoke.py)."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)


def outcome(report: dict) -> dict:
    """What one segment produced, as the program reports it; the whole
    report is kept under `report` for the per-layer readers."""
    out = {
        "digest": report["determinism_digest"],
        "events": int(report["events_processed"]),
        "rounds": int(report["rounds"]),
        "microsteps": int(report["microsteps"]),
    }
    out["drops"] = sum(int(report.get(k, 0)) for k in DROP_KEYS)
    out["report"] = report
    return out


class Replay:
    """One Simulation, its initial state kept on the device, replayed."""

    def __init__(self, cell: Cell):
        from shadow_tpu.config.options import ConfigOptions
        from shadow_tpu.core.checkpoint import snapshot_state
        from shadow_tpu.sim import Simulation

        opts = ConfigOptions.from_dict(copy.deepcopy(cell.shadow))
        if opts.faults.supervisor.enabled or opts.pressure.active:
            raise BenchError("a retrying plane is on; the window would hide faults")
        self.sim = Simulation(opts, world=cell.chips)
        # the chunk DONATES its input: keep a copy that no dispatch consumes
        self.snap = snapshot_state(self.sim.state)

    def segment(self) -> dict:
        """Restore the initial state and run the whole segment. The two
        host spans name the idle gaps of a traced window."""
        import jax

        from shadow_tpu.core.checkpoint import restore_snapshot

        with jax.profiler.TraceAnnotation("bench.restore_snapshot"):
            self.sim.state = restore_snapshot(self.snap)
        with jax.profiler.TraceAnnotation("bench.Simulation.run"):
            return outcome(self.sim.run(progress=False))

    def window(self, seconds: float) -> tuple[list[dict], float]:
        """Whole segments until `seconds` of wall time have passed."""
        results = []
        t0 = time.perf_counter()
        while True:
            results.append(self.segment())
            wall = time.perf_counter() - t0
            if wall >= seconds:
                return results, wall

    def answer(self) -> dict:
        """The last segment's final state as numpy arrays: what `correct`
        compares with the reference."""
        import jax
        import numpy as np

        n = self.sim._num_real
        st = jax.device_get(self.sim.state)
        return {
            "digest": np.asarray(st.stats.digest)[:n],
            "events": np.asarray(st.stats.events)[:n],
            "counters": {k: np.asarray(getattr(st.stats, k))[:n] for k in COUNTERS},
            "rounds": int(st.stats.rounds),
            "model": {k: np.asarray(v)[:n] for k, v in st.model.items()},
        }

    def free(self) -> None:
        """Drop the program's device state before the reference runs."""
        self.sim.state = None
        self.snap = None
        self.sim = None


@dataclasses.dataclass
class TraceContext:
    """What a per-layer metric reader reads: the trace's reduction over the
    traced segments and the program's counters over the same segments."""

    reduction: object  # trace_reduce.Reduction
    window_s: float
    segments: int
    rounds: int
    microsteps: int
    reports: list  # each traced segment's `Simulation.run` report
    engine_cfg: object  # the chunk program's resolved EngineConfig
    peaks: dict | None  # the device kind's row of peaks.json
