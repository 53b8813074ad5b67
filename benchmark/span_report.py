"""A cell's window, untraced then traced, reduced to the program's spans.

    python3 benchmark/span_report.py --workload <cell> --seed <n> --seconds <s>
        [--keep <dir>] [--hosts <n>] [--horizon "<n> s"]

Set-up as in run.py (one warm segment). Then whole segments for
`--seconds` untraced and for at most run.TRACE_S under the profiler; the
trace is reduced by trace_reduce (the accepted per-layer numbers) and by
span_reduce (the program's own spans and scopes). One JSON line: both
windows' sim-s per wall-s, the per-round numbers of both reductions, the
host spans, the idle shares and the labelled idle gaps. `--keep` keeps
the trace and the chunk program's HLO text there; `--hosts` and
`--horizon` cut the cell (a fixture), the default runs it as it is.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import correct as C  # noqa: E402
import harness as H  # noqa: E402
import run as R  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    ap.add_argument("--hosts", type=int, default=None)
    ap.add_argument("--horizon", default=None)
    return ap.parse_args(argv)


def cut(cell: H.Cell, hosts: int | None, horizon: str | None) -> H.Cell:
    if hosts is not None:
        (group,) = cell.shadow["hosts"].values()
        group["count"] = hosts
    if horizon is not None:
        cell.shadow["general"]["stop_time"] = horizon
        cell.horizon_ns = round(H._parse_seconds(horizon) * 1e9)
    return cell


def report(argv=None, root: str = ROOT, require_tpu: bool = True) -> dict:
    import span_reduce as S
    import trace_reduce as T

    args = parse(argv)
    cell = cut(H.load_cell(args.workload, args.seed, root), args.hosts, args.horizon)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".bench_jax_cache")
    R.devices(cell.chips, root, require_tpu)
    sys.path.insert(0, root)
    rp = H.Replay(cell)
    rp.segment()
    text = rp.sim.engine.run_chunk.lower(rp.snap, rp.sim.params).compile().as_text()

    plain, plain_wall = rp.window(args.seconds)
    tmp = tempfile.mkdtemp(prefix="span_trace_")
    try:
        import jax

        jax.profiler.start_trace(tmp)
        try:
            traced, wall = rp.window(min(args.seconds, R.TRACE_S))
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        t0 = time.perf_counter()
        profile = T.load_profile(path)
        red = T.reduce_profile(profile, T.hlo_map(text), chips=cell.chips)
        spans = S.reduce_spans(profile, text, chips=cell.chips)
        reduce_s = time.perf_counter() - t0
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            shutil.copy(path, os.path.join(args.keep, f"{cell.name}.xplane.pb"))
            with open(os.path.join(args.keep, f"{cell.name}.hlo.txt"), "w") as f:
                f.write(text)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    rounds = sum(s["rounds"] for s in traced)
    sim_s = cell.horizon_ns / 1e9
    return {
        "cell": cell.name,
        "seed": args.seed,
        "faults": sum(C.segment_faults(plain + traced)),
        "untraced": {"segments": len(plain), "wall_s": plain_wall,
                     "sim_s_per_wall_s": len(plain) * sim_s / plain_wall},
        "traced": {"segments": len(traced), "wall_s": wall, "rounds": rounds,
                   "sim_s_per_wall_s": len(traced) * sim_s / wall,
                   "reduce_s": reduce_s},
        "accepted": {
            "device.idle_share": 100.0 * (1.0 - red.busy_s / wall),
            "microstep.ms_per_round": 1e3 * red.scopes["microsteps"] / rounds,
            "exchange.ms_per_round": 1e3 * red.scopes["exchange"] / rounds,
            "round.other_ms_per_round": 1e3 * red.scopes["other"] / rounds,
        },
        "spans": S.per_round(spans, rounds),
        "markers_ms_per_round": {k: 1e3 * v / rounds for k, v in spans.markers.items()},
        "host_spans": spans.host_spans,
        "idle": {"idle_s": spans.idle_s, "in_run_s": spans.idle_in_run_s,
                 "named_s": spans.idle_named_s, "run_gaps": spans.run_gaps,
                 "run_gaps_unnamed": spans.run_gaps_unnamed},
        "idle_gaps": spans.idle_gaps,
        "idle_gaps_by_thread_name": red.idle_gaps,
        "scopes_s": red.scopes,
    }


def main(argv=None) -> int:
    try:
        out = report(argv)
    except H.BenchError as e:
        print(f"[no result] {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
