"""Record a cell's traced window as a test fixture: the trace and the HLO.

    python3 benchmark/span_report.py --workload <cell> --seed <n> --seconds <s>
        --keep <dir> [--hosts <n>] [--horizon "<n> s"]

Set-up as in run.py (one warm segment). Then whole segments for at most
`--seconds` (and run.TRACE_S) under the profiler. The trace and the chunk
program's HLO text are kept in `--keep` as `<cell>.xplane.pb` and
`<cell>.hlo.txt`; `--hosts` and `--horizon` cut the cell to a fixture's
size. One JSON line: the traced segments and rounds, and the trace's
`trace_reduce.Reduction`, which the tests pin.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile

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
    ap.add_argument("--keep", required=True)
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


def record(argv=None, root: str = ROOT, require_tpu: bool = True) -> dict:
    import trace_reduce as T

    args = parse(argv)
    cell = cut(H.load_cell(args.workload, args.seed, root), args.hosts, args.horizon)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".bench_jax_cache")
    import jax

    R.devices(cell.chips, root, require_tpu)
    sys.path.insert(0, root)
    rp = H.Replay(cell)
    rp.segment()
    text = rp.sim.engine.run_chunk.lower(rp.snap, rp.sim.params).compile().as_text()

    tmp = tempfile.mkdtemp(prefix="span_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            traced, _ = rp.window(min(args.seconds, R.TRACE_S))
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        red = T.reduce_profile(T.load_profile(path), T.hlo_map(text), chips=cell.chips)
        os.makedirs(args.keep, exist_ok=True)
        shutil.copy(path, os.path.join(args.keep, f"{cell.name}.xplane.pb"))
        with open(os.path.join(args.keep, f"{cell.name}.hlo.txt"), "w") as f:
            f.write(text)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "cell": cell.name,
        "seed": args.seed,
        "faults": sum(C.segment_faults(traced)),
        "segments": len(traced),
        "rounds": sum(s["rounds"] for s in traced),
        "reduction": dataclasses.asdict(red),
    }


def main(argv=None) -> int:
    try:
        out = record(argv)
    except H.BenchError as e:
        print(f"[no result] {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
