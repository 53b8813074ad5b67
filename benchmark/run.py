"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's `Simulation` on one TPU, keeps a device copy of
its initial state, and runs one whole segment to compile and warm the
chunk program (`setup_s` runs from process start to the window's start).
The window replays whole segments from that copy for `--seconds`:
`sim_s_per_wall_s` is all simulated time over all wall time of the window.
With `--trace 1` the window is traced instead (whole segments, at most
TRACE_S of them) and the per-layer metrics are read from the trace. Then
the program's state is freed and the reference runs the same segment;
`correct` holds when every number compared is within its limit.

No TPU, too few chips, an unknown device kind, or a checkout without the
program: exit 2 and no result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_S = 3.0  # traced segments: whole ones, until this much wall time

sys.path.insert(0, HERE)

import correct as C  # noqa: E402
import harness as H  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices(chips: int, root: str, require_tpu: bool):
    """The devices JAX found and their kind's row of peaks.json, refused
    unless they are enough known TPUs."""
    import jax

    devs = jax.devices()
    peaks = H.load_json(H.bench_file(root, "peaks.json")).get(devs[0].device_kind)
    if require_tpu:
        if devs[0].platform != "tpu":
            raise H.BenchError(f"no TPU: JAX found {devs[0].platform}")
        if peaks is None:
            raise H.BenchError(f"device kind {devs[0].device_kind!r} is not in peaks.json")
    if len(devs) < chips:
        raise H.BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs, peaks


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def chunk_hlo(rp: H.Replay):
    """The chunk program's compiled HLO, mapped to scopes (a cache load)."""
    import trace_reduce as T

    t0 = time.perf_counter()
    hlo = T.hlo_map(rp.sim.engine.run_chunk.lower(rp.snap, rp.sim.params).compile().as_text())
    log(f"[trace] hlo_s={time.perf_counter() - t0:.3f}")
    return hlo


def traced_window(rp: H.Replay, seconds: float, hlo, chips: int):
    """Whole segments under the profiler; the trace reduced to numbers."""
    import jax

    import trace_reduce as T

    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            results, wall = rp.window(min(seconds, TRACE_S))
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        t2 = time.perf_counter()
        red = T.reduce_profile(T.load_profile(path), hlo, chips=chips)
        log(f"[trace] trace_bytes={os.path.getsize(path)} "
            f"reduce_s={time.perf_counter() - t2:.3f}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return results, wall, red


def run(argv=None, *, root: str = ROOT, require_tpu: bool = True, t_start: float = T_START):
    """One run; the result line's object. Raises BenchError for no result."""
    args = parse(argv)
    cell = H.load_cell(args.workload, args.seed, root)
    # the cache lives in the checkout at a fixed path (part of its key)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".bench_jax_cache")
    # set-up's phases, for where its spread comes from
    ta = time.perf_counter()
    import jax  # noqa: F401

    tb = time.perf_counter()
    devs, peaks = devices(cell.chips, root, require_tpu)
    tc = time.perf_counter()
    sys.path.insert(0, root)
    try:
        import shadow_tpu  # noqa: F401
    except ImportError as e:
        raise H.BenchError(f"the program is not in this checkout: {e}") from e
    clock = H.CompileClock()

    t0 = time.perf_counter()
    log(f"[start] before_jax_s={ta - t_start:.3f} jax_import_s={tb - ta:.3f} "
        f"backend_init_s={tc - tb:.3f} program_import_s={t0 - tc:.3f}")
    rp = H.Replay(cell)
    t1 = time.perf_counter()
    warm = rp.segment()  # compiles (or loads) and warms the chunk program
    t2 = time.perf_counter()
    setup_s = t2 - t_start
    log(f"[setup] cell={cell.name} seed={args.seed} build_s={t1 - t0:.3f} "
        f"warm_segment_s={t2 - t1:.3f} compiles={clock.count} "
        f"compile_s={clock.seconds:.3f} setup_s={setup_s:.3f}")
    warm.pop("report")
    log(f"[warm] {warm}")

    hlo = chunk_hlo(rp) if args.trace else None
    compiles0 = clock.count
    red = None
    if args.trace:
        segments, wall, red = traced_window(rp, args.seconds, hlo, cell.chips)
    else:
        segments, wall = rp.window(args.seconds)
    compiles_in_window = clock.count - compiles0
    mem = max(peak_bytes(d) for d in devs[: cell.chips])  # the fullest chip
    faults = C.segment_faults(segments)
    log(f"[window] segments={len(segments)} wall_s={wall!r} events_per_segment="
        f"{segments[0]['events']} digest={segments[0]['digest']} "
        f"rounds_per_segment={segments[0]['rounds']} compiles_in_window={compiles_in_window}")
    log(f"[memory] peak_bytes_in_use={mem}")

    answer = rp.answer()
    engine_cfg = rp.sim.engine.cfg
    rp.free()
    del rp
    gc.collect()
    t3 = time.perf_counter()
    ref = H.reference_module(cell.model).reference(cell.shadow).run()
    log(f"[reference] seconds={time.perf_counter() - t3:.3f} events={int(ref.events.sum())} "
        f"rounds={ref.rounds}")
    checks = C.compare(answer, ref, segments)
    checks["compiles_in_window"] = (compiles_in_window, 0)
    ok = all(v <= lim for v, lim in checks.values())

    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs),
              "memory_peak_bytes": mem}
    result = {"correct": ok, "attempted": len(segments), "failed": sum(faults)}
    if args.trace:
        n_rounds = sum(s["rounds"] for s in segments)
        ctx = H.TraceContext(
            reduction=red, window_s=wall, segments=len(segments), rounds=n_rounds,
            microsteps=sum(s["microsteps"] for s in segments),
            reports=[s["report"] for s in segments], engine_cfg=engine_cfg, peaks=peaks,
        )
        metrics = {}
        for entry, read in H.metric_readers(root):
            if cell.name not in entry.get("workloads", [cell.name]):
                continue
            value = read(ctx)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = wall
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": red.top_ops, "idle_gaps": red.idle_gaps}
        log(f"[trace] busy_s={red.busy_s!r} window_s={wall!r} scopes={red.scopes} "
            f"chunk_dispatches={red.chunk_dispatches}")
    else:
        sim_s = len(segments) * cell.horizon_ns / 1e9
        result["metrics"] = {
            "sim_s_per_wall_s": {"value": sim_s / wall, "unit": "sim-s/wall-s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"[check] {k}={v} limit={lim}")
    return result


def main(argv=None) -> int:
    try:
        result = run(argv)
    except H.BenchError as e:
        log(f"[no result] {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
