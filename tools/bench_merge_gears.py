#!/usr/bin/env python3
"""Sweep merge gear width x send occupancy on the exchange-merge pair.

The adaptive-exchange question (ISSUE 4): how much of the merge's cost is
the (dst, t, order) sort over the STATIC outbox width, and how much does a
gear-truncated width recover at realistic occupancies? This tool times, on
a synthetic [H, B] outbox filled to a per-host occupancy level:

  - sort:   the destination sort + urgency selection half (`merge_plan`)
  - gather: the apply half (`merge_apply` slab write)
  - total:  the fused `merge_flat_events` path (what the engine runs at
            queue capacities below 48, on every backend)

at each gear width (the flattened input is H x gear columns — exactly the
slice `core/engine._gear_sliced_outbox` feeds the merge). CPU-runnable by
design; on TPU the same sweep maps the chip's economics.

Usage: python tools/bench_merge_gears.py [--hosts 4096] [--budget 8]
           [--cap 32] [--iters 30] [--occupancy 1,2,4,8] [--json]
Output: one JSON line per (occupancy, gear) with ms per merge and the
sort-vs-gather split, then a summary of the speedup of the best exact
gear over full width per occupancy.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from shadow_tpu.ops.events import EVENT_PAYLOAD_WORDS, make_queue  # noqa: E402
from shadow_tpu.ops.merge import (  # noqa: E402
    merge_apply,
    merge_flat_events,
    merge_plan,
)


def synth_outbox(rng, hosts: int, budget: int, occ: int):
    """[H, B] lanes with each host's first `occ` columns live (the exact
    layout the engine's cursor append produces), random dst/t, unique
    orders."""
    cols = np.arange(budget)[None, :]
    live = cols < occ
    dst = rng.integers(0, hosts, (hosts, budget)).astype(np.int32)
    t = rng.integers(1, 1 << 40, (hosts, budget)).astype(np.int64)
    t = np.where(live, t, np.int64((1 << 62) - 1))  # TIME_MAX-ish empties
    order = (
        np.arange(hosts * budget, dtype=np.int64).reshape(hosts, budget)
        + (1 << 40)
    )
    kind = rng.integers(0, 4, (hosts, budget)).astype(np.int32)
    payload = rng.integers(
        0, 99, (hosts, budget, EVENT_PAYLOAD_WORDS)
    ).astype(np.int32)
    return dst, t, order, kind, payload, live


def flat_at_gear(arrays, gear: int, time_max: int):
    dst, t, order, kind, payload, live = arrays
    g = gear
    fl = lambda a: jnp.asarray(a[:, :g].reshape(-1, *a.shape[2:]))  # noqa: E731
    t_f = fl(t)
    valid = (t_f != time_max) & (fl(dst) >= 0)
    return fl(dst), t_f, fl(order), fl(kind), fl(payload), valid


def timed(fn, *args, iters=30):
    out = fn(*args)  # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3, out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--hosts", type=int, default=4096)
    p.add_argument("--budget", type=int, default=8)
    p.add_argument("--cap", type=int, default=32)
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--occupancy", default="1,2,4,8",
                   help="comma list of live sends per host")
    p.add_argument("--json", action="store_true",
                   help="JSON lines only (no human summary)")
    args = p.parse_args(argv)

    from shadow_tpu.simtime import TIME_MAX

    rng = np.random.default_rng(7)
    q = make_queue(args.hosts, args.cap)
    # the engine's own auto ladder (kept in lock-step with core/gears.py;
    # a 1-wide budget collapses the ladder, so full width is re-appended)
    from shadow_tpu.core.gears import resolve_gear_ladder

    gears = resolve_gear_ladder("auto", args.budget) or [args.budget]
    occs = [int(o) for o in args.occupancy.split(",")]
    rows = []
    for occ in occs:
        arrays = synth_outbox(rng, args.hosts, args.budget, min(occ, args.budget))
        for gear in gears:
            if gear < occ:
                continue  # would shed: the engine replays these, skip
            flat = flat_at_gear(arrays, gear, TIME_MAX)

            plan = jax.jit(
                lambda qt, *f: merge_plan(qt, *f, max_inserts=args.cap)
            )
            ms_sort, planned = timed(plan, q.t, *flat, iters=args.iters)
            apply_ = jax.jit(merge_apply)
            ms_gather, _ = timed(apply_, q, *planned, iters=args.iters)
            fused = jax.jit(
                lambda qq, *f: merge_flat_events(
                    qq, *f, max_inserts=args.cap
                )
            )
            ms_total, _ = timed(fused, q, *flat, iters=args.iters)
            row = {
                "hosts": args.hosts, "budget": args.budget, "occ": occ,
                "gear": gear, "rows": args.hosts * gear,
                "sort_ms": round(ms_sort, 3),
                "gather_ms": round(ms_gather, 3),
                "total_ms": round(ms_total, 3),
                "backend": jax.default_backend(),
            }
            rows.append(row)
            print(json.dumps(row))
    if not args.json:
        for occ in occs:
            mine = [r for r in rows if r["occ"] == occ]
            if not mine:
                continue
            full = next(r for r in mine if r["gear"] == args.budget)
            best = min(mine, key=lambda r: r["total_ms"])
            print(
                f"# occ={occ}: full-width {full['total_ms']:.3f} ms -> "
                f"gear {best['gear']} {best['total_ms']:.3f} ms "
                f"({full['total_ms'] / max(best['total_ms'], 1e-9):.2f}x); "
                f"sort share at full width "
                f"{full['sort_ms'] / max(full['total_ms'], 1e-9):.0%}",
                file=sys.stderr,
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
