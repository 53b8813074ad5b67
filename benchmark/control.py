"""The control: the reference with one stated guarantee broken, put in the
program's place, must come out not correct.

The guarantee broken is the conservative lookahead: a round may run only
as far as the least path latency, so that every packet arrives exactly
its path latency after it left. The control runs rounds twice as long
(the step a later PR could be tempted by: half the rounds), so a packet
sent early in a round arrives at the round's end, late. At the cell's own
size and seeds this prints, per seed, every number `correct` compares and
whether the control passed it:

    python3 benchmark/control.py --workload <cell> --seeds 11 12 13
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import correct as C  # noqa: E402
import harness as H  # noqa: E402


def as_answer(res) -> tuple[dict, list[dict]]:
    """A reference Result in the shape of the program's answer."""
    answer = {"digest": res.digest, "events": res.events, "rounds": res.rounds,
              "model": res.model, "counters": res.counters}
    drops = int(res.dropped.sum()) + int(res.counters["pkts_budget_dropped"].sum())
    return answer, [{"digest": "", "events": int(res.events.sum()), "drops": drops}]


def control_checks(cell: H.Cell, reference: object | None = None) -> dict:
    """Numbers compared for the control against the reference."""
    mod = H.reference_module(cell.model)
    ref = reference or mod.reference(cell.shadow).run()
    ctl = mod.reference(cell.shadow, runahead_scale=2).run()
    answer, segments = as_answer(ctl)
    return C.compare(answer, ref, segments)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        t0 = time.perf_counter()
        checks = control_checks(H.load_cell(args.workload, seed))
        failed = [k for k, (v, lim) in checks.items() if v > lim]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": not failed, "failed": failed,
                          "checks": {k: v for k, (v, _) in checks.items()},
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
