"""What decides `correct`: the timed path's answer against the reference.

The answer is the state the last segment of the window left: every host's
event-order digest (a fold of the time, kind and order of each event it
executed), its event count, its packet counters (sent, delivered, lost,
unreachable, CoDel- and budget-dropped), the model's per-host state, and
the number of rounds. The reference runs the same configuration and seed from t=0 to
the horizon. Every number compared is exact, so every limit is 0.
"""

from __future__ import annotations

import numpy as np

LIMITS = {
    "digest_mismatch_hosts": 0,
    "event_mismatch_hosts": 0,
    "state_mismatch_leaves": 0,
    "counter_mismatch_hosts": 0,
    "rounds_gap": 0,
    "drops": 0,
    "segments_differing": 0,
}


def segment_faults(segments: list[dict]) -> list[bool]:
    """Per window segment: a drop, or a digest or event count that differs
    from the window's first segment."""
    first = segments[0]
    return [
        s["drops"] > 0 or (s["digest"], s["events"]) != (first["digest"], first["events"])
        for s in segments
    ]


def compare(answer: dict, ref, segments: list[dict]) -> dict:
    """{name: (value, limit)} of every number compared."""
    leaves = [
        k for k, v in answer["model"].items()
        if k not in ref.model or not np.array_equal(np.asarray(v), ref.model[k])
    ]
    counters = np.zeros(len(ref.digest), bool)
    for k, v in answer["counters"].items():
        counters |= np.asarray(v) != ref.counters[k]
    ref_drops = int(ref.dropped.sum()) + int(ref.counters["pkts_budget_dropped"].sum())
    values = {
        "digest_mismatch_hosts": int((answer["digest"] != ref.digest).sum()),
        "event_mismatch_hosts": int((answer["events"] != ref.events).sum()),
        "state_mismatch_leaves": len(leaves),
        "counter_mismatch_hosts": int(counters.sum()),
        "rounds_gap": abs(answer["rounds"] - ref.rounds),
        "drops": sum(s["drops"] for s in segments) + ref_drops,
        "segments_differing": sum(segment_faults(segments)),
    }
    return {k: (v, LIMITS[k]) for k, v in values.items()}
