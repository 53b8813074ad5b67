"""From a profiler trace of the window to the program's own spans and scopes.

`trace_reduce.py` splits the chunk program's device time into three
scopes and names idle gaps by host events on lines picked by thread name.
This module reads what the program marks itself, from the same trace and
HLO text:

    markers     device seconds of the chunk program's ops per `shadow_*`
                named scope (`shadow_window`, `shadow_collective`,
                `shadow_merge_order`, `shadow_merge_write`,
                `shadow_handler`, and the older markers), averaged over
                the chips used. An op counts toward every marker in its
                `op_name` path, inherited from its caller when it has none
                of its own; control flow (while, conditional, ...) is not
                counted, as in trace_reduce
    host_spans  seconds and count of each `shadow.*` annotation the program
                opens (`shadow.run`, `shadow.chunk`, `shadow.dispatch`,
                `shadow.sync`, `shadow.heartbeat`, `shadow.export`,
                `shadow.restore`)
    idle_gaps   the first chip's longest gaps between device ops, named by
                the programs on either side and by the innermost annotation
                (`shadow.*` or `bench.*`) that spans the gap's middle, read
                from the host lines that carry annotations
    idle_*_s    the first chip's idle time between its first and last op:
                all of it, the part inside a `shadow.run` span, and the part
                under an annotation other than `shadow.run`/`shadow.chunk`
    run_gaps    the first chip's gaps of at least GAP_S whose middle lies
                inside a `shadow.run` span, and how many of them no span
                below `shadow.run`/`shadow.chunk` names (`run_gaps_unnamed`)
"""

from __future__ import annotations

import bisect
import dataclasses
import re

import trace_reduce as T

PREFIX = "shadow_"
ANNOTATIONS = ("shadow.", "bench.")
OUTER = ("shadow.run", "shadow.chunk")  # spans that only contain others
GAP_S = 1e-3


@dataclasses.dataclass
class MarkerMap:
    module: str
    markers: dict  # instruction name -> frozenset of `shadow_*` scopes
    opcode: dict  # instruction name -> opcode


def _markers_of(op_name: str | None) -> frozenset | None:
    if not op_name:
        return None
    found = frozenset(p for p in op_name.split("/") if p.startswith(PREFIX))
    return found or None


def marker_map(text: str) -> MarkerMap:
    """The `shadow_*` scopes of every instruction of a compiled module's HLO
    text (the walk of trace_reduce.hlo_map, keeping every marker)."""
    module = re.search(r"^HloModule\s+([\w.\-]+)", text, re.M).group(1)
    comps: dict[str, list] = {}
    entry = cur = None
    for line in text.splitlines():
        if cur is None:
            m = T._COMP.match(line)
            if m and not line.startswith("HloModule"):
                cur = m.group(1)
                comps[cur] = []
                if line.startswith("ENTRY"):
                    entry = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = T._INSTR.match(line)
        if m:
            name, rhs = m.groups()
            op = T._OPNAME.search(rhs)
            called = T._CALLS.findall(rhs)
            for b in T._BRANCHES.findall(rhs):
                called += [c.strip().lstrip("%") for c in b.split(",") if c.strip()]
            comps[cur].append((name, T._opcode(rhs), op.group(1) if op else None, called))
    markers: dict[str, frozenset] = {}
    opcode: dict[str, str] = {}
    stack = [(entry, frozenset())]
    seen = set()
    while stack:
        comp, inherited = stack.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, opc, op_name, called in comps[comp]:
            mk = _markers_of(op_name) or inherited
            markers[name] = mk
            opcode[name] = opc
            stack.extend((c, mk) for c in called)
    return MarkerMap(module=module, markers=markers, opcode=opcode)


@dataclasses.dataclass
class SpanReduction:
    markers: dict  # marker -> device seconds (chunk program, mean over chips)
    host_spans: dict  # span name -> [seconds, count]
    idle_gaps: list  # [[label, seconds]]
    idle_s: float
    idle_in_run_s: float
    idle_named_s: float
    run_gaps: int
    run_gaps_unnamed: int


def _plane_markers(dev, mm: MarkerMap) -> dict:
    lines = {ln.name: ln for ln in dev.lines}
    mods = sorted(
        (e.start_ns, e.start_ns + e.duration_ns)
        for e in lines["XLA Modules"].events
        if e.name.startswith(mm.module + "(")
    )
    starts = [a for a, _ in mods]
    out: dict[str, float] = {}
    for e in lines["XLA Ops"].events:
        k = bisect.bisect_right(starts, e.start_ns) - 1
        if k < 0 or e.start_ns >= mods[k][1]:
            continue
        name = T._instr_name(e.name)
        if name not in mm.markers or mm.opcode.get(name) in T.CONTAINERS:
            continue
        for mk in mm.markers[name]:
            out[mk] = out.get(mk, 0.0) + e.duration_ns * 1e-9
    return out


def annotated_host_events(profile) -> list[tuple[int, int, str]]:
    """(start_ns, duration_ns, name) of every event on each host line that
    carries an annotation of the program or the benchmark."""
    out = []
    for p in profile.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            evs = [(e.start_ns, e.duration_ns, e.name) for e in ln.events]
            if any(n.startswith(ANNOTATIONS) for _, _, n in evs):
                out.extend(evs)
    return out


def _covered(intervals, a, b) -> float:
    """Length of [a, b] covered by the union of `intervals` (s, e)."""
    total, end = 0.0, a
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, b)
        if e > s:
            total += e - s
            end = e
    return total


def reduce_spans(profile, hlo_text: str, chips: int = 1, top: int = 10) -> SpanReduction:
    mm = marker_map(hlo_text)
    planes = list(profile.planes)
    devs = sorted(
        (p for p in planes if p.name.startswith("/device:TPU:")),
        key=lambda p: int(p.name.rsplit(":", 1)[1]),
    )[:chips]
    if len(devs) < chips:
        raise ValueError(f"the trace has {len(devs)} TPU device planes, not {chips}")
    per_chip = [_plane_markers(d, mm) for d in devs]
    markers = {
        k: sum(c.get(k, 0.0) for c in per_chip) / chips
        for k in sorted(set().union(*per_chip))
    }

    host = annotated_host_events(profile)
    spans: dict[str, list] = {}
    for _, d, n in host:
        if n.startswith("shadow."):
            s = spans.setdefault(n, [0.0, 0])
            s[0] += d * 1e-9
            s[1] += 1

    lines = {ln.name: ln for ln in devs[0].lines}
    _, _, gaps = T._union((e.start_ns, e.start_ns + e.duration_ns)
                          for e in lines["XLA Ops"].events)
    runs = [(s, s + d) for s, d, n in host if n == "shadow.run"]
    named = [(s, s + d) for s, d, n in host
             if n.startswith(ANNOTATIONS) and n not in OUTER]
    idle = sum(b - a for a, b in gaps)
    in_run = sum(_covered(runs, a, b) for a, b in gaps)
    named_idle = sum(_covered(named, a, b) for a, b in gaps)

    marks = [(s, d, n) for s, d, n in host if n.startswith(ANNOTATIONS)]
    progs = sorted((e.start_ns, e.name.split("(")[0]) for e in lines["XLA Modules"].events)
    prog_starts = [t for t, _ in progs]

    def label(a, b):
        i = bisect.bisect_left(prog_starts, b)
        before = progs[i - 1][1] if i > 0 else "start"
        after = progs[i][1] if i < len(progs) else "end"
        return f"{before} -> {after}: {T._host_label(marks, (a + b) / 2)}"

    def under(spans, t):
        return any(s <= t <= e for s, e in spans)

    mids = [(a + b) / 2 for a, b in gaps if b - a >= GAP_S * 1e9]
    in_runs = [t for t in mids if under(runs, t)]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return SpanReduction(
        markers=markers,
        host_spans=spans,
        idle_gaps=[[label(a, b), (b - a) * 1e-9] for a, b in longest],
        idle_s=idle * 1e-9,
        idle_in_run_s=in_run * 1e-9,
        idle_named_s=named_idle * 1e-9,
        run_gaps=len(in_runs),
        run_gaps_unnamed=sum(not under(named, t) for t in in_runs),
    )


def per_round(red: SpanReduction, rounds: int) -> dict:
    """The per-layer numbers these spans and scopes give, in ms a round;
    None where the trace holds nothing to read (no collective on one
    chip, a program without the marker)."""

    def ms(v):
        return 1e3 * v / rounds if v and rounds else None

    sp = red.host_spans
    run_s = sp.get("shadow.run", [0.0])[0]
    sync_s = sp.get("shadow.sync", [0.0])[0]
    mk = red.markers
    return {
        "driver.host_ms_per_round": ms(run_s - sync_s) if run_s else None,
        "barrier.ms_per_round": ms(mk.get("shadow_window")),
        "collective.ms_per_round": ms(mk.get("shadow_collective")),
        "merge.order_ms_per_round": ms(mk.get("shadow_merge_order")),
        "merge.write_ms_per_round": ms(mk.get("shadow_merge_write")),
        "handler.ms_per_round": ms(mk.get("shadow_handler")),
    }

