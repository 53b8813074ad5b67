"""From a profiler trace of the window to device numbers.

The TPU trace names each device operation by its HLO instruction
("%fusion.2800 = s32[10001] fusion(...)") and carries no scope path. The
compiled program's HLO text carries the path in each instruction's
`metadata={op_name=".../shadow_microsteps/..."}`, and the control flow
that owns the rest (an instruction without metadata belongs to the
computation that calls it). So one walk of the HLO text maps instruction
names to scopes and to the program's `shadow_*` markers, then the
reduction sums the durations of the trace's events. A per-layer reader
gets this `Reduction`:

    busy_s      union of the device-op intervals (all programs)
    span_s      first device op start to last device op end
    scopes      seconds of the chunk program's ops per scope: microsteps
                (`shadow_microsteps`), exchange (`shadow_exchange`, with
                `shadow_merge` inside it), other (the rest of the round)
    markers     seconds of the chunk program's ops per `shadow_*` named
                scope on their `op_name` path (`shadow_window`,
                `shadow_collective`, `shadow_merge_order`, ...): an op
                counts toward every marker on its path, inherited from its
                caller where it has no path of its own
    host_spans  seconds and count of each `shadow.*` annotation the program
                opens on the host (`shadow.run`, `shadow.sync`, ...)
    chunk_dispatches  runs of the chunk program
    top_ops     the device operations that took most time
    idle_gaps   the longest gaps between device ops, named by the programs
                on either side ("jit__unknown -> jit_copy": a chunk, then
                a snapshot copy) and the innermost `shadow.*` or `bench.*`
                annotation spanning the gap's middle

Control flow (while, conditional, ...) is counted in no scope and no
marker: its body's ops are. Scopes and markers are averaged over the chips
used; the top ops and idle gaps are the first chip's.
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import re

SCOPES = (("microsteps", "shadow_microsteps"), ("exchange", "shadow_exchange"))
MARKER = "shadow_"  # a path component the program names its scopes with
ANNOTATIONS = ("shadow.", "bench.")  # host annotations: the program's, the benchmark's
CONTAINERS = {"while", "conditional", "call", "async-start", "async-done"}

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\)?\s*([a-z][\w\-]*)\(")
_CALLS = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)=%?([\w.\-]+)"
)
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPNAME = re.compile(r'op_name="([^"]*)"')


def _scope_of(op_name: str | None) -> str | None:
    if not op_name:
        return None
    for scope, marker in SCOPES:
        if marker in op_name:
            return scope
    return None


def _markers_of(op_name: str | None) -> frozenset | None:
    if not op_name:
        return None
    return frozenset(p for p in op_name.split("/") if p.startswith(MARKER)) or None


def _opcode(rhs: str) -> str:
    """The opcode of an instruction's right-hand side (after its shape)."""
    depth = 0
    for i, ch in enumerate(rhs):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            m = re.match(r"\s*([a-z][\w\-]*)\(", rhs[i:])
            if m:
                return m.group(1)
    m = _OPCODE.search(rhs)
    return m.group(1) if m else ""


@dataclasses.dataclass
class HloMap:
    module: str  # the HloModule's name ("jit__unknown")
    scope: dict  # instruction name -> "microsteps" | "exchange" | "other"
    markers: dict  # instruction name -> frozenset of `shadow_*` markers
    opcode: dict  # instruction name -> opcode


def hlo_map(text: str) -> HloMap:
    """Scope and markers of every instruction of a compiled module's HLO
    text. An instruction whose `op_name` names no scope takes its caller's;
    one without a `shadow_*` marker on its path takes its caller's set."""
    module = re.search(r"^HloModule\s+([\w.\-]+)", text, re.M).group(1)
    comps: dict[str, list] = {}
    entry = None
    cur = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP.match(line)
            if m and not line.startswith("HloModule"):
                cur = m.group(1)
                comps[cur] = []
                if line.startswith("ENTRY"):
                    entry = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if m:
            name, rhs = m.groups()
            op = _OPNAME.search(rhs)
            called = _CALLS.findall(rhs)
            for b in _BRANCHES.findall(rhs):
                called += [c.strip().lstrip("%") for c in b.split(",") if c.strip()]
            comps[cur].append((name, _opcode(rhs), op.group(1) if op else None, called))
    scope: dict[str, str] = {}
    markers: dict[str, frozenset] = {}
    opcode: dict[str, str] = {}
    # top-down from the entry: a computation takes its caller's scope and markers
    stack = [(entry, "other", frozenset())]
    seen = set()
    while stack:
        comp, inherited, inherited_mk = stack.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, opc, op_name, called in comps[comp]:
            s = _scope_of(op_name) or inherited
            mk = _markers_of(op_name) or inherited_mk
            scope[name] = s
            markers[name] = mk
            opcode[name] = opc
            stack.extend((c, s, mk) for c in called)
    return HloMap(module=module, scope=scope, markers=markers, opcode=opcode)


@dataclasses.dataclass
class Reduction:
    busy_s: float
    span_s: float  # first device op start to last device op end
    scopes: dict  # scope -> seconds (chunk program only)
    markers: dict  # `shadow_*` marker -> seconds (chunk program only)
    host_spans: dict  # `shadow.*` annotation -> [seconds, count]
    chunk_dispatches: int
    top_ops: list  # [[name, seconds]]
    idle_gaps: list  # [[name, seconds]]


def _instr_name(event_name: str) -> str:
    m = re.match(r"%?([\w.\-]+)\s*=", event_name)
    return m.group(1) if m else event_name


def load_profile(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _union(intervals):
    total, end = 0, None
    gaps = []
    for s, e in sorted(intervals):
        if end is None:
            total, end = e - s, e
            first = s
            continue
        if s > end:
            gaps.append((end, s))
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total, (end - first if end is not None else 0), gaps


def _host_label(host_events, t_ns: float) -> str:
    """The innermost host event (shortest) spanning `t_ns`."""
    best = None
    for s, d, name in host_events:
        if s <= t_ns <= s + d and (best is None or d < best[0]):
            best = (d, name)
    return best[1][:80] if best else "no host event"


def annotated_host_events(profile) -> list[tuple[int, int, str]]:
    """(start_ns, duration_ns, name) of every annotation of the program or
    the benchmark on the host planes."""
    return [
        (e.start_ns, e.duration_ns, e.name)
        for p in profile.planes
        if p.name.startswith("/host:")
        for ln in p.lines
        for e in ln.events
        if e.name.startswith(ANNOTATIONS)
    ]


def _reduce_plane(dev, hlo: HloMap):
    """One chip's busy intervals, seconds per scope, per marker and per op."""
    lines = {ln.name: ln for ln in dev.lines}
    modules = [
        (e.start_ns, e.start_ns + e.duration_ns)
        for e in lines["XLA Modules"].events
        if e.name.startswith(hlo.module + "(")
    ]
    intervals = []
    scopes = {"microsteps": 0.0, "exchange": 0.0, "other": 0.0}
    markers: dict[str, float] = {}
    per_op: dict[str, float] = {}
    mi = sorted(modules)
    starts = [a for a, _ in mi]
    for e in lines["XLA Ops"].events:
        s, d = e.start_ns, e.duration_ns
        intervals.append((s, s + d))
        name = _instr_name(e.name)
        k = bisect.bisect_right(starts, s) - 1
        in_chunk = k >= 0 and s < mi[k][1]
        if in_chunk and name in hlo.scope:
            if hlo.opcode.get(name) in CONTAINERS:
                continue
            sc = hlo.scope[name]
            scopes[sc] += d * 1e-9
            for mk in hlo.markers[name]:
                markers[mk] = markers.get(mk, 0.0) + d * 1e-9
            key = f"{sc}:{name}"
        else:
            key = f"outside:{name}"
        per_op[key] = per_op.get(key, 0.0) + d * 1e-9
    return lines, modules, scopes, markers, per_op, _union(intervals)


def reduce_profile(profile, hlo: HloMap, top: int = 10, chips: int = 1) -> Reduction:
    """Reduce the trace of the first `chips` TPU planes with `hlo`'s scopes
    and markers: busy time, scope and marker seconds are averaged over
    those chips; the top ops and idle gaps are the first chip's."""
    planes = list(profile.planes)
    devs = [p for p in planes if p.name.startswith("/device:TPU:")]
    if len(devs) < chips:
        raise ValueError(f"the trace has {len(devs)} TPU device planes, not {chips}")
    devs = sorted(devs, key=lambda p: int(p.name.rsplit(":", 1)[1]))[:chips]
    per_chip = [_reduce_plane(d, hlo) for d in devs]
    lines, modules, _, _, per_op, (_, span, gaps) = per_chip[0]
    busy = sum(c[5][0] for c in per_chip) / chips
    scopes = {k: sum(c[2][k] for c in per_chip) / chips for k in per_chip[0][2]}
    markers = {
        k: sum(c[3].get(k, 0.0) for c in per_chip) / chips
        for k in sorted(set().union(*(c[3] for c in per_chip)))
    }
    host = annotated_host_events(profile)
    host_spans: dict[str, list] = {}
    for _, d, n in host:
        if n.startswith("shadow."):
            s = host_spans.setdefault(n, [0.0, 0])
            s[0] += d * 1e-9
            s[1] += 1
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    progs = sorted((e.start_ns, e.name.split("(")[0]) for e in lines["XLA Modules"].events)
    prog_starts = [t for t, _ in progs]

    def label(a, b):
        i = bisect.bisect_left(prog_starts, b)
        before = progs[i - 1][1] if i > 0 else "start"
        after = progs[i][1] if i < len(progs) else "end"
        return f"{before} -> {after}: {_host_label(host, (a + b) / 2)}"

    return Reduction(
        busy_s=busy * 1e-9,
        span_s=span * 1e-9,
        scopes=scopes,
        markers=markers,
        host_spans=host_spans,
        chunk_dispatches=len(modules),
        top_ops=[[k, v] for k, v in sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[label(a, b), (b - a) * 1e-9] for a, b in gaps],
    )
