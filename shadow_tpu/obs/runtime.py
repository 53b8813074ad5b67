"""Runtime observatory: wall-clock attribution plane, compile-latency
ledger, and bridge-stall telemetry (`observability.runtime`).

Wall-clock time is the denominator of every BASELINE headline, yet until
now it was attributed by hand: BASELINE r6's "~83% of the CPU microstep
is handler dispatch" decomposition was a one-off manual exercise, the
drivers' scattered host timers (PR 3 PerfTimers, bench per-chunk walls,
supervisor snapshot spans) never reconciled against the run's total
wall, and cold jit compiles silently leaked into measured windows. This
module is the third observatory (after HBM, obs/memory.py, and network,
obs/netobs.py), and it follows the same observer contract: everything
here is HOST-SIDE — no traced code, digests/events/drops bit-identical
on or off, the default jaxpr fingerprints byte-unchanged
(tests/test_runtime.py is the gate).

Three instruments:

  `CompileLedger` — every jitted chunk program the engine caches (the
  base chunk, each merge-gear variant, each (gear, capacity, budget)
  pressure rung, the cosim prepare/guarded programs) records its
  lowering + backend-compile wall time (precise, via the
  jax.monitoring duration events emitted during the cold call), the
  TRIGGER that caused the compile (cold start, gear shift, pressure
  regrow), and cache hit counts. This is the number ROADMAP item 6's
  persistent/async compile cache must beat.

  `WallLedger` — unifies the drivers' host timers into one per-chunk
  attribution: each chunk's wall is split into named spans (compile /
  dispatch / host_python / snapshot / replay / export) whose sum equals
  the chunk wall EXACTLY (the residual not covered by an explicit span
  is host_python), paired with a per-chunk realtime factor
  (sim-seconds advanced per wall-second — Rain's serving-level metric,
  arxiv 2606.03352) surfaced as the heartbeat `rt=` field. Spans that
  overlap a dispatch (a replay's snapshot restore, a regrown program's
  compile) are RE-ATTRIBUTED out of the dispatch span rather than
  double-counted, so per-chunk sums always reconcile.

  `BridgeTelemetry` — the cosim bridge's per-window stall split:
  CPU-plane execute vs device-plane wall vs bridge (staging injection,
  capture draining, and the residual marshalling between them), plus a
  per-syscall-batch injection-latency histogram. This is ROADMAP item
  4's before/after instrument: the COREC-style lock-free bridge (arxiv
  2401.12815) is justified exactly when the bridge share dominates.

`tools/rt_report.py` reads the exported `runtime{}` block and prints the
attribution verdict; `tools/bench_compare.py` diffs the bench rows'
`runtime{}` blocks for realtime-factor and compile-wall regressions.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Any

from jax.profiler import StepTraceAnnotation, TraceAnnotation

# WallLedger span names. `host_python` is the residual: whatever part of
# a chunk's wall no explicit span covered (heartbeats, counter reads,
# controller bookkeeping) — which is why per-chunk span sums equal the
# chunk wall by construction.
SPAN_NAMES = (
    "compile", "dispatch", "host_python", "snapshot", "replay", "export",
)

# bounded in-memory series (a resident-service run must not grow
# unbounded Python lists; overflow is counted, never silent)
MAX_CHUNK_RECORDS = 4096
MAX_WINDOW_RECORDS = 4096
# rt series entries exported into sim-stats (the newest are kept — the
# steady-state tail is the serving-posture signal)
MAX_EXPORTED_SERIES = 512

# per-syscall-batch injection-latency histogram bucket edges (seconds).
# Decade-ish log spacing from 0.1 ms to 3 s; the last bucket is +inf.
INJECT_HIST_EDGES_S = (
    1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 0.1, 0.3, 1.0, 3.0,
)


# ---------------------------------------------------------------------------
# compile ledger
# ---------------------------------------------------------------------------

# jax.monitoring routes compile-pipeline durations to listeners; the ONE
# module-level listener dispatches to whichever ledger entry is armed
# (the drivers are single-threaded, so a stack suffices). Registered
# lazily and exactly once per process — jax 0.4.x has no unregister API,
# and re-registering per ledger would leak listeners across the many
# sims a test process builds.
_ACTIVE_ENTRIES: list[dict] = []
_LISTENER_ON = False


def _on_compile_duration(name: str, secs: float, **_kw) -> None:
    if not _ACTIVE_ENTRIES:
        return
    e = _ACTIVE_ENTRIES[-1]
    if name.endswith("jaxpr_trace_duration"):
        e["trace_s"] += secs
    elif name.endswith("jaxpr_to_mlir_module_duration"):
        e["lower_s"] += secs
    elif name.endswith("backend_compile_duration"):
        e["compile_s"] += secs


def _ensure_listener() -> bool:
    global _LISTENER_ON
    if _LISTENER_ON:
        return True
    try:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_duration
        )
        _LISTENER_ON = True
    except Exception:
        # older/foreign jax without the monitoring API: the ledger still
        # records cold-call walls, only the lower/compile split is absent
        pass
    return _LISTENER_ON


class CompileLedger:
    """Per-program compile accounting for lazily-jitted chunk programs.

    `instrument(kind, label, trigger, fn)` wraps a jitted callable: the
    FIRST call (the one that traces, lowers, and compiles) is recorded
    as one ledger entry — cold-call wall, plus the precise trace/lower/
    backend-compile durations harvested from jax.monitoring while the
    call runs — and every later call counts as a cache hit. Wrapping is
    pure host-side observation: the callable's arguments and results
    pass through untouched, so the traced program cannot change.

    `wall` (optional, a WallLedger) receives a reattribution of the
    compile pipeline's seconds out of the enclosing dispatch span, so
    the attribution plane shows compiles as compile time, not as a
    mysteriously slow first dispatch.
    """

    def __init__(self, wall: "WallLedger | None" = None):
        self.entries: list[dict] = []
        self.cache_hits = 0
        self.wall = wall
        self.monitored = _ensure_listener()

    def instrument(self, kind: str, label: str, trigger: str, fn):
        entry_box: dict[str, Any] = {"e": None}

        def wrapped(*args, **kw):
            e = entry_box["e"]
            if e is not None:
                e["hits"] += 1
                self.cache_hits += 1
                return fn(*args, **kw)
            e = {
                "kind": kind, "label": label, "trigger": trigger,
                "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
                "cold_s": 0.0, "t0": time.monotonic(), "hits": 0,
            }
            entry_box["e"] = e
            self.entries.append(e)
            _ACTIVE_ENTRIES.append(e)
            try:
                out = fn(*args, **kw)
            finally:
                _ACTIVE_ENTRIES.pop()
                e["cold_s"] = time.monotonic() - e["t0"]
                if self.wall is not None:
                    # in the finally: a cold call that compiles and then
                    # RAISES (a freshly regrown rung dying in-dispatch)
                    # must still show its pipeline as compile time, or
                    # the enclosing dispatch/replay spans absorb it and
                    # the controller's compile-delta subtraction sees 0
                    self.wall.reattribute(
                        "dispatch", "compile", self.pipeline_s(e)
                    )
            return out

        return wrapped

    @staticmethod
    def pipeline_s(e: dict) -> float:
        """One entry's trace+lower+compile pipeline seconds (the honest
        'what a warm cache would have saved' figure; cold_s additionally
        includes the first dispatch's enqueue)."""
        return e["trace_s"] + e["lower_s"] + e["compile_s"]

    def total_pipeline_s(self) -> float:
        return sum(self.pipeline_s(e) for e in self.entries)

    def compiles_in(self, t0: float, t1: float) -> float:
        """Pipeline seconds of entries whose cold call STARTED inside
        the [t0, t1) monotonic window — what bench.py subtracts so
        sim-s/wall-s never silently folds a mid-run compile in."""
        return sum(
            self.pipeline_s(e) for e in self.entries if t0 <= e["t0"] < t1
        )

    def events(self) -> list[tuple[str, float, float]]:
        """(label, t0_monotonic, duration_s) per compile — the Chrome
        trace's compile track (RoundTracer.note_compiles)."""
        return [
            (
                f"{e['kind']}:{e['label']} ({e['trigger']})",
                e["t0"],
                max(self.pipeline_s(e), e["cold_s"], 1e-6),
            )
            for e in self.entries
        ]

    def summary(self) -> dict:
        by_trigger: dict[str, int] = {}
        for e in self.entries:
            by_trigger[e["trigger"]] = by_trigger.get(e["trigger"], 0) + 1
        return {
            "programs": len(self.entries),
            "cache_hits": self.cache_hits,
            "monitored": self.monitored,
            "compile_wall_s": round(self.total_pipeline_s(), 4),
            "backend_compile_s": round(
                sum(e["compile_s"] for e in self.entries), 4
            ),
            "lower_s": round(
                sum(e["lower_s"] + e["trace_s"] for e in self.entries), 4
            ),
            "cold_wall_s": round(
                sum(e["cold_s"] for e in self.entries), 4
            ),
            "by_trigger": by_trigger,
            "entries": [
                {
                    "kind": e["kind"], "label": e["label"],
                    "trigger": e["trigger"],
                    "compile_s": round(e["compile_s"], 4),
                    "lower_s": round(e["lower_s"] + e["trace_s"], 4),
                    "cold_s": round(e["cold_s"], 4),
                    "hits": e["hits"],
                }
                for e in self.entries
            ],
        }


# ---------------------------------------------------------------------------
# wall-clock attribution plane
# ---------------------------------------------------------------------------


def span_or_null(wall: "WallLedger | None", name: str):
    """`with span_or_null(wall, "dispatch"):` — always a profiler
    annotation `shadow.<name>`, and the ledger's span too when the
    observatory is on and `name` is one of SPAN_NAMES (any other span's
    seconds stay in the ledger's host_python residual), so driver loops
    carry one code path.

    The annotation lands on the host plane of a running profiler session
    (`observability.profile_dir`, or any `jax.profiler.start_trace`), on
    the clock of the device ops, so a trace names the host work behind
    every gap between dispatches. With no session it costs ~1 us."""
    ann = TraceAnnotation(f"shadow.{name}")
    if wall is None or name not in SPAN_NAMES:
        return ann
    return _both(ann, wall.span(name))


@contextmanager
def _both(outer, inner):
    with outer, inner:
        yield


def chunk_span(step: int):
    """`shadow.chunk`, one iteration of a driver loop, as a profiler step
    (`step_num` = the chunk's index)."""
    return StepTraceAnnotation("shadow.chunk", step_num=step)


class WallLedger:
    """Per-chunk wall-clock attribution with a realtime-factor series.

    Protocol (driver loop):
        wall.sync_sim(int(state.now))          # once, before the loop
        ...
        wall.chunk_start()
        with wall.span("dispatch"): ...        # dispatch + block
        with wall.span("export"): ...          # drains/samples
        wall.chunk_end(int(state.now))         # closes the chunk

    Per-chunk exactness: chunk wall == sum of its spans, because the
    residual no span covered is folded into `host_python` at
    `chunk_end`. Overlapping attribution (a compile inside a dispatch,
    a snapshot inside a supervised dispatch) goes through
    `reattribute(frm, to, sec)`, which MOVES seconds between spans at
    chunk close (clamped at the source span's balance) instead of
    counting them twice.
    """

    def __init__(self, max_chunks: int = MAX_CHUNK_RECORDS):
        self.totals = {s: 0.0 for s in SPAN_NAMES}
        self.chunks: list[dict] = []
        self.chunks_total = 0
        self.chunks_dropped = 0
        self.max_chunks = int(max_chunks)
        self.rt_last: float | None = None
        self.wall0: float | None = None
        self._cur: dict | None = None
        self._t0: float | None = None
        self._moves: list[tuple[str, str, float]] = []
        self._last_sim_ns = 0

    def sync_sim(self, sim_ns: int) -> None:
        """Adopt the state's current sim time as the rt baseline, so a
        resumed/restored run's first chunk is not credited with the
        whole pre-restore horizon."""
        self._last_sim_ns = int(sim_ns)

    def chunk_start(self) -> None:
        self._cur = {s: 0.0 for s in SPAN_NAMES}
        self._t0 = time.monotonic()
        self._moves = []
        if self.wall0 is None:
            self.wall0 = self._t0

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            sec = time.perf_counter() - t0
            if self._cur is not None:
                self._cur[name] = self._cur.get(name, 0.0) + sec
            else:
                # outside a chunk (warm-up work): totals-only accounting
                self.totals[name] = self.totals.get(name, 0.0) + sec

    def reattribute(self, frm: str, to: str, sec: float) -> None:
        """Move `sec` from span `frm` to span `to` inside the open chunk
        (applied clamped at chunk close). No-op outside a chunk."""
        if self._cur is not None and sec > 0:
            self._moves.append((frm, to, float(sec)))

    def pending_to(self, name: str) -> float:
        """Seconds already queued for reattribution INTO `name` in the
        open chunk — lets a caller measuring an enclosing interval
        subtract what an inner instrument already claimed."""
        return sum(s for _f, t, s in self._moves if t == name)

    def chunk_end(self, sim_ns: int) -> float | None:
        """Close the open chunk; returns its realtime factor."""
        if self._cur is None:
            return None
        t1 = time.monotonic()
        cur, self._cur = self._cur, None
        for frm, to, sec in self._moves:
            sec = min(sec, cur.get(frm, 0.0))
            cur[frm] = cur.get(frm, 0.0) - sec
            cur[to] = cur.get(to, 0.0) + sec
        self._moves = []
        wall = max(t1 - (self._t0 or t1), 0.0)
        cur["host_python"] += max(wall - sum(cur.values()), 0.0)
        for k, v in cur.items():
            self.totals[k] = self.totals.get(k, 0.0) + v
        sim_delta = max(int(sim_ns) - self._last_sim_ns, 0)
        self._last_sim_ns = int(sim_ns)
        rt = (sim_delta / 1e9) / max(wall, 1e-9)
        self.rt_last = rt
        self.chunks_total += 1
        rec = {
            "wall_s": wall, "sim_ns": sim_delta, "rt": rt,
            "spans": {k: v for k, v in cur.items() if v > 0},
        }
        if len(self.chunks) < self.max_chunks:
            self.chunks.append(rec)
        else:
            self.chunks_dropped += 1
        return rt

    # ---- exporters ---------------------------------------------------------

    def rt_series(self) -> list[float]:
        return [c["rt"] for c in self.chunks]

    def summary(self, total_wall_s: float | None = None) -> dict:
        attributed = sum(self.totals.values())
        rts = sorted(self.rt_series())
        chunk_walls = sum(c["wall_s"] for c in self.chunks)
        out: dict[str, Any] = {
            "spans_s": {k: round(v, 4) for k, v in self.totals.items()},
            "chunks": self.chunks_total,
            "chunks_recorded": len(self.chunks),
            "attributed_wall_s": round(attributed, 4),
            "chunk_wall_s": round(chunk_walls, 4),
        }
        if total_wall_s:
            out["total_wall_s"] = round(float(total_wall_s), 4)
            out["attributed_share"] = round(
                attributed / max(float(total_wall_s), 1e-9), 4
            )
        if attributed > 0:
            out["shares"] = {
                k: round(v / attributed, 4)
                for k, v in self.totals.items() if v > 0
            }
        if rts:
            series = self.rt_series()[-MAX_EXPORTED_SERIES:]
            out["realtime_factor"] = {
                "overall": round(
                    sum(c["sim_ns"] for c in self.chunks) / 1e9
                    / max(chunk_walls, 1e-9), 4,
                ),
                "last": round(self.rt_last or 0.0, 4),
                "p50": round(rts[len(rts) // 2], 4),
                "min": round(rts[0], 4),
                "max": round(rts[-1], 4),
                "series": [round(r, 4) for r in series],
                **(
                    {"series_dropped": self.chunks_total - len(series)}
                    if self.chunks_total > len(series) else {}
                ),
            }
        return out


# ---------------------------------------------------------------------------
# bridge-stall telemetry (cosim)
# ---------------------------------------------------------------------------


class BridgeTelemetry:
    """Per-window wall split for the hybrid (cosim) bridge.

    Three lanes per window — `cpu_plane` (the CPU hosts' event loops),
    `device_plane` (the guarded device dispatch), and `bridge` (staging
    injection + capture draining + the residual marshalling between the
    planes) — plus a per-syscall-batch injection-latency histogram
    (`note_batch`). The split answers ROADMAP item 4's question: a
    bridge share that dominates the window wall is the COREC ring-buffer
    rebuild's justification; one that doesn't says the bottleneck is
    elsewhere. Host-side observation only."""

    LANES = ("cpu_plane", "device_plane", "bridge")

    def __init__(self, max_windows: int = MAX_WINDOW_RECORDS):
        self.totals = {k: 0.0 for k in self.LANES}
        self.windows: list[dict] = []
        self.windows_total = 0
        self.windows_dropped = 0
        self.max_windows = int(max_windows)
        self.rt_last: float | None = None
        self.batch_counts = [0] * (len(INJECT_HIST_EDGES_S) + 1)
        self.batches = 0
        self.batch_entries = 0
        self.batch_wall_s = 0.0
        self._cur: dict | None = None
        self._t0: float | None = None
        self._last_sim_ns = 0

    def sync_sim(self, sim_ns: int) -> None:
        self._last_sim_ns = int(sim_ns)

    def window_start(self) -> None:
        self._cur = {k: 0.0 for k in self.LANES}
        self._t0 = time.monotonic()

    def note(self, lane: str, sec: float) -> None:
        if self._cur is not None:
            self._cur[lane] += max(float(sec), 0.0)

    def note_batch(self, sec: float, entries: int) -> None:
        """One staged-send injection batch (one `_inject` dispatch): its
        wall latency lands in the log-spaced histogram, its seconds in
        the window's bridge lane."""
        self.batches += 1
        self.batch_entries += int(entries)
        self.batch_wall_s += max(float(sec), 0.0)
        i = 0
        while i < len(INJECT_HIST_EDGES_S) and sec > INJECT_HIST_EDGES_S[i]:
            i += 1
        self.batch_counts[i] += 1
        self.note("bridge", sec)

    def window_end(self, sim_ns: int) -> float | None:
        if self._cur is None:
            return None
        t1 = time.monotonic()
        cur, self._cur = self._cur, None
        wall = max(t1 - (self._t0 or t1), 0.0)
        # the residual — python marshalling between the measured lanes —
        # is bridge work by definition (it exists only to couple them)
        cur["bridge"] += max(wall - sum(cur.values()), 0.0)
        for k, v in cur.items():
            self.totals[k] += v
        sim_delta = max(int(sim_ns) - self._last_sim_ns, 0)
        self._last_sim_ns = int(sim_ns)
        rt = (sim_delta / 1e9) / max(wall, 1e-9)
        self.rt_last = rt
        self.windows_total += 1
        rec = {"wall_s": wall, "sim_ns": sim_delta, "rt": rt, **cur}
        if len(self.windows) < self.max_windows:
            self.windows.append(rec)
        else:
            self.windows_dropped += 1
        return rt

    def summary(self) -> dict:
        total = sum(self.totals.values())
        rts = sorted(w["rt"] for w in self.windows)
        out: dict[str, Any] = {
            "windows": self.windows_total,
            "windows_recorded": len(self.windows),
            "spans_s": {k: round(v, 4) for k, v in self.totals.items()},
            "syscall_batches": {
                "batches": self.batches,
                "entries": self.batch_entries,
                "wall_s": round(self.batch_wall_s, 4),
                "hist_edges_s": list(INJECT_HIST_EDGES_S),
                "hist_counts": list(self.batch_counts),
            },
        }
        if total > 0:
            out["shares"] = {
                k: round(v / total, 4) for k, v in self.totals.items()
            }
            out["bridge_share"] = out["shares"].get("bridge", 0.0)
        if rts:
            out["realtime_factor"] = {
                "last": round(self.rt_last or 0.0, 4),
                "p50": round(rts[len(rts) // 2], 4),
                "min": round(rts[0], 4),
                "max": round(rts[-1], 4),
            }
        return out


# ---------------------------------------------------------------------------
# shared report assembly
# ---------------------------------------------------------------------------


def assemble_runtime_report(
    *,
    wall: WallLedger | None = None,
    compiles: CompileLedger | None = None,
    bridge: BridgeTelemetry | None = None,
    total_wall_s: float | None = None,
) -> dict:
    """The ONE `runtime{}` block assembly every exporter shares (sim
    stats_report, the hybrid driver, bench rows) — the netobs
    `assemble_network_report` idiom, so the block's shape cannot drift
    between exporters."""
    out: dict[str, Any] = {}
    if wall is not None:
        out.update(wall.summary(total_wall_s))
    if bridge is not None:
        out["bridge"] = bridge.summary()
        if "realtime_factor" not in out and bridge.windows:
            rts = sorted(w["rt"] for w in bridge.windows)
            sim_s = sum(w["sim_ns"] for w in bridge.windows) / 1e9
            walls = sum(w["wall_s"] for w in bridge.windows)
            out["realtime_factor"] = {
                "overall": round(sim_s / max(walls, 1e-9), 4),
                "last": round(bridge.rt_last or 0.0, 4),
                "p50": round(rts[len(rts) // 2], 4),
                "min": round(rts[0], 4),
                "max": round(rts[-1], 4),
                "series": [
                    round(w["rt"], 4)
                    for w in bridge.windows[-MAX_EXPORTED_SERIES:]
                ],
            }
    if compiles is not None:
        out["compiles"] = compiles.summary()
    return out


def bench_runtime_block(
    compiles: CompileLedger | None,
    wall: WallLedger | None,
    sim_adv_s: float,
    wall_s: float,
    window: tuple[float, float] | None = None,
) -> dict:
    """The BENCH row's compact `runtime{}` block (the diffable shape
    tools/bench_compare.py gates on): total compile wall, the compile
    wall that landed INSIDE the measured window, the realtime factor,
    and the factor with in-window compiles excluded — so sim-s/wall-s
    never silently folds a cold compile in."""
    out: dict[str, Any] = {
        "realtime_factor": round(sim_adv_s / max(wall_s, 1e-9), 4),
    }
    if compiles is not None:
        cin = (
            compiles.compiles_in(*window) if window is not None else 0.0
        )
        out.update({
            "compile_wall_s": round(compiles.total_pipeline_s(), 4),
            "compile_in_window_s": round(cin, 4),
            "compile_programs": len(compiles.entries),
            "cache_hits": compiles.cache_hits,
            "realtime_factor_ex_compile": round(
                sim_adv_s / max(wall_s - cin, 1e-9), 4
            ),
        })
    if wall is not None:
        s = wall.summary()
        if "shares" in s:
            out["shares"] = s["shares"]
    return out
