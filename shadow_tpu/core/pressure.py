"""Pressure plane: drop-free operation under capacity pressure.

Every fixed-shape lane in the engine sheds under pressure — per-host
event queues count push overflow into `queue.dropped` (ops/events.py),
the exchange merge and the alltoall blocks shed into `queue.dropped` /
`stats.a2a_shed`, and the per-host send budget drops into
`stats.pkts_budget_dropped`. At the host counts ROADMAP item 1 targets,
silent capacity pressure becomes the dominant failure mode, while the
reference Shadow never drops an event. This module makes pressure a
POLICY instead of a fate (`pressure:` config block, options.py):

  drop      — today's semantics (default). No pressure code is traced;
              the program is bit-identical to the pre-pressure engine.
  escalate  — drop-free by construction. The chunk while_loop aborts
              uniformly across the mesh at the first round where any
              host would drop (the psum'd `stats.pressure` total, same
              mechanism as `stats.gear_shed`); the driver restores the
              pre-chunk device snapshot, migrates the state to a grown
              shape — queue capacity C -> C' via the exactness-gated
              `ops.events.migrate_queue`, and/or a wider outbox B' —
              and replays the chunk. Accepted chunks carry ZERO drops,
              so the accepted trajectory is bit-identical to a run
              launched at the final shape (with the valve pins
              `Engine.run_chunk_resized` documents).
  abort     — loud failure. The same first-drop abort stops the run at
              the dropping round; the driver exports honest artifacts
              (the state INCLUDING the drop, flagged `pressure.aborted`)
              instead of silently shedding for the rest of the horizon.

`ResilienceController` below generalizes `core/gears.run_adaptive_chunk`
into ONE snapshot-replay loop arbitrating both axes: merge-gear shifts
(a too-narrow gear is a transient perf choice — replay one gear up) and
capacity regrows (a too-small shape is a correctness hazard — replay at
a grown shape). One cached jitted program exists per (gear, capacity,
budget) triple (`Engine.run_chunk_resized`), the ladders are bounded
(`max_capacity` is the HBM guard), and regrow is also PROACTIVE: at
chunk boundaries the always-on `stats.q_occ_hwm` / `stats.outbox_hwm`
high-waters trigger a grow BEFORE anything drops, so steady pressure
costs one migration, not a replayed chunk.

The hierarchical exchange rides both axes for free: an escalated outbox
width B' flows through `Engine.resized_cfg`'s dataclasses.replace, so the
auto inter-shard block size (`EngineConfig.hier_block_size`, derived from
hosts_per_shard x effective_gear_cols) re-derives at the regrown shape —
a wider outbox also widens the alltoall blocks, and the grown program
stays shed-free for the same traffic that grew it. An EXPLICIT a2a_block
is pinned across regrows (explicit settings always win); if a regrow
outgrows it, the block overflow stays loud via the usual gear_shed /
a2a_shed accounting rather than silently resizing the wire format.

Graceful degradation when escalation itself fails: a grown program's
compile/dispatch dying of RESOURCE_EXHAUSTED / XlaRuntimeError marks
that rung (and everything above it) unusable and falls back one rung;
when cornered — drops persist but no usable rung remains — the
controller raises `PressureAbort` with the last good pre-chunk snapshot
kept, so the drivers still export sim-stats/trace artifacts for the
completed prefix (the PR 5 supervisor's graceful-abort posture).

Determinism note (shadowlint control-plane rules apply): decisions here
read CONCRETE device counters between dispatches and feed deterministic
replay — no wall-clock, no RNG. A controller bug can cost replays or
migrations, never correctness: accepted chunks are gated by the in-jit
zero-drop condition, not by anything this module computes.
"""

from __future__ import annotations

import time
from typing import Any

import numpy as np

from shadow_tpu.core.integrity import IntegrityAbort, describe_signature
from shadow_tpu.core.pipeline import (
    ChunkPipeline,
    Flight,
    host_summary,
)

DEFAULT_MAX_CAPACITY_FACTOR = 8  # auto max_capacity = 8x the base slab
DEFAULT_MAX_OUTBOX_FACTOR = 4  # auto max_outbox = 4x the base budget


class PressureAbort(RuntimeError):
    """The pressure policy stopped the run: `abort` saw its first drop,
    or `escalate` was cornered (drops persist with no usable rung left).
    The driver still owns a state to export honest artifacts from —
    `ResilienceController.abort_export_state` documents which one."""


def _is_oom(e: BaseException) -> bool:
    """The grown-program failure signature: XLA's allocation failures
    carry RESOURCE_EXHAUSTED (jaxlib raises the status name in the
    message) or an out-of-memory text. Deliberately MESSAGE-based, not
    type-based: every XlaRuntimeError flavor shares one Python type, and
    treating a non-memory failure (INVALID_ARGUMENT, internal errors) as
    an OOM would launder a real bug into rung-poisoning fallbacks — such
    failures must propagate to the supervisor/driver instead."""
    msg = str(e)
    return "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower()


def resolve_ladder(base: int, ceiling: int, growth: int) -> list[int]:
    """Geometric shape ladder [base, base*g, ...] bounded by `ceiling`
    (inclusive). The base rung is always present; a ceiling below the
    base is a config error the options parser rejects upstream."""
    base, ceiling, growth = int(base), int(ceiling), int(growth)
    ladder = [base]
    while ladder[-1] * growth <= ceiling:
        ladder.append(ladder[-1] * growth)
    return ladder


class ResilienceController:
    """The drivers' shared chunk loop: gear shifts + capacity regrows
    from one snapshot-replay seam.

    Construction:
      gearctl   — a `core.gears.GearController` (or None: full width
                  always). Gear decisions and accounting stay in the
                  gear controller; this class only arbitrates WHEN a
                  replay is a gear problem vs a capacity problem.
      rungctl   — a `core.gears.BlockRungController` (or None: the
                  alltoall block stays at its top rung). A shed is a rung
                  problem when the aborted chunk's per-pair high-water
                  passed the rung, a gear problem when its outbox
                  high-water passed the gear; the replay raises each
                  axis that shed. With it, `dispatch` takes the rung as a
                  fifth argument.
      rung_top  — budget -> the auto alltoall block at that send budget:
                  the rung ladder's top after an outbox regrow.
      pressure  — a `config.options.PressureOptions` with an active
                  policy (escalate/abort), or None (gears only — the
                  exact `run_adaptive_chunk` behavior PR 4 shipped).
      reshard   — optional callable(state) -> state applied after a
                  migration (the mesh drivers pass a device_put onto
                  their NamedSharding specs; eager-op outputs keep
                  axis-0 sharding in simple cases but the specs are the
                  contract).
      memory    — optional `obs.memory.MemoryGuard`: pre-dispatch
                  admission for grown rungs. A candidate whose
                  predicted extra footprint (static-model delta x
                  replay concurrency x safety factor) exceeds the
                  device's MEASURED headroom is refused/poisoned
                  BEFORE dispatch — the escalation then corners into
                  the same loud PressureAbort an OOM round-trip would
                  have forced, minus the wasted compile+dispatch.
                  Inert when no allocator limit is measurable.

    `run_chunk(state, dispatch, rounds0=None)` mirrors
    `run_adaptive_chunk`: dispatch(state, gear, capacity, budget) runs
    one chunk program at that shape and may consume its input (the
    pre-chunk snapshot is an independent device copy). Returns
    (state, accepted_gear, chunk_outbox_hwm)."""

    def __init__(
        self,
        *,
        gearctl=None,
        rungctl=None,
        rung_top=None,
        pressure=None,
        integrity=None,
        queue_block: int = 0,
        reshard=None,
        log=None,
        memory=None,
        wall=None,
    ):
        self.gearctl = gearctl
        self.rungctl = rungctl
        self._rung_top = rung_top
        self.pressure = pressure
        # integrity sentinel (core/integrity.py): the third arbitration
        # branch — a chunk whose in-jit invariant guards tripped is
        # restored from the pre-chunk snapshot and replayed AT THE SAME
        # SHAPE; a violation reproducing with the same (shard, round,
        # bitmask) signature is deterministic (IntegrityAbort), one that
        # does not reproduce is transient SDC (counted, survived).
        self.integrity = integrity  # config.options.IntegrityOptions | None
        self.integrity_on = bool(
            integrity is not None and getattr(integrity, "enabled", False)
        )
        self.iv_transients = 0  # violations that did not reproduce
        self.iv_replays = 0  # chunk replays the sentinel forced
        self.iv_deterministic: dict | None = None  # the abort's naming
        # test-only state-mutation hook: callable(state, attempt) -> state
        # applied AFTER the pre-chunk snapshot, before each dispatch
        # attempt — the seam tests/test_integrity.py uses to emulate
        # in-flight SDC (a one-shot scribble must not survive into the
        # replay, exactly like real corruption of in-dispatch buffers).
        # None in production.
        self.test_scribble = None
        self.queue_block = int(queue_block)
        self._reshard = reshard
        self._log = log
        self.memory = memory  # obs.memory.MemoryGuard | None
        # runtime observatory (obs/runtime.WallLedger | None): snapshot
        # copies, restores, and replay attempts re-attribute their wall
        # out of the driver's enclosing dispatch span. Host-side only —
        # never consulted for any decision.
        self.wall = wall
        self.policy = pressure.policy if pressure is not None else "drop"
        self.escalate = self.policy == "escalate"
        self.abort_on_drop = self.policy == "abort"
        # ladders resolve lazily from the FIRST state seen (the base
        # shape lives in the state, and under a supervisor rewind the
        # state is the only truth — see run_chunk's shape derivation)
        self._cap_ladder: list[int] | None = None
        self._box_ladder: list[int] | None = None
        self._cap_poisoned: set[int] = set()  # rungs that OOM'd
        self._box_poisoned: set[int] = set()
        # accounting for sim-stats / BENCH
        self.regrows = 0  # reactive shape migrations (drop -> replay)
        self.proactive_regrows = 0  # headroom-driven boundary migrations
        self.replays = 0  # chunks replayed after a pressure abort
        self.oom_fallbacks = 0  # grown programs that OOM'd and fell back
        self.memory_refusals = 0  # rungs the memory guard refused pre-dispatch
        # last fully-refused proactive pick (cap, budget, headroom) — a
        # near-limit run re-triggers the same pick every boundary, and
        # an unchanged refusal must not re-count/re-log per chunk
        self._proactive_refused: tuple | None = None
        self.aborted = False
        self.last_error: str | None = None
        self.ob_hwm_run = 0  # run-wide outbox high-water (per-chunk resets)
        self._abort_state = None  # abort policy: the dropping state
        self._last_snap = None  # escalate: last good pre-chunk snapshot

    # ---- host-side counter reads ------------------------------------------

    @staticmethod
    def _drop_abort(s, baseline: dict | None):
        """PressureAbort naming the per-category drop deltas of summary
        `s` past `baseline` (None = zero), or None when nothing dropped —
        the one formatter the abort policy's two drivers share (the
        modeled controller's in-loop check and the hybrid driver's
        post-window check must report identically)."""
        if s.pressure_total <= (sum(baseline.values()) if baseline else 0):
            return None
        cats = s.cats
        base = baseline or {k: 0 for k in cats}
        detail = ", ".join(
            f"{k}+{v - base[k]}"
            for k, v in sorted(cats.items())
            if v > base[k]
        )
        return PressureAbort(
            f"pressure: abort policy hit its first capacity drop ({detail})"
        )

    @classmethod
    def raise_if_dropped(cls, state, baseline: dict | None = None):
        """Raise PressureAbort when `state` carries capacity drops past
        `baseline` (None = zero)."""
        err = cls._drop_abort(host_summary(state), baseline)
        if err is not None:
            raise err

    # ---- ladders -----------------------------------------------------------

    def _ensure_ladders(self, cap: int, budget: int):
        if self._cap_ladder is not None:
            return
        p = self.pressure
        max_cap = p.max_capacity or cap * DEFAULT_MAX_CAPACITY_FACTOR
        max_box = p.max_outbox or budget * DEFAULT_MAX_OUTBOX_FACTOR
        self._cap_ladder = resolve_ladder(cap, max_cap, p.growth_factor)
        self._box_ladder = resolve_ladder(budget, max_box, p.growth_factor)

    def _next_rung(self, ladder: list[int], cur: int, poisoned=()) -> int | None:
        for rung in ladder:
            if rung > cur and rung not in poisoned:
                return rung
        return None

    def _say(self, msg: str):
        if self._log is not None:
            print(f"[pressure] {msg}", file=self._log)

    # ---- wall attribution (obs/runtime.py; no-ops without a ledger) --------

    def _wall_move(self, to: str, sec: float):
        """Re-attribute `sec` of the driver's enclosing dispatch span to
        the snapshot/replay span — observation only."""
        if self.wall is not None:
            self.wall.reattribute("dispatch", to, sec)

    def _snap_timed(self, state):
        from shadow_tpu.core.checkpoint import snapshot_state

        t0 = time.perf_counter()
        snap = snapshot_state(state)
        self._wall_move("snapshot", time.perf_counter() - t0)
        return snap

    def restore(self, snap):
        from shadow_tpu.core.checkpoint import restore_snapshot

        t0 = time.perf_counter()
        out = restore_snapshot(snap)
        self._wall_move("replay", time.perf_counter() - t0)
        return out

    # ---- migration ---------------------------------------------------------

    def migrate(self, state, new_cap: int, new_budget: int):
        """Re-seat `state` at (new_cap, new_budget): queue planes through
        the exactness-gated grow ops, a fresh (empty) outbox at the new
        width — migrations happen at chunk boundaries, where the
        exchange has always just cleared the outbox, asserted here via
        the cheap per-shard count word. The gear ladder follows a budget
        change (the new full width becomes the ladder top, so the replay
        loop keeps its cannot-shed terminal rung)."""
        import jax

        from shadow_tpu.core.engine import make_empty_outbox
        from shadow_tpu.ops.events import migrate_queue

        cap = state.queue.t.shape[1]
        budget = state.outbox.t.shape[1]
        if new_cap != cap:
            state = state._replace(
                queue=migrate_queue(state.queue, new_cap, self.queue_block)
            )
        if new_budget != budget:
            assert (
                int(np.asarray(jax.device_get(state.outbox.count)).sum()) == 0
            ), "outbox migration outside a chunk boundary"
            state = state._replace(
                outbox=make_empty_outbox(
                    state.outbox.t.shape[0], new_budget, state.outbox.count
                )
            )
            if self.gearctl is not None:
                g = self.gearctl
                g.ladder = sorted(set(g.ladder) | {int(new_budget)})
                if g.gear not in g.ladder:
                    g.gear = g.top
            if self.rungctl is not None:
                self.rungctl.retop(self._rung_top(int(new_budget)))
        if self._reshard is not None:
            state = self._reshard(state)
        return state

    # ---- the chunk loop ----------------------------------------------------

    def run_chunk(self, state, dispatch, rounds0=None):
        """One ACCEPTED chunk, with shed-exact gear replay and drop-exact
        capacity escalation from a single pre-chunk snapshot: the lag-0
        form of `core.pipeline.ChunkPipeline` on this seam, reading the
        boundary numbers on the host.

        `dispatch(state, gear, capacity, budget)` runs one chunk program
        at that shape (donation-safe). `rounds0` keeps the hybrid
        drivers' zero-round guarded windows out of the gear controller,
        exactly as `run_adaptive_chunk` documents.

        Returns (state, accepted_gear, chunk_outbox_hwm)."""
        pipe = ChunkPipeline(dispatch, seam=self, lag=0)
        pipe.start(state)
        pipe.advance(rounds0)
        return pipe.state, pipe.gear, pipe.hwm

    def begin(self, state, dispatch, base) -> Flight:
        """Snapshot `state` where a replay may need it and dispatch one
        chunk on it at the controllers' current gear and rung. `base` is
        the summary of `state`, pending or read.

        Shapes are derived from the STATE, not from controller memory: a
        supervisor rewind can hand back a pre-migration state, and the
        state's own shapes are the only truth about which program runs."""
        gearctl, rungctl = self.gearctl, self.rungctl
        gear = gearctl.gear if gearctl is not None else 0
        rung = rungctl.rung if rungctl is not None else 0
        cap = budget = 0
        if self.pressure is not None:
            cap = state.queue.t.shape[1]
            budget = state.outbox.t.shape[1]
            if self.escalate:
                self._ensure_ladders(cap, budget)
        need_snap = (
            (gearctl is not None and gear < gearctl.top)
            or (rungctl is not None and rung < rungctl.top)
            or self.escalate
            or self.integrity_on
        )
        snap = self._snap_timed(state) if need_snap else None
        f = Flight(base, snap, gear, rung, cap, budget)
        self._dispatch(f, state, dispatch)
        return f

    def _dispatch(self, f: Flight, state, dispatch):
        """One attempt of chunk `f` on `state`. A dispatch error is kept
        on the flight for `settle` (an OOM of a grown program falls back
        a rung there)."""
        if self.test_scribble is not None:
            state = self.test_scribble(state, f.attempts)
        f.attempts += 1
        f.t_disp = time.perf_counter()
        f.comp0 = (
            self.wall.pending_to("compile") if self.wall is not None else 0.0
        )
        f.out = f.error = None
        try:
            if self.rungctl is not None:
                f.out = dispatch(state, f.gear, f.cap, f.budget, f.rung)
            else:
                f.out = dispatch(state, f.gear, f.cap, f.budget)
        except (KeyboardInterrupt, SystemExit, PressureAbort, IntegrityAbort):
            raise
        except Exception as e:
            f.error = e

    def reset_hwm(self, state):
        """Zero the per-chunk high-waters the controllers read (a running
        max could never signal a downshift); runs on every chunk output
        before the next chunk takes it."""
        stats = state.stats._replace(outbox_hwm=state.stats.outbox_hwm * 0)
        if self.rungctl is not None:
            stats = stats._replace(a2a_pair_hwm=stats.a2a_pair_hwm * 0)
        return state._replace(stats=stats)

    def settle(self, f: Flight, s, dispatch) -> bool:
        """Arbitrate chunk `f`'s current attempt from its summary `s` (None
        after a dispatch error): True when the attempt was discarded and
        the chunk re-dispatched from its pre-chunk snapshot (the caller
        reads the new attempt), False when it is accepted. Raises
        PressureAbort / IntegrityAbort where a policy stops the run."""
        self._last_snap = f.snap
        if f.error is not None:
            if not self._oom_fallback(f, dispatch):
                raise f.error
            return True
        base = f.base.read()
        if self.wall is not None and f.attempts > 1:
            # a replay attempt's wall, minus whatever compile pipeline
            # the regrown program just paid (that part is already bound
            # for the compile span — moving it twice would double-count)
            sec = time.perf_counter() - f.t_disp
            sec -= self.wall.pending_to("compile") - f.comp0
            self._wall_move("replay", sec)
        if self.integrity_on:
            # integrity arbitration FIRST: a violating attempt's other
            # counters (shed/pressure) may themselves be scribbled — the
            # attempt is discarded wholesale either way, so nothing below
            # may act on it
            if s.iv_total > base.iv_total:
                self._violation_replay(f, s.iv_signature, dispatch)
                return True
            if f.iv_sig is not None:
                # the replay came back clean: the violation was
                # transient SDC — counted, logged, survived
                self.iv_transients += 1
                self._say(
                    "transient SDC survived: the violation did not "
                    "reproduce on replay; continuing with the clean chunk"
                )
                f.iv_sig = None
        if s.gear_shed > base.gear_shed:
            # the discarded attempt's high-waters name the burst that
            # shed it and the axis it shed on
            gearctl, rungctl = self.gearctl, self.rungctl
            gear_shed = gearctl is not None and s.outbox_hwm > f.gear
            rung_shed = rungctl is not None and s.a2a_pair_hwm > f.rung
            if not (gear_shed or rung_shed):
                raise RuntimeError(
                    f"chunk shed at gear {f.gear}, rung {f.rung} with "
                    f"outbox high-water {s.outbox_hwm} and per-pair "
                    f"high-water {s.a2a_pair_hwm} inside both"
                )
            if gear_shed:
                f.gear = gearctl.note_shed(s.outbox_hwm)
            if rung_shed:
                f.rung = rungctl.note_shed(s.a2a_pair_hwm)
            self._dispatch(f, self.restore(f.snap), dispatch)
            return True
        if self.pressure is not None and s.pressure_total > base.pressure_total:
            if self.abort_on_drop:
                # honest stop AT the drop: the exported state includes
                # the dropping round, counters and all (the abort policy
                # runs at lag 0, so the attempt's output is intact)
                self.aborted = True
                self._abort_state = f.out
                raise self._drop_abort(s, base.cats)
            self._escalate_replay(f, s.cats, base.cats, dispatch)
            return True
        return False

    def accept(self, f: Flight, s, rounds0=None):
        """Chunk `f` is accepted with summary `s`: fold its high-waters
        into the controllers (which may move them for the next chunk)."""
        hwm = s.outbox_hwm
        self.ob_hwm_run = max(self.ob_hwm_run, hwm)
        advanced = rounds0 is None or s.rounds > rounds0
        if advanced:
            if self.gearctl is not None:
                self.gearctl.note_chunk(f.gear, hwm)
            if self.rungctl is not None:
                self.rungctl.note_chunk(f.rung, s.a2a_pair_hwm)
        self._last_snap = None

    def _violation_replay(self, f: Flight, sig: tuple, dispatch):
        """An attempt tripped the integrity guards: a violation that
        reproduces at the same (shard, round, bitmask) signature across a
        snapshot replay is a deterministic engine bug (IntegrityAbort);
        one that does not is transient SDC. Replays at the same shape."""
        detail = describe_signature(sig)
        if f.iv_sig is not None and sig == f.iv_sig:
            self.aborted = True
            self.iv_deterministic = {
                "signature": [list(x) for x in sig],
                "detail": detail,
            }
            self.last_error = f"deterministic integrity violation: {detail}"
            raise IntegrityAbort(
                f"integrity: violation REPRODUCED across a snapshot replay "
                f"(deterministic engine bug, not SDC) — {detail}"
            )
        if f.iv_sig is not None:
            # the previous violation did not reproduce at its signature:
            # transient SDC, counted
            self.iv_transients += 1
        f.iv_sig = sig
        f.iv_attempts += 1
        if f.iv_attempts > self.integrity.max_replays:
            # cornered WITHOUT dispatching another replay: iv_replays
            # counts replays that actually ran (iv_attempts - 1 here),
            # not this refusal
            self.aborted = True
            self.iv_deterministic = {
                "signature": [list(x) for x in sig],
                "detail": detail,
                "nonreproducing": True,
            }
            self.last_error = (
                f"integrity violations persist without reproducing after "
                f"{f.iv_attempts - 1} replays; last: {detail}"
            )
            raise IntegrityAbort(f"integrity: cornered — {self.last_error}")
        self.iv_replays += 1
        self._say(
            f"invariant violation ({detail}); restoring pre-chunk snapshot "
            f"and replaying to classify (attempt {f.iv_attempts}/"
            f"{self.integrity.max_replays})"
        )
        self._dispatch(f, self.restore(f.snap), dispatch)

    def _oom_fallback(self, f: Flight, dispatch) -> bool:
        """A GROWN program could not compile/dispatch: which axis blew
        the budget is unknowable from here, so every axis currently above
        base falls back one rung and its abandoned rungs (and everything
        above — bigger only) are poisoned, and the chunk is re-dispatched
        from its snapshot. False where the error is not such an OOM. The
        shrink is fits-checked against the restored snapshot (the state
        we actually rewind to): a lower rung the live events no longer
        fit would silently truncate them — the exact loss this plane
        exists to prevent — so an unfitting fallback corners into a loud
        PressureAbort instead (migrate_queue's shrink contract,
        ops/events.py)."""
        e, cap, budget = f.error, f.cap, f.budget
        grown_cap = self.escalate and cap > self._cap_ladder[0]
        grown_box = self.escalate and budget > self._box_ladder[0]
        if not ((grown_cap or grown_box) and _is_oom(e)):
            return False
        self.oom_fallbacks += 1
        self.last_error = f"{type(e).__name__}: {e}"
        restored = self.restore(f.snap)
        lower_cap, lower_box = cap, budget
        if grown_cap:
            import jax.numpy as jnp

            from shadow_tpu.ops.events import migration_fits

            for rung in self._cap_ladder:
                if rung >= cap:
                    self._cap_poisoned.add(rung)
            lower_cap = next(
                (
                    r
                    for r in sorted(self._cap_ladder, reverse=True)
                    if r < cap
                    and r not in self._cap_poisoned
                    and bool(jnp.all(migration_fits(restored.queue, r)))
                ),
                None,
            )
            if lower_cap is None:
                self.aborted = True
                raise PressureAbort(
                    f"pressure: cornered — grown program failed at "
                    f"capacity {cap} ({self.last_error}) and the live "
                    f"events no longer fit any usable lower rung "
                    f"(shrinking would silently truncate them)"
                ) from e
        if grown_box:
            for rung in self._box_ladder:
                if rung >= budget:
                    self._box_poisoned.add(rung)
            lower_box = max(
                r for r in self._box_ladder
                if r < budget and r not in self._box_poisoned
            )
        self._say(
            f"grown program failed at (cap={cap}, outbox={budget}) "
            f"({self.last_error}); falling back to (cap={lower_cap}, "
            f"outbox={lower_box})"
        )
        self._reseat(f, self.migrate(restored, lower_cap, lower_box), dispatch)
        return True

    def _reseat(self, f: Flight, state, dispatch):
        """Re-dispatch chunk `f` on a migrated `state`, with a fresh
        snapshot at the new shape."""
        f.cap = state.queue.t.shape[1]
        f.budget = state.outbox.t.shape[1]
        f.snap = self._snap_timed(state)
        self._last_snap = f.snap
        self._dispatch(f, state, dispatch)

    def _escalate_replay(self, f: Flight, cats, cats0, dispatch):
        """A chunk attempt dropped: pick the grown shape from the aborted
        attempt's per-category deltas, restore the pre-chunk snapshot,
        migrate, and replay the chunk at the new shape. Raises
        PressureAbort when cornered (a dropping axis cannot grow)."""
        cap, budget = f.cap, f.budget
        queue_side = cats["queue"] > cats0["queue"]
        box_side = (
            cats["budget"] > cats0["budget"]
            or cats["a2a"] > cats0["a2a"]
            or cats["outbox"] > cats0["outbox"]
        )
        new_cap, new_budget = cap, budget
        if queue_side:
            up = self._next_rung(self._cap_ladder, cap, self._cap_poisoned)
            if up is None:
                self.aborted = True
                self.last_error = (
                    f"queue pressure at capacity {cap} with no usable rung "
                    f"left (ladder {self._cap_ladder}, poisoned "
                    f"{sorted(self._cap_poisoned)})"
                )
                raise PressureAbort(f"pressure: cornered — {self.last_error}")
            new_cap = up
        if box_side:
            up = self._next_rung(self._box_ladder, budget, self._box_poisoned)
            if up is None:
                self.aborted = True
                self.last_error = (
                    f"outbox pressure at budget {budget} with no usable "
                    f"rung left (ladder {self._box_ladder}, poisoned "
                    f"{sorted(self._box_poisoned)})"
                )
                raise PressureAbort(f"pressure: cornered — {self.last_error}")
            new_budget = up
        if (new_cap, new_budget) == (cap, budget):
            # drops grew but no category moved past its entry value —
            # cannot happen by construction (delta > 0 implies some
            # category grew); guard against it anyway, loudly
            self.aborted = True
            raise PressureAbort(
                "pressure: drop detected but no growth axis identified"
            )
        self._admit_or_corner(cap, budget, new_cap, new_budget)
        self.regrows += 1
        self.replays += 1
        self._say(
            f"capacity drop at (cap={cap}, outbox={budget}); replaying "
            f"chunk at (cap={new_cap}, outbox={new_budget})"
        )
        self._reseat(
            f,
            self.migrate(self.restore(f.snap), new_cap, new_budget),
            dispatch,
        )

    def _admit_or_corner(self, cap, budget, new_cap, new_budget):
        """Memory-informed pre-dispatch admission (obs/memory.MemoryGuard):
        a grown rung whose predicted footprint exceeds measured headroom
        is poisoned BEFORE its compile+dispatch — and since every higher
        rung needs strictly more bytes, a refusal corners the escalation
        immediately, exactly as an exhausted ladder does. No-op without a
        guard or without a measured allocator limit."""
        if self.memory is None or (new_cap, new_budget) == (cap, budget):
            return
        ok, need, headroom = self.memory.admit(
            cap, budget, new_cap, new_budget
        )
        if ok:
            return
        self.memory_refusals += 1
        if new_cap != cap:
            for rung in self._cap_ladder:
                if rung >= new_cap:
                    self._cap_poisoned.add(rung)
        if new_budget != budget:
            for rung in self._box_ladder:
                if rung >= new_budget:
                    self._box_poisoned.add(rung)
        self.aborted = True
        self.last_error = (
            f"memory guard refused rung (cap={new_cap}, "
            f"outbox={new_budget}) before dispatch: predicted need "
            f"{need} bytes (static-model delta x replay concurrency x "
            f"safety {self.memory.safety_factor}) exceeds measured "
            f"headroom {headroom} bytes"
        )
        raise PressureAbort(f"pressure: cornered — {self.last_error}")

    def _admitted_proactive(self, cap, budget, new_cap, new_budget):
        """Proactive-growth admission. Unlike the reactive case (both
        axes DROPPED, so partial growth would just drop again), a
        proactive regrow is purely opportunistic — when the combined
        growth does not fit measured headroom, each single axis is
        retried alone, so an affordable queue-only (or outbox-only)
        migration still happens instead of the run later eating a
        reactive drop + replayed chunk. A full refusal just skips the
        boundary regrow (nothing has dropped yet). Returns the admitted
        shape."""
        if self.memory is None or (new_cap, new_budget) == (cap, budget):
            return new_cap, new_budget
        candidates = [(new_cap, new_budget)]
        for cand in ((new_cap, budget), (cap, new_budget)):
            if cand != (cap, budget) and cand not in candidates:
                candidates.append(cand)
        need_all = headroom = None
        for i, cand in enumerate(candidates):
            ok, need, headroom = self.memory.admit(cap, budget, *cand)
            if i == 0:
                need_all = need  # the COMBINED requirement, for the log
            if ok:
                if cand != (new_cap, new_budget):
                    self.memory_refusals += 1
                    self._say(
                        f"memory guard trimmed proactive regrow "
                        f"(cap={new_cap}, outbox={new_budget}) -> "
                        f"(cap={cand[0]}, outbox={cand[1]}): the combined "
                        f"growth exceeds measured headroom"
                    )
                return cand
        # full refusal. A near-limit run re-triggers the same proactive
        # pick at EVERY boundary; count/log the refusal only when the
        # situation changed (new shape, or headroom moved) so
        # memory_refusals stays a decision count, not a chunk count.
        key = (new_cap, new_budget, headroom)
        if key != self._proactive_refused:
            self._proactive_refused = key
            self.memory_refusals += 1
            self._say(
                f"memory guard skipped proactive regrow to "
                f"(cap={new_cap}, outbox={new_budget}): predicted need "
                f"{need_all} bytes > measured headroom {headroom} bytes"
            )
        return cap, budget

    def proactive_shape(self, state, s):
        """Boundary regrow BEFORE anything drops, decided from the
        accepted chunk's summary `s`: the always-on occupancy high-water
        crossing the headroom threshold grows the queue; a chunk whose
        outbox high-water FILLED the budget grows the outbox (hwm ==
        budget means one more send next chunk would be a budget drop —
        the gear controller's exactly-filled rule, applied to the shape).
        Returns the (capacity, budget) the next chunk's input migrates
        to, or None. `state` is that input (its shapes only are read)."""
        import math

        p = self.pressure
        if not self.escalate or not p.headroom:
            return None
        cap = state.queue.t.shape[1]
        budget = state.outbox.t.shape[1]
        new_cap, new_budget = cap, budget
        occ, chunk_hwm = s.q_occ_hwm, s.outbox_hwm
        if occ >= math.ceil(p.headroom * cap):
            up = self._next_rung(self._cap_ladder, cap, self._cap_poisoned)
            if up is not None:
                new_cap = up
        if chunk_hwm >= budget:
            up = self._next_rung(self._box_ladder, budget, self._box_poisoned)
            if up is not None:
                new_budget = up
        new_cap, new_budget = self._admitted_proactive(
            cap, budget, new_cap, new_budget
        )
        if (new_cap, new_budget) == (cap, budget):
            return None
        self.proactive_regrows += 1
        self._say(
            f"proactive regrow: occupancy hwm {occ}/{cap}, outbox hwm "
            f"{chunk_hwm}/{budget} -> (cap={new_cap}, outbox={new_budget})"
        )
        return new_cap, new_budget

    # ---- abort/export ------------------------------------------------------

    def abort_export_state(self):
        """State the driver should export artifacts from after a
        PressureAbort: under the abort policy, the dropping state itself
        (the honest record — it includes the drop that stopped the run);
        under escalate-cornered, a fresh copy of the last good pre-chunk
        snapshot (the failed attempts were discarded). None when neither
        exists (abort before any chunk ran) — then the in-hand state is
        all there is."""
        from shadow_tpu.core.checkpoint import restore_snapshot

        if self._abort_state is not None:
            return self._abort_state
        if self._last_snap is not None:
            return restore_snapshot(self._last_snap)
        return None

    def current_shape(self, state) -> tuple[int, int]:
        """(queue_capacity, send_budget) of a state — the heartbeat's
        `cap=` source."""
        return state.queue.t.shape[1], state.outbox.t.shape[1]

    def integrity_report(self) -> dict:
        """JSON-able integrity{} accounting for sim-stats / BENCH rows:
        the transient/replay counts plus — after an IntegrityAbort — the
        deterministic violation's naming (invariants, round, shard)."""
        out: dict[str, Any] = {
            "transients": self.iv_transients,
            "replays": self.iv_replays,
            "max_replays": (
                self.integrity.max_replays if self.integrity is not None
                else 0
            ),
        }
        if self.iv_deterministic is not None:
            out["deterministic"] = self.iv_deterministic
        return out

    def report(self) -> dict:
        """JSON-able summary for sim-stats / BENCH rows."""
        out: dict[str, Any] = {
            "policy": self.policy,
            "regrows": self.regrows,
            "proactive_regrows": self.proactive_regrows,
            "replays": self.replays,
            "oom_fallbacks": self.oom_fallbacks,
        }
        if self.memory_refusals:
            out["memory_refusals"] = self.memory_refusals
        if self.memory is not None and self.memory.monitor is not None:
            hb = self.memory.monitor.headroom_bytes()
            if hb is not None:
                out["headroom_bytes"] = hb
        if self._cap_ladder is not None:
            out["capacity_ladder"] = list(self._cap_ladder)
            out["outbox_ladder"] = list(self._box_ladder)
        if self._cap_poisoned:
            out["capacity_poisoned"] = sorted(self._cap_poisoned)
        if self._box_poisoned:
            out["outbox_poisoned"] = sorted(self._box_poisoned)
        if self.aborted:
            out["aborted"] = True
        if self.last_error:
            out["last_error"] = self.last_error
        return out
