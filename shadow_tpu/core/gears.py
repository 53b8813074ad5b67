"""Occupancy-adaptive merge gears: ladder resolution + the chunk controller.

The exchange merge's (dst, t, order) sort runs over the full static outbox
width N = H x B every round, even though the round tracer shows most rounds
carry a small fraction of that (BASELINE.md round-cost split: the merge is
~1.3 ms of a ~2.1 ms round on v5e). Conservative-PDES merge cost should
track ACTUAL per-round traffic, not the worst-case bound — so the engine
compiles the round body at a small ladder of outbox column widths ("gears",
`Engine.run_chunk_gear`) and the driver picks next chunk's gear here, from
the always-on `stats.outbox_hwm` signal (the most sends any one host staged
in a round).

Exactness is preserved by construction, not by prediction: a gear that
would shed (some host staged more sends than the gear's column width —
detected exactly by `ops.merge.gear_shed_count` feeding `stats.gear_shed`)
aborts the chunk at the first shedding round, and the driver restores the
pre-chunk `SimState` snapshot (`core.checkpoint.snapshot_state`) and
replays that chunk one gear up. The top gear is always the full send budget
and can never shed, so the replay loop terminates, and accepted chunks are
bit-identical to the full-width engine on every workload — digests, event
counts, and drop counters included (tests/test_gears.py is the gate).

The hierarchical exchange composes with gears through the SAME abort
contract: the gear width rescales the inter-shard block size too
(`EngineConfig.hier_block_size` derives from rows_g = hosts_per_shard x
effective_gear_cols), so a narrow gear also thins the alltoall blocks —
and a block overflow under a gear is psum'd into `stats.gear_shed`
exactly like a sort-width shed, tripping the same abort-and-replay one
gear up. At the top gear the hierarchical block size equals the flat
alltoall's, so the ladder's termination argument carries over unchanged
(core/engine.py `_exchange_hierarchical`; tests/test_hier.py gates the
geared matrix).

The controller is deliberately simple and deterministic:
  - upshift immediately (on a shed, or when the observed high-water
    reaches the current gear's width — headroom of one lane column);
  - downshift only after `down_lag` consecutive chunks whose high-water
    fits the lower gear (hysteresis: a replay costs a whole chunk, a
    too-wide sort costs only its width).
Determinism note: gear choices affect WHICH program runs, never what it
computes — a controller bug can cost replays, not correctness.

`BlockRungController` below applies the same controller, replay contract
included, to the flat alltoall's per-pair block width (the rung ladder
of the auto `a2a_block`, docs/architecture.md "Alltoall block rungs").
"""

from __future__ import annotations

DOWN_LAG = 2  # chunks of low occupancy before shifting down


def resolve_gear_ladder(spec, send_budget: int) -> list[int]:
    """`experimental.merge_gears` -> sorted ladder of outbox column widths.

    Accepted specs:
      0 / None / False / "off"  -> []  (gears disabled, full width always)
      "auto" / True             -> ~{B/8, B/4, B/2, B} (deduped, >= 1)
      [ints]                    -> explicit widths, validated against the
                                   send budget; the full width B is always
                                   appended so the replay loop terminates.
    """
    if not spec or (isinstance(spec, str) and spec.lower() == "off"):
        return []
    b = int(send_budget)
    if spec is True or (isinstance(spec, str) and spec.lower() == "auto"):
        ladder = sorted({max(1, b // 8), max(1, b // 4), max(1, b // 2), b})
    else:
        if isinstance(spec, int):
            spec = [spec]
        try:
            gears = sorted({int(g) for g in spec})
        except (TypeError, ValueError) as e:
            raise ValueError(
                f"merge_gears must be 'auto', an int, or a list of ints, "
                f"got {spec!r}"
            ) from e
        for g in gears:
            if g < 1 or g > b:
                raise ValueError(
                    f"merge gear {g} out of range [1, sends_per_host_round"
                    f"={b}]"
                )
        ladder = gears if gears[-1] == b else gears + [b]
    return [] if ladder == [b] else ladder


class GearController:
    """Per-run gear state: pick next chunk's gear, account replays.

    Drivers call, per chunk:
        gear = ctl.gear                       # width to dispatch at
        ... run, on shed: gear = ctl.note_shed(); restore + replay ...
        ctl.note_chunk(gear, ob_hwm)          # accepted chunk's signal
    """

    def __init__(self, ladder: list[int], down_lag: int = DOWN_LAG):
        if not ladder:
            raise ValueError("GearController needs a non-empty ladder")
        self.ladder = list(ladder)
        # start at the TOP gear: the boot chunk's occupancy is unknown and
        # a replay costs a whole chunk; the first observation adapts down
        self.gear = self.ladder[-1]
        self.down_lag = int(down_lag)
        self.replays = 0  # chunks re-run one gear up after a shed
        self.chunks: dict[int, int] = {}  # accepted chunks per gear
        self._low_streak = 0

    @property
    def top(self) -> int:
        return self.ladder[-1]

    def _fit(self, hwm: int) -> int:
        """Smallest ladder gear with headroom over the observed high-water
        (strictly greater: hwm == gear means the width was exactly filled,
        one more send next chunk would shed — step up preemptively)."""
        for g in self.ladder:
            if hwm < g:
                return g
        return self.top

    def note_shed(self, observed_hwm: int | None = None) -> int:
        """A chunk shed at the current gear: pick the replay gear and
        reset the downshift streak. With `observed_hwm` (the ABORTED
        chunk's outbox high-water, read before the snapshot restore) the
        replay jumps straight to a gear that fits the burst it actually
        saw — one replay instead of walking the ladder rung by rung when
        traffic jumped several gears at once. The jump is a floor, not a
        guarantee: the aborted chunk stopped at its first shedding round,
        so later rounds may burst higher and shed again — each replay
        still moves strictly up the ladder, so the loop terminates."""
        self.replays += 1
        self._low_streak = 0
        idx = self.ladder.index(self.gear)
        up = self.ladder[min(idx + 1, len(self.ladder) - 1)]
        if observed_hwm is not None:
            up = max(up, self._fit(observed_hwm))
        self.gear = up
        return self.gear

    def note_chunk(self, gear: int, ob_hwm: int) -> int:
        """Record an ACCEPTED chunk run at `gear` whose outbox high-water
        was `ob_hwm`; returns the gear for the next chunk."""
        self.chunks[gear] = self.chunks.get(gear, 0) + 1
        want = self._fit(ob_hwm)
        if want > self.gear:
            self.gear = want  # headroom exhausted: step up before a shed
            self._low_streak = 0
        elif want < self.gear:
            self._low_streak += 1
            if self._low_streak >= self.down_lag:
                self.gear = want
                self._low_streak = 0
        else:
            self._low_streak = 0
        return self.gear

    def report(self) -> dict:
        """JSON-able summary for sim-stats / BENCH rows."""
        return {
            "ladder": list(self.ladder),
            "chunks_per_gear": {str(g): n for g, n in sorted(self.chunks.items())},
            "replays": self.replays,
        }


RUNG_BASE = 512  # the alltoall block ladder's lowest rung (rows)


def resolve_block_ladder(top: int) -> list[int]:
    """The alltoall block-rung ladder: RUNG_BASE, 2 x RUNG_BASE, ...
    while below `top` (the auto block size), then `top` itself, so the
    replay loop keeps a rung that behaves exactly as the fixed block."""
    top = int(top)
    ladder = []
    k = RUNG_BASE
    while k < top:
        ladder.append(k)
        k *= 2
    return ladder + [top]


class BlockRungController(GearController):
    """The alltoall block width for the next chunk, from the rows each
    (source shard, destination shard) pair actually sent.

    The gears' controller with another fit and another start:
      - the fit is the smallest rung at or above TWICE the chunk's
        per-pair high-water (`stats.a2a_pair_hwm`): a block holds one
        round of one pair, and the headroom keeps a busier round from
        shedding; upshift at once, downshift after `down_lag` chunks;
      - a shed (a pair sent more than the rung in some round; the chunk
        aborted there) replays at the fit of what the aborted chunk saw;
      - `begin_run` seats the first chunk of every `Simulation.run` from
        the state it starts on (its pending events), never below the
        rung carried from the last run, so a run that restarts from an
        initial state does not shed in its first round.
    The controller lives on the Simulation; `report` counts the current
    run's chunks and replays.
    """

    def __init__(self, top: int, down_lag: int = DOWN_LAG):
        super().__init__(resolve_block_ladder(top), down_lag)
        self.fresh = True  # no run has set the rung yet
        self.pair_hwm = 0  # the current run's per-pair high-water

    @property
    def rung(self) -> int:
        return self.gear

    def _fit(self, hwm: int) -> int:
        for k in self.ladder:
            if 2 * hwm <= k:
                return k
        return self.top

    def begin_run(self, pending_per_pair: int) -> int:
        """Seat the run's first chunk: the fit of `pending_per_pair`
        (pending events on the fullest shard / world), raised to the
        carried rung after the first run. Resets the run's accounting."""
        want = self._fit(pending_per_pair)
        self.gear = want if self.fresh else max(self.gear, want)
        self.fresh = False
        self._low_streak = 0
        self.chunks = {}
        self.replays = 0
        self.pair_hwm = 0
        return self.gear

    def note_shed(self, observed_hwm: int | None = None) -> int:
        if observed_hwm is not None:
            self.pair_hwm = max(self.pair_hwm, int(observed_hwm))
        return super().note_shed(observed_hwm)

    def note_chunk(self, gear: int, ob_hwm: int) -> int:
        self.pair_hwm = max(self.pair_hwm, int(ob_hwm))
        return super().note_chunk(gear, ob_hwm)

    def retop(self, top: int):
        """A regrown outbox moved the auto block: rebuild the ladder
        under the new top, keeping the rung where it is still a rung."""
        self.ladder = resolve_block_ladder(top)
        if self.gear not in self.ladder:
            self.gear = self.top

    def report(self) -> dict:
        return {
            "ladder": list(self.ladder),
            "chunks_per_rung": {
                str(k): n for k, n in sorted(self.chunks.items())
            },
            "replays": self.replays,
            "pair_hwm": self.pair_hwm,
        }


def run_adaptive_chunk(ctl: GearController, state, dispatch, rounds0=None):
    """One ACCEPTED chunk at the controller's gear, with shed-exact replay
    — the gears-only face of the shared snapshot-replay loop, which now
    lives in `core.pressure.ResilienceController` (the pressure plane
    generalized this loop to arbitrate capacity regrows from the same
    seam; with no pressure policy the controller reduces exactly to the
    gear behavior shipped here in PR 4).

    `dispatch(state, gear)` runs one chunk program at that gear and
    returns the new state (donation-safe: the pre-chunk snapshot is an
    independent device copy, so the dispatch may consume its input).
    On a shed the chunk's entire result — queue, digests, counters, trace
    ring — is discarded by restoring the snapshot, and the SAME chunk
    re-runs one gear up; the top gear is the full send budget and cannot
    shed, so this terminates. Accepted results are therefore bit-identical
    to a full-width run by construction.

    `rounds0` (the dispatch-entry `stats.rounds`, hybrid driver): when
    given and the dispatch retired ZERO rounds — a guarded window that
    exited immediately on its probe or horizon — the controller is NOT
    fed: an idle window's hwm of 0 says nothing about traffic, and
    counting it would downshift past real occupancy and buy the next busy
    window a guaranteed shed + full-chunk replay.

    Returns (state, accepted_gear, chunk_outbox_hwm). The per-chunk
    `stats.outbox_hwm` is folded into the controller and RESET (a running
    max could never signal a downshift); callers wanting the run-wide
    high-water track the returned value."""
    from shadow_tpu.core.pressure import ResilienceController

    rc = ResilienceController(gearctl=ctl)
    return rc.run_chunk(
        state, lambda s, g, _cap, _budget: dispatch(s, g), rounds0=rounds0
    )
