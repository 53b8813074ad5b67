"""The driver loop's chunk pipeline: one chunk in flight, each chunk's
boundary numbers read one chunk late, from one small summary.

Everything the host decides between two chunks — done or not, the
heartbeat line, the gear and rung controllers, the seam's shed / drop /
violation arbitration — needs a dozen integers of the chunk's output,
not the output. `Engine.chunk_summary` reduces them on the device to one
small int64 array (a row per shard), dispatched right after the chunk
and before the next chunk donates that output; the host makes one
device-to-host read of it a chunk (`Summary`).

At each chunk boundary `ChunkPipeline.launch` enqueues, on chunk N's
output and without any host read: summary N, the seam's high-water
resets, the seam's snapshot of chunk N+1's input, and chunk N+1 at the
rung and gear the controllers hold now. Only then does `settle` block
on summary N (`shadow.sync`), so the chip runs chunk N+1 while the host
reads and prints chunk N's numbers (lag 1). Where the summary shows a
shed, a capacity drop or an integrity violation, the seam restores chunk
N's pre-chunk snapshot and replays N exactly as before, and the chunk in
flight is discarded (it ran on a result nobody accepts). A chunk
dispatched on a `done` state runs zero rounds (`_run_chunk`'s cond
starts with `~done`), so the successor in flight when summary N reports
`done` is chunk N's state, bit for bit, and is the run's result.

A driver whose work between chunks reads the whole state (pcap capture,
the supervisor, the round tracer, the flow ledger, the memory monitor,
the runtime observatory, the abort policy's export, the hybrid driver's
guarded windows) runs the same code at lag 0: the summary is read before
the next dispatch. `ResilienceController.run_chunk` is the lag-0 form of
one chunk.
"""

from __future__ import annotations

import numpy as np

from shadow_tpu.obs.runtime import span_or_null

# how the host folds a summary column over the shards' rows
SUM, MAX, ONE, ROWS = "sum", "max", "one", "rows"

# the drop categories of the pressure plane's growth decision
DROP_CATEGORIES = ("queue", "budget", "a2a", "outbox")


def summary_lanes(state) -> list[tuple[str, str, object]]:
    """(name, fold, lane) for each lane the summary reads, in column
    order. Lanes a configuration leaves out (None) are left out, so the
    layout is a function of the state's structure alone."""
    st = state.stats
    queue = getattr(state, "queue", None)
    lanes = [
        ("now", ONE, getattr(state, "now", None)),
        ("done", ONE, getattr(state, "done", None)),
        ("rounds", ONE, getattr(st, "rounds", None)),
        ("events", SUM, getattr(st, "events", None)),
        ("microsteps", SUM, getattr(st, "microsteps", None)),
        ("ici_bytes", SUM, getattr(st, "ici_bytes", None)),
        ("q_occ_hwm", MAX, getattr(st, "q_occ_hwm", None)),
        ("gear_shed", MAX, getattr(st, "gear_shed", None)),
        ("outbox_hwm", MAX, getattr(st, "outbox_hwm", None)),
        ("a2a_pair_hwm", MAX, getattr(st, "a2a_pair_hwm", None)),
        ("ici_intra", SUM, getattr(st, "ici_intra", None)),
        ("ici_inter", SUM, getattr(st, "ici_inter", None)),
        ("faults_dropped", SUM, getattr(st, "faults_dropped", None)),
        ("faults_delayed", SUM, getattr(st, "faults_delayed", None)),
        ("ec_timer", SUM, getattr(st, "ec_timer", None)),
        ("ec_pkt", SUM, getattr(st, "ec_pkt", None)),
        ("fl_done", SUM, getattr(st, "fl_done", None)),
        ("fl_bg_bytes", ONE, getattr(st, "fl_bg_bytes", None)),
        ("fl_bg_dropped", ONE, getattr(st, "fl_bg_dropped", None)),
        ("pressure", MAX, getattr(st, "pressure", None)),
        ("drop_queue", SUM, getattr(queue, "dropped", None)),
        ("drop_budget", SUM, getattr(st, "pkts_budget_dropped", None)),
        ("drop_a2a", SUM, getattr(st, "a2a_shed", None)),
        ("drop_outbox", SUM, getattr(st, "ob_dropped", None)),
        ("integrity", MAX, getattr(st, "integrity", None)),
        ("iv_mask", ROWS, getattr(st, "iv_mask", None)),
        ("iv_round", ROWS, getattr(st, "iv_round", None)),
    ]
    return [lane for lane in lanes if lane[2] is not None]


def summary_layout(state) -> tuple[tuple[str, str], ...]:
    return tuple((name, fold) for name, fold, _ in summary_lanes(state))


def summary_row(state):
    """One shard's summary row, i64[1, K], from its local arrays (traced:
    `Engine.chunk_summary` jits it, under shard_map on a mesh). Sums and
    maxima stay on the shard; the host folds the rows."""
    import jax.numpy as jnp

    cols = []
    for _, fold, lane in summary_lanes(state):
        if fold == SUM:
            cols.append(jnp.sum(lane, dtype=jnp.int64))
        elif fold == MAX:
            cols.append(jnp.max(lane).astype(jnp.int64))
        else:  # a replicated scalar, or this shard's one per-shard lane
            cols.append(jnp.reshape(lane, (-1,))[0].astype(jnp.int64))
    return jnp.stack(cols)[None, :]


class Summary:
    """A chunk's boundary numbers on the host, folded over the shards.
    Lanes the configuration leaves out read None."""

    ici_intra = ici_inter = None
    faults_dropped = faults_delayed = None
    ec_timer = ec_pkt = fl_done = None
    fl_bg_bytes = fl_bg_dropped = None
    pressure = integrity = None
    iv_mask = iv_round = None
    now = done = rounds = events = microsteps = ici_bytes = 0
    q_occ_hwm = gear_shed = outbox_hwm = a2a_pair_hwm = 0
    drop_queue = drop_budget = drop_a2a = drop_outbox = 0

    def __init__(self, layout, rows):
        rows = np.asarray(rows, dtype=np.int64).reshape(-1, len(layout))
        for j, (name, fold) in enumerate(layout):
            col = rows[:, j]
            if fold == SUM:
                value = int(col.sum())
            elif fold == MAX:
                value = int(col.max())
            elif fold == ONE:
                value = int(col[0])
            else:
                value = tuple(int(x) for x in col)
            setattr(self, name, value)
        self.done = bool(self.done)

    def read(self) -> Summary:
        """Itself: a summary read on the host is already read."""
        return self

    @property
    def cats(self) -> dict[str, int]:
        """Cumulative capacity drops per category (the growth decision's
        input: queue-side grows the slab, the rest the send budget)."""
        return {k: getattr(self, f"drop_{k}") for k in DROP_CATEGORIES}

    @property
    def pressure_total(self) -> int:
        """The global cumulative capacity-drop total: the psum'd device
        signal where the program carries it, else the categories' sum."""
        if self.pressure is not None:
            return self.pressure
        return sum(self.cats.values())

    @property
    def iv_total(self) -> int:
        return self.integrity or 0

    @property
    def iv_signature(self) -> tuple:
        """(shard, first violating round, bitmask) per violating shard:
        `core.integrity.violation_signature` from the summary."""
        if self.iv_mask is None:
            return ()
        return tuple(
            (shard, self.iv_round[shard], mask)
            for shard, mask in enumerate(self.iv_mask)
            if mask != 0
        )


def host_summary(state) -> Summary:
    """The same Summary read lane by lane on the host (one transfer):
    for drivers with no engine at hand. It reads at once, so it may be
    taken of a state that a dispatch will donate next."""
    import jax

    lanes = summary_lanes(state)
    world = int(np.shape(state.stats.gear_shed)[0])
    values = jax.device_get([lane for _, _, lane in lanes])
    rows = np.zeros((world, len(lanes)), np.int64)
    for j, ((_, fold, _), a) in enumerate(zip(lanes, values)):
        a = np.asarray(a)
        parts = a.reshape(world, -1) if a.ndim else np.full((world, 1), a)
        if fold == SUM:
            rows[:, j] = parts.sum(axis=1, dtype=np.int64)
        elif fold == MAX:
            rows[:, j] = parts.max(axis=1)
        else:
            rows[:, j] = parts[:, 0]
    return Summary(tuple((n, f) for n, f, _ in lanes), rows)


class PendingSummary:
    """A summary dispatched on the device and not yet read."""

    def __init__(self, layout, rows):
        self.layout = layout
        self.rows = rows
        self._read = None

    def read(self) -> Summary:
        if self._read is None:
            self._read = Summary(self.layout, np.asarray(self.rows))
        return self._read


class Flight:
    """One chunk from its pre-chunk state to its accepted output: the
    current attempt's output (or dispatch error) and pending summary,
    the shape it runs at, and what replays of it share (the summary of
    its input, the pre-chunk snapshot, the integrity classifier's
    state)."""

    def __init__(self, base, snap=None, gear=0, rung=0, cap=0, budget=0):
        self.base = base  # summary of the chunk's input (pending or read)
        self.snap = snap  # pre-chunk snapshot, or None
        self.gear, self.rung, self.cap, self.budget = gear, rung, cap, budget
        self.out = None
        self.error: BaseException | None = None
        self.pending = None  # summary of `out`
        self.attempts = 0
        self.t_disp = 0.0
        self.comp0 = 0.0
        self.iv_sig = None
        self.iv_attempts = 0


class ChunkPipeline:
    """Drives chunks with `lag` chunks in flight (0 or 1), accepting
    them one at a time through the snapshot-replay seam when there is
    one (`core.pressure.ResilienceController`).

    `dispatch` runs one chunk: `dispatch(state)` without a seam, the
    seam's `dispatch(state, gear, capacity, budget[, rung])` with one.
    `summarize(state)` dispatches a summary (`Engine.chunk_summary`);
    None reads it on the host at once (`host_summary`).

    Counters (`report`): `chunks` accepted, `ahead` of them whose
    successor was already queued when the driver accepted them,
    `discarded` chunks in flight thrown away for a replay."""

    def __init__(self, dispatch, summarize=None, *, seam=None, lag: int = 1):
        self.dispatch = dispatch
        self.summarize = summarize
        self.seam = seam
        self.lag = int(lag)
        self.chunks = 0
        self.ahead = 0
        self.discarded = 0
        self.state = None  # the newest state no dispatch has consumed
        self.last: Summary | None = None  # of the last accepted chunk
        self.gear = 0  # the gear the last accepted chunk ran at
        self.hwm = 0  # the last accepted chunk's outbox high-water
        self._base = None  # pending summary of `state`
        self._flight: Flight | None = None  # launched, not yet settled
        self._next: Flight | None = None  # its successor, in flight
        self._nxt_in = None  # the successor's input

    def _pend(self, state):
        if self.summarize is None:
            return host_summary(state)
        return self.summarize(state)

    def start(self, state) -> Summary:
        """Seat the pipeline on `state` (a run's first state, or one a
        driver replaced between chunks); returns its summary."""
        self.state = state
        self._next = None
        self._base = self._pend(state)
        with span_or_null(None, "sync"):
            self.last = self._base.read()
        return self.last

    def _begin(self, state, base) -> Flight:
        if self.seam is not None:
            return self.seam.begin(state, self.dispatch, base)
        f = Flight(base)
        try:
            f.out = self.dispatch(state)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            f.error = e
        return f

    def _boundary(self, out):
        return self.seam.reset_hwm(out) if self.seam is not None else out

    def launch(self):
        """Enqueue the next chunk's boundary work without a host read: the
        chunk itself (at lag 1 already in flight), its summary, the
        seam's resets and, at lag 1, its successor."""
        f = self._next if self._next is not None else self._begin(
            self.state, self._base
        )
        self._next = None
        self._flight = f
        self._queue(f)

    def _queue(self, f: Flight):
        self._nxt_in = self._nxt = None
        if f.error is not None:
            return
        try:
            # the host form reads here: a failure of the chunk surfaces
            f.pending = self._pend(f.out)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            f.error = e
            return
        self._nxt_in = self._boundary(f.out)
        if self.lag:
            self._nxt = self._begin(self._nxt_in, f.pending)

    def settle(self, rounds0=None) -> Summary:
        """Read the launched chunk's summary (`shadow.sync`) and run the
        seam's arbitration until the chunk is accepted; returns its
        summary. At lag 1 its successor is in flight on return and
        `state` is that successor's output."""
        f = self._flight
        while True:
            s = None
            if f.error is None:
                try:
                    with span_or_null(None, "sync"):
                        s = f.pending.read()
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    f.error = e
            if self.seam is None:
                if f.error is not None:
                    raise f.error
                break
            if not self.seam.settle(f, s, self.dispatch):
                break
            if self._nxt is not None:
                self.discarded += 1
            self._queue(f)
        nxt, nxt_in = self._nxt, self._nxt_in
        self._flight = self._nxt = self._nxt_in = None
        self.chunks += 1
        if nxt is not None:
            self.ahead += 1
        self.last = s
        self.hwm = s.outbox_hwm
        if self.seam is not None:
            self.seam.accept(f, s, rounds0)
            self.gear = f.gear
            grown = self.seam.proactive_shape(nxt_in, s)
            if grown is not None:
                if nxt is not None:
                    # the successor ran at the old shape: re-seat its
                    # input from its snapshot at the grown one
                    self.discarded += 1
                    nxt_in = self.seam.restore(nxt.snap)
                    nxt = None
                nxt_in = self.seam.migrate(nxt_in, *grown)
        self._base = f.pending
        if nxt is not None:
            self._next = nxt
            self.state = nxt.out if nxt.error is None else nxt_in
        else:
            self.state = nxt_in
        return s

    def advance(self, rounds0=None) -> Summary:
        """`launch`, then `settle`: one accepted chunk."""
        self.launch()
        return self.settle(rounds0)

    def run_one(self, state, rounds0=None):
        """One accepted chunk from `state` (lag 0); returns the state the
        next chunk starts from. A `state` other than the pipeline's own
        (a supervisor's restore) re-seats the pipeline first."""
        if state is not self.state:
            self.start(state)
        self.advance(rounds0)
        return self.state

    def report(self) -> dict:
        return {
            "lag": self.lag,
            "chunks": self.chunks,
            "ahead": self.ahead,
            "discarded": self.discarded,
        }
