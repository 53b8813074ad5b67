"""Event-queue kernel unit tests (reference analogue:
src/main/core/work/event_queue.rs tests + event.rs ordering tests)."""

import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.ops import (
    make_queue,
    next_time,
    pop_min,
    push_one,
    pack_order,
    queue_len,
    merge_flat_events,
)
from shadow_tpu.ops.events import EVENT_PAYLOAD_WORDS
from shadow_tpu.simtime import TIME_MAX

H, C = 4, 8


def _push(q, host, t, order, kind=1, payload=None):
    mask = jnp.arange(H) == host
    tt = jnp.full((H,), t, jnp.int64)
    oo = jnp.full((H,), order, jnp.int64)
    kk = jnp.full((H,), kind, jnp.int32)
    pp = jnp.zeros((H, EVENT_PAYLOAD_WORDS), jnp.int32)
    if payload is not None:
        pp = pp + jnp.asarray(payload, jnp.int32)[None, :]
    return push_one(q, mask, tt, oo, kk, pp)


def test_push_pop_roundtrip():
    q = make_queue(H, C)
    q = _push(q, 0, 100, 5)
    q = _push(q, 0, 50, 7)
    q = _push(q, 2, 10, 1)
    nt = np.asarray(next_time(q))
    assert nt[0] == 50 and nt[2] == 10 and nt[1] == TIME_MAX

    q, ev, active = pop_min(q, TIME_MAX)
    assert list(np.asarray(active)) == [True, False, True, False]
    assert np.asarray(ev.t)[0] == 50
    assert np.asarray(ev.t)[2] == 10

    q, ev, active = pop_min(q, TIME_MAX)
    assert np.asarray(ev.t)[0] == 100
    assert not np.asarray(active)[2]


def test_pop_respects_limit():
    q = make_queue(H, C)
    q = _push(q, 1, 500, 1)
    q, ev, active = pop_min(q, 500)  # strictly-before semantics
    assert not np.asarray(active)[1]
    q, ev, active = pop_min(q, 501)
    assert np.asarray(active)[1]


def test_deterministic_tiebreak_packets_before_local():
    """Equal times: packets (is_local=0) pop before local tasks, then by
    (src, seq) — the event.rs:102-155 total order."""
    q = make_queue(H, C)
    q = _push(q, 0, 100, pack_order(1, 0, 3))  # local task
    q = _push(q, 0, 100, pack_order(0, 2, 9))  # packet from host 2
    q = _push(q, 0, 100, pack_order(0, 1, 11))  # packet from host 1
    q, ev, _ = pop_min(q, TIME_MAX)
    assert np.asarray(ev.order)[0] == int(pack_order(0, 1, 11))
    q, ev, _ = pop_min(q, TIME_MAX)
    assert np.asarray(ev.order)[0] == int(pack_order(0, 2, 9))
    q, ev, _ = pop_min(q, TIME_MAX)
    assert np.asarray(ev.order)[0] == int(pack_order(1, 0, 3))


def test_overflow_counts_dropped():
    q = make_queue(2, 2)
    mask = jnp.array([True, False])
    t = jnp.zeros((2,), jnp.int64)
    o = jnp.zeros((2,), jnp.int64)
    k = jnp.zeros((2,), jnp.int32)
    p = jnp.zeros((2, EVENT_PAYLOAD_WORDS), jnp.int32)
    for i in range(3):
        q = push_one(q, mask, t + i, o + i, k, p)
    assert int(q.dropped[0]) == 1
    assert int(queue_len(q)[0]) == 2


def _merge_reference(q, dst, t, order, kind, payload, valid, max_inserts):
    """The insertion contract in plain numpy: per host, its valid rows by
    (t, order); the r-th takes the r-th free slot, up to min(free, r_cap);
    the rest count in `dropped`. Returns the merged queue's fields."""
    out = {f: np.array(getattr(q, f)) for f in q._fields}
    dst, t, order, kind, payload, valid = (
        np.asarray(a) for a in (dst, t, order, kind, payload, valid)
    )
    r_cap = min(max_inserts, out["t"].shape[1])
    for h in range(out["t"].shape[0]):
        rows = np.flatnonzero(valid & (dst == h))
        rows = rows[np.lexsort((order[rows], t[rows]))]
        free = np.flatnonzero(out["t"][h] == TIME_MAX)[:r_cap]
        for slot, r in zip(free, rows):
            out["t"][h, slot], out["order"][h, slot] = t[r], order[r]
            out["kind"][h, slot], out["payload"][h, slot] = kind[r], payload[r]
        out["dropped"][h] += rows.size - min(rows.size, free.size)
    return out


def _assert_merge_is_reference(q, merged, *rows, max_inserts, what=""):
    want = _merge_reference(q, *rows, max_inserts)
    for f in merged._fields:
        assert np.array_equal(np.asarray(getattr(merged, f)), want[f]), (
            f"{what} field {f}"
        )


def test_merge_flat_events_sorted_and_counted():
    q = make_queue(H, C)
    q = _push(q, 1, 5, 1)  # pre-existing event occupies slot 0 of host 1
    n = 6
    dst = jnp.array([1, 1, 3, 1, 0, 2], jnp.int32)
    t = jnp.array([30, 10, 7, 20, 9, 9], jnp.int64)
    order = jnp.array([pack_order(0, s, i) for i, s in enumerate([2, 3, 0, 1, 1, 1])], jnp.int64)
    kind = jnp.full((n,), 2, jnp.int32)
    payload = jnp.tile(jnp.arange(n, dtype=jnp.int32)[:, None], (1, EVENT_PAYLOAD_WORDS))
    valid = jnp.array([True, True, True, True, False, True])

    q2 = merge_flat_events(q, dst, t, order, kind, payload, valid, max_inserts=C)
    assert int(queue_len(q2)[1]) == 4  # 1 old + 3 merged
    assert int(queue_len(q2)[0]) == 0  # invalid entry not inserted
    assert int(queue_len(q2)[2]) == 1
    assert int(queue_len(q2)[3]) == 1

    # pop host 1 in order: 5 (old), then 10/20/30 by time
    times = []
    for _ in range(4):
        q2, ev, active = pop_min(q2, TIME_MAX)
        assert np.asarray(active)[1]
        times.append(int(np.asarray(ev.t)[1]))
    assert times == [5, 10, 20, 30]


def test_merge_sheds_latest_not_earliest():
    """Under overflow pressure the merge must keep the most urgent events:
    drop priority is (time, order), not the raw order key."""
    q = make_queue(1, 1)
    dst = jnp.zeros((2,), jnp.int32)
    t = jnp.array([100, 5], jnp.int64)
    # the later event has the *smaller* order key (earlier src)
    order = jnp.array([pack_order(0, 0, 1), pack_order(0, 3, 1)], jnp.int64)
    kind = jnp.zeros((2,), jnp.int32)
    payload = jnp.zeros((2, EVENT_PAYLOAD_WORDS), jnp.int32)
    q2 = merge_flat_events(q, dst, t, order, kind, payload, jnp.ones((2,), bool), 4)
    q2, ev, active = pop_min(q2, TIME_MAX)
    assert int(np.asarray(ev.t)[0]) == 5  # urgent event survived
    assert int(q2.dropped[0]) == 1


def test_merge_overflow_drops_counted():
    q = make_queue(2, 2)
    n = 4
    dst = jnp.zeros((n,), jnp.int32)
    t = jnp.arange(n, dtype=jnp.int64) + 1
    order = jnp.array([pack_order(0, 0, i) for i in range(n)], jnp.int64)
    kind = jnp.zeros((n,), jnp.int32)
    payload = jnp.zeros((n, EVENT_PAYLOAD_WORDS), jnp.int32)
    valid = jnp.ones((n,), bool)
    q2 = merge_flat_events(q, dst, t, order, kind, payload, valid, max_inserts=8)
    assert int(queue_len(q2)[0]) == 2
    assert int(q2.dropped[0]) == 2
    # lowest-order entries won the slots
    q2, ev, _ = pop_min(q2, TIME_MAX)
    assert int(np.asarray(ev.t)[0]) == 1


def test_merge_gather_and_scatter_paths_agree():
    """The merge (destination sort + gather, the one formulation on every
    backend) leaves exactly the numpy reference's queue for any input,
    overflow included: slot for slot, and every shed counted."""
    rng = np.random.default_rng(7)
    for trial in range(8):
        hh, cc = int(rng.integers(1, 12)), int(rng.integers(1, 6))
        n = int(rng.integers(1, 40))
        q = make_queue(hh, cc)
        # pre-occupy random slots
        occ = rng.random((hh, cc)) < 0.4
        qt = np.where(occ, rng.integers(1, 1000, (hh, cc)), np.asarray(q.t))
        qo = np.where(
            occ,
            rng.integers(0, 1 << 40, (hh, cc)),
            np.asarray(q.order),
        )
        q = q._replace(t=jnp.asarray(qt), order=jnp.asarray(qo))
        dst = jnp.asarray(rng.integers(0, hh, n), jnp.int32)
        t = jnp.asarray(rng.integers(1, 1000, n), jnp.int64)
        order = jnp.asarray(
            [int(pack_order(0, int(rng.integers(0, hh)), 1000 + i)) for i in range(n)],
            jnp.int64,
        )
        kind = jnp.asarray(rng.integers(0, 5, n), jnp.int32)
        payload = jnp.asarray(
            rng.integers(0, 100, (n, EVENT_PAYLOAD_WORDS)), jnp.int32
        )
        valid = jnp.asarray(rng.random(n) < 0.8)
        rows = (dst, t, order, kind, payload, valid)
        merged = merge_flat_events(q, *rows, max_inserts=cc)
        _assert_merge_is_reference(
            q, merged, *rows, max_inserts=cc, what=f"trial {trial}"
        )


def test_dshard_segments_block_takes_most_urgent_rows():
    """The alltoall/hierarchical block of each destination shard holds its
    k most urgent (t, order) rows — the shed set the urgency contract
    names — for groups under, at and over k."""
    from shadow_tpu.ops.merge import dshard_segments

    rng = np.random.default_rng(5)
    for trial in range(6):
        world, n = int(rng.integers(2, 5)), int(rng.integers(8, 60))
        k = int(rng.integers(1, n))
        dshard = rng.integers(0, world + 1, n).astype(np.int32)  # world = invalid
        t = rng.integers(0, 50, n).astype(np.int64)  # many equal times
        order = rng.permutation(n).astype(np.int64) << 33  # unique, high bits
        s_idx, start, seg_len = (
            np.asarray(a) for a in dshard_segments(
                jnp.asarray(dshard), jnp.asarray(t), jnp.asarray(order), world, k
            )
        )
        for g in range(world):
            rows = np.flatnonzero(dshard == g)
            assert seg_len[g] == rows.size, (trial, g)
            want = rows[np.lexsort((order[rows], t[rows]))][:k]
            got = s_idx[start[g] : start[g] + min(rows.size, k)]
            assert sorted(got) == sorted(want), (trial, g)


def _sorted_keys(case):
    """Ascending destination keys (invalid rows = H) of a bounds case."""
    rng = np.random.default_rng(31)
    if case == "hosts_without_rows":  # H=50, only every 7th host holds rows
        return 50, np.sort(rng.choice(np.arange(0, 50, 7), 300))
    if case == "all_rows_invalid":
        return 20, np.full(260, 20)
    if case == "rows_below_one_block":  # N < 128, not a multiple of 128
        return 9, np.sort(rng.integers(0, 10, 77))
    if case == "one_host_holds_every_row":
        return 12, np.full(384, 5)
    if case == "every_row_below_a_whole_block":  # N = 2 x 128, no pad
        return 3, np.sort(rng.integers(0, 3, 256))
    # segments of 90-170 rows, so most start or end across a block edge
    lens = rng.integers(90, 170, 16)
    return 16, np.repeat(np.arange(16), lens)


@pytest.mark.parametrize("case", [
    "hosts_without_rows", "all_rows_invalid", "rows_below_one_block",
    "one_host_holds_every_row", "every_row_below_a_whole_block",
    "segments_cross_block_edges",
])
def test_segment_starts_equal_searchsorted(case):
    """The blocked segment bounds are `searchsorted(side="left")` exactly."""
    from shadow_tpu.ops.merge import _segment_starts

    hh, keys = _sorted_keys(case)
    got = np.asarray(_segment_starts(jnp.asarray(keys, jnp.int32), hh + 1))
    want = np.searchsorted(keys, np.arange(hh + 1), side="left")
    assert np.array_equal(got, want), case


@pytest.mark.parametrize("hot", [0, 2, 4], ids=["first", "middle", "last"])
def test_merge_gather_sheds_long_segment_like_scatter(hot):
    """A host whose segment spans several r_cap chunks (the urgency loop
    runs more than once) sheds exactly as the numpy reference does. The
    hot host is the first, a middle and the last one: the edges of
    `_segment_starts`."""
    rng = np.random.default_rng(41)
    hh, cc, n = 5, 4, 60
    q = make_queue(hh, cc)
    occ = rng.random((hh, cc)) < 0.3
    q = q._replace(
        t=jnp.asarray(np.where(occ, rng.integers(1, 900, (hh, cc)), np.asarray(q.t))),
        order=jnp.asarray(np.where(occ, rng.integers(0, 1 << 40, (hh, cc)), np.asarray(q.order))),
    )
    # the hot host receives every second row, over 7 x r_cap; the rest spread
    dst = np.where(np.arange(n) % 2 == 0, hot, rng.integers(0, hh, n))
    assert (dst == hot).sum() > 2 * cc
    # three distinct times: the shed cut falls among equal times, where
    # the order key decides
    t = jnp.asarray(rng.integers(1, 4, n), jnp.int64)
    order = jnp.asarray(
        [int(pack_order(0, int(rng.integers(0, hh)), 300 + i)) for i in range(n)],
        jnp.int64,
    )
    kind = jnp.asarray(rng.integers(0, 5, n), jnp.int32)
    payload = jnp.asarray(rng.integers(0, 100, (n, EVENT_PAYLOAD_WORDS)), jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.9)
    rows = (jnp.asarray(dst, jnp.int32), t, order, kind, payload, valid)
    merged = merge_flat_events(q, *rows, max_inserts=cc)
    assert int(np.asarray(merged.dropped)[hot]) > 0
    _assert_merge_is_reference(q, merged, *rows, max_inserts=cc)


def _drain_queue(q):
    out = []
    for _ in range(q.t.shape[0] * q.t.shape[1] + 1):
        q, ev, active = pop_min(q, jnp.int64(TIME_MAX))
        if not bool(np.asarray(active).any()):
            break
        out.append((
            np.asarray(ev.t).tolist(),
            np.asarray(ev.order).tolist(),
            np.asarray(ev.kind).tolist(),
        ))
    return out


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("load", ["light", "overflow"])
def test_merge_flat_events_matches_reference(seed, load):
    """Random row sets into a random pre-filled queue: the merged queue's
    OBSERVABLE behavior (drain order via pop_min, drop counts) is the
    numpy reference's. `overflow` sends one destination past its free
    slots, so equality covers the shed order too."""
    rng = np.random.default_rng(seed)
    h, cap = 6, 8
    q = make_queue(h, cap)
    # pre-fill some slots so free ranks are nontrivial
    seq = 0
    for _ in range(int(rng.integers(0, 3))):
        t0 = rng.integers(1, 500, size=h).astype(np.int64)
        order = np.asarray(pack_order(1, np.arange(h), np.full(h, seq)))
        seq += 1
        mask = rng.random(h) < 0.7
        q = push_one(
            q, jnp.asarray(mask), jnp.asarray(t0), jnp.asarray(order),
            jnp.full((h,), 2, jnp.int32),
            jnp.zeros((h, EVENT_PAYLOAD_WORDS), jnp.int32),
        )
    n = 24 if load == "overflow" else 10
    hot = int(rng.integers(0, h))
    dst = rng.integers(0, h, size=n).astype(np.int32)
    if load == "overflow":
        dst[: n // 2] = hot  # slam one destination past its free slots
    t = rng.integers(600, 1000, size=n).astype(np.int64)
    order = np.array(
        [int(pack_order(0, int(rng.integers(0, h)), 1000 + j))
         for j in range(n)], np.int64,
    )
    kind = rng.integers(0, 5, size=n).astype(np.int32)
    payload = rng.integers(0, 100, size=(n, EVENT_PAYLOAD_WORDS)).astype(np.int32)
    valid = rng.random(n) < 0.9
    rows = (
        jnp.asarray(dst), jnp.asarray(t), jnp.asarray(order),
        jnp.asarray(kind), jnp.asarray(payload), jnp.asarray(valid),
    )
    merged = merge_flat_events(q, *rows, max_inserts=cap)
    want = q._replace(**{
        f: jnp.asarray(v)
        for f, v in _merge_reference(q, *rows, cap).items()
    })
    np.testing.assert_array_equal(
        np.asarray(merged.dropped), np.asarray(want.dropped)
    )
    assert _drain_queue(merged) == _drain_queue(want)
    if load == "overflow":
        assert int(np.asarray(merged.dropped).sum()) > 0
