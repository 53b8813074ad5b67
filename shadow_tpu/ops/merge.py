"""Deterministic batch merge of cross-host events into per-host queues.

This is the round-barrier half of the reference's cross-host path: in Shadow a
worker locks the destination host's `Mutex<EventQueue>` and pushes
(src/main/core/worker.rs:644-654). On TPU there are no locks: all packets
emitted during a round are staged in a flat outbox, exchanged at the barrier,
and inserted here in an order fully determined by the packed event order key —
so the result is bit-identical for any shard count or arrival interleaving.

The algorithm (`merge_plan`, the same on every backend; static shapes, no
scatters — measured on v5e the original scatter formulation was ~60% of
total round cost):
  1. ONE stable single-key sort groups entries by destination (invalid
     entries sort to the end). The TPU compiler's time for a sort grows
     steeply with its key words: the earlier (dst, time, order) key — five
     32-bit words once i64 is split — did not compile in 10 minutes at the
     flagship's 250k rows, where this 1-key sort takes ~18 s. The
     sort carries each entry's source index and the words of its time and
     order as one [5, N] operand (the key repeated per row; stable, so
     every row takes the same permutation): the grouped [N, 5] matrix;
  2. per-host segment bounds by a blocked search over the sorted dst
     column (`_segment_starts`): an [H + 1, N / 128] compare-and-sum
     finds each host's 128-key block, ONE row gather reads it, and a
     128-wide compare-count finishes the bound. No search loop;
  3. each host's `r_cap` most urgent entries by (time, order) come from a
     batched [H, 2 r_cap] row sort over its segment, chunk by chunk (one
     chunk in a round that sheds nothing), so under overflow pressure the
     *latest* events are shed, never the most urgent ones. A chunk is ONE
     row gather of the grouped matrix: index, time and order together;
  4. each host's r-th free slot *gathers* its r-th most urgent entry, all
     event fields bit-packed into one [N, W] i32 row matrix (row gathers
     move contiguous words, amortizing the per-element index cost that
     made seven separate field gathers the dominant merge cost). Entries
     beyond the free count or `max_inserts` land in `dropped` (counted,
     never silent).

Merge gears (round 7) shrink the sort: every entry point here is
width-parameterized (N is just the length of the flat arrays handed in), so
the engine compiles the round body at a ladder of outbox column widths and
feeds the sort H x gear_cols rows instead of H x B. The truncation is
positional on the [H, B] lane layout (host h's k-th send sits in column k),
so it is exact whenever no host staged more than gear_cols sends that
round — `gear_shed_count` is the exact detector, and the driver replays a
shedding chunk one gear up from a pre-chunk snapshot (core/engine.py
`_gear_sliced_outbox`, core/gears.py).

Formulations tried and rejected in round 5 (measured on the v5e, kept for
the record — all three looked faster in isolated microbenches and were not):
  - fully-SoA element gathers per field: in-context element gathers are
    descriptor-rate-bound (~7 ns/element, ~5 gathers) — 8.6 s/chunk vs the
    packed row gather's 0.74 s (row descriptors amortize all 9 words);
  - vmap(dynamic_slice) per-host contiguous blocks: lowers to a
    10k-iteration while LOOP on TPU (~0.45 s per field per chunk);
  - lax.gather with multi-element slice_sizes: same while-loop lowering.

Replaced (measured in a v5e trace of the 10k-host PHOLD cells):
  - segment bounds by `jnp.searchsorted(s_dst, arange(H + 1))`: its scan
    method is a while loop of ceil(log2(N + 1)) dependent passes, each an
    (H + 1)-element gather, ~6.7 ns an element (17-18 x 10,001 elements,
    1.13-1.20 ms a round);
  - the window read as an index gather then a row gather, `s_idx[pos]`
    (~6.6 ns an element, 0.80 ms a round sparse) then `tw[idx]` of 4-word
    rows (~11.8 ns a row, 1.42 ms): the grouping sort permuted a bare iota;
  - the words as four more [N] operands of the grouping sort: the same
    permutation, but the described-v5e compile grows ~16 s per [N] operand
    (27 s -> 72 s for the 10k-host PHOLD chunk); the [5, N] operand under
    a repeated key compiles as fast as the two-operand sort.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from shadow_tpu.ops.events import (
    ORDER_MAX,
    BucketQueue,
    EventQueue,
    as_flat,
    bucket_rebuild,
)
from shadow_tpu.simtime import TIME_MAX


def gear_shed_count(sent_round, gear_cols: int):
    """Exact count of outbox entries a gear-truncated merge would lose:
    host h's sends occupy lane columns 0..sent_round[h]-1, so exactly
    max(sent_round[h] - gear_cols, 0) of its entries sit in the trimmed
    columns. Zero iff the truncation is lossless — the gear-shed detector
    (fed into stats.gear_shed; a nonzero delta aborts the chunk for a
    snapshot replay one gear up, so results stay bit-identical to the
    full-width merge)."""
    return jnp.sum(jnp.maximum(sent_round.astype(jnp.int64) - gear_cols, 0))


def _urgent_rows(group, t, order, valid, n_groups: int, need):
    """bool[N]: for each group g, its `need[g]` most urgent valid rows by
    (t, order).

    Exact threshold search, no sort: per group, the smallest time T with
    at least need rows at or before it (63 halvings of the non-negative
    time range), then among the rows AT T the smallest order key O that
    completes the count (63 more). Live order keys are unique, so the rows
    at or before (T, O) number exactly `need`. Each step counts rows per
    group through an [N, G] mask — cheap for the few destination shards
    this serves."""
    gi = jnp.arange(n_groups, dtype=jnp.int32)
    oh = valid[:, None] & (group[:, None] == gi[None, :])  # [N, G]
    g_safe = jnp.minimum(group, n_groups - 1)

    def count(mask):
        return jnp.sum((oh & mask[:, None]).astype(jnp.int32), axis=0)

    def least(key, base, want):
        # smallest v >= 0 with count(base & key <= v) >= want, per group
        def body(i, below):
            cand = below + (jnp.int64(1) << (62 - i))
            short = count(base & (key <= cand[g_safe])) < want
            return jnp.where(short, cand, below)

        return lax.fori_loop(
            0, 63, body, jnp.full((n_groups,), -1, jnp.int64)
        ) + 1

    t_cut = least(t, valid, need)
    at_cut = t == t_cut[g_safe]
    before = t < t_cut[g_safe]
    o_cut = least(order, at_cut, need - count(before))
    sel = before | (at_cut & (order <= o_cut[g_safe]))
    return valid & sel & (need[g_safe] > 0)


def dshard_segments(dshard, t, order, world: int, k: int):
    """Group local outbox rows by destination shard, each group led by
    its (at most) `k` most urgent rows.

    One single-key stable sort over (2 x shard + shed flag): a group's
    rows that fit its k-row block come first, in source order, then the
    rows it sheds. When no group holds more than k rows (always, if k
    covers every local row) nothing is ranked; otherwise `_urgent_rows`
    picks the k most urgent per group, so the LATEST rows shed. Invalid
    rows must arrive with `dshard == world` so they sort past every group.

    Returns (s_idx i32[N], start i32[world], seg_len i32[world]): group j's
    block rows are s_idx[start[j] : start[j] + min(seg_len[j], k)], and
    seg_len[j] counts its valid rows before truncation. Shared by the flat
    alltoall exchange and the hierarchical exchange's intra-shard
    compaction tier, so the two paths cannot drift on which rows a block
    carries (the bit-identity contract between them). Order inside a
    block is not urgency order: the receiving merge re-derives it."""
    n_loc = dshard.shape[0]
    valid = dshard < world
    gi = jnp.arange(world, dtype=jnp.int32)
    seg_len = jnp.sum(
        (dshard[:, None] == gi[None, :]).astype(jnp.int32), axis=0
    )
    if k >= n_loc:
        keep = valid
    else:
        keep = lax.cond(
            jnp.any(seg_len > k),
            lambda: _urgent_rows(
                dshard, t, order, valid, world, jnp.minimum(seg_len, k)
            ),
            lambda: valid,
        )
    key = jnp.where(keep, 2 * dshard, 2 * dshard + 1)
    _, s_idx = lax.sort(
        (key, jnp.arange(n_loc, dtype=jnp.int32)), num_keys=1, is_stable=True
    )
    # every key below 2j belongs to a valid row of a group before j
    start = jnp.cumsum(seg_len) - seg_len
    return s_idx, start, seg_len


def _split64(x):
    """i64[...] -> i32[..., 2] words (low, high). Shifts and masks, not a
    64-bit bitcast: the TPU compiler crashes on a bitcast-convert between
    i32 word pairs and i64 whose result feeds a conditional (a CHECK in
    its 64-bit rewrite, found compiling the alltoall exchange)."""
    lo = lax.bitcast_convert_type(
        (x & 0xFFFFFFFF).astype(jnp.uint32), jnp.int32
    )
    return jnp.stack([lo, (x >> 32).astype(jnp.int32)], axis=-1)


def _join64(w):
    """i32[..., 2] words (low, high) -> i64[...], inverse of `_split64`."""
    lo = lax.bitcast_convert_type(w[..., 0], jnp.uint32).astype(jnp.int64)
    return (w[..., 1].astype(jnp.int64) << 32) | lo


def _pack_words(t, order, kind, payload):
    """[N] i64 ×2, [N] i32, [N, P] i32 -> [N, 4 + 1 + P] i32 row matrix."""
    return jnp.concatenate(
        [_split64(t), _split64(order), kind[:, None], payload], axis=1
    )


def _unpack_words(g, p_words):
    """[H, C, 4 + 1 + P] i32 -> (t i64, order i64, kind i32, payload i32[P])."""
    return (
        _join64(g[..., 0:2]), _join64(g[..., 2:4]), g[..., 4],
        g[..., 5 : 5 + p_words],
    )


def merge_plan(
    q_t,  # i64[H, C] — the queue's time plane ONLY (free-slot source)
    dst,
    t,
    order,
    kind,
    payload,
    valid,
    max_inserts: int,
):
    """Steps 1-4 of the merge, WITHOUT writing the queue: returns
    (take bool[H, C], g i32[H, C, W], dropped_add i64[H]).

    Split out so the engine can wrap only THIS half in the empty-round
    `lax.cond`: a cond whose branches return the whole queue copies every
    slab at the branch boundary each round (traced at ~55% of the PHOLD
    round cost on v5e); a cond returning the plan copies one [H, C, W]
    packed block, and `merge_apply` runs unconditionally as a single cheap
    where-pass. Takes only the queue's TIME plane: passing the whole queue
    through the cond made every plane a second consumer and forced XLA to
    copy the slabs around the branch anyway (measured as a 40% round-cost
    regression on PHOLD-torus before the narrowing)."""
    num_hosts, cap = q_t.shape
    n = dst.shape[0]
    r_cap = min(max_inserts, cap)
    # steps 1-3 and step 4 carry scopes of their own, so that a device
    # trace splits the merge between ordering rows and writing slabs
    with jax.named_scope("shadow_merge_order"):
        dst_key = jnp.where(valid, dst.astype(jnp.int32), jnp.int32(num_hosts))
        iota = jnp.arange(n, dtype=jnp.int32)

        # -- 1. group rows by destination: ONE single-key stable sort (rows of
        # a destination keep their source order; invalid rows sort last). The
        # urgency order inside a destination is a per-host row sort below, not
        # a (dst, t, order) key of this sort: the TPU compiler's cost for a
        # sort grows steeply with its key words, and that 5-word key at the
        # flagship's 250k rows did not compile in 10 minutes. The source
        # index and the words of t and order are sorted with it, so that a
        # host's window is one row gather of the grouped [N, 5] matrix: as
        # ONE [5, N] operand under the key repeated per row (a stable sort
        # gives every row the same permutation): the compiler's time grows
        # with every extra [N] operand, not with the rows of one.
        carried = jnp.concatenate(
            [iota[None], _split64(t).T, _split64(order).T], axis=0
        )
        s_key, s_carried = lax.sort(
            (jnp.broadcast_to(dst_key, carried.shape), carried),
            dimension=1, num_keys=1, is_stable=True,
        )
        s_dst, rows = s_key[0], s_carried.T  # i32[N], i32[N, 5]

        # -- 2. segment bounds: host h's rows are rows[start[h]:start[h + 1]]
        with jax.named_scope("shadow_merge_bounds"):
            start = _segment_starts(s_dst, num_hosts + 1)
            seg_len = start[1:] - start[:-1]  # i32[H]

        # -- 3. host h takes its n_ins most urgent rows
        with jax.named_scope("shadow_merge_window"):
            n_ins = jnp.minimum(seg_len, r_cap)  # i32[H]
            sel = _segment_urgency_rows(rows, start[:-1], seg_len, r_cap)

    with jax.named_scope("shadow_merge_write"):
        # -- 4. the r-th free slot of host h takes row sel[h, r]: a one-hot
        # select over r (per-row dynamic gathers lower to slow custom kernels
        # on TPU), then ONE row gather of all event fields bit-packed into an
        # [N, W] i32 matrix
        free = q_t == TIME_MAX  # [H, C]
        free_rank = jnp.cumsum(free.astype(jnp.int32), axis=1) - 1  # [H, C]
        take = free & (free_rank < n_ins[:, None])
        oh = free_rank[:, :, None] == jnp.arange(r_cap, dtype=jnp.int32)
        row = jnp.sum(jnp.where(oh, sel[:, None, :], 0), axis=2)
        row = jnp.where(take, row, 0)
        words = _pack_words(t, order, kind.astype(jnp.int32), payload)
        g = words[row]  # [H, C, W]

        # -- overflow accounting (elementwise: order-independent,
        # deterministic)
        inserted = jnp.sum(take.astype(jnp.int32), axis=1)
        dropped_add = (seg_len - inserted).astype(jnp.int64)
    return take, g, dropped_add


def merge_empty_plan(q_t, p_words: int):
    """A no-op insertion plan (the empty-round cond branch)."""
    num_hosts, cap = q_t.shape
    return (
        jnp.zeros((num_hosts, cap), bool),
        jnp.zeros((num_hosts, cap, 5 + p_words), jnp.int32),
        jnp.zeros((num_hosts,), jnp.int64),
    )


def merge_apply(q: EventQueue, take, g, dropped_add) -> EventQueue:
    """Write a `merge_plan` into the queue (one masked slab pass)."""
    p_words = q.payload.shape[2]
    with jax.named_scope("shadow_merge_write"):
        g_t, g_order, g_kind, g_payload = _unpack_words(g, p_words)
        return EventQueue(
            t=jnp.where(take, g_t, q.t),
            order=jnp.where(take, g_order, q.order),
            kind=jnp.where(take, g_kind, q.kind),
            payload=jnp.where(take[:, :, None], g_payload, q.payload),
            dropped=q.dropped + dropped_add,
        )


def merge_flat_events(
    q: EventQueue,
    dst,  # i32[N] local host index of each entry
    t,  # i64[N]
    order,  # i64[N] packed tiebreak key (unique per live entry)
    kind,  # i32[N]
    payload,  # i32[N, P]
    valid,  # bool[N]
    max_inserts: int,
) -> EventQueue:
    """Insert the valid rows into their hosts' free slots: `merge_plan`
    then `merge_apply`. Overflow sheds by (time, order), so the most
    urgent events always win slots.

    Accepts either queue type: a `BucketQueue` merges through its flat slab
    view and comes back with freshly rebuilt block caches — merges are
    wholesale cache-rebuild points (along with checkpoint restore). This
    entry point rebuilds unconditionally (the hybrid bridge's per-window
    injection lands here); the engine's split plan/apply path refreshes the
    caches itself so empty rounds can skip the rebuild."""
    if isinstance(q, BucketQueue):
        merged = merge_flat_events(
            as_flat(q), dst, t, order, kind, payload, valid, max_inserts
        )
        return bucket_rebuild(merged, q.block)
    return merge_apply(
        q, *merge_plan(q.t, dst, t, order, kind, payload, valid, max_inserts)
    )


_BLOCK = 128  # lanes of a TPU vector register


def _segment_starts(s_key, n_bounds: int):
    """i32[n_bounds]: start[q] = #(s_key < q) for an ascending i32 `s_key`,
    with no search loop (`jnp.searchsorted(s_key, arange(n_bounds))`
    exactly, side="left").

    The keys, padded with a sentinel no query exceeds, are viewed as
    [nb, 128] blocks. The blocks whose last key is below q all lie wholly
    below it; their count j[q] is one [n_bounds, nb] compare-and-sum. The
    rest of the answer lies in block j[q]: one row gather of it and a
    128-wide compare-count. When every key is below q, j[q] = nb only
    without padding, and the clamped last block then counts all 128."""
    n = s_key.shape[0]
    nb = max(1, -(-n // _BLOCK))
    blocks = jnp.pad(
        s_key, (0, nb * _BLOCK - n), constant_values=jnp.iinfo(jnp.int32).max
    ).reshape(nb, _BLOCK)
    q = jnp.arange(n_bounds, dtype=jnp.int32)[:, None]
    j = jnp.minimum(
        jnp.sum((blocks[None, :, -1] < q).astype(jnp.int32), axis=1), nb - 1
    )
    return j * _BLOCK + jnp.sum((blocks[j] < q).astype(jnp.int32), axis=1)


def _segment_urgency_rows(rows, start, seg_len, r: int):
    """[H, r] original row indices of each destination segment's `r` most
    urgent rows in ascending (t, order); -1 pads short segments.

    `rows` is the grouped [N, 5] i32 matrix (source index, then the
    `_split64` words of t and order); segment h is
    `rows[start[h] : start[h] + seg_len[h]]` (source order inside). Each
    chunk of `r` segment rows is read by ONE row gather and merged into
    the running top-r by one batched [H, 2r] row sort (one traced sort:
    the TPU compiler charges per sort instance). A drop-free round has
    seg_len <= r_cap everywhere, so it runs one iteration; a round that
    sheds pays one more per extra chunk of its longest segment. Exact:
    live order keys are unique, so the only ties are between pads."""
    cols = jnp.arange(r, dtype=jnp.int32)

    def chunk(c):
        off = c * r + cols[None, :]
        inside = off < seg_len[:, None]
        w = rows[jnp.where(inside, start[:, None] + off, 0)]  # [H, r, 5]
        idx = jnp.where(inside, w[..., 0], -1)
        ct = jnp.where(inside, _join64(w[..., 1:3]), TIME_MAX)
        co = jnp.where(inside, _join64(w[..., 3:5]), ORDER_MAX)
        return ct, co, idx

    def top(ct, co, ci):
        st, so, si = lax.sort((ct, co, ci), dimension=1, num_keys=2)
        return st[:, :r], so[:, :r], si[:, :r]

    def body(c, best):
        return top(*(
            jnp.concatenate([b, x], axis=1) for b, x in zip(best, chunk(c))
        ))

    h = seg_len.shape[0]
    pads = (
        jnp.full((h, r), TIME_MAX, jnp.int64),
        jnp.full((h, r), ORDER_MAX, jnp.int64),
        jnp.full((h, r), -1, jnp.int32),
    )
    n_chunks = jnp.maximum((jnp.max(seg_len) + r - 1) // r, 1)
    return lax.fori_loop(0, n_chunks, body, pads)[2]
