"""Plain reference of the device plane's semantics, in numpy.

It imports nothing of the program and takes nothing the program made: it
reads the same configuration dict, builds its own graph, hosts, queues
and RNG lanes, and runs the conservative round loop the program's
documentation states (a round runs to min next-event time + the least
path latency; a host executes its earliest (time, order) event in each
microstep; packets leave through an egress token bucket, arrive after the
path latency, pass an ingress token bucket and CoDel, and are merged into
their destination's queue at the end of the round, in (dst, time, order)
order; each executed event folds (time, kind, order) into the host's FNV
digest). Per-host queues are a [H, C] slab here only because numpy wants
arrays; the order of execution is the plain one.

Only what the benchmark's configurations use is implemented; anything else
in a configuration is refused (`NotSupported`), never guessed.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

TIME_MAX = (1 << 63) - 1
ORDER_MAX = (1 << 63) - 1
SRC_SHIFT = 40
SRC_MASK = (1 << 22) - 1
SEQ_MASK = (1 << 40) - 1
LOCAL_SHIFT = 62
KIND_MASK = 0xFFFF
KIND_PKT = 1 << 16
KIND_INGRESS_DONE = 1 << 17
PAYLOAD_WORDS = 4
SIZE_WORD = 0
FNV_OFFSET = np.uint64(0xCBF29CE484222325)
FNV_PRIME = np.uint64(1099511628211)
MIX1 = np.uint64(0x9E3779B97F4A7C15)
MIX2 = np.uint64(0xC2B2AE3D27D4EB4F)
MTU_BITS = 1500 * 8
TB_INTERVAL_NS = 1_000_000
CODEL_TARGET_NS = 10_000_000
CODEL_INTERVAL_NS = 100_000_000
NS = {"ns": 1, "us": 1_000, "ms": 1_000_000, "s": 1_000_000_000}
SI = {"": 1, "K": 10**3, "M": 10**6, "G": 10**9, "T": 10**12,
      "Ki": 2**10, "Mi": 2**20, "Gi": 2**30, "Ti": 2**40}
# experimental keys that pick how the program computes, not what: every
# one is documented digest-exact, so the reference ignores them
LEVERS = {
    "microstep_events", "event_queue_block", "rounds_per_chunk", "merge_gears",
    "timer_wheel", "exchange", "merge_rows", "overflow_shed", "merge_scatter",
}


class NotSupported(Exception):
    pass


def time_ns(value, default_unit: str) -> int:
    if isinstance(value, (int, float)):
        return round(value * NS[default_unit])
    num, unit = re.fullmatch(r"\s*([0-9.]+)\s*([a-z]*)\s*", value).groups()
    return round(float(num) * NS[unit or default_unit])


def bits_per_s(value: str) -> int:
    num, prefix = re.fullmatch(r"\s*([0-9.]+)\s*([KMGT]?i?)bit\s*", value).groups()
    return round(float(num) * SI[prefix])


# --------------------------------------------------------------------------
# RNG lanes: xoshiro256++ per host, seeded by splitmix64(seed, host)
# --------------------------------------------------------------------------

_U = np.uint64


def rng_init(num_hosts: int, seed: int) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = _U(seed % (1 << 64)) + np.arange(num_hosts, dtype=np.uint64) * _U(
            0xD1342543DE82EF95
        )
        lanes = []
        for _ in range(4):
            x = x + _U(0x9E3779B97F4A7C15)
            z = (x ^ (x >> _U(30))) * _U(0xBF58476D1CE4E5B9)
            z = (z ^ (z >> _U(27))) * _U(0x94D049BB133111EB)
            lanes.append(z ^ (z >> _U(31)))
    return np.stack(lanes, axis=1)


def _rotl(x, k):
    return (x << _U(k)) | (x >> _U(64 - k))


def rng_uniform(s: np.ndarray, mask: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """float32 in [0, 1) from the top 24 bits; lanes advance where `mask`."""
    s0, s1, s2, s3 = s[:, 0], s[:, 1], s[:, 2], s[:, 3]
    result = _rotl(s0 + s3, 23) + s0
    t = s1 << _U(17)
    n2 = s2 ^ s0
    n3 = s3 ^ s1
    n1 = s1 ^ n2
    n0 = s0 ^ n3
    n2 = n2 ^ t
    n3 = _rotl(n3, 45)
    new = np.stack([n0, n1, n2, n3], axis=1)
    s = np.where(mask[:, None], new, s)
    u = (result >> _U(40)).astype(np.float32) * np.float32(1.0 / (1 << 24))
    return s, u


# --------------------------------------------------------------------------
# network: GML graph, shortest paths by latency, hosts
# --------------------------------------------------------------------------


def parse_gml(text: str):
    nodes, edges = [], []
    for kind, body in re.findall(r"\b(node|edge)\s*\[(.*?)\]", text, re.S):
        attrs = dict(
            (k, v.strip('"'))
            for k, v in re.findall(r'(\w+)\s+("[^"]*"|\S+)', body)
        )
        (nodes if kind == "node" else edges).append(attrs)
    return nodes, edges


def path_latency(text: str):
    """(node ids, lat_ns[N, N] with -1 unreachable, bw_down, bw_up)."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import dijkstra

    nodes, edges = parse_gml(text)
    ids = [int(n["id"]) for n in nodes]
    index = {v: i for i, v in enumerate(ids)}
    n = len(ids)
    lat = np.full((n, n), -1, np.int64)
    undirected = not re.search(r"\bdirected\s+1\b", text)
    for e in edges:
        if set(e) - {"source", "target", "latency"}:
            raise NotSupported(f"edge attributes {sorted(e)}")
        a, b = index[int(e["source"])], index[int(e["target"])]
        d = time_ns(e["latency"], "ms")
        lat[a, b] = d
        if undirected:
            lat[b, a] = d
    mask = lat >= 0
    w = csr_matrix((lat[mask].astype(np.float64), np.nonzero(mask)), shape=(n, n))
    dist = dijkstra(w, directed=True)
    out = np.where(np.isinf(dist), -1, np.rint(dist)).astype(np.int64)
    # a node reaches itself over its self-loop, never for free
    out[np.arange(n), np.arange(n)] = np.diag(lat)
    bw_down = np.array([bits_per_s(v["host_bandwidth_down"]) for v in nodes], np.int64)
    bw_up = np.array([bits_per_s(v["host_bandwidth_up"]) for v in nodes], np.int64)
    return index, out, bw_down, bw_up


def expand_hosts(hosts_cfg: dict) -> list[dict]:
    """`count` expands a group into name1..nameN; host ids follow name order."""
    flat = []
    for name, h in hosts_cfg.items():
        if set(h) - {"count", "network_node_id", "processes"}:
            raise NotSupported(f"host options {sorted(h)}")
        (proc,) = h["processes"]
        if set(proc) - {"model", "model_args", "start_time"}:
            raise NotSupported(f"process options {sorted(proc)}")
        count = int(h.get("count", 1))
        names = [name] if count == 1 else [f"{name}{i}" for i in range(1, count + 1)]
        for nm in names:
            flat.append(
                {
                    "name": nm,
                    "node_id": int(h["network_node_id"]),
                    "model": proc["model"],
                    "model_args": dict(proc.get("model_args", {})),
                    "start_time": time_ns(proc.get("start_time", 0), "s"),
                }
            )
    flat.sort(key=lambda x: x["name"])
    for i, x in enumerate(flat):
        x["host_id"] = i
    return flat


# --------------------------------------------------------------------------
# token bucket and CoDel, one lane per host, vectorised over hosts
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Buckets:
    cap: np.ndarray
    refill: np.ndarray
    tokens: np.ndarray
    itv: np.ndarray

    @classmethod
    def for_bandwidth(cls, bw: np.ndarray) -> "Buckets":
        if (bw <= 0).any():
            raise NotSupported("unlimited bandwidth")
        refill = np.maximum(bw * TB_INTERVAL_NS // 10**9, 1).astype(np.int64)
        return cls(refill + MTU_BITS, refill, refill + MTU_BITS, np.zeros_like(refill))

    def depart(self, rows, t, size):
        """(departure, tokens after, interval after) of sending `size` bits
        at `t` on lanes `rows`, without charging them."""
        cap, refill = self.cap[rows], self.refill[rows]
        itv = np.maximum(t // TB_INTERVAL_NS, self.itv[rows])
        elapsed = itv - self.itv[rows]
        gain = np.where(elapsed < (1 << 20), elapsed * refill, cap)
        tokens = np.minimum(cap, self.tokens[rows] + gain)
        ok = tokens >= size
        k = (size - tokens + refill - 1) // refill
        dep = np.where(ok, np.maximum(t, itv * TB_INTERVAL_NS), (itv + k) * TB_INTERVAL_NS)
        left = np.where(ok, tokens - size, tokens + k * refill - size)
        return dep, left, np.where(ok, itv, itv + k)

    def charge(self, rows, t, size):
        dep, left, itv = self.depart(rows, t, size)
        self.tokens[rows] = left
        self.itv[rows] = itv
        return dep


@dataclasses.dataclass
class Codel:
    first_above: np.ndarray
    drop_next: np.ndarray
    count: np.ndarray
    dropping: np.ndarray

    @classmethod
    def zeros(cls, h: int) -> "Codel":
        z = np.zeros(h, np.int64)
        return cls(z.copy(), z.copy(), z.copy(), np.zeros(h, bool))

    @staticmethod
    def _law(now, count):
        c = np.maximum(count, 1).astype(np.float64)
        return now + np.round(np.float64(CODEL_INTERVAL_NS) / np.sqrt(c)).astype(np.int64)

    def on_packet(self, rows, now, sojourn) -> np.ndarray:
        """RFC 8289's control law; True where the packet is dropped."""
        fa, dn = self.first_above[rows], self.drop_next[rows]
        cnt, dropping = self.count[rows], self.dropping[rows]
        below = sojourn < CODEL_TARGET_NS
        unset = fa == 0
        ok = ~below & ~unset & (now >= fa)
        self.first_above[rows] = np.where(below, 0, np.where(unset, now + CODEL_INTERVAL_NS, fa))
        # dropping state: leave it, or drop at the next scheduled time
        leave = dropping & ~ok
        due = dropping & ok & (now >= dn)
        cnt_due = cnt + 1
        # not dropping: enter the dropping state
        enter = ~dropping & ok
        recent = (now - dn) < 16 * CODEL_INTERVAL_NS
        cnt_enter = np.where(recent & (cnt > 2), cnt - 2, 1)
        new_cnt = np.where(due, cnt_due, np.where(enter, cnt_enter, cnt))
        self.count[rows] = new_cnt
        self.drop_next[rows] = np.where(
            due, self._law(dn, new_cnt), np.where(enter, self._law(now, new_cnt), dn)
        )
        self.dropping[rows] = np.where(leave, False, np.where(enter, True, dropping))
        return due | enter


# --------------------------------------------------------------------------
# the round loop
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Ctx:
    """One event per row: the handler's view (rows = hosts that execute)."""

    t: np.ndarray
    kind: np.ndarray
    payload: np.ndarray
    is_packet: np.ndarray
    src: np.ndarray
    host_id: np.ndarray
    state: dict
    params: dict
    rng: np.ndarray


@dataclasses.dataclass
class Result:
    digest: np.ndarray
    events: np.ndarray
    dropped: np.ndarray
    rounds: int
    microsteps: int
    model: dict
    counters: dict
    q_hwm: int  # most events any host held at once
    sends_hwm: int  # most packets any host sent in one round


def pack_order(is_local: int, src, seq):
    return (np.int64(is_local) << LOCAL_SHIFT) | (src.astype(np.int64) << SRC_SHIFT) | (
        seq & SEQ_MASK
    )


class Reference:
    """The reference simulation of one configuration dict and seed."""

    def __init__(self, shadow: dict, model, *, runahead_scale: int = 1):
        g, ex = shadow["general"], shadow.get("experimental", {})
        unknown = set(ex) - LEVERS - {"event_queue_capacity", "sends_per_host_round"}
        if unknown or set(g) - {"stop_time", "seed"}:
            raise NotSupported(f"options {sorted(unknown | (set(g) - {'stop_time', 'seed'}))}")
        graph = shadow["network"]["graph"]
        if set(graph) != {"type", "inline"} or graph["type"] != "gml":
            raise NotSupported("graph other than an inline GML")
        index, lat, bw_down, bw_up = path_latency(graph["inline"])
        self.hosts = expand_hosts(shadow["hosts"])
        h = len(self.hosts)
        self.h = h
        self.node_of = np.array([index[x["node_id"]] for x in self.hosts], np.int64)
        self.lat = lat
        self.stop = time_ns(g["stop_time"], "s")
        self.seed = int(g["seed"])
        self.cap = int(ex["event_queue_capacity"])
        self.budget = int(ex["sends_per_host_round"])
        self.r_cap = self.cap  # merge inserts per host per round
        self.limit = 2 * self.cap  # microsteps per round (livelock valve)
        # the round's lookahead: the least path latency (runahead floor 1 ms)
        self.runahead = max(1_000_000, int(lat[lat >= 0].min())) * runahead_scale
        self.eg = Buckets.for_bandwidth(bw_up[self.node_of])
        self.ing = Buckets.for_bandwidth(bw_down[self.node_of])
        self.codel = Codel.zeros(h)
        self.model = model
        self.params, self.state, events = model.build(self.hosts, self.seed)
        self.rng = rng_init(h, self.seed)
        c = self.cap
        self.qt = np.full((h, c), TIME_MAX, np.int64)
        self.qo = np.full((h, c), ORDER_MAX, np.int64)
        self.qk = np.zeros((h, c), np.int64)
        self.qp = np.zeros((h, c, PAYLOAD_WORDS), np.int32)
        self.seq = np.zeros(h, np.int64)
        self.digest = np.full(h, FNV_OFFSET, np.uint64)
        self.events = np.zeros(h, np.int64)
        self.dropped = np.zeros(h, np.int64)
        self.q_hwm = 0
        self.sends_hwm = 0
        self.counters = {
            k: np.zeros(h, np.int64)
            for k in ("pkts_sent", "pkts_delivered", "pkts_lost", "pkts_unreachable",
                      "pkts_codel_dropped", "pkts_budget_dropped", "monotonic_violations")
        }
        for host, t, kind, payload in events:
            pl = np.zeros(PAYLOAD_WORDS, np.int32)
            pl[: len(payload)] = payload
            rows = np.array([host])
            self._push(rows, np.array([t]), pack_order(1, rows, self.seq[rows]),
                       np.array([kind]), pl[None])
            self.seq[host] += 1
        self.rounds = 0
        self.microsteps = 0

    # ---- queue ---------------------------------------------------------

    def _push(self, rows, t, order, kind, payload):
        """Insert one event on each of `rows` (distinct); a full queue drops."""
        free = self.qt[rows] == TIME_MAX
        has = free.any(axis=1)
        self.dropped[rows[~has]] += 1
        rows, col = rows[has], free[has].argmax(axis=1)
        self.qt[rows, col] = t[has]
        self.qo[rows, col] = order[has]
        self.qk[rows, col] = kind[has]
        self.qp[rows, col] = payload[has]
        self._note_occupancy(rows)

    def _note_occupancy(self, rows):
        if rows.size:
            occ = (self.qt[rows] != TIME_MAX).sum(axis=1).max()
            self.q_hwm = max(self.q_hwm, int(occ))

    def _pop(self, window_end):
        """Each host's earliest (time, order) event before `window_end`."""
        tmin = self.qt.min(axis=1)
        rows = np.nonzero(tmin < window_end)[0]
        if rows.size == 0:
            return None
        t = tmin[rows]
        cand = self.qt[rows] == t[:, None]
        col = np.where(cand, self.qo[rows], ORDER_MAX).argmin(axis=1)
        order = self.qo[rows, col]
        kind = self.qk[rows, col]
        payload = self.qp[rows, col].copy()
        self.qt[rows, col] = TIME_MAX
        self.qo[rows, col] = ORDER_MAX
        return rows, t, order, kind, payload

    # ---- one microstep ---------------------------------------------------

    def _microstep(self, window_end, staged, sent_round) -> bool:
        popped = self._pop(window_end)
        if popped is None:
            return False
        rows, t, order, kind, payload = popped
        self.events[rows] += 1
        with np.errstate(over="ignore"):
            x = (t.astype(np.uint64) * MIX1) ^ (kind.astype(np.uint64) * MIX2) ^ order.astype(
                np.uint64
            )
            self.digest[rows] = (self.digest[rows] ^ x) * FNV_PRIME

        is_pkt = (kind & KIND_PKT) != 0
        dispatch = np.ones(rows.size, bool)
        ingress = is_pkt & ((kind & KIND_INGRESS_DONE) == 0)
        if ingress.any():
            ri, ti = rows[ingress], t[ingress]
            bits = payload[ingress, SIZE_WORD].astype(np.int64) * 8
            sojourn = self.ing.depart(ri, ti, bits)[0] - ti
            drop = self.codel.on_packet(ri, ti, sojourn)
            self.counters["pkts_codel_dropped"][ri[drop]] += 1
            keep = ~drop
            ri, ti, bits = ri[keep], ti[keep], bits[keep]
            dep = self.ing.charge(ri, ti, bits)
            later = dep > ti
            sel = np.nonzero(ingress)[0]
            dispatch[sel[drop]] = False
            lsel = sel[keep][later]
            dispatch[lsel] = False
            # shaped: requeued past the bucket with the same order key
            self._push(rows[lsel], dep[later], order[lsel],
                       kind[lsel] | KIND_INGRESS_DONE, payload[lsel])
        self.counters["pkts_delivered"][rows[dispatch & is_pkt]] += 1

        rows, t, kind, payload, order, is_pkt = (
            a[dispatch] for a in (rows, t, kind, payload, order, is_pkt)
        )
        if rows.size == 0:
            return True
        ctx = Ctx(
            t=t, kind=kind & KIND_MASK, payload=payload,
            is_packet=is_pkt, src=(order >> SRC_SHIFT) & SRC_MASK, host_id=rows,
            state={k: v[rows] for k, v in self.state.items()},
            params={k: v[rows] for k, v in self.params.items()},
            rng=self.rng[rows],
        )
        state, rng, pushes, sends = self.model.handle(ctx)
        for k, v in state.items():
            self.state[k][rows] = v

        for mask, t_req, pkind, ppay in pushes:
            m = np.nonzero(mask)[0]
            r = rows[m]
            self.counters["monotonic_violations"][r[t_req[m] < t[m]]] += 1
            self._push(r, np.maximum(t_req[m], t[m]), pack_order(1, r, self.seq[r]),
                       pkind[m].astype(np.int64) & KIND_MASK, ppay[m])
            self.seq[r] += 1

        for s in sends:
            counts = np.where(s["mask"], s.get("count", 1), 0)
            for j in range(int(s.get("count_max", 1))):
                mask = counts > j
                rng, u = rng_uniform(rng, mask)  # the path-loss draw
                m = np.nonzero(mask)[0]
                if m.size == 0:
                    continue
                r, tm = rows[m], t[m]
                self.counters["pkts_sent"][r] += 1
                sorder = pack_order(0, r, self.seq[r])
                self.seq[r] += 1
                over = sent_round[r] >= self.budget
                size = s["size"][m].astype(np.int64)
                depart = tm.copy()
                ok_b = np.nonzero(~over)[0]
                depart[ok_b] = self.eg.charge(r[ok_b], tm[ok_b], size[ok_b] * 8)
                dst = s["dst"][m].astype(np.int64)
                bad = (dst < 0) | (dst >= self.h)
                lat = self.lat[self.node_of[r], self.node_of[np.clip(dst, 0, self.h - 1)]]
                unreach = (lat < 0) | bad
                self.counters["pkts_unreachable"][r[unreach]] += 1
                # no configuration here sets a path loss: nothing is lost
                budget_drop = ~unreach & over
                self.counters["pkts_budget_dropped"][r[budget_drop]] += 1
                ok = ~unreach & ~over
                sent_round[r[ok]] += 1
                pay = s["payload"][m].copy()
                if j > 0:
                    pay = pay + j * s["payload_inc"][m]
                pay[:, SIZE_WORD] = s["size"][m]
                arrive = np.maximum(depart + np.maximum(lat, 0), window_end)
                staged.append((dst[ok], arrive[ok], sorder[ok],
                               s["kind"][m][ok].astype(np.int64) | KIND_PKT, pay[ok]))
        self.rng[rows] = rng
        return True

    # ---- the exchange at the end of a round -----------------------------

    def _merge(self, staged):
        if not staged:
            return
        dst, t, order, kind, pay = (np.concatenate(a) for a in zip(*staged))
        idx = np.lexsort((order, t, dst))
        dst, t, order, kind, pay = dst[idx], t[idx], order[idx], kind[idx], pay[idx]
        start = np.searchsorted(dst, dst, side="left")
        rank = np.arange(dst.size) - start
        free = (self.qt == TIME_MAX)
        nfree = free.sum(axis=1)
        take = rank < np.minimum(self.r_cap, nfree[dst])
        np.add.at(self.dropped, dst[~take], 1)
        dst, t, order, kind, pay, rank = (a[take] for a in (dst, t, order, kind, pay, rank))
        cols = np.argsort(~free, axis=1, kind="stable")  # free slots first
        col = cols[dst, rank]
        self.qt[dst, col] = t
        self.qo[dst, col] = order
        self.qk[dst, col] = kind
        self.qp[dst, col] = pay
        self._note_occupancy(np.unique(dst))

    def run(self) -> Result:
        while True:
            gmin = int(self.qt.min())
            if gmin >= self.stop:
                break
            window_end = min(gmin + self.runahead, self.stop)
            staged: list = []
            sent_round = np.zeros(self.h, np.int64)
            steps = 0
            while steps < self.limit and self._microstep(window_end, staged, sent_round):
                steps += 1
            self.microsteps += steps
            self.rounds += 1
            self.sends_hwm = max(self.sends_hwm, int(sent_round.max()))
            self._merge(staged)
        return Result(
            digest=self.digest, events=self.events, dropped=self.dropped,
            rounds=self.rounds, microsteps=self.microsteps, model=self.state,
            counters=self.counters, q_hwm=self.q_hwm, sends_hwm=self.sends_hwm,
        )
