"""Plain reference of the `tgen_tcp` model: bulk TCP transfers between
device-modelled endpoints, written from the model's documented semantics.

Each host runs one client and one server. In phase k client i sends a
flow of `flow_segs` segments to peer (i + 1 + k mod (H - 1)) mod H; after
the FIN-ACK it waits `flow_gap` and starts the next phase, `flows` phases
in all. Sequence numbers count whole segments; a DATA segment is mss + 40
bytes on the wire and every control segment 40. Every segment carries the
flow's phase, and a segment of another phase or peer is ignored.

Client (per flow): SYN, then SYN-ACK opens the connection; Reno (RFC 5681)
with cwnd in 1/1024 segments, slow start by the segments an ACK covers,
congestion avoidance by 1/cwnd, three duplicate ACKs trigger fast
retransmit (ssthresh = max(flight / 2, 2), cwnd = ssthresh + 3), and each
further duplicate ACK inflates cwnd by one; NewReno (RFC 6582): a partial
ACK retransmits the segment it names and keeps recovery, an ACK reaching
`recover` ends it with cwnd = ssthresh; cwnd stays in [1, cwnd_cap]
(cwnd_cap stands for the peer's advertised window). RTT (RFC 6298): one
segment timed at a time, never a retransmitted one (Karn), rto = srtt +
max(1 ms, 4 rttvar) in [rto_min, rto_max], doubled on each timeout. An
expired RTO resends the SYN or FIN, or goes back to snd_una with cwnd 1.
A TX event sends up to `tx_batch` new segments in one burst and queues the
next TX event while the window allows more. One lazy RTO event a host
chases the deadline (set to t + rto on progress, cleared when nothing is
outstanding or the flow ends).

Server: a SYN is accepted only while listening; a SYN for the connection it
already holds is answered again (its SYN-ACK was lost); a SYN from another
client while busy is dropped, and that client retries on its RTO. DATA is
reassembled in a 32-segment window (a bitmap of the segments buffered
beyond rcv_nxt); out-of-order and duplicate segments are acked at once,
in-order ones at least every second segment, and at once when they fill a
hole (RFC 1122 delayed ACK, held at most `delack`, 0 disables it). ACKs
carry rcv_nxt and the bitmap. A FIN is accepted when every segment is in;
a FIN to a listening server (our FIN-ACK was lost) is answered statelessly.

The model draws nothing at random: its state never reads the RNG lanes.
Each row below is one host's one event; the rows are split by what the
event is, and each kind is handled on its own. This file imports nothing
of the program.
"""

from __future__ import annotations

import numpy as np

from reference.engine import PAYLOAD_WORDS, TIME_MAX, Reference, time_ns

KIND_TICK, KIND_SEG, KIND_TX, KIND_RTO, KIND_DELACK = 0, 1, 2, 3, 4
FT_SYN, FT_SYNACK, FT_DATA, FT_ACK, FT_FIN, FT_FINACK = 1, 2, 3, 4, 5, 6
PW_SEQ, PW_ACK, PW_META = 1, 2, 3  # payload words; word 0 is the wire size
IDLE, SYN_SENT, ESTABLISHED, FIN_SENT, DONE = 0, 1, 2, 3, 4
HDR_BYTES = 40
ONE = 1 << 10  # one segment of cwnd / ssthresh
NEVER = TIME_MAX
WINDOW = 32  # reassembly window beyond rcv_nxt, in segments

# model_args and their defaults (times in ms where no unit is given)
COUNTS = {"flow_segs": 64, "mss": 1460, "flows": 1, "cwnd_init": 2, "cwnd_cap": 16}
TIMES = {"rto_init": "1 s", "rto_min": "200 ms", "rto_max": "60 s",
         "flow_gap": "10 ms", "delack": "40 ms"}


def trailing_ones(x: np.ndarray) -> np.ndarray:
    """How many of the low bits of each u32 are set before the first clear one."""
    out = np.zeros(x.shape, np.int32)
    odd = np.nonzero(x & np.uint32(1))[0]
    bits = (x[odd, None] >> np.arange(32, dtype=np.uint32)) & np.uint32(1)
    out[odd] = np.cumprod(bits, axis=1).sum(axis=1)
    return out


class Emissions:
    """What the rows' events emit: the TX push (port A), the timer push
    (port B) and one wire segment a row, as the engine takes them."""

    def __init__(self, n: int):
        self.tx = np.zeros(n, bool)
        self.b_mask = np.zeros(n, bool)
        self.b_t = np.zeros(n, np.int64)
        self.b_kind = np.zeros(n, np.int64)
        self.send = np.zeros(n, bool)
        self.dst = np.zeros(n, np.int64)
        self.size = np.full(n, HDR_BYTES, np.int32)
        self.count = np.ones(n, np.int32)
        self.payload = np.zeros((n, PAYLOAD_WORDS), np.int32)

    def segment(self, rows, ftype, dst, phase, seq=0, ack=0, size=HDR_BYTES, count=1):
        self.send[rows] = True
        self.dst[rows] = dst
        self.size[rows] = size
        self.count[rows] = count
        self.payload[rows, PW_SEQ] = seq
        self.payload[rows, PW_ACK] = ack
        self.payload[rows, PW_META] = ftype | (phase << 8)

    def push_b(self, rows, t, kind):
        self.b_mask[rows] = True
        self.b_t[rows] = t
        self.b_kind[rows] = kind


class TgenTcp:
    def build(self, hosts, seed):
        h = len(hosts)
        if h < 2:
            raise ValueError("tgen_tcp needs at least 2 hosts")
        for x in hosts:
            unknown = set(x["model_args"]) - set(COUNTS) - set(TIMES) - {"tx_batch"}
            if unknown:
                raise ValueError(f"tgen_tcp args {sorted(unknown)}")
        batches = {int(x["model_args"].get("tx_batch", 4)) for x in hosts}
        if len(batches) != 1:
            raise ValueError("tgen_tcp: one tx_batch for every host")
        self.tx_batch = max(batches.pop(), 1)
        params = {
            k: np.array([int(x["model_args"].get(k, d)) for x in hosts], np.int32)
            for k, d in COUNTS.items()
        }
        params.update({
            k: np.array([time_ns(x["model_args"].get(k, d), "ms") for x in hosts], np.int64)
            for k, d in TIMES.items()
        })
        if (params["flow_segs"] < 1).any():
            raise ValueError("tgen_tcp: flow_segs must be >= 1")
        self.num_hosts = h

        def lane(value, dtype):
            return np.full(h, value, dtype)

        i32, i64 = np.int32, np.int64
        state = {
            # client
            "c_state": lane(IDLE, i32), "c_phase": lane(0, i32), "c_peer": lane(0, i32),
            "snd_una": lane(0, i32), "snd_nxt": lane(0, i32),
            "cwnd_x": lane(ONE, i32), "ssth_x": lane(0x7FFFFFFF, i32),
            "dup": lane(0, i32), "recover": lane(-1, i32),
            "srtt": lane(0, i64), "rttvar": lane(0, i64), "rto": params["rto_init"].copy(),
            "rtt_seq": lane(-1, i32), "rtt_t0": lane(0, i64),
            "deadline": lane(NEVER, i64), "timer_alive": lane(False, bool),
            "tx_alive": lane(False, bool), "flow_t0": lane(0, i64),
            # server
            "sv_state": lane(0, i32), "sv_peer": lane(0, i32), "sv_phase": lane(0, i32),
            "rcv_nxt": lane(0, i32), "sv_bm": lane(0, np.uint32),
            "da_pend": lane(False, bool), "da_t": lane(NEVER, i64), "da_alive": lane(False, bool),
            # counters
            "d_sent": lane(0, i64), "d_rtx": lane(0, i64), "fast_rtx": lane(0, i64),
            "timeouts": lane(0, i64), "flows_done": lane(0, i64), "flow_rtx0": lane(0, i64),
            "fct_sum": lane(0, i64), "segs_rcvd": lane(0, i64), "dup_segs": lane(0, i64),
            "bytes_rcvd": lane(0, i64), "done_t": lane(0, i64),
        }
        events = [(x["host_id"], x["start_time"], KIND_TICK, ())
                  for x in hosts if int(x["model_args"].get("flows", 1)) > 0]
        return params, state, events

    # ------------------------------------------------------------------ #

    def handle(self, ctx):
        n = ctx.t.size
        s = {k: v.copy() for k, v in ctx.state.items()}
        p = ctx.params
        t = ctx.t
        src = ctx.src.astype(np.int32)
        ftype = ctx.payload[:, PW_META] & 0xFF
        phase = ctx.payload[:, PW_META] >> 8
        out = Emissions(n)
        w = dict(t=t, src=src, ftype=ftype, phase=phase,
                 seq=ctx.payload[:, PW_SEQ], ack=ctx.payload[:, PW_ACK],
                 size=ctx.payload[:, 0])
        seg = ctx.is_packet & (ctx.kind == KIND_SEG)
        delack = ~ctx.is_packet & (ctx.kind == KIND_DELACK)

        def rows(mask):
            return np.nonzero(mask)[0]

        def local(kind):
            return rows(~ctx.is_packet & (ctx.kind == kind))

        # what re-arms the RTO deadline, and what clears it, on this event
        rearm = np.zeros(n, bool)
        clear = np.zeros(n, bool)

        self._server_syn(s, rows(seg & (ftype == FT_SYN)), w, out)
        self._server_data(s, p, rows(seg & (ftype == FT_DATA)), w, out)
        self._server_fin(s, rows(seg & (ftype == FT_FIN)), w, out)
        self._delack(s, rows(delack), t, out)

        mine = seg & (src == s["c_peer"]) & (phase == s["c_phase"])
        synack = rows(mine & (ftype == FT_SYNACK) & (s["c_state"] == SYN_SENT))
        s["c_state"][synack] = ESTABLISHED
        rearm[synack] = True
        acks = rows(mine & (ftype == FT_ACK) & (s["c_state"] == ESTABLISHED))
        moved = self._client_ack(s, p, acks, w, out, rearm, clear)
        # the window is held to [1, cwnd_cap] segments at every event
        s["cwnd_x"] = np.clip(s["cwnd_x"], ONE, p["cwnd_cap"] << 10)
        self._client_finack(
            s, p, rows(mine & (ftype == FT_FINACK) & (s["c_state"] == FIN_SENT)), t, out, clear)
        self._client_tick(s, p, local(KIND_TICK), ctx, out, rearm)
        self._client_tx(s, p, local(KIND_TX), t, out, rearm)
        self._client_rto(s, p, local(KIND_RTO), t, out, rearm, clear)

        # the deadline: cleared, or restarted from this event at the rto
        s["deadline"] = np.where(clear, NEVER, np.where(rearm, t + s["rto"], s["deadline"]))
        # restart the TX chain where the window opened and none is queued
        opened = np.zeros(n, bool)
        opened[synack] = True
        opened[moved] = True
        can_send = (
            (s["c_state"] == ESTABLISHED)
            & (s["snd_nxt"] < s["snd_una"] + (s["cwnd_x"] >> 10))
            & (s["snd_nxt"] < p["flow_segs"])
        )
        restart = opened & can_send & ~s["tx_alive"]
        s["tx_alive"] |= restart
        out.tx |= restart
        # a live deadline with no RTO event queued queues one (a DELACK
        # event touches neither). Port B takes one push an event: the next
        # flow's TICK, else the delayed-ACK timer, else the RTO chain
        arm = ~delack & (s["deadline"] != NEVER) & ~s["timer_alive"]
        s["timer_alive"] |= arm
        free = arm & ~out.b_mask
        out.push_b(rows(free), s["deadline"][free], KIND_RTO)

        pushes = [
            (out.tx, t.copy(), np.full(n, KIND_TX), np.zeros((n, PAYLOAD_WORDS), np.int32)),
            (out.b_mask, out.b_t, out.b_kind, np.zeros((n, PAYLOAD_WORDS), np.int32)),
        ]
        inc = np.zeros((n, PAYLOAD_WORDS), np.int32)
        inc[:, PW_SEQ] = 1  # segment k of a burst carries seq + k
        send = {"mask": out.send, "dst": out.dst, "size": out.size,
                "kind": np.full(n, KIND_SEG), "payload": out.payload,
                "count": out.count, "count_max": self.tx_batch, "payload_inc": inc}
        return s, ctx.rng, pushes, [send]

    # ---- server ---------------------------------------------------------

    @staticmethod
    def _same_conn(s, r, w):
        return (s["sv_peer"][r] == w["src"][r]) & (s["sv_phase"][r] == w["phase"][r])

    def _server_syn(self, s, r, w, out):
        listening = s["sv_state"][r] == 0
        new = r[listening]
        again = r[~listening & self._same_conn(s, r, w)]
        s["sv_state"][new] = 1
        s["sv_peer"][new] = w["src"][new]
        s["sv_phase"][new] = w["phase"][new]
        s["rcv_nxt"][new] = 0
        s["sv_bm"][new] = 0
        s["da_pend"][new] = False
        both = np.concatenate([new, again])
        out.segment(both, FT_SYNACK, w["src"][both], w["phase"][both])

    def _server_data(self, s, p, r, w, out):
        r = r[(s["sv_state"][r] == 1) & self._same_conn(s, r, w)]
        rel = w["seq"][r] - s["rcv_nxt"][r]
        ahead = r[(rel > 0) & (rel <= WINDOW)]
        stale = r[(rel < 0) | (rel > WINDOW)]
        in_order = r[rel == 0]
        payload_bytes = (w["size"] - HDR_BYTES).astype(np.int64)

        # beyond rcv_nxt inside the window: buffered
        bit = (w["seq"][ahead] - s["rcv_nxt"][ahead] - 1).astype(np.uint32)
        s["sv_bm"][ahead] |= np.uint32(1) << bit
        # already received, or beyond the window: counted, dropped
        s["dup_segs"][stale] += 1
        # in order: rcv_nxt passes it and the buffered run behind it
        filled_hole = s["sv_bm"][in_order] != 0
        step = 1 + trailing_ones(s["sv_bm"][in_order])
        s["rcv_nxt"][in_order] += step
        s["sv_bm"][in_order] = np.where(
            step >= 32, np.uint32(0), s["sv_bm"][in_order] >> np.minimum(step, 31).astype(np.uint32))
        got = np.concatenate([ahead, in_order])
        s["segs_rcvd"][got] += 1
        s["bytes_rcvd"][got] += payload_bytes[got]

        # delayed ACK: an in-order segment is held if none is, the timer is
        # on and it filled no hole; out-of-order and stale ones ack at once
        now = (s["da_pend"][in_order] | (p["delack"][in_order] == 0) | filled_hole)
        held = in_order[~now]
        s["da_pend"][held] = True
        s["da_t"][held] = w["t"][held] + p["delack"][held]
        start_timer = held[~s["da_alive"][held]]
        s["da_alive"][start_timer] = True
        out.push_b(start_timer, s["da_t"][start_timer], KIND_DELACK)
        self._ack(s, np.concatenate([ahead, stale, in_order[now]]), out)

    def _server_fin(self, s, r, w, out):
        established = s["sv_state"][r] == 1
        close = r[established & self._same_conn(s, r, w) & (s["rcv_nxt"][r] == w["seq"][r])]
        stateless = r[s["sv_state"][r] == 0]
        s["sv_state"][close] = 0
        s["rcv_nxt"][close] = 0
        s["sv_bm"][close] = 0
        s["da_pend"][close] = False
        both = np.concatenate([close, stateless])
        out.segment(both, FT_FINACK, w["src"][both], w["phase"][both])

    def _delack(self, s, r, t, out):
        """The delayed-ACK timer event: fire at da_t, or queue itself again
        for a later da_t; with no ACK held it ends."""
        held = r[s["da_pend"][r]]
        due = held[t[held] >= s["da_t"][held]]
        later = held[t[held] < s["da_t"][held]]
        s["da_alive"][r] = False
        s["da_alive"][later] = True
        out.push_b(later, s["da_t"][later], KIND_DELACK)
        self._ack(s, due, out)

    @staticmethod
    def _ack(s, r, out):
        s["da_pend"][r] = False
        out.segment(r, FT_ACK, s["sv_peer"][r], s["sv_phase"][r],
                    seq=s["sv_bm"][r].astype(np.int32), ack=s["rcv_nxt"][r])

    # ---- client ---------------------------------------------------------

    def _client_ack(self, s, p, r, w, out, rearm, clear):
        """Reno with NewReno recovery; returns the rows whose ACK may open
        the window (new and duplicate ACKs)."""
        t = w["t"]
        ackno = w["ack"]
        una = s["snd_una"][r]
        new = r[ackno[r] > una]
        dup = r[(ackno[r] == una) & (s["snd_nxt"][r] > una)]

        # RTT sample from the timed segment (RFC 6298 2.2-2.3)
        timed = new[(s["rtt_seq"][new] >= 0) & (ackno[new] > s["rtt_seq"][new])]
        sample = t[timed] - s["rtt_t0"][timed]
        srtt, rttvar = s["srtt"][timed], s["rttvar"][timed]
        first = srtt == 0
        s["rttvar"][timed] = np.where(first, sample // 2, (3 * rttvar + np.abs(srtt - sample)) // 4)
        s["srtt"][timed] = np.where(first, sample, (7 * srtt + sample) // 8)
        s["rto"][timed] = np.clip(
            s["srtt"][timed] + np.maximum(1_000_000, 4 * s["rttvar"][timed]),
            p["rto_min"][timed], p["rto_max"][timed])
        s["rtt_seq"][timed] = -1

        # new ACK
        recovering = s["recover"][new] >= 0
        full = new[recovering & (ackno[new] >= s["recover"][new])]
        partial = new[recovering & (ackno[new] < s["recover"][new])]
        normal = new[~recovering]
        s["cwnd_x"][full] = s["ssth_x"][full]
        s["recover"][full] = -1
        cwnd = s["cwnd_x"][normal]
        slow = cwnd < s["ssth_x"][normal]
        s["cwnd_x"][normal] = np.where(
            slow, cwnd + ((ackno[normal] - s["snd_una"][normal]) << 10),
            cwnd + (1 << 20) // np.maximum(cwnd, 1))
        s["dup"][new] = 0
        s["snd_una"][new] = ackno[new]
        # NewReno partial ACK: retransmit the segment it names
        s["rtt_seq"][partial] = -1
        self._retransmit(s, p, partial, ackno[partial], out)
        # every segment acked: FIN
        fin = new[s["snd_una"][new] >= p["flow_segs"][new]]
        s["c_state"][fin] = FIN_SENT
        out.segment(fin, FT_FIN, s["c_peer"][fin], s["c_phase"][fin], seq=p["flow_segs"][fin])

        # duplicate ACK
        s["dup"][dup] += 1
        in_recovery = s["recover"][dup] >= 0
        s["cwnd_x"][dup[in_recovery]] += ONE
        fast = dup[~in_recovery & (s["dup"][dup] == 3)]
        flight = s["snd_nxt"][fast] - s["snd_una"][fast]
        s["ssth_x"][fast] = np.maximum((flight << 10) // 2, 2 << 10)
        s["cwnd_x"][fast] = s["ssth_x"][fast] + (3 << 10)
        s["recover"][fast] = s["snd_nxt"][fast]
        s["rtt_seq"][fast] = -1
        s["fast_rtx"][fast] += 1
        self._retransmit(s, p, fast, s["snd_una"][fast], out)

        rearm[new] = True
        # nothing outstanding: the deadline is cleared (any ACK)
        quiet = r[(s["c_state"][r] == ESTABLISHED) & (s["snd_nxt"][r] == s["snd_una"][r])]
        clear[quiet] = True
        return np.concatenate([new, dup])

    @staticmethod
    def _retransmit(s, p, r, seq, out):
        s["d_rtx"][r] += 1
        out.segment(r, FT_DATA, s["c_peer"][r], s["c_phase"][r], seq=seq,
                    size=p["mss"][r] + HDR_BYTES)

    def _client_finack(self, s, p, r, t, out, clear):
        s["flows_done"][r] += 1
        s["fct_sum"][r] += t[r] - s["flow_t0"][r]
        s["c_phase"][r] += 1
        more = s["c_phase"][r] < p["flows"][r]
        s["c_state"][r] = np.where(more, IDLE, DONE)
        last = r[~more]
        s["done_t"][last] = t[last]
        clear[r] = True
        nxt = r[more]
        out.push_b(nxt, t[nxt] + p["flow_gap"][nxt], KIND_TICK)

    def _client_tick(self, s, p, r, ctx, out, rearm):
        r = r[(s["c_state"][r] == IDLE) & (s["c_phase"][r] < p["flows"][r])]
        k = s["c_phase"][r]
        h = self.num_hosts
        peer = ((ctx.host_id[r] + 1 + k % (h - 1)) % h).astype(np.int32)
        s["c_peer"][r] = peer
        s["c_state"][r] = SYN_SENT
        s["snd_una"][r] = 0
        s["snd_nxt"][r] = 0
        s["cwnd_x"][r] = p["cwnd_init"][r] << 10
        s["ssth_x"][r] = 0x7FFFFFFF
        s["dup"][r] = 0
        s["recover"][r] = -1
        s["srtt"][r] = 0
        s["rttvar"][r] = 0
        s["rto"][r] = p["rto_init"][r]
        s["rtt_seq"][r] = -1
        s["flow_t0"][r] = ctx.t[r]
        s["flow_rtx0"][r] = s["d_rtx"][r]
        rearm[r] = True
        out.segment(r, FT_SYN, peer, k)

    def _client_tx(self, s, p, r, t, out, rearm):
        s["tx_alive"][r] = False
        r = r[s["c_state"][r] == ESTABLISHED]
        limit = np.minimum(s["snd_una"][r] + (s["cwnd_x"][r] >> 10), p["flow_segs"][r])
        burst = np.clip(limit - s["snd_nxt"][r], 0, self.tx_batch)
        go = burst > 0
        r, limit, burst = r[go], limit[go], burst[go]
        first = s["snd_nxt"][r].copy()
        s["snd_nxt"][r] += burst
        s["d_sent"][r] += burst
        # time the burst's first segment unless one is timed already
        untimed = s["rtt_seq"][r] < 0
        s["rtt_seq"][r[untimed]] = first[untimed]
        s["rtt_t0"][r[untimed]] = t[r[untimed]]
        more = r[s["snd_nxt"][r] < limit]
        s["tx_alive"][more] = True
        out.tx[more] = True
        rearm[r] = True
        out.segment(r, FT_DATA, s["c_peer"][r], s["c_phase"][r], seq=first,
                    size=p["mss"][r] + HDR_BYTES, count=burst)

    def _client_rto(self, s, p, r, t, out, rearm, clear):
        armed = s["deadline"][r] != NEVER
        dead = r[~armed]
        s["timer_alive"][dead] = False
        clear[dead] = True
        early = r[armed & (t[r] < s["deadline"][r])]
        out.push_b(early, s["deadline"][early], KIND_RTO)
        expired = r[armed & (t[r] >= s["deadline"][r])]

        cs = s["c_state"][expired]
        syn = expired[cs == SYN_SENT]
        fin = expired[cs == FIN_SENT]
        data = expired[(cs == ESTABLISHED) & (s["snd_nxt"][expired] > s["snd_una"][expired])]
        out.segment(syn, FT_SYN, s["c_peer"][syn], s["c_phase"][syn])
        out.segment(fin, FT_FIN, s["c_peer"][fin], s["c_phase"][fin], seq=p["flow_segs"][fin])
        # go back to snd_una with one segment of window
        flight = s["snd_nxt"][data] - s["snd_una"][data]
        s["ssth_x"][data] = np.maximum((flight << 10) // 2, 2 << 10)
        s["cwnd_x"][data] = ONE
        s["snd_nxt"][data] = s["snd_una"][data] + 1
        s["dup"][data] = 0
        s["recover"][data] = -1
        s["rtt_seq"][data] = -1
        self._retransmit(s, p, data, s["snd_una"][data], out)

        timeout = np.concatenate([syn, fin, data])
        s["timeouts"][timeout] += 1
        s["rto"][timeout] = np.minimum(s["rto"][timeout] * 2, p["rto_max"][timeout])
        rearm[timeout] = True
        out.push_b(expired, t[expired] + s["rto"][expired], KIND_RTO)


def reference(shadow: dict, **kw) -> Reference:
    return Reference(shadow, TgenTcp(), **kw)
