"""Simulation checkpoint / resume.

The reference has none (SURVEY.md §5.4 — a dead manager is a dead
simulation; determinism-as-reproducibility is its only recovery story).
On TPU the entire simulation state is a pytree of device arrays, so
snapshotting is a flatten + savez; this is a genuine capability the
rebuild adds on top of reference parity.

Format: one .npz with the flattened SimState leaves plus a guard record
(engine-config fingerprint + treedef repr + model-param digest) so
restoring into a mismatched simulation build fails loudly instead of
corrupting silently. Hybrid checkpoints add the bridge's CPU half.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np


class CheckpointError(Exception):
    pass


def snapshot_state(state):
    """Cheap in-memory pre-chunk snapshot: a device-resident copy of every
    leaf. The full .npz checkpoint path (below) is for durability; this is
    the gear replay loop's working copy — the jitted chunk DONATES its
    input buffers, so a plain reference to the pre-chunk pytree would be
    invalidated by the dispatch. `jnp.copy` stays on device (no host
    round-trip) and copies only HBM-to-HBM, microseconds against a
    multi-round chunk; no guard record is needed because the snapshot
    never leaves this process or this engine build."""
    return jax.tree.map(jnp.copy, state)


def restore_snapshot(snap):
    """A fresh donation-safe copy of a `snapshot_state` result. The copy
    (rather than the snapshot itself) is handed to the replay dispatch so
    the snapshot survives — a replay at a mid-ladder gear can shed again
    and need yet another restore. Traced as `shadow.restore`: the
    per-leaf device copies sit between two chunks' dispatches."""
    from shadow_tpu.obs.runtime import span_or_null

    with span_or_null(None, "restore"):
        return snapshot_state(snap)


def _params_digest(params) -> str:
    """Digest of the model/routing parameter leaves: same-shaped states
    driven by DIFFERENT params (model_args, graph latencies) must not
    pass the guard. The derived routing rows are excluded — they are a
    deterministic function of node_of/lat/loss/jitter (already hashed)
    and can reach hundreds of MB."""
    if hasattr(params, "lat_rows"):  # EngineParams
        params = params._replace(
            lat_rows=None, loss_rows=None, jit_rows=None
        )
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(params):
        h.update(np.ascontiguousarray(jax.device_get(leaf)).tobytes())
    return h.hexdigest()


def _dump_leaves(state) -> tuple[dict, object]:
    leaves, treedef = jax.tree_util.tree_flatten(state)
    arrays = {
        f"leaf_{i}": np.asarray(jax.device_get(x))
        for i, x in enumerate(leaves)
    }
    return arrays, treedef


def _restore_leaves(data, state, engine=None):
    """Validate the stored leaves against `state`'s tree and rebuild it,
    re-sharding onto the engine's mesh when present (ensemble restores
    pass engine=None: world=1, no mesh to re-shard onto)."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    new_leaves = []
    for i in range(len(leaves)):
        arr = data[f"leaf_{i}"]
        ref = leaves[i]
        if arr.shape != ref.shape or arr.dtype != np.asarray(ref).dtype:
            raise CheckpointError(f"leaf {i}: shape/dtype mismatch")
        new_leaves.append(jnp.asarray(arr))
    out = jax.tree_util.tree_unflatten(treedef, new_leaves)
    out = _refresh_queue_caches(out)
    if engine is not None and engine.mesh is not None:
        specs = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(engine.mesh, s),
            engine.state_specs(),
        )
        out = jax.device_put(out, specs)
    return out


def _refresh_queue_caches(state):
    """Checkpoint restore is a block-cache REBUILD point (like the exchange
    merge): a bucketed queue's (bt, bo, bfill) minima are derived state, so
    they are recomputed from the restored slab rather than trusted from the
    file — a hand-edited or bit-rotted .npz can desynchronize the caches but
    never the simulation. Ensemble states carry a leading replica axis on
    every plane; the rebuild vmaps over it (same derivation per replica)."""
    from shadow_tpu.ops.events import BucketQueue, bucket_rebuild

    q = getattr(state, "queue", None)
    if isinstance(q, BucketQueue):
        if q.t.ndim == 3:  # [R, H, C]: stacked ensemble queue
            block = q.t.shape[2] // q.bt.shape[2]
            state = state._replace(
                queue=jax.vmap(lambda qq: bucket_rebuild(qq, block))(q)
            )
        else:
            state = state._replace(queue=bucket_rebuild(q, q.block))
    # the timer wheel IS the BucketQueue machinery — same derived-cache
    # rule on restore (ops/wheel.py)
    w = getattr(state, "wheel", None)
    if isinstance(w, BucketQueue):
        if w.t.ndim == 3:
            block = w.t.shape[2] // w.bt.shape[2]
            state = state._replace(
                wheel=jax.vmap(lambda ww: bucket_rebuild(ww, block))(w)
            )
        else:
            state = state._replace(wheel=bucket_rebuild(w, w.block))
    return state


def _fingerprint(engine_cfg, treedef, params) -> str:
    """The full EngineConfig participates via asdict — so a checkpoint
    written under one `microstep_events` (or queue layout, exchange, ...)
    refuses to restore into a sim built with another. For K specifically
    this is stricter than strictly necessary (K>1 histories are
    bit-identical to K=1), but mid-simulation the PEEKED batch state is
    never part of SimState, so cross-K restores would be safe only by an
    argument the guard cannot check; refusing loudly is the contract."""
    blob = json.dumps(
        {
            "cfg": dataclasses.asdict(engine_cfg),
            "treedef": str(treedef),
            "params": _params_digest(params),
        },
        sort_keys=True,
    )
    return hashlib.sha256(blob.encode()).hexdigest()


# shape-defining EngineConfig fields normalized out of the migration
# fingerprint: a checkpoint differing from the target sim ONLY in these
# (plus the derived auto-sizes) can be re-seated through the exactness-
# gated migration ops instead of refused. Everything else — model,
# params, policies, queue LAYOUT KIND (bucket-ness changes the treedef),
# mesh/world — still refuses loudly.
_MIGRATABLE_CFG_FIELDS = (
    "queue_capacity",
    "queue_block",
    "sends_per_host_round",
    "max_round_inserts",
    "microstep_limit",
    "a2a_block",
    # timer-wheel shape (ops/wheel.py): slots/block migrate through the
    # same exactness-gated ops as the queue capacity. Wheel PRESENCE
    # (on vs off) changes the state treedef, which both fingerprints
    # carry — an on/off cross-restore still refuses loudly.
    "wheel_slots",
    "wheel_block",
)


def _migration_fingerprint(engine_cfg, treedef, params) -> str:
    """`_fingerprint` with the capacity-shape fields normalized to 0 —
    the secondary guard the cross-capacity restore path compares."""
    cfgd = dataclasses.asdict(engine_cfg)
    for f in _MIGRATABLE_CFG_FIELDS:
        cfgd[f] = 0
    blob = json.dumps(
        {
            "cfg": cfgd,
            "treedef": str(treedef),
            "params": _params_digest(params),
        },
        sort_keys=True,
    )
    return hashlib.sha256(blob.encode()).hexdigest()


def _state_shape_meta(state) -> dict:
    """The state's actual capacity shapes — recorded at save time so a
    checkpoint written MID-ESCALATION (pressure plane regrew the slab
    past the configured base) still restores: leaf shapes are validated
    against these, not the builder's config."""
    from shadow_tpu.ops.events import BucketQueue

    q = state.queue
    meta = {
        "queue_capacity": int(q.t.shape[-1]),
        "queue_block": int(q.block) if isinstance(q, BucketQueue) else 0,
        "sends_per_host_round": int(state.outbox.t.shape[-1]),
    }
    # wheel keys only when a wheel exists: wheel-off checkpoints keep the
    # pre-wheel meta byte-for-byte, so older checkpoints (no wheel keys)
    # still compare equal against wheel-off sims and load the exact path
    w = getattr(state, "wheel", None)
    if w is not None:
        meta["wheel_slots"] = int(w.t.shape[-1])
        meta["wheel_block"] = int(w.block)
    return meta


def _shaped_template(state, meta: dict):
    """`state`'s pytree with the queue/outbox planes re-shaped to the
    checkpoint's recorded capacity — the shape/dtype reference
    `_restore_leaves` validates stored leaves against."""
    from shadow_tpu.core.engine import make_empty_outbox
    from shadow_tpu.ops.events import (
        BucketQueue, make_bucket_queue, make_queue,
    )

    if (meta["queue_block"] > 0) != isinstance(state.queue, BucketQueue):
        raise CheckpointError(
            "checkpoint queue layout (flat vs bucketed) does not match "
            "this simulation; migration cannot cross layout kinds"
        )
    wheel_slots = int(meta.get("wheel_slots", 0))
    if (wheel_slots > 0) != (getattr(state, "wheel", None) is not None):
        raise CheckpointError(
            "checkpoint timer-wheel presence (on vs off) does not match "
            "this simulation; migration cannot cross the wheel boundary "
            "— rebuild with the same experimental.timer_wheel setting"
        )
    h = state.queue.t.shape[0]
    queue = (
        make_bucket_queue(h, meta["queue_capacity"], meta["queue_block"])
        if meta["queue_block"]
        else make_queue(h, meta["queue_capacity"])
    )
    outbox = make_empty_outbox(
        h, meta["sends_per_host_round"], state.outbox.count
    )
    state = state._replace(queue=queue, outbox=outbox)
    if wheel_slots:
        from shadow_tpu.ops.wheel import make_wheel

        state = state._replace(
            wheel=make_wheel(h, wheel_slots, int(meta.get("wheel_block", 0)))
        )
    return state


def _migrate_restored(state, sim):
    """Re-seat a source-shaped restored state at the target sim's shapes
    through the pressure plane's migration ops. Refuses (loudly) exactly
    when migration would lose information: live events that cannot fit
    the target capacity, or in-flight outbox entries (chunk-boundary
    checkpoints never carry any; anything else cannot re-seat)."""
    import jax.numpy as jnp

    from shadow_tpu.core.engine import make_empty_outbox
    from shadow_tpu.ops.events import migrate_queue, migration_fits
    from shadow_tpu.simtime import TIME_MAX

    cfg = sim.engine_cfg
    # under an escalate pressure policy a checkpoint written at a GROWN
    # shape resumes at that shape (shrinking just to re-escalate would
    # cost a refusal risk and replays for nothing); every other policy
    # gets exactly the configured shapes
    escalating = (
        getattr(getattr(sim.cfg, "pressure", None), "policy", "drop")
        == "escalate"
    )
    cap = int(state.queue.t.shape[-1])
    budget = int(state.outbox.t.shape[-1])
    target_cap = max(cfg.queue_capacity, cap) if escalating else (
        cfg.queue_capacity
    )
    target_budget = (
        max(cfg.sends_per_host_round, budget) if escalating
        else cfg.sends_per_host_round
    )
    if cap != target_cap or (
        getattr(state.queue, "block", 0) or 0
    ) != cfg.queue_block:
        if cap > target_cap and not bool(
            jnp.all(migration_fits(state.queue, target_cap))
        ):
            occ = int(jnp.max(jnp.sum(
                (state.queue.t != TIME_MAX).astype(jnp.int32), axis=-1
            )))
            raise CheckpointError(
                f"cannot resume at queue capacity {target_cap}: "
                f"the checkpoint holds up to {occ} live events per host "
                f"(written at capacity {cap}) — resume at >= {occ} slots"
            )
        state = state._replace(
            queue=migrate_queue(state.queue, target_cap, cfg.queue_block)
        )
    if budget != target_budget:
        if bool(jnp.any(state.outbox.t != TIME_MAX)):
            raise CheckpointError(
                "checkpoint carries in-flight outbox entries; a different "
                "send budget cannot re-seat them (this never happens for "
                "chunk-boundary checkpoints)"
            )
        state = state._replace(
            outbox=make_empty_outbox(
                state.outbox.t.shape[0], target_budget, state.outbox.count
            )
        )
    # timer wheel: same exactness-gated migration as the queue (slot
    # positions unobservable; live timers must fit the target). Presence
    # was already matched by _shaped_template / the treedef guard.
    w = getattr(state, "wheel", None)
    if w is not None:
        from shadow_tpu.ops.wheel import migrate_wheel, resolve_wheel_block

        target_slots = cfg.wheel_slots
        target_block = resolve_wheel_block(target_slots, cfg.wheel_block)
        if (
            int(w.t.shape[-1]) != target_slots
            or int(w.block) != target_block
        ):
            if int(w.t.shape[-1]) > target_slots and not bool(
                jnp.all(migration_fits(w, target_slots))
            ):
                occ = int(jnp.max(jnp.sum(
                    (w.t != TIME_MAX).astype(jnp.int32), axis=-1
                )))
                raise CheckpointError(
                    f"cannot resume at wheel_slots {target_slots}: the "
                    f"checkpoint holds up to {occ} live timers per host "
                    f"(written at {int(w.t.shape[-1])} slots) — resume "
                    f"at >= {occ} slots"
                )
            state = state._replace(
                wheel=migrate_wheel(w, target_slots, cfg.wheel_block)
            )
    if sim.engine.mesh is not None:
        specs = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(sim.engine.mesh, s),
            sim.engine.state_specs(),
        )
        state = jax.device_put(state, specs)
    return state


def save_checkpoint(path: str, sim) -> str:
    """Snapshot a `Simulation` (modeled sims; hybrid/mixed sims go through
    `save_checkpoint_hybrid`)."""
    arrays, treedef = _dump_leaves(sim.state)
    arrays["__guard__"] = np.frombuffer(
        _fingerprint(sim.engine_cfg, treedef, sim.params).encode(),
        dtype=np.uint8,
    )
    # cross-capacity restore metadata (pressure plane): the secondary
    # guard matches across capacity-shape config changes, and __shape__
    # records the state's ACTUAL shapes (escalation may have regrown
    # them past the configured base) so the loader can rebuild the
    # source template and migrate. Older checkpoints lack both and keep
    # loading through the exact path unchanged.
    arrays["__guard_migrate__"] = np.frombuffer(
        _migration_fingerprint(sim.engine_cfg, treedef, sim.params).encode(),
        dtype=np.uint8,
    )
    arrays["__shape__"] = np.frombuffer(
        json.dumps(_state_shape_meta(sim.state), sort_keys=True).encode(),
        dtype=np.uint8,
    )
    if not path.endswith(".npz"):
        path += ".npz"  # savez appends it anyway; return the real filename
    np.savez_compressed(path, **arrays)
    return path


def load_checkpoint(path: str, sim) -> None:
    """Restore state into a freshly built `Simulation`. The config must
    match exactly EXCEPT the capacity shapes (queue capacity/block, send
    budget — `_MIGRATABLE_CFG_FIELDS`): a checkpoint written at capacity
    C resumes into a sim built at C' through the pressure plane's
    exactness-gated migration ops, refusing only when migration is
    impossible (live events past C', in-flight outbox entries, or a
    queue-layout-kind change)."""
    data = np.load(path)
    _, treedef = jax.tree_util.tree_flatten(sim.state)
    want = _fingerprint(sim.engine_cfg, treedef, sim.params)
    got = bytes(data["__guard__"]).decode()
    meta = None
    if "__shape__" in data.files:
        meta = json.loads(bytes(data["__shape__"]).decode())
    if got == want and (
        meta is None or meta == _state_shape_meta(sim.state)
    ):
        sim.state = _restore_leaves(data, sim.state, sim.engine)
        return
    # exact guard failed (config differs) or shapes differ (escalated
    # checkpoint): try the migration path
    if meta is None or "__guard_migrate__" not in data.files:
        raise CheckpointError(
            "checkpoint does not match this simulation (different config, "
            "model, or engine version; pre-migration checkpoints carry no "
            "shape record to migrate from)"
        )
    want_m = _migration_fingerprint(sim.engine_cfg, treedef, sim.params)
    got_m = bytes(data["__guard_migrate__"]).decode()
    if got_m != want_m:
        raise CheckpointError(
            "checkpoint does not match this simulation (different config, "
            "model, or engine version — beyond the migratable capacity "
            "shapes)"
        )
    template = _shaped_template(sim.state, meta)
    restored = _restore_leaves(data, template, engine=None)
    sim.state = _migrate_restored(restored, sim)


# ---------------------------------------------------------------- ensemble


def ensemble_fingerprint(engine_cfg, state, params, replica_meta) -> str:
    """Guard record for campaign checkpoints: the reconciled EngineConfig,
    the STACKED state treedef (carries R in every leaf shape via the
    treedef + leaf validation), the stacked params digest, and the
    replica metadata (labels/seeds/schedule descriptors from the campaign
    expansion) — so a checkpoint written by one campaign refuses to
    restore into a differently-composed one, even when shapes happen to
    match."""
    _, treedef = jax.tree_util.tree_flatten(state)
    blob = json.dumps(
        {
            "cfg": dataclasses.asdict(engine_cfg),
            "treedef": str(treedef),
            "params": _params_digest(params),
            "replicas": replica_meta,
        },
        sort_keys=True,
    )
    return hashlib.sha256(blob.encode()).hexdigest()


def save_ensemble_checkpoint(path: str, state, fingerprint: str) -> str:
    """Snapshot a stacked ensemble SimState (every leaf [R, ...]). The
    campaign supervisor's periodic on-disk durability point — same .npz
    layout as the solo checkpoints, guarded by `ensemble_fingerprint`."""
    arrays, _ = _dump_leaves(state)
    arrays["__guard__"] = np.frombuffer(
        fingerprint.encode(), dtype=np.uint8
    )
    if not path.endswith(".npz"):
        path += ".npz"
    np.savez_compressed(path, **arrays)
    return path


def load_ensemble_checkpoint(path: str, state, fingerprint: str):
    """Restore a stacked ensemble state saved by `save_ensemble_checkpoint`
    into a freshly built campaign of the same composition. `state` is the
    fresh stacked state (tree/shape template); returns the restored one
    (bucket caches rebuilt per replica, like the solo path)."""
    data = np.load(path)
    got = bytes(data["__guard__"]).decode()
    if got != fingerprint:
        raise CheckpointError(
            "ensemble checkpoint does not match this campaign (different "
            "config, replica composition, or engine version)"
        )
    return _restore_leaves(data, state, engine=None)


# ---------------------------------------------------------------- hybrid

from shadow_tpu.simtime import TIME_MAX  # noqa: E402

_SEG_FIELDS = ("flags", "seq", "ack", "wnd", "mss", "wscale",
               "sack_ok", "sack", "src_port", "dst_port")


def _pack_byte_stores(stores) -> tuple[bytes, bytes]:
    """Flatten `HybridSimulation._bytes` (per-gid {key: (window, NetPacket)})
    into (JSON index, concatenated payload buffer). NetPacket/Segment are
    flat int/str/bytes dataclasses, so no object serialization is needed —
    and none is wanted: pickle here would hand code execution to whoever
    can write the checkpoint file (the sha256 guard is data, not auth)."""
    recs, chunks, off = [], [], 0

    def put(b: bytes) -> tuple[int, int]:
        nonlocal off
        chunks.append(b)
        start = off
        off += len(b)
        return start, len(b)

    for gid, store in enumerate(stores):
        for key, (widx, pkt) in store.items():
            rec = {
                "gid": gid, "key": key, "w": widx,
                "sip": pkt.src_ip, "sp": pkt.src_port,
                "dip": pkt.dst_ip, "dp": pkt.dst_port,
                "pr": pkt.proto, "pl": put(pkt.payload),
            }
            if pkt.seg is not None:
                rec["seg"] = {f: getattr(pkt.seg, f) for f in _SEG_FIELDS}
                # pkt.payload mirrors seg.payload for TCP (sockets.py:29-30):
                # store the bytes once and share the slice on restore
                rec["segpl"] = (rec["pl"] if pkt.seg.payload == pkt.payload
                                else put(pkt.seg.payload))
            recs.append(rec)
    return json.dumps(recs).encode(), b"".join(chunks)


def _unpack_byte_stores(idx_json: bytes, buf: bytes, n_hosts: int):
    from shadow_tpu.host.sockets import NetPacket
    from shadow_tpu.tcp.segment import Segment

    stores: list[dict] = [{} for _ in range(n_hosts)]
    for rec in json.loads(idx_json.decode()):
        start, length = rec["pl"]
        payload = buf[start:start + length]
        seg = None
        if "seg" in rec:
            s0, sl = rec["segpl"]
            segpl = payload if [s0, sl] == rec["pl"] else buf[s0:s0 + sl]
            kw = rec["seg"]
            # JSON round-trips tuples as lists; Segment carries SACK blocks
            # as a tuple of (start, end) pairs
            kw["sack"] = tuple(tuple(b) for b in kw.get("sack", ()))
            seg = Segment(payload=segpl, **kw)
        pkt = NetPacket(
            src_ip=rec["sip"], src_port=rec["sp"],
            dst_ip=rec["dip"], dst_port=rec["dp"],
            proto=rec["pr"], payload=payload, seg=seg,
        )
        stores[rec["gid"]][rec["key"]] = (rec["w"], pkt)
    return stores


def _hybrid_fingerprint(hsim, treedef) -> str:
    cfgd = dataclasses.asdict(hsim.engine_cfg)
    # a resumed run legitimately extends the horizon; everything else
    # must match exactly
    cfgd.pop("stop_time", None)
    blob = json.dumps(
        {
            "cfg": cfgd,
            "treedef": str(treedef),
            "params": _params_digest(hsim.params),
            # process specs: same host names running different programs
            # or model args are a different simulation
            "specs": [
                (s.name, s.model, sorted(map(str, s.model_args.items())),
                 str(s.programs))
                for s in hsim.specs
            ],
        },
        sort_keys=True,
    )
    return hashlib.sha256(blob.encode()).hexdigest()


def save_checkpoint_hybrid(path: str, hsim) -> str:
    """Snapshot a `HybridSimulation` (VERDICT r3 missing #5): the device
    plane plus the bridge's CPU half — host clocks, event-order counters,
    per-host stat counters, process outcomes, staging cursors, and the
    parked payload byte stores (in-flight device packets may still need
    their bytes at delivery).

    Scope (enforced loudly): every process must have finished ON ITS OWN
    — a daemon reaped by the stop-time shutdown was still alive at the
    horizon (state_at_stop == "running") and its live state is already
    lost, so the snapshot refuses. Pending host events (deliveries or
    timers scheduled past the horizon) likewise refuse: the resume path
    rebuilds host queues empty and cannot reconstruct them."""
    for h in hsim.hosts:
        for p in h.processes.values():
            state = getattr(p.state, "value", p.state)
            at_stop = getattr(p, "state_at_stop", state)
            if state != "zombie" or at_stop != "zombie":
                raise CheckpointError(
                    f"process {p.name} on {h.name} was {at_stop!r} at the "
                    "horizon: hybrid checkpoints require every process to "
                    "have exited on its own (live process state cannot "
                    "snapshot)"
                )
        if h.next_event_time() != TIME_MAX:
            raise CheckpointError(
                f"host {h.name} has events pending past the horizon; "
                "cannot snapshot (they would be lost on resume)"
            )
    if hsim._staged or any(hsim._stage_buf):
        raise CheckpointError("staged sends in flight; cannot snapshot")
    arrays, treedef = _dump_leaves(hsim.state)
    arrays["__guard__"] = np.frombuffer(
        _hybrid_fingerprint(hsim, treedef).encode(), dtype=np.uint8
    )
    bridge = {
        "window_idx": hsim._window_idx,
        "unreach": hsim._unreach,
        "model_pkts_unrouted": hsim._model_pkts_unrouted,
        "hosts": [
            {
                "name": h.name,
                "now": h.now(),
                "seq": h._seq,
                "counters": h.counters,
                "if_counters": h.if_counters,
                "closed_socket_stats": h.closed_socket_stats,
                "heartbeats": h.heartbeats,
                "hb_prev": h._hb_prev,
                "hb_closed_seen": sorted(h._hb_closed_seen),
                "procs": [
                    {
                        "pid": p.pid,
                        "name": getattr(p, "name", "?"),
                        "exit_code": getattr(p, "exit_code", None),
                        "term_signal": getattr(p, "term_signal", None),
                    }
                    for p in h.processes.values()
                ],
            }
            for h in hsim.hosts
        ],
    }
    arrays["__bridge__"] = np.frombuffer(
        json.dumps(bridge).encode(), dtype=np.uint8
    )
    # payload byte stores: packets already injected into the device plane
    # carry only (src, key); the bytes must survive the resume or their
    # eventual capture degrades (echo reconstruction, delivery counters).
    # Serialized WITHOUT pickle (a tampered checkpoint must not be able to
    # execute code on load): flat JSON records + one payload byte buffer.
    recs_json, payload_buf = _pack_byte_stores(hsim._bytes)
    arrays["__bytes_idx__"] = np.frombuffer(recs_json, dtype=np.uint8)
    arrays["__bytes_buf__"] = np.frombuffer(payload_buf, dtype=np.uint8)
    arrays["__send_seq__"] = np.asarray(hsim._send_seq)
    if not path.endswith(".npz"):
        path += ".npz"
    np.savez_compressed(path, **arrays)
    return path


def load_checkpoint_hybrid(path: str, hsim) -> None:
    """Restore into a freshly built `HybridSimulation` of the same config
    (stop_time may differ — that is the point of resuming)."""
    from shadow_tpu.host.process import ProcState

    data = np.load(path, allow_pickle=False)
    if "__bytes_idx__" not in data.files:
        raise CheckpointError(
            "checkpoint uses an older byte-store format; re-create it with "
            "this version (loading would leave the simulation half-restored)"
        )
    _, treedef = jax.tree_util.tree_flatten(hsim.state)
    want = _hybrid_fingerprint(hsim, treedef)
    got = bytes(data["__guard__"]).decode()
    if got != want:
        raise CheckpointError(
            "checkpoint does not match this simulation (different config, "
            "model, or engine version)"
        )
    state = _restore_leaves(data, hsim.state, hsim.engine)
    hsim.state = state._replace(
        done=jnp.zeros((), bool)  # resume the horizon
    )
    bridge = json.loads(bytes(data["__bridge__"]).decode())
    hsim._window_idx = bridge["window_idx"]
    hsim._unreach = bridge["unreach"]
    hsim._model_pkts_unrouted = bridge.get("model_pkts_unrouted", 0)
    hsim._send_seq = np.asarray(data["__send_seq__"]).copy()
    hsim._bytes = _unpack_byte_stores(
        bytes(data["__bytes_idx__"]),
        bytes(data["__bytes_buf__"]),
        len(hsim._bytes),
    )
    by_name = {h["name"]: h for h in bridge["hosts"]}
    for h in hsim.hosts:
        rec = by_name.get(h.name)
        if rec is None:
            raise CheckpointError(f"host {h.name} missing from checkpoint")
        # the freshly built host scheduled its processes' start events:
        # those processes already RAN to completion before the snapshot —
        # drop the pending events and adopt the recorded outcomes instead
        h._q.clear()
        h._cancelled.clear()
        h._now = rec["now"]
        h._seq = rec["seq"]
        h.counters.update(rec["counters"])
        for k, v in rec.get("if_counters", {}).items():
            h.if_counters[k].update(v)
        h.closed_socket_stats = list(rec.get("closed_socket_stats", []))
        h.heartbeats = list(rec.get("heartbeats", []))
        h._hb_prev = rec.get("hb_prev")
        h._hb_closed_seen = set(rec.get("hb_closed_seen", []))
        recs = {pr["pid"]: pr for pr in rec["procs"]}
        for p in h.processes.values():
            pr = recs.get(p.pid)
            if pr is None:
                raise CheckpointError(
                    f"process {p.pid} on {h.name} missing from checkpoint"
                )
            # match each plane's own state type: coroutine processes
            # compare against the ProcState enum (kill() would re-kill a
            # plain string), native ones use strings
            p.state = (
                ProcState.ZOMBIE
                if isinstance(p.state, ProcState)
                else "zombie"
            )
            p.state_at_stop = "zombie"
            p.exit_code = pr["exit_code"]
            p.term_signal = pr["term_signal"]
