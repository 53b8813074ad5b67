"""shadowlint stage B: jaxpr-level audit of the jitted round body.

Stage A sees source; this stage sees what JAX will actually compile. For
small echo and phold configs on the CPU backend it traces
`core/engine._run_chunk` (tracing only — nothing is compiled or
executed, so the known jaxlib heap corruption in compiled runs cannot
reach this stage) and asserts:

  1. LANE WIDTHS — the traced carry's output dtypes match the registry
     (shadow_tpu/core/lanes.py STATE_LANES), via jax.eval_shape on the
     real SimState. This is the check ROADMAP item 1's "memory diet"
     will deliberately edit: narrowing a lane means changing lanes.py
     and this assertion follows; an accidental `astype` somewhere in the
     round body fails here even if stage A's heuristics missed it.

  2. CARRY DOWN-CASTS — no `convert_element_type` whose INPUT is one of
     the chunk function's top-level carry lanes registered 64-bit and
     whose output is a narrower integer. Interior casts (e.g. widening a
     bool sum, narrowing a bounded index) are legal and only counted.

  3. FLOAT SCATTER-ADD — scatter-adds with floating dtype are counted
     and pinned; digest-feeding lanes are integer by construction, and
     a float scatter-add appearing where none existed means a reduction
     moved off the deterministic integer path.

  4. PRIMITIVE FINGERPRINT — the multiset of jaxpr primitives (and eqn
     total) per config is recorded in tools/lint/jaxpr_baseline.json,
     keyed by jax version. A mismatch is a compile-surface change:
     deliberate ones re-record with --update-fingerprint; accidental
     ones (a new cond materializing slabs, shape churn forcing
     recompiles) get caught at lint time instead of in a BENCH
     regression.
"""

from __future__ import annotations

import functools
import json
import os
import sys

FINGERPRINT_FILE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "jaxpr_baseline.json"
)

# small, fast-to-trace configs covering the two model classes the digest
# gates lean on: echo (integer-only, packet path) and phold (float
# exponential draws, timer path). Kept tiny — tracing cost only.
AUDIT_CONFIGS = {
    "echo": dict(
        model="udp_echo",
        hosts=[
            dict(host_id=0, name="server", start_time=0,
                 model_args={"role": "server"}),
            dict(host_id=1, name="c1", start_time=0,
                 model_args={"role": "client", "peer": "server",
                             "interval": "100 ms"}),
        ],
        stop=200_000_000,
        kw=dict(qcap=16, trace_rounds=8),
    ),
    "phold": dict(
        model="phold",
        hosts=None,  # mk_hosts(4) below
        stop=200_000_000,
        kw=dict(qcap=16),
    ),
    # network observatory ON (ISSUE 10): event-class lanes, flow ledger,
    # and safe-window telemetry traced in — pins the gated program's
    # compile surface while `echo`/`phold` above pin that the DEFAULT
    # (observatory-off) programs stay byte-unchanged.
    "tgen_netobs": dict(
        model="tgen_tcp",
        hosts="tgen",  # mk_hosts(4, tgen args) below
        stop=400_000_000,
        kw=dict(qcap=16, trace_rounds=8, netobs=True, flow_records=16,
                sends_budget=16),
    ),
    # integrity sentinel ON (ISSUE 11): the per-round invariant guards,
    # the violation lanes, and the dual digest traced in — pins the
    # GATED program's compile surface while `echo`/`phold` above pin
    # that the default (sentinel-off) programs stay byte-unchanged.
    "phold_integrity": dict(
        model="phold",
        hosts=None,  # mk_hosts(4) below
        stop=200_000_000,
        kw=dict(qcap=16, integrity=True),
    ),
    # fluid traffic plane ON (ISSUE 13): the background-flow ODE carry,
    # the per-round forward-Euler advance, the outbox byte fold, and the
    # latency/loss coupling traced in — pins the GATED program's compile
    # surface (and audits the fluid.* f64 lane dtypes) while
    # `echo`/`phold`/`tgen_netobs` above pin that the default
    # (fluid-off) programs stay byte-unchanged.
    "tgen_fluid": dict(
        model="tgen_tcp",
        hosts="tgen",  # mk_hosts(4, tgen args) below
        stop=400_000_000,
        kw=dict(qcap=16, sends_budget=16, fluid={
            "link_capacity": "100 Mbit",
            "latency_factor_max": 1.5,
            "loss_max": 0.05,
            "classes": [{"src_zone": 0, "dst_zone": 0,
                         "rate": "80 Mbit", "start": 0}],
        }),
    ),
    # timer wheel ON: the wheel carry lanes, merged queue∪wheel pops and
    # spill routing traced in — pins the GATED program's compile surface
    # (and audits the wheel.* lane dtypes) while `echo`/`phold` above pin
    # that the default (wheel-off) programs stay byte-unchanged.
    "phold_wheel": dict(
        model="phold",
        hosts=None,  # mk_hosts(4) below
        stop=200_000_000,
        kw=dict(qcap=16, wheel_slots=8),
    ),
}


def _audit_findings_cls():
    from tools.lint.astlint import Finding

    return Finding


def _state_lane_paths(lanes):
    """STATE_LANES entries as (attr-chain tuple, dtype string)."""
    return [
        (tuple(path.split(".")), dt) for path, dt in lanes.STATE_LANES.items()
    ]


def _walk_attr(obj, chain):
    for name in chain:
        if obj is None:
            return None
        if isinstance(obj, dict):
            obj = obj.get(name)
        else:
            obj = getattr(obj, name, None)
    return obj


def _flatten_with_paths(tree, prefix=()):
    """(path, leaf) pairs in jax.tree flatten order: NamedTuples and
    tuples/lists positionally (NamedTuples labeled by field name), dicts
    by sorted key — mirrors jax's default pytree registry so the list
    aligns with jaxpr invars."""
    if tree is None:
        return []
    if hasattr(tree, "_fields"):  # NamedTuple
        out = []
        for name in tree._fields:
            out += _flatten_with_paths(getattr(tree, name), prefix + (name,))
        return out
    if isinstance(tree, (tuple, list)):
        out = []
        for i, v in enumerate(tree):
            out += _flatten_with_paths(v, prefix + (str(i),))
        return out
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out += _flatten_with_paths(tree[k], prefix + (str(k),))
        return out
    return [(prefix, tree)]


def _iter_eqns(jaxpr):
    """Every eqn of a jaxpr, recursing into sub-jaxprs (while/cond/scan/
    pjit bodies) wherever they hide in eqn params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _sub_jaxprs(v):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _sub_jaxprs(x)


def _build(name, spec):
    from tests.engine_harness import build_sim, mk_hosts
    from shadow_tpu.core.engine import Engine

    hosts = spec["hosts"]
    if hosts == "tgen":
        hosts = mk_hosts(
            4, {"flow_segs": 4, "flows": 1, "cwnd_cap": 4}
        )
    elif hosts is None:
        hosts = mk_hosts(4, {"mean_delay": "50 ms", "population": 2})
    cfg, model, params, mstate, events = build_sim(
        spec["model"], hosts, spec["stop"], **spec["kw"]
    )
    eng = Engine(cfg, model)
    state, params = eng.init_state(params, mstate, events, seed=1)
    return cfg, model, state, params


def run_audit(
    root: str | None = None,
    update: bool = False,
    configs: tuple[str, ...] = (
        "echo", "phold", "tgen_netobs", "tgen_fluid", "phold_integrity",
        "phold_wheel",
    ),
    fingerprint_file: str = FINGERPRINT_FILE,
):
    """Returns (findings, report dict per config)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if root is None:
        from tools.lint.astlint import repo_root

        root = repo_root()
    if root not in sys.path:
        sys.path.insert(0, root)

    import jax

    from tools.lint.astlint import load_lanes
    from shadow_tpu.core import engine as engine_mod

    Finding = _audit_findings_cls()
    lanes = load_lanes(root)
    lane_paths = _state_lane_paths(lanes)
    findings: list = []
    report: dict = {}

    try:
        with open(fingerprint_file, encoding="utf-8") as f:
            recorded_all = json.load(f)
    except OSError:
        recorded_all = {}
    ver = jax.__version__
    recorded_ver = recorded_all.get(ver, {})
    changed = False

    for name in configs:
        spec = AUDIT_CONFIGS[name]
        cfg, model, state, params = _build(name, spec)
        fn = functools.partial(engine_mod._run_chunk, cfg, model, None)

        # ---- 1: carry lane widths (the traced OUTPUT SimState)
        out_state = jax.eval_shape(fn, state, params)
        for chain, want in lane_paths:
            leaf = _walk_attr(out_state, chain)
            if leaf is None:
                continue  # optional plane absent in this config
            got = str(leaf.dtype)
            if got != want:
                findings.append(Finding(
                    "RB", "shadow_tpu/core/engine.py", 1,
                    f"[{name}] carry lane {'.'.join(chain)} traced as "
                    f"{got}, registry (core/lanes.py) requires {want}",
                ))

        # ---- 2-4: jaxpr walk
        closed = jax.make_jaxpr(fn)(state, params)
        jaxpr = closed.jaxpr

        # top-level invars <-> (state, params) leaves, jax flatten order
        state_paths = [p for p, _ in _flatten_with_paths(state)]
        n_state = len(state_paths)
        invar_lane: dict = {}
        for i, var in enumerate(jaxpr.invars[:n_state]):
            path = ".".join(state_paths[i])
            want = lanes.STATE_LANES.get(path)
            if want in ("int64", "uint64"):
                invar_lane[var] = (path, want)

        prim_counts: dict[str, int] = {}
        int_downcasts = 0
        float_scatter_adds = 0
        for eqn in _iter_eqns(jaxpr):
            pname = eqn.primitive.name
            prim_counts[pname] = prim_counts.get(pname, 0) + 1
            if pname == "convert_element_type":
                src = eqn.invars[0]
                src_dt = getattr(getattr(src, "aval", None), "dtype", None)
                dst_dt = eqn.params.get("new_dtype")
                if src_dt is None or dst_dt is None:
                    continue
                src_s, dst_s = str(src_dt), str(dst_dt)
                if (
                    src_s in ("int64", "uint64")
                    and dst_s.startswith(("int", "uint"))
                    and dst_s not in ("int64", "uint64")
                ):
                    int_downcasts += 1
                    if src in invar_lane:
                        path, want = invar_lane[src]
                        findings.append(Finding(
                            "RB", "shadow_tpu/core/engine.py", 1,
                            f"[{name}] registered {want} carry lane "
                            f"`{path}` down-cast to {dst_s} inside the "
                            f"round body",
                        ))
            elif pname == "scatter-add":
                out_dt = str(eqn.outvars[0].aval.dtype)
                if out_dt.startswith(("float", "bfloat", "complex")):
                    float_scatter_adds += 1

        fp = {
            "eqns": sum(prim_counts.values()),
            "primitives": dict(sorted(prim_counts.items())),
            "int64_downcasts": int_downcasts,
            "float_scatter_adds": float_scatter_adds,
        }
        rec = recorded_ver.get(name)
        if update:
            recorded_ver[name] = fp
            changed = True
            status = "recorded" if rec is None or rec != fp else "unchanged"
        elif rec is None:
            # never auto-record: a jax upgrade landing together with an
            # accidental compile-surface change must not bless itself
            status = "unrecorded"
            findings.append(Finding(
                "RB", "tools/lint/jaxpr_baseline.json", 1,
                f"[{name}] no primitive fingerprint recorded for "
                f"jax=={ver} — review the compile surface and pin it with "
                f"`python -m tools.lint --jaxpr-only --update-fingerprint`",
            ))
        elif rec != fp:
            status = "MISMATCH"
            diffs = []
            for k in ("eqns", "int64_downcasts", "float_scatter_adds"):
                if rec.get(k) != fp[k]:
                    diffs.append(f"{k} {rec.get(k)} -> {fp[k]}")
            rp, cp = rec.get("primitives", {}), fp["primitives"]
            for prim in sorted(set(rp) | set(cp)):
                if rp.get(prim, 0) != cp.get(prim, 0):
                    diffs.append(f"{prim} {rp.get(prim, 0)} -> {cp.get(prim, 0)}")
            findings.append(Finding(
                "RB", "tools/lint/jaxpr_baseline.json", 1,
                f"[{name}] primitive fingerprint changed for jax=={ver}: "
                f"{'; '.join(diffs[:12])} — if the compile-surface change "
                f"is deliberate, re-record with "
                f"`python -m tools.lint --jaxpr-only --update-fingerprint`",
            ))
        else:
            status = "ok"
        report[name] = {
            "eqns": fp["eqns"],
            "int64_downcasts": int_downcasts,
            "float_scatter_adds": float_scatter_adds,
            "fingerprint_status": status,
        }

    if changed:
        # fingerprints are kept for the installed jax only
        recorded_all = {ver: recorded_ver}
        try:
            with open(fingerprint_file, "w", encoding="utf-8") as f:
                json.dump(recorded_all, f, indent=2, sort_keys=True)
                f.write("\n")
        except OSError as e:  # read-only checkout: record-mode is advisory
            print(
                f"shadowlint: could not record jaxpr fingerprint "
                f"({e}); rerun with a writable tree to pin it",
                file=sys.stderr,
            )

    return findings, report


if __name__ == "__main__":  # pragma: no cover - debugging aid
    fs, rep = run_audit(update="--update" in sys.argv)
    for f in fs:
        print(f)
    print(json.dumps(rep, indent=2))
    sys.exit(1 if fs else 0)
