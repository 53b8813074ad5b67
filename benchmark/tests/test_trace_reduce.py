"""The trace reduction, on traces recorded on the chip and on hand-made
inputs.

`small_phold.*`: one v5e, a 200-host PHOLD segment of 1 s, from a program
without the host spans (PR 22). `spans_phold.*`: a traced window of two
200-host PHOLD segments of 1 s (40 rounds) recorded on one v5e from a
program with the spans and scopes (`span_report.py --hosts 200 --horizon
"1 s" --seconds 0.05 --keep <dir>`, gzipped). The numbers pinned here are
what the reduction gave before its markers and host spans were folded into
it (the scopes, busy time and top ops) and what the separate span
reduction gave (the markers and host spans)."""

import gzip
import os

import pytest
from conftest import REPO

import harness as H
import trace_reduce as T

ROUNDS = 40  # in the recorded window of spans_phold
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

PINNED = {
    "small_phold": {
        "chunk_dispatches": 1,
        "busy_s": 0.003685877,
        "span_s": 0.023305043,
        "scopes": {"microsteps": 0.0009058560000000144, "exchange": 0.0017185679999999909,
                   "other": 0.00017417499999999977},
        "top_ops": [
            ["exchange:fusion.1525", 0.00042816799999999993],
            ["exchange:fusion.1523", 0.0003621600000000005],
            ["exchange:sort.36", 0.00010199000000000002],
            ["exchange:fusion.1527", 9.622000000000002e-05],
            ["exchange:fusion.3", 9.614699999999999e-05],
            ["exchange:fusion.1475", 6.3669e-05],
            ["microsteps:select_select_fusion.25", 5.703800000000001e-05],
            ["exchange:fusion.1528", 4.914500000000001e-05],
            ["outside:custom-call.2", 4.2321000000000004e-05],
            ["microsteps:compare_select_fusion.654", 4.199699999999999e-05],
        ],
        "markers": {"shadow_exchange": 0.0017185679999999909,
                    "shadow_merge": 0.001689719999999991,
                    "shadow_microsteps": 0.0009058560000000144},
        "host_spans": {},
    },
    "spans_phold": {
        "chunk_dispatches": 2,
        "busy_s": 0.006665019,
        "span_s": 0.056464046000000004,
        "scopes": {"microsteps": 0.001828384999999968, "exchange": 0.002561553999999955,
                   "other": 0.0003332080000000009},
        "top_ops": [
            ["exchange:fusion.1252", 0.0007243050000000018],
            ["exchange:fusion.1254", 0.0006534979999999999],
            ["exchange:sort.36", 0.00020401899999999997],
            ["microsteps:select_reduce_fusion.17", 0.00019850200000000017],
            ["exchange:fusion.3", 0.000149757],
            ["exchange:fusion.1256", 0.00014972699999999997],
            ["microsteps:select_select_fusion.25", 0.00011459500000000009],
            ["microsteps:or_reduce_fusion.1", 9.380899999999994e-05],
            ["microsteps:reduce.231", 9.24660000000001e-05],
            ["outside:custom-call.2", 8.5899e-05],
        ],
        "markers": {"shadow_exchange": 0.002561553999999955,
                    "shadow_handler": 1.843800000000014e-05,
                    "shadow_merge": 0.0024935779999999603,
                    "shadow_merge_order": 0.0019589839999999813,
                    "shadow_merge_write": 0.00040845000000000166,
                    "shadow_microsteps": 0.001828384999999968,
                    "shadow_window": 3.9285e-05},
        "host_spans": {"shadow.restore": [0.034544175, 2], "shadow.run": [0.027842434, 2],
                       "shadow.chunk": [0.027792504000000003, 4],
                       "shadow.sync": [0.010185031, 6], "shadow.dispatch": [0.00121169, 2],
                       "shadow.export": [4.25e-06, 2],
                       "shadow.heartbeat": [0.016328152999999998, 2]},
    },
}


def _load(stem):
    with gzip.open(os.path.join(DATA, f"{stem}.hlo.txt.gz"), "rt") as f:
        text = f.read()
    profile = T.load_profile(os.path.join(DATA, f"{stem}.xplane.pb.gz"))
    return profile, T.reduce_profile(profile, T.hlo_map(text))


@pytest.fixture(scope="module")
def seed_fixture():
    return _load("small_phold")


@pytest.fixture(scope="module")
def spans_fixture():
    return _load("spans_phold")


def _per_round(red, rounds):
    """What each per-layer reader reads from `red` over `rounds` rounds."""
    ctx = H.TraceContext(reduction=red, window_s=1.0, segments=2, rounds=rounds,
                         microsteps=0, reports=[], engine_cfg=None, peaks=None)
    return {e["name"]: read(ctx) for e, read in H.metric_readers(REPO)}


def test_union_counts_overlap_once_and_finds_gaps():
    busy, span, gaps = T._union([(0, 10), (5, 15), (20, 30), (30, 31)])
    assert (busy, span) == (26, 31)
    assert gaps == [(15, 20)]


def test_hlo_map_takes_scope_from_metadata_or_caller():
    text = "\n".join([
        "HloModule jit_chunk, entry_computation_layout={()->()}",
        "",
        "%body.1 (p: s32[]) -> s32[] {",
        "  %p = s32[] parameter(0)",
        "  ROOT %add.1 = s32[] add(%p, %p)",
        "}",
        "",
        "%fused.6 (q: s32[]) -> s32[] {",
        "  ROOT %q = s32[] parameter(0)",
        "}",
        "",
        "ENTRY %main.2 (a: s32[]) -> s32[] {",
        "  %a = s32[] parameter(0)",
        '  %while.3 = s32[] while(%a), condition=%body.1, body=%body.1, '
        'metadata={op_name="jit(f)/while/body/shadow_microsteps/while"}',
        '  %fusion.4 = s32[] fusion(%while.3), kind=kLoop, calls=%fused.6, '
        'metadata={op_name="jit(f)/shadow_exchange/shadow_merge/sort"}',
        "  ROOT %copy.5 = s32[] copy(%fusion.4)",
        "}",
    ])
    m = T.hlo_map(text)
    assert m.module == "jit_chunk"
    assert m.scope["while.3"] == "microsteps"
    assert m.scope["add.1"] == "microsteps"  # inherited from the while
    assert m.scope["fusion.4"] == "exchange"
    assert m.scope["copy.5"] == "other"
    assert m.opcode["while.3"] == "while" and m.opcode["fusion.4"] == "fusion"


def test_marker_map_counts_every_marker_of_a_path():
    text = "\n".join([
        "HloModule jit_chunk, entry_computation_layout={()->()}",
        "",
        "%branch.1 (p: s32[]) -> s32[] {",
        "  %p = s32[] parameter(0)",
        "  ROOT %add.1 = s32[] add(%p, %p)",
        "}",
        "",
        "ENTRY %main.2 (a: s32[]) -> s32[] {",
        "  %a = s32[] parameter(0)",
        '  %cond.3 = s32[] conditional(%a, %a, %a), branch_computations={%branch.1, '
        '%branch.1}, metadata={op_name="jit(f)/while/body/shadow_exchange/shadow_merge/cond"}',
        '  %sort.4 = s32[] sort(%cond.3), metadata={op_name="jit(f)/shadow_exchange/'
        'shadow_merge/cond/branch_1_fun/shadow_merge_order/sort"}',
        '  ROOT %pmin.5 = s32[] all-reduce(%sort.4), metadata={op_name="jit(f)/'
        'shadow_window/shadow_collective/pmin"}',
        "}",
    ])
    m = T.hlo_map(text)
    assert m.markers["cond.3"] == {"shadow_exchange", "shadow_merge"}
    assert m.markers["add.1"] == {"shadow_exchange", "shadow_merge"}  # inherited
    assert m.markers["sort.4"] == {"shadow_exchange", "shadow_merge", "shadow_merge_order"}
    assert m.markers["pmin.5"] == {"shadow_window", "shadow_collective"}
    assert m.markers["a"] == frozenset()
    assert m.opcode["cond.3"] == "conditional"
    # the same walk gives the scopes
    assert m.scope["add.1"] == m.scope["sort.4"] == "exchange"
    assert m.scope["pmin.5"] == "other"


def test_recorded_chip_trace_reduces(seed_fixture):
    _, red = seed_fixture
    assert red.chunk_dispatches == 1
    assert 0 < red.busy_s <= red.span_s
    assert all(v > 0 for v in red.scopes.values())
    # leaf ops of one program run one at a time: their sum fits in busy
    assert sum(red.scopes.values()) <= red.busy_s * 1.0001
    assert 0 < len(red.top_ops) <= 10 and 0 < len(red.idle_gaps) <= 10
    assert all(s > 0 and " -> " in name for name, s in red.idle_gaps)


@pytest.mark.parametrize("stem", sorted(PINNED))
def test_accepted_reduction_of_the_recorded_fixtures_is_pinned(stem):
    """Scopes, busy time, span, dispatches and top ops as pinned before the
    markers and host spans joined the reduction: bit for bit."""
    _, red = _load(stem)
    pinned = PINNED[stem]
    for key in ("chunk_dispatches", "busy_s", "span_s", "scopes", "top_ops"):
        assert getattr(red, key) == pinned[key], key


@pytest.mark.parametrize("stem", sorted(PINNED))
def test_markers_and_host_spans_equal_the_span_reduction(stem):
    _, red = _load(stem)
    assert red.markers == PINNED[stem]["markers"]
    assert red.host_spans == PINNED[stem]["host_spans"]


def test_older_markers_read_the_accepted_scopes(seed_fixture):
    _, red = seed_fixture
    assert red.markers["shadow_microsteps"] == pytest.approx(red.scopes["microsteps"])
    assert red.markers["shadow_exchange"] == pytest.approx(red.scopes["exchange"])
    assert 0 < red.markers["shadow_merge"] <= red.markers["shadow_exchange"]
    # a program without the spans: nothing to read, nothing named
    assert red.host_spans == {}
    assert _per_round(red, 20)["driver.host_ms_per_round"] is None
    assert all(label.endswith("no host event") for label, _ in red.idle_gaps)


def test_recorded_spans_and_scopes_reduce(spans_fixture):
    _, red = spans_fixture
    mk = red.markers
    for name in ("shadow_window", "shadow_merge_order", "shadow_merge_write",
                 "shadow_handler"):
        assert mk.get(name, 0) > 0, name
    assert "shadow_collective" not in mk  # one chip
    assert mk["shadow_handler"] < mk["shadow_microsteps"]
    assert mk["shadow_window"] <= red.scopes["other"] + 1e-12
    halves = mk["shadow_merge_order"] + mk["shadow_merge_write"]
    assert halves <= mk["shadow_merge"] * (1 + 1e-9)
    # the accepted scopes are unmoved by the markers beside them
    assert mk["shadow_exchange"] == pytest.approx(red.scopes["exchange"])
    for name in ("shadow.run", "shadow.chunk", "shadow.dispatch", "shadow.sync",
                 "shadow.heartbeat", "shadow.restore"):
        assert red.host_spans[name][1] > 0, name
    assert red.host_spans["shadow.sync"][0] < red.host_spans["shadow.run"][0]
    pr = _per_round(red, ROUNDS)
    assert pr["collective.ms_per_round"] is None
    assert pr["exchange.rows_per_round"] is None  # no reports handed
    for name in ("driver.host_ms_per_round", "barrier.ms_per_round",
                 "merge.order_ms_per_round", "merge.write_ms_per_round",
                 "handler.ms_per_round"):
        assert pr[name] > 0, name
    assert pr["merge.order_ms_per_round"] == 1e3 * mk["shadow_merge_order"] / ROUNDS
    run_s, sync_s = red.host_spans["shadow.run"][0], red.host_spans["shadow.sync"][0]
    assert pr["driver.host_ms_per_round"] == 1e3 * (run_s - sync_s) / ROUNDS


def test_every_chunk_gap_of_the_recorded_window_is_named(spans_fixture):
    profile, red = spans_fixture
    host = T.annotated_host_events(profile)
    (dev,) = [p for p in profile.planes if p.name.startswith("/device:TPU:")]
    ops = {ln.name: ln for ln in dev.lines}["XLA Ops"].events
    _, _, gaps = T._union((e.start_ns, e.start_ns + e.duration_ns) for e in ops)
    runs = [(s, s + d) for s, d, n in host if n == "shadow.run"]
    named = [(s, s + d) for s, d, n in host
             if n not in ("shadow.run", "shadow.chunk")]

    def under(spans, t):
        return any(s <= t <= e for s, e in spans)

    # every gap of a millisecond or more inside `shadow.run` lies under a
    # span below it that names it
    mids = [(a + b) / 2 for a, b in gaps if b - a >= 1e6 and under(runs, (a + b) / 2)]
    assert mids and all(under(named, t) for t in mids)
    long = [label for label, s in red.idle_gaps if s >= 1e-3]
    assert long and not any(label.endswith("no host event") for label in long)
    assert long[0].endswith("shadow.heartbeat")  # the chunk gap
