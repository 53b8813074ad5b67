"""The program's spans and scopes read from recorded chip traces.

`small_phold.*` is test_trace_reduce's fixture (one v5e, a 200-host
PHOLD segment of 1 s, from a program without the spans): the accepted
reduction's numbers on it are pinned here, and span_reduce finds the
older markers there with the same seconds. `spans_phold.*` is a traced
window of two
200-host PHOLD segments of 1 s (40 rounds) recorded on one v5e from a
program with the spans and scopes (`span_report.py --hosts 200 --horizon
"1 s" --seconds 0.05 --keep <dir>`, gzipped)."""

import gzip
import os

import pytest

import span_reduce as S
import trace_reduce as T

ROUNDS = 40  # in the recorded window of spans_phold
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _load(stem):
    with gzip.open(os.path.join(DATA, f"{stem}.hlo.txt.gz"), "rt") as f:
        text = f.read()
    return T.load_profile(os.path.join(DATA, f"{stem}.xplane.pb.gz")), text


@pytest.fixture(scope="module")
def seed_fixture():
    return _load("small_phold")


@pytest.fixture(scope="module")
def spans_fixture():
    return _load("spans_phold")


def test_accepted_reduction_of_the_seed_fixture_is_pinned(seed_fixture):
    profile, text = seed_fixture
    red = T.reduce_profile(profile, T.hlo_map(text))
    assert red.chunk_dispatches == 1
    assert red.busy_s == pytest.approx(0.003685877, rel=1e-12)
    assert red.span_s == pytest.approx(0.023305043, rel=1e-12)
    assert red.scopes == pytest.approx({
        "microsteps": 0.000905856, "exchange": 0.001718568, "other": 0.000174175,
    }, rel=1e-9)
    assert [k for k, _ in red.top_ops] == [
        "exchange:fusion.1525", "exchange:fusion.1523", "exchange:sort.36",
        "exchange:fusion.1527", "exchange:fusion.3", "exchange:fusion.1475",
        "microsteps:select_select_fusion.25", "exchange:fusion.1528",
        "outside:custom-call.2", "microsteps:compare_select_fusion.654",
    ]
    assert red.top_ops[0][1] == pytest.approx(0.000428168, rel=1e-9)
    assert red.top_ops[-1][1] == pytest.approx(0.000041997, rel=1e-9)


def test_older_markers_read_the_accepted_scopes(seed_fixture):
    profile, text = seed_fixture
    red = T.reduce_profile(profile, T.hlo_map(text))
    spans = S.reduce_spans(profile, text)
    assert spans.markers["shadow_microsteps"] == pytest.approx(red.scopes["microsteps"])
    assert spans.markers["shadow_exchange"] == pytest.approx(red.scopes["exchange"])
    assert 0 < spans.markers["shadow_merge"] <= spans.markers["shadow_exchange"]
    # a program without the spans: nothing to read, nothing named
    assert spans.host_spans == {}
    assert S.per_round(spans, 20)["driver.host_ms_per_round"] is None
    assert all(label.endswith("no host event") for label, _ in spans.idle_gaps)


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
    m = S.marker_map(text)
    assert m.markers["cond.3"] == {"shadow_exchange", "shadow_merge"}
    assert m.markers["add.1"] == {"shadow_exchange", "shadow_merge"}  # inherited
    assert m.markers["sort.4"] == {"shadow_exchange", "shadow_merge", "shadow_merge_order"}
    assert m.markers["pmin.5"] == {"shadow_window", "shadow_collective"}
    assert m.opcode["cond.3"] == "conditional"


def test_recorded_spans_and_scopes_reduce(spans_fixture):
    profile, text = spans_fixture
    red = T.reduce_profile(profile, T.hlo_map(text))
    spans = S.reduce_spans(profile, text)
    mk = spans.markers
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
        assert spans.host_spans[name][1] > 0, name
    assert spans.host_spans["shadow.sync"][0] < spans.host_spans["shadow.run"][0]
    pr = S.per_round(spans, ROUNDS)
    assert pr["collective.ms_per_round"] is None
    assert all(v > 0 for k, v in pr.items() if k != "collective.ms_per_round")


def test_every_chunk_gap_of_the_recorded_window_is_named(spans_fixture):
    profile, text = spans_fixture
    spans = S.reduce_spans(profile, text)
    assert spans.run_gaps > 0 and spans.run_gaps_unnamed == 0
    assert 0 < spans.idle_in_run_s <= spans.idle_s
    # the gaps of a millisecond or more all lie under a span that names
    # them (the chunk gap under shadow.heartbeat)
    long = [label for label, s in spans.idle_gaps if s >= S.GAP_S]
    assert long and not any(label.endswith("no host event") for label in long)
    assert long[0].endswith("shadow.heartbeat")
