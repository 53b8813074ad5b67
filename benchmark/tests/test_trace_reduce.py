"""The trace reduction, on a trace recorded on the chip (one v5e, a 200-host
PHOLD segment of 1 s, PR 22) and on hand-made inputs."""

import os

import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


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


def test_recorded_chip_trace_reduces():
    with open(os.path.join(DATA, "small_phold.hlo.txt.gz"), "rb") as f:
        import gzip

        hlo = T.hlo_map(gzip.decompress(f.read()).decode())
    red = T.reduce_profile(T.load_profile(os.path.join(DATA, "small_phold.xplane.pb.gz")), hlo)
    assert red.chunk_dispatches == 1
    assert 0 < red.busy_s <= red.span_s
    assert all(v > 0 for v in red.scopes.values())
    # leaf ops of one program run one at a time: their sum fits in busy
    assert sum(red.scopes.values()) <= red.busy_s * 1.0001
    assert 0 < len(red.top_ops) <= 10 and 0 < len(red.idle_gaps) <= 10
    assert all(s > 0 and " -> " in name for name, s in red.idle_gaps)
