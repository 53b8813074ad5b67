"""The control (the reference with rounds longer than the least path
latency: packets arrive late) must come out not correct, on every cell,
while the reference compared with itself is correct. Tiny size here; the
chip runs of benchmark/control.py at the cells' own size are in PERF.md."""

import pytest
from conftest import REPO

import control
import correct as C
import harness as H

CELLS = [w["name"] for w in H.manifest(REPO)["workloads"]]


@pytest.mark.parametrize("seed", [3, 2**31 + 99])
@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_reference_passes(tiny_root, cell, seed):
    c = H.load_cell(cell, seed, tiny_root)
    mod = H.reference_module(c.model)
    ref = mod.reference(c.shadow).run()
    again = mod.reference(c.shadow).run()
    answer, segments = control.as_answer(again)
    same = C.compare(answer, ref, segments)
    assert all(v <= lim for v, lim in same.values()), same
    checks = control.control_checks(c, ref)
    assert checks["digest_mismatch_hosts"][0] > 0
    assert any(v > lim for v, lim in checks.values())
