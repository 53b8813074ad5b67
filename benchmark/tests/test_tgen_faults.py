"""The tgen cell's `correct` against a fault planted in its own model.

`test_faults.py`'s half-batch fault leaves half the hosts out of PHOLD's
handler; here it is planted in the tgen-TCP handler, on a tiny copy of
`tgen_10k_1gbit_switch.bulk`, and the run must come out not correct."""

import dataclasses

import harness as H
import run as R

CELL = "tgen_10k_1gbit_switch.bulk"


def test_half_batch_in_the_tcp_handler_is_not_correct(tiny_root, monkeypatch):
    from shadow_tpu.models.tgen import TgenTcpModel

    assert H.load_cell(CELL, 1, tiny_root).model == "tgen_tcp"
    orig = TgenTcpModel.handle

    def handle(self, ctx):
        return orig(self, dataclasses.replace(ctx, active=ctx.active & (ctx.host_id % 2 == 0)))

    monkeypatch.setattr(TgenTcpModel, "handle", handle)
    res = R.run(["--workload", CELL, "--seed", "21", "--seconds", "0.3", "--trace", "0"],
                root=tiny_root, require_tpu=False)
    assert res["correct"] is False
    failed = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert "digest_mismatch_hosts" in failed, failed
