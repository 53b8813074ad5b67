"""A run with the timed path broken underneath must come out not correct.

Each case drives the rest of a run (the chip check skipped) on a tiny
copy of a cell, with one fault planted in the program: a chunk that
returns its state unchanged, half of each microstep's hosts left out of
the model handler, the end-of-round exchange left out (on one chip it is
the merge; there is no exchange between chips to drop), and an answer
altered where it is produced (the time folded into the digest)."""

import dataclasses

import jax.numpy as jnp
import pytest
from conftest import REPO

import harness as H
import run as R

CELLS = [w["name"] for w in H.manifest(REPO)["workloads"]]


def _stuck_chunk(engine, monkeypatch):
    def stuck(cfg, model, axis, st, params):
        return st._replace(done=jnp.asarray(True), now=jnp.asarray(cfg.stop_time, jnp.int64))

    monkeypatch.setattr(engine, "_run_chunk", stuck)


def _half_batch(engine, monkeypatch):
    from shadow_tpu.models.phold import PholdModel

    orig = PholdModel.handle

    def handle(self, ctx):
        return orig(self, dataclasses.replace(ctx, active=ctx.active & (ctx.host_id % 2 == 0)))

    monkeypatch.setattr(PholdModel, "handle", handle)


def _no_exchange(engine, monkeypatch):
    monkeypatch.setattr(engine, "_exchange", lambda cfg, axis, st: st)


def _altered_answer(engine, monkeypatch):
    orig = engine._digest_update
    monkeypatch.setattr(engine, "_digest_update",
                        lambda digest, active, t, kind, order: orig(digest, active, t + 1, kind, order))


FAULTS = {"state_unchanged": _stuck_chunk, "half_batch": _half_batch,
          "no_exchange": _no_exchange, "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, cell, fault):
    from shadow_tpu.core import engine

    FAULTS[fault](engine, monkeypatch)
    res = R.run(["--workload", cell, "--seed", "21", "--seconds", "0.3", "--trace", "0"],
                root=tiny_root, require_tpu=False)
    assert res["correct"] is False
    failed = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert "digest_mismatch_hosts" in failed or "rounds_gap" in failed, failed
