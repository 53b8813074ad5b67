"""Plain reference of the `phold` model: each host starts with `population`
jobs; a job that matures sends a message to a uniformly drawn host; an
arrived message is held for an exponential time (mean `mean_delay`,
floored at 1 us) and then matures as a job. Draws come from the host's own
RNG lane, the hold first, then the peer.

The hold's -log(1 - u) is float32 arithmetic the way the device computes
it: a table of the device's own float32 log over all 2**24 values u can
take, made once per run by JAX on the default device (no program code).
"""

from __future__ import annotations

import numpy as np

from reference.engine import PAYLOAD_WORDS, Reference, rng_uniform, time_ns

KIND_JOB = 0
KIND_MSG = 1
_LOG_TABLE = None


def neg_log_table() -> np.ndarray:
    """-log(max(1e-7, 1 - u)) in float32 for u = k / 2**24, on the device."""
    global _LOG_TABLE
    if _LOG_TABLE is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def table():
            u = jnp.arange(1 << 24, dtype=jnp.uint32).astype(jnp.float32) * jnp.float32(
                1.0 / (1 << 24)
            )
            return -jnp.log(jnp.maximum(jnp.float32(1e-7), jnp.float32(1.0) - u))

        _LOG_TABLE = np.asarray(table())
    return _LOG_TABLE


class Phold:
    def build(self, hosts, seed):
        h = len(hosts)
        for x in hosts:
            if set(x["model_args"]) - {"population", "mean_delay", "payload_bytes", "size_bytes"}:
                raise ValueError(f"phold args {sorted(x['model_args'])}")
        params = {
            "mean_delay": np.array(
                [time_ns(x["model_args"].get("mean_delay", "100 ms"), "ms") for x in hosts],
                np.int64,
            ),
            # the program reads `payload_bytes`; `size_bytes` is not read
            "size": np.array(
                [int(x["model_args"].get("payload_bytes", 64)) for x in hosts], np.int32
            ),
        }
        state = {"handled": np.zeros(h, np.int64)}
        events = [
            (x["host_id"], x["start_time"], KIND_JOB, ())
            for x in hosts
            for _ in range(int(x["model_args"].get("population", 1)))
        ]
        self.num_hosts = h
        self.table = neg_log_table()
        return params, state, events

    def handle(self, ctx):
        n = ctx.t.size
        job = ctx.kind == KIND_JOB
        arrived = ctx.kind == KIND_MSG
        rng, u_hold = rng_uniform(ctx.rng, arrived)
        x = self.table[(u_hold * np.float32(1 << 24)).astype(np.int64)]
        hold = np.maximum((x * ctx.params["mean_delay"].astype(np.float32)).astype(np.int64), 1_000)
        zeros = np.zeros((n, PAYLOAD_WORDS), np.int32)
        push = (arrived, ctx.t + hold, np.full(n, KIND_JOB), zeros)
        rng, u_dst = rng_uniform(rng, job)
        dst = np.minimum((u_dst * np.float32(self.num_hosts)).astype(np.int64), self.num_hosts - 1)
        send = {
            "mask": job, "dst": dst, "size": ctx.params["size"],
            "kind": np.full(n, KIND_MSG), "payload": zeros,
        }
        state = {"handled": ctx.state["handled"] + 1}
        return state, rng, [push], [send]


def reference(shadow: dict, **kw) -> Reference:
    return Reference(shadow, Phold(), **kw)
