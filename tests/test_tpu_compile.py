"""Compile the round-chunk program for a described TPU v5e, with no chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (jax.experimental.topologies). These cases catch
what only that compiler refuses — a 64-bit collective it cannot lower, a
compiler crash, a program that takes minutes to compile — before any chip
time is spent. Nothing runs: results and times need the chip
(chip_smoke.py).

The engine picks its pops (one-hot on the TPU, gather on the CPU) from
`jax.default_backend()`, which is "cpu" here; each case steers it to "tpu"
while the program is traced. The exchange merge is the same program on
every backend. The topology is described
inside a fixture so that only the xdist worker that runs this file loads
the TPU library.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

import bench
from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.core import engine as E
from shadow_tpu.sim import Simulation


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _torus_config(n: int, side: int, per_node: int) -> dict:
    """Bench config `n` with its widths kept and its host count cut to a
    side x side torus of `per_node` hosts each."""
    cfg, _, _ = bench.baseline_config(n, small=True)
    proto = next(iter(cfg["hosts"].values()))
    cfg["network"]["graph"]["inline"] = bench.torus_gml(side, lat_ms=50)
    cfg["hosts"] = {
        f"n{i:03d}": {**proto, "count": per_node, "network_node_id": i}
        for i in range(side * side)
    }
    return cfg


def _shape(x, sharding):
    return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                sharding=sharding)


def _compile(monkeypatch, cfg: dict, world: int, devices):
    """Build `cfg` on the CPU, then compile its chunk program (as
    `__graft_entry__.entry()` builds it) for `world` described chips."""
    sim = Simulation(ConfigOptions.from_dict(cfg), world=world)
    eng = sim.engine
    trees = (sim.state, sim.params)
    if world == 1:
        fn = functools.partial(E._run_chunk, sim.engine_cfg, eng.model, None)
        one = SingleDeviceSharding(devices[0])
        args = jax.tree.map(lambda x: _shape(x, one), trees)
    else:
        mesh = Mesh(devices[:world], (E.AXIS,))
        specs = (eng.state_specs(), eng.param_specs())
        fn = E._shard_map(
            functools.partial(E._run_chunk, sim.engine_cfg, eng.model, E.AXIS),
            mesh, specs, specs[0],
        )
        args = jax.tree.map(
            lambda x, s: _shape(x, NamedSharding(mesh, s)), trees, specs
        )
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        return jax.jit(fn).lower(*args).compile()


def test_flagship_chunk_compiles_for_v5e(topo, no_cache, monkeypatch):
    """Bench config 6's chunk at its widths (tgen-TCP, capacity 28 in
    blocks of 7, K=4, 24 sends per host per round) on 8 hosts: the K-way
    pop, the bucketed queue and the gather merge in their TPU forms."""
    cfg = _torus_config(6, side=2, per_node=2)
    cfg["experimental"]["microstep_events"] = 4
    compiled = _compile(monkeypatch, cfg, 1, topo.devices)
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_phold_chunk_compiles_for_v5e(topo, no_cache, monkeypatch):
    """The driver's PHOLD entry shape (gather merge, one-hot pop, K=1) on
    a small single-switch graph."""
    import __graft_entry__ as ge

    compiled = _compile(monkeypatch, ge._sim_config(32, 2), 1, topo.devices)
    assert compiled.memory_analysis().temp_size_in_bytes > 0


@pytest.mark.parametrize("exchange", ["alltoall", "hierarchical"])
def test_sharded_chunk_compiles_for_four_v5e(topo, no_cache, monkeypatch,
                                             exchange):
    """Bench config 2's PHOLD torus at world=4 over a mesh of the four
    described chips: the i64 barrier min (two 32-bit all-reduces on the
    TPU) and the destination-sharded exchange."""
    cfg = _torus_config(2, side=2, per_node=8)
    cfg["experimental"]["exchange"] = exchange
    compiled = _compile(monkeypatch, cfg, 4, topo.devices)
    assert "all-to-all" in compiled.as_text()


@pytest.mark.parametrize("world", [1, 4])
def test_chunk_summary_compiles_for_v5e_without_collectives(topo, no_cache,
                                                            world):
    """The driver loop's per-chunk summary (`Engine.chunk_summary`) for
    the described chips: a row of int64 numbers per shard, reduced on the
    shard, so no collective (the TPU lowers no 64-bit max all-reduce) and
    nothing crosses chips for it."""
    import re

    from jax.sharding import PartitionSpec as P

    from shadow_tpu.core.pipeline import summary_row

    cfg = _torus_config(2, side=2, per_node=8)
    sim = Simulation(ConfigOptions.from_dict(cfg), world=world)
    if world == 1:
        fn = summary_row
        one = SingleDeviceSharding(topo.devices[0])
        args = jax.tree.map(lambda x: _shape(x, one), sim.state)
    else:
        mesh = Mesh(topo.devices[:world], (E.AXIS,))
        specs = sim.engine.state_specs()
        fn = E._shard_map(summary_row, mesh, (specs,), P(E.AXIS))
        args = jax.tree.map(
            lambda x, s: _shape(x, NamedSharding(mesh, s)), sim.state, specs
        )
    text = jax.jit(fn).lower(args).compile().as_text()
    assert not re.search(
        r"\b(all-reduce|all-gather|all-to-all|collective-permute)", text
    )
