"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed with JAX, lowers each
program for a chip that is described and not attached, and refuses what
the chip would refuse (Mosaic lowering rules, block shapes, memory).
The topology is described inside a fixture, never at import, because
only one process at a time may load the TPU library and pytest-xdist
workers import every test file. Keep these tests in this one file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.serve import asa as serve_asa
from repro.xsim import backfill, events, policies
from repro.xsim.grid import XSimConfig, make_grid

# the reservation kernel's table widths: the sweep benchmark's
# (54 cells × 19 seeds, 53 rows) and the full-size centers'
# (54 cells × 4 seeds, 2,313 rows)
KERNEL_SHAPES = [(1026, 53), (216, 2313)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to an enabled persistent
    cache but cannot be read back without the chip; keep it off."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B,N", KERNEL_SHAPES)
def test_freed_matrix_compiles(one_chip, B, N):
    f32 = jax.ShapeDtypeStruct((B, N), jnp.float32, sharding=one_chip)
    run = jax.ShapeDtypeStruct((B, N), jnp.bool_, sharding=one_chip)
    compiled = backfill.freed_matrix.lower(f32, f32, run).compile()
    assert _has_kernel(compiled)


def test_sweep_tpu_mode_compiles(one_chip):
    """The sweep benchmark's program with ``freed_mode="tpu"``: the
    single-scenario kernel call inside ``sim_step``, batched by vmap."""
    cfg = XSimConfig(n_warm=16, n_backlog=12, n_arrivals=16, max_stages=9,
                     t0=3600.0)
    grid = make_grid(cfg, n_seeds=19, shrink=1 / 64.0)
    assert grid.n == 1026
    fleet = policies.init_fleet(int(grid.geo_idx.max()) + 1)
    ests = policies.scenario_estimators(fleet, jnp.asarray(grid.geo_idx), 1)
    states = _shapes(jax.eval_shape(grid.build, ests), one_chip)
    compiled = events.sweep.lower(
        states, n_steps=cfg.n_steps, chunk_steps=cfg.chunk_steps,
        freed_mode="tpu", pred_mode=cfg.pred_mode,
        naive=False).compile()
    assert _has_kernel(compiled)


def test_decision_step_compiles(one_chip):
    """The serve step over a 65,536-slot tenant table, 256 queries."""
    table = _shapes(jax.eval_shape(lambda: serve_asa.init_table(65536)),
                    one_chip)
    b = 256
    q = serve_asa.QueryBatch(
        slot=jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip),
        observed_wait=jax.ShapeDtypeStruct((b,), jnp.float32,
                                           sharding=one_chip),
        has_obs=jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one_chip))
    mask = jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(serve_asa.decision_step).lower(table, q,
                                                      mask).compile()
    mem = compiled.memory_analysis()
    table_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(table))
    assert mem.argument_size_in_bytes >= table_bytes
