"""Ahead-of-time compiles of the DSE's device path for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described `v5e:2x2` topology and refuses what the chip would refuse
(scoped-VMEM overflow, unaligned blocks), which interpret mode cannot
show. Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. Keep these compiles in this one file, for the same
reason.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config, list_archs
from repro.core import extract_workloads, get_workloads
from repro.core.dse import grid_axes
from repro.kernels.dse_eval import BLOCK_C, dse_eval, dse_eval_batched
from repro.traffic.cost_table import (DEFAULT_HW, DEFAULT_KV_LATTICE,
                                      DEFAULT_PROMPT_LATTICE,
                                      DEFAULT_SLOT_LATTICE, _lattice_shapes,
                                      build_cost_tables)
from repro.traffic.sim import SimConfig


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-device compile cannot be read back without the chip:
    keep it out of any persistent cache so later runs stay silent."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("net", ["resnet152", "densenet201"])
def test_sweep_kernel_compiles_961_grid(one_chip, no_compile_cache, net):
    """The paper's Fig. 2 path: 961 configs (padded to the block) against
    ResNet-152's 156-row layer table and DenseNet-201's 201 rows (the
    longest table in the zoo), both longer than one layer chunk."""
    C = -(-grid_axes().size ** 2 // BLOCK_C) * BLOCK_C
    L = len(get_workloads(net))
    compiled = dse_eval.lower(
        _spec((C, 2), jnp.float32, one_chip),
        _spec((L, 5), jnp.float32, one_chip),
        block_c=BLOCK_C, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_batched_sweep_kernel_compiles_cost_table_shape(one_chip,
                                                        no_compile_cache):
    """The full cost-table build: every arch's lattice points x DEFAULT_HW
    in one fused dispatch."""
    shapes = _lattice_shapes(DEFAULT_SLOT_LATTICE, DEFAULT_KV_LATTICE,
                             DEFAULT_PROMPT_LATTICE)
    lens = [len(extract_workloads(get_config(a), sh))
            for a in list_archs() for sh in shapes]
    S, L, C = len(lens), max(lens), len(DEFAULT_HW)
    assert (S, L, C) == (570, 18, 8)
    compiled = dse_eval_batched.lower(
        _spec((C, 2), jnp.float32, one_chip),
        _spec((S, L, 5), jnp.float32, one_chip),
        block_c=min(BLOCK_C, C), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lockstep_engine_compiles_yi9b_lanes(one_chip, no_compile_cache):
    """The x64 multi-lane replay engine for one yi-9b lane per DEFAULT_HW
    config at the capacity sweep's 1200-request probes."""
    from repro.traffic import lockstep

    ts = build_cost_tables(archs=["yi-9b"], backend="numpy")
    tables = [ts.table("yi-9b", h, w) for h, w in DEFAULT_HW]
    packed = lockstep._pack_tables(tables)
    dims = packed.pop("dims")
    cfg = SimConfig()
    n_max = 1200
    L = len(tables)
    with jax.enable_x64(True):
        static = {k: _spec(v.shape, v.dtype, one_chip)
                  for k, v in packed.items()}
        scal = {k: _spec((), np.float64, one_chip)
                for k in ("zero", "clock")}
        compiled = lockstep._engine(cfg.slots, cfg.ub_kib is not None,
                                    dims).lower(
            static, _spec((L, 3 * (n_max + 1)), np.float64, one_chip),
            _spec((L,), np.int64, one_chip), scal).compile()
    assert compiled.memory_analysis() is not None
