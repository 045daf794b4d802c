"""Ahead-of-time Mosaic compiles of the token kernels for a described TPU v5e.

Interpret mode (every other kernel test) runs the kernel bodies through
XLA on the CPU, so it cannot see what the TPU compiler refuses: primitives
Mosaic has no lowering for, unaligned slices, VMEM overflow.  These tests
compile ``tick_step_pallas`` (both modes) and ``token_select_pallas`` with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology, at
paper geometry (S=8, J=16, W=8), fleet geometry (S=128, J=1024, W=4), the
128-node cell's (S=128, J=8, W=8: themis in one grid step) and the
multi-tenant cell's (S=128, J=64, W=8: themis mode, and the per-server
``group-then-user-fair`` policy chain with no operand rounded to
bfloat16).  Nothing runs; a compile that passes is not a chip run.

The topology is described inside a module fixture — never at import, in
``conftest.py`` or in ``parametrize`` — so every pytest worker collects the
same tests and only the worker given this file loads the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.global_sync import local_segments
from repro.core.job_table import JobTable
from repro.core.policy import Policy
from repro.kernels.tick_step.kernel import tick_step_pallas
from repro.kernels.tick_step.ref import MODES
from repro.kernels.token_select.kernel import token_select_pallas

GEOMETRIES = {"paper": (8, 16, 8), "fleet": (128, 1024, 4),
              "fleet128": (128, 8, 8)}
#: The multi-tenant cell: 64 job slots on 128 nodes, themis only.
TENANTS128 = (128, 64, 8)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A described-topology compile is written to the persistent cache but
    # can never be read back without a chip: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("mode", MODES)
def test_tick_step_compiles_for_v5e(one_chip, mode, geometry):
    s, j, w = GEOMETRIES[geometry]
    window = (_spec((s, j, w), jnp.float32, one_chip) if mode == "fifo"
              else None)
    args = (_spec((s, j), jnp.float32, one_chip),
            _spec((s, j), jnp.int32, one_chip),
            window,
            _spec((s, w), jnp.bool_, one_chip),
            _spec((s, w), jnp.float32, one_chip))
    text = _compiled_text(
        functools.partial(tick_step_pallas, mode=mode, interpret=False), args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_token_select_compiles_for_v5e(one_chip, geometry):
    s, j, w = GEOMETRIES[geometry]
    args = (_spec((s, j), jnp.float32, one_chip),
            _spec((s, j), jnp.int32, one_chip),
            _spec((s, w), jnp.float32, one_chip))
    text = _compiled_text(
        functools.partial(token_select_pallas, interpret=False), args)
    assert "tpu_custom_call" in text


def test_tenant_cell_kernels_compile_for_v5e(one_chip):
    s, j, w = TENANTS128
    text = _compiled_text(
        functools.partial(tick_step_pallas, mode="themis", interpret=False),
        (_spec((s, j), jnp.float32, one_chip),
         _spec((s, j), jnp.int32, one_chip), None,
         _spec((s, w), jnp.bool_, one_chip),
         _spec((s, w), jnp.float32, one_chip)))
    assert "tpu_custom_call" in text


def test_group_then_user_fair_chain_compiles_for_v5e(one_chip):
    """The per-server chain of the multi-tenant cell: three levels over 64
    slots, ``[1, 64]``, ``[64, 64²]`` and ``[64², 64]`` on each of 128
    servers.  The product is taken at full precision, so the chip's
    compiler rounds none of its operands to bfloat16."""
    s, j, _ = TENANTS128
    policy = Policy.parse("group-then-user-fair")

    def chain(active, user, group, demand):
        ones = jnp.ones((j,), jnp.float32)
        table = JobTable(active=active, user_id=user, group_id=group,
                         size=ones, priority=ones, last_heartbeat=ones)
        return local_segments(policy, table, demand)

    compiled = jax.jit(chain).lower(
        _spec((j,), jnp.bool_, one_chip), _spec((j,), jnp.int32, one_chip),
        _spec((j,), jnp.int32, one_chip),
        _spec((s, j), jnp.bool_, one_chip)).compile()
    assert "bf16" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9
