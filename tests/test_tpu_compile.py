"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at the sizes the system runs (on-chip payloads: 2048-token
int32 rows, f32 feature rows, the bank's narrow leaves).

Nothing runs: a compile that passes says the chip's compiler accepts the
kernel (tiling, VMEM, lowering), not that its results are right -- the
interpret-mode parity tests and ``chip_smoke.py`` cover that. The topology
is described inside a fixture, so only the worker that runs these tests
loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.reservoir_compact import ops as rc_ops
from repro.kernels.tbs_step import ops as ts_ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is there
    return compiled


@pytest.mark.parametrize("cap,bcap,D,dtype", [
    (4096, 64, 2048, jnp.int32),     # LM reservoir: 2048-token rows
    (4096, 512, 8, jnp.float32),     # feature rows
])
def test_tbs_step_apply_compiles_for_v5e(one_chip, cap, bcap, D, dtype):
    _compile(
        lambda it, bt, s: ts_ops.tbs_step_apply(it, bt, s, impl="pallas"),
        one_chip, ((cap, D), dtype), ((bcap, D), dtype), ((cap,), jnp.int32),
    )


@pytest.mark.parametrize("D", [2, 1])
def test_tbs_step_apply_banked_compiles_for_v5e(one_chip, D):
    T, cap, bcap = 4096, 64, 32
    _compile(
        lambda it, bt, s: ts_ops.tbs_step_apply_banked(it, bt, s,
                                                       impl="pallas"),
        one_chip, ((T, cap, D), jnp.float32), ((T, bcap, D), jnp.float32),
        ((T, cap), jnp.int32),
    )


@pytest.mark.parametrize("cap,D,dtype", [
    (4096, 2048, jnp.int32),
    (4096, 8, jnp.float32),
    (4 * 4160, 2048, jnp.int32),     # D-R-TBS global view of 4 shards
])
def test_reservoir_compact_compiles_for_v5e(one_chip, cap, D, dtype):
    _compile(
        lambda it, m: rc_ops.reservoir_compact(it, m, impl="pallas"),
        one_chip, ((cap, D), dtype), ((cap,), jnp.bool_),
    )
