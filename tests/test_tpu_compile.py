"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at the sizes the system runs (on-chip payloads: 2048-token
int32 rows, f32 feature rows, the bank's narrow leaves; the fused SSD
forward at the SSM configurations' widths).

Nothing runs: a compile that passes says the chip's compiler accepts the
kernel (tiling, VMEM, lowering), not that its results are right -- the
interpret-mode parity tests and ``chip_smoke.py`` cover that. The topology
is described inside a fixture, so only the worker that runs these tests
loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.reservoir_compact import ops as rc_ops
from repro.kernels.ssd_scan import ops as ssd_ops
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


@pytest.mark.parametrize("H,P,G,N", [
    (32, 64, 1, 128),    # mamba2-370m
    (80, 64, 1, 64),     # zamba2-2.7b
    (64, 64, 1, 128),    # granite-4.0-h-micro
])
def test_ssd_fused_compiles_for_v5e(one_chip, H, P, G, N):
    B, S = 8, 2048       # an eval chunk of the LM cell: 8 rows of 2048 tokens
    _compile(
        lambda xbc, dt, a, d: ssd_ops.ssd_fused(
            xbc, dt, a, d, head_dim=P, groups=G, state=N, chunk=256),
        one_chip, ((B, S, H * P + 2 * G * N), jnp.bfloat16),
        ((B, S, H), jnp.float32), ((H,), jnp.float32), ((H,), jnp.float32),
    )


def _lm_lowered(one_chip, fn):
    """``fn(api, params, batch)`` lowered for the described chip at a small
    mamba2 (2 layers, d_model 256, the published state and head widths)."""
    import dataclasses

    from repro.configs.mamba2_370m import CONFIG
    from repro.models import zoo

    cfg = dataclasses.replace(CONFIG, num_layers=2, d_model=256,
                              vocab_size=512)
    api = zoo.build(cfg)

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        spec, jax.eval_shape(api.init_params, jax.random.key(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 512), jnp.int32,
                                            sharding=one_chip)}
    return jax.jit(lambda p, b: fn(api, p, b)).lower(params, batch)


def test_mamba_lm_forward_lowers_to_the_ssd_kernel(one_chip):
    lowered = _lm_lowered(one_chip, lambda api, p, b: api.forward(p, b))
    assert "tpu_custom_call" in lowered.as_text()
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_mamba_lm_gradient_lowers_without_the_ssd_kernel(one_chip):
    lowered = _lm_lowered(one_chip,
                          lambda api, p, b: jax.grad(api.loss)(p, b))
    assert "tpu_custom_call" not in lowered.as_text()


def _copied_shapes(hlo: str) -> set[tuple[int, ...]]:
    """The shapes of the optimized HLO's ``copy`` instructions."""
    return {tuple(int(n) for n in m.group(1).split(","))
            for m in re.finditer(r"= \w+\[([\d,]+)\]\{[^}]*\} copy\(", hlo)}


@pytest.mark.parametrize("arch", ["mamba2_370m", "granite_4_0_h_micro"])
def test_mamba2_eval_projection_has_no_layout_copies(one_chip, arch):
    """Not differentiated, the in-projection's z and xBC come out of their
    own dots row-major, as the gate norm, the conv and the SSD kernel read
    them: no layout copy of either between the projection and the kernel
    (at 32 heads of d 1024 and at 64 of d 2048)."""
    import dataclasses
    import importlib

    from repro.models import ssm as S

    cfg = dataclasses.replace(
        importlib.import_module(f"repro.configs.{arch}").CONFIG, num_layers=2)
    B, T = 16, 2048      # an eval tick of the LM cells

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = [jax.tree_util.tree_map(spec, jax.eval_shape(
        lambda k: S.ssm_params(cfg, k), jax.random.key(i))) for i in range(2)]

    def forward(params, h):
        for p in params:
            h = h + S.apply_ssm(cfg, p, h)[0]
        return h

    hlo = jax.jit(forward).lower(
        params, spec(jax.ShapeDtypeStruct((B, T, cfg.d_model), jnp.bfloat16))
    ).compile().as_text()
    assert "tpu_custom_call" in hlo   # the SSD kernel is still there
    conv_dim = cfg.ssm_d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    assert not {(B, T, conv_dim), (B, T, cfg.ssm_d_inner)} & _copied_shapes(hlo)
