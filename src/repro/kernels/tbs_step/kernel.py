"""Fused TBS-step Pallas-TPU kernel: the whole tick's buffer rewrite as one pass.

The sampler step (decay-downsample slot map + batch insert + victim
replacement, composed by :mod:`repro.core.rtbs` into one ``src`` map) is a
two-source row gather: output row i of key t pulls reservoir row
``src[t, i]`` when ``src[t, i] < cap``, else batch row ``src[t, i] - cap``.

Each output row is one DMA from its source row in HBM straight to its slot
in the output in HBM. The payload never passes through VMEM, so VMEM use is
nil whatever the row width D or the capacity, and no arithmetic touches it:
:mod:`.ops` hands the kernel 32-bit words, so every dtype comes back bit for
bit. The grid is (key, row block); a row block's slice of ``src`` sits in
SMEM, and at most ``INFLIGHT`` row copies are outstanding at a time.

Rows are laid out ``[L, 128]`` (words padded to whole 128-lane rows): the
copy then slices only the untiled row axis, which the DMA engine can address
one row at a time."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
INFLIGHT = 16


def _kernel(src_ref, items_hbm, batch_hbm, out_hbm, sem, *, cap, bcap, block):
    t = pl.program_id(0)
    base = pl.program_id(1) * block
    n = jnp.minimum(block, cap - base)

    def wait_one():
        # waits for one row's bytes: every copy of the step is one row
        pltpu.make_async_copy(items_hbm.at[t, pl.ds(0, 1)],
                              out_hbm.at[t, pl.ds(0, 1)], sem).wait()

    def start(r, carry):
        @pl.when(r >= INFLIGHT)
        def _():
            wait_one()

        s = src_ref[0, r]
        dst = out_hbm.at[t, pl.ds(base + r, 1)]

        @pl.when(s < cap)
        def _():
            row = jnp.maximum(s, 0)
            pltpu.make_async_copy(items_hbm.at[t, pl.ds(row, 1)], dst,
                                  sem).start()

        @pl.when(s >= cap)
        def _():
            row = jnp.minimum(s - cap, bcap - 1)
            pltpu.make_async_copy(batch_hbm.at[t, pl.ds(row, 1)], dst,
                                  sem).start()

        return carry

    def drain(r, carry):
        wait_one()
        return carry

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, jnp.minimum(n, INFLIGHT), drain, 0)


def gather(items, batch, src, *, block=128, interpret=False):
    """items [T, cap, L, 128] int32; batch [T, bcap, L, 128] int32;
    src [T, cap] int32 -> out [T, cap, L, 128] with out[t, i] =
    items[t, src[t, i]] if src[t, i] < cap else batch[t, src[t, i] - cap]
    (indices clamped into range as in the oracle,
    :func:`repro.kernels.tbs_step.ref.apply_banked_ref`)."""
    T, cap = items.shape[:2]
    bcap = batch.shape[1]
    assert items.dtype == batch.dtype == jnp.int32, (items.dtype, batch.dtype)
    assert items.shape[2:] == batch.shape[2:] and items.shape[-1] == LANES
    assert src.shape == (T, cap), (src.shape, T, cap)
    b = min(block, cap)
    nb = pl.cdiv(cap, b)
    src = jnp.pad(src.astype(jnp.int32), ((0, 0), (0, nb * b - cap)))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, cap=cap, bcap=bcap, block=b),
        grid=(T, nb),
        in_specs=[
            pl.BlockSpec((None, 1, b), lambda t, i: (t * nb + i, 0, 0),
                         memory_space=pltpu.SMEM),
            hbm, hbm,
        ],
        out_specs=hbm,
        out_shape=jax.ShapeDtypeStruct(items.shape, jnp.int32),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        name="tbs_step_gather",
        interpret=interpret,
    )(src.reshape(T * nb, 1, b), items, batch)
