"""Flash attention Pallas-TPU kernel: online softmax over [block_q, block_k]
VMEM tiles; grid = (batch*q_heads, nq, nk) with the kv axis innermost so the
f32 accumulator scratch persists across kv steps. GQA: the kv BlockSpec
index-maps q-head bh -> kv head bh // group_size. Causal and sliding-window
masking are positional; fully-masked kv tiles are skipped via @pl.when."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30  # python float: jnp scalars would be captured consts in the kernel


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, window, block_q, block_k, nk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos0 = qi * block_q
    kpos0 = ki * block_k
    # skip tiles that are entirely masked out (causal upper / window lower)
    run = jnp.bool_(True)
    if causal:
        run &= kpos0 <= qpos0 + block_q - 1
    if window:
        run &= kpos0 + block_k - 1 > qpos0 - window

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)              # [bq, hd]
        k = k_ref[0].astype(jnp.float32)              # [bk, hd]
        v = v_ref[0].astype(jnp.float32)              # [bk, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                      # [bq, bk]
        qpos = qpos0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def flash_attention_bhsd(q, k, v, *, causal=True, window=0, scale=None,
                         block_q=128, block_k=128, interpret=False):
    """q: [BH, S, hd]; k, v: [BKV, T, hd] with BH = BKV * group. -> [BH, S, hd].
    Scores are scaled by ``scale`` (None: 1/sqrt(hd))."""
    BH, S, hd = q.shape
    BKV, T, _ = k.shape
    group = BH // BKV
    bq = min(block_q, S)
    bk = min(block_k, T)
    assert S % bq == 0 and T % bk == 0
    nq, nk = S // bq, T // bk
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    grid = (BH, nq, nk)
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window,
            block_q=bq, block_k=bk, nk=nk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, qi, ki: (bh // group, ki, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, qi, ki: (bh // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
