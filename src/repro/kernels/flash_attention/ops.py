"""jit wrapper for the flash attention kernel ([B,S,H,hd] layout, GQA),
interpret=True on CPU hosts (kernel body executed by the Pallas interpreter)."""
from __future__ import annotations

import functools

import jax

from . import kernel


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "block_q", "block_k")
)
def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    block_q=128, block_k=128):
    """q [B,S,H,hd]; k,v [B,T,KV,hd] -> [B,S,H,hd]; scores scaled by
    ``scale`` (None: 1/sqrt(hd))."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, T, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, T, hd)
    # GQA layout: q heads of one kv group must be adjacent per batch --
    # [B, H, ...] with H = KV * G is exactly that ordering.
    of = kernel.flash_attention_bhsd(
        qf, kf, vf, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, interpret=_on_cpu(),
    )
    return of.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
