"""Pure-jnp oracle for reservoir compaction (stable boolean-mask compact)."""
from __future__ import annotations

import jax.numpy as jnp


def compact_ref(items, mask):
    """items [cap, D]; mask [cap] bool -> (compacted [cap, D] zero-padded,
    count). Stable: surviving rows keep their order."""
    cap = items.shape[0]
    mask_i = mask.astype(jnp.int32)
    pos = jnp.cumsum(mask_i) - mask_i
    dest = jnp.where(mask, pos, cap)
    out = jnp.zeros_like(items).at[dest].set(items, mode="drop")
    return out, jnp.sum(mask_i)
