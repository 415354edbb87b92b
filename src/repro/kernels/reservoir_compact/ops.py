"""Reservoir compaction: pack the masked rows to the buffer head.

Each survivor's destination slot is an exclusive prefix sum of the mask,
computed in jnp; its inverse is a one-source slot map that the ``tbs_step``
row-gather kernel applies (slot ``cap`` reads a zero row, so rows past the
count come back zero). There is no compaction kernel of its own: the
prefix sum is O(cap) integer work, and the payload moves through the same
bit-exact gather as the sampler step.

``impl`` routes as in :mod:`repro.kernels.tbs_step.ops` (``None`` = the
compiled kernel on TPU, the jnp oracle elsewhere).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.tbs_step import ops as tbs_ops

from . import ref


@functools.partial(jax.jit, static_argnames=("block", "impl"))
def _compact(items, mask, *, block, impl):
    cap = mask.shape[0]
    m = mask.astype(jnp.int32)
    if impl == "ref":
        def one(leaf):
            out, _ = ref.compact_ref(leaf.reshape(cap, -1), mask)
            return out.reshape(leaf.shape)

        return jax.tree_util.tree_map(one, items), jnp.sum(m)
    dest = jnp.where(mask, jnp.cumsum(m) - m, cap)
    src = jnp.full((cap,), cap, jnp.int32).at[dest].set(
        jnp.arange(cap, dtype=jnp.int32), mode="drop")
    zero = jax.tree_util.tree_map(
        lambda a: jnp.zeros((1,) + a.shape[1:], a.dtype), items)
    out = tbs_ops.tbs_step_apply(items, zero, src, block=block, impl=impl)
    return out, jnp.sum(m)


def reservoir_compact(items, mask, *, block=128, impl=None):
    """items: pytree of [cap, ...] leaves; mask [cap] bool -> (compacted
    items, count int32). Stable: surviving rows keep their relative order;
    rows past the count are zero. Any dtype, bit-exact."""
    return _compact(items, mask, block=block,
                    impl=tbs_ops.resolve_impl(impl))
