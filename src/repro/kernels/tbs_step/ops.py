"""jit wrapper for the fused TBS-step payload pass.

Implementation routing (``impl``):

  * ``None``        -- auto: compiled Pallas kernel on TPU, pure-jnp oracle on
                       CPU/GPU (the oracle IS the fast path there; interpret
                       mode is for kernel-body validation, not throughput).
  * ``"pallas"``    -- compiled kernel (TPU).
  * ``"interpret"`` -- kernel body under the Pallas interpreter (CPU CI parity
                       tests execute the real kernel logic this way).
  * ``"ref"``       -- pure-jnp oracle.

All leaves reach the kernel together, as one array of 32-bit words per row:
32-bit dtypes are bitcast, narrower ones are bitcast to unsigned and
zero-extended, 64-bit ones split into two words, and bools widen to 0/1;
the leaves' words are concatenated and zero-padded to whole 128-lane rows.
The kernel copies words, so every dtype round-trips bit for bit, and one
launch moves the whole payload of a tick.

The backend-dependent choice is resolved OUTSIDE the jit boundary and passed
as a static argument, so it is part of the jit cache key: flipping
``jax.default_backend()`` between calls re-dispatches instead of silently
reusing a stale interpret/compiled decision. When called inside an outer jit
the choice is baked at the OUTER trace, which owns the cache-key problem.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel, ref

IMPLS = ("pallas", "interpret", "ref")


def resolve_impl(impl: str | None) -> str:
    """``None`` -> the compiled kernel on TPU, the jnp oracle elsewhere."""
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS} or None; got {impl!r}")
    return impl


def _to_words(leaf):
    """[T, rows, ...] any dtype -> [T, rows, W] int32."""
    dt = leaf.dtype
    if dt == jnp.bool_:
        w = leaf.astype(jnp.int32)
    elif dt.itemsize == 4:
        w = jax.lax.bitcast_convert_type(leaf, jnp.int32)
    elif dt.itemsize < 4:
        u = jnp.dtype(f"uint{8 * dt.itemsize}")
        w = jax.lax.bitcast_convert_type(leaf, u).astype(jnp.int32)
    else:  # 64-bit: two words per element
        w = jax.lax.bitcast_convert_type(leaf, jnp.int32)
    return w.reshape(leaf.shape[:2] + (-1,))


def _from_words(w, like):
    """Inverse of :func:`_to_words` for a leaf shaped and typed as ``like``."""
    dt = like.dtype
    if dt == jnp.bool_:
        return w.astype(jnp.bool_).reshape(like.shape)
    if dt.itemsize == 4:
        return jax.lax.bitcast_convert_type(w, dt).reshape(like.shape)
    if dt.itemsize < 4:
        u = jnp.dtype(f"uint{8 * dt.itemsize}")
        return jax.lax.bitcast_convert_type(w.astype(u), dt).reshape(
            like.shape)
    w = w.reshape(like.shape + (dt.itemsize // 4,))
    return jax.lax.bitcast_convert_type(w, dt)


def _pack(leaves):
    """Leaves [T, rows, ...] -> (one [T, rows, L, 128] int32 word array,
    each leaf's words per row)."""
    words = [_to_words(leaf) for leaf in leaves]
    w = jnp.concatenate(words, axis=-1)
    T, rows, W = w.shape
    L = -(-W // kernel.LANES)
    w = jnp.pad(w, ((0, 0), (0, 0), (0, L * kernel.LANES - W)))
    return w.reshape(T, rows, L, kernel.LANES), [x.shape[-1] for x in words]


def _unpack(w, likes, widths):
    """Inverse of :func:`_pack` for leaves shaped and typed as ``likes``."""
    w = w.reshape(w.shape[:2] + (-1,))
    out, at = [], 0
    for like, width in zip(likes, widths):
        out.append(_from_words(w[..., at:at + width], like))
        at += width
    return out


@functools.partial(jax.jit, static_argnames=("block", "impl"))
def _apply(items, batch, src, *, block, impl):
    """items leaves [T, cap, ...]; batch leaves [T, bcap, ...]; src [T, >= cap]."""
    leaves, tree = jax.tree_util.tree_flatten(items)
    bleaves = tree.flatten_up_to(batch)
    T, cap = leaves[0].shape[:2]
    src = src[:, :cap]
    if impl == "ref":
        out = [ref.apply_banked_ref(leaf.reshape(T, cap, -1),
                                    bleaf.reshape(T, bleaf.shape[1], -1),
                                    src).reshape(leaf.shape)
               for leaf, bleaf in zip(leaves, bleaves)]
    else:
        items_w, widths = _pack(leaves)
        words = kernel.gather(items_w, _pack(bleaves)[0], src, block=block,
                              interpret=(impl == "interpret"))
        out = _unpack(words, leaves, widths)
    return jax.tree_util.tree_unflatten(tree, out)


def tbs_step_apply_banked(items, batch_items, src, *, block=128, impl=None):
    """Banked :func:`tbs_step_apply` (DESIGN.md Sec. 13): apply T independent
    tick slot-maps ``src[T, cap]`` to T stacked reservoirs in ONE launch.
    ``items`` leaves are [T, cap, ...] (the touched keys' reservoirs, gathered
    from the bank), ``batch_items`` leaves [T, bcap, ...] (their routed
    sub-batches). Same impl routing as the single-reservoir wrapper;
    ``impl="ref"`` is the vmap-of-ref parity oracle."""
    return _apply(items, batch_items, jnp.asarray(src, jnp.int32),
                    block=block, impl=resolve_impl(impl))


def tbs_step_apply(items, batch_items, src, *, block=128, impl=None):
    """Apply the composed tick slot-map ``src[cap]`` (values in
    [0, cap + bcap): reservoir row, or ``cap +`` batch row) to an item pytree:
    one two-source payload pass for the whole tree. Leaves may have any
    trailing shape and any dtype; the copy is bit-exact."""
    add = functools.partial(jax.tree_util.tree_map, lambda a: a[None])
    out = _apply(add(items), add(batch_items),
                   jnp.asarray(src, jnp.int32)[None], block=block,
                   impl=resolve_impl(impl))
    return jax.tree_util.tree_map(lambda a: a[0], out)
