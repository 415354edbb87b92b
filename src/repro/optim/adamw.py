"""AdamW with decoupled weight decay + global-norm clipping (pure pytrees).

Master weights / moments are kept in the params' dtype (f32 by default) and
sharded with the same PartitionSpecs as the params (fully-sharded optimizer
state; DESIGN.md Sec. 6)."""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.obs.profile import scope as _scope


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


def adamw_init(params: Any) -> dict:
    zeros = lambda p: jnp.zeros_like(p)
    return {
        "m": jax.tree_util.tree_map(zeros, params),
        "v": jax.tree_util.tree_map(zeros, params),
        "count": jnp.zeros((), jnp.int32),
    }


def global_norm(tree: Any) -> jax.Array:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree_util.tree_leaves(tree))
    )


def clip_by_global_norm(grads: Any, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads
    ), norm


def adamw_update(cfg: AdamWConfig, grads, opt_state, params, lr_scale=1.0):
    """One AdamW step. Returns (new_params, new_opt_state, metrics)."""
    with _scope("train.adamw"):
        grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
        count = opt_state["count"] + 1
        b1c = 1.0 - cfg.b1 ** count.astype(jnp.float32)
        b2c = 1.0 - cfg.b2 ** count.astype(jnp.float32)
        lr = cfg.lr * jnp.asarray(lr_scale, jnp.float32)

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m = cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * g32
            v = cfg.b2 * v.astype(jnp.float32) + (1 - cfg.b2) * g32 * g32
            mh = m / b1c
            vh = v / b2c
            step = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
            newp = p.astype(jnp.float32) - lr * step
            return newp.astype(p.dtype), m.astype(p.dtype), v.astype(p.dtype)

        flat_p, tdef = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_m = jax.tree_util.tree_leaves(opt_state["m"])
        flat_v = jax.tree_util.tree_leaves(opt_state["v"])
        out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = jax.tree_util.tree_unflatten(tdef, [o[0] for o in out])
        new_m = jax.tree_util.tree_unflatten(tdef, [o[1] for o in out])
        new_v = jax.tree_util.tree_unflatten(tdef, [o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "count": count}, {"grad_norm": gnorm}
