"""Faults planted in the timed path of the LM cell, each a way the program
could go wrong that the comparison has to catch.

Each takes an object with pytest's ``setattr(target, name, value)`` and
patches the program underneath the harness; the next trace of the cell's
programs (after ``jax.clear_caches()``) runs broken. The tests plant them
at a small size; ``bench/tools/control.py`` reads them on the chip at the
cell's own size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def optimizer_unchanged(mp):
    """The AdamW step returns its parameters and state unchanged."""
    mp.setattr("repro.train.steps.adamw_update",
               lambda cfg, g, opt, params, lr_scale=1.0:
               (params, opt, {"grad_norm": 0.0}))


def sampler_unchanged(mp):
    """The sampler's step returns its state unchanged."""
    import repro.core.rtbs as rtbs

    mp.setattr(rtbs, "step", lambda key, state, *a, **k: state)


def half_batch(mp):
    """Each training step sees half of its minibatch, the mean taken over
    the rest."""
    import repro.train.steps as steps

    make = steps.make_train_step

    def halved(*a, **k):
        step = make(*a, **k)

        def train_step(params, opt, batch):
            b = batch["tokens"].shape[0] // 2
            return step(params, opt, {"tokens": batch["tokens"][:b]})

        return train_step

    mp.setattr(steps, "make_train_step", halved)


def token_altered(mp):
    """The payload pass writes every stored sequence with its first token
    off by one."""
    import repro.kernels.tbs_step.ops as ops

    apply = ops.tbs_step_apply

    def altered(items, batch, src, **k):
        out = apply(items, batch, src, **k)
        return jax.tree_util.tree_map(lambda a: a.at[:, 0].add(1), out)

    mp.setattr(ops, "tbs_step_apply", altered)


def no_evict(mp):
    """Once the reservoir is saturated, its tick map keeps every slot: the
    bookkeeping goes on, but no arrival replaces an old item."""
    import repro.core.rtbs as rtbs

    tick_map = rtbs.tick_map

    def keep(key, nfull, weight, total_weight, bcount, decay, *, cap, bcap,
             n):
        src, c3, w_new = tick_map(key, nfull, weight, total_weight, bcount,
                                  decay, cap=cap, bcap=bcap, n=n)
        full = (total_weight >= n) & (w_new >= n)
        return jnp.where(full, jnp.arange(cap, dtype=jnp.int32), src), c3, \
            w_new

    mp.setattr(rtbs, "tick_map", keep)


FAULTS = {f.__name__: f for f in (optimizer_unchanged, sampler_unchanged,
                                  half_batch, token_altered, no_evict)}
