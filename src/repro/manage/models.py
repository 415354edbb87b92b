"""Model adapters for the online model-management loop (DESIGN.md Sec. 8).

A :class:`ModelAdapter` is the model-side counterpart of
:class:`repro.core.api.Sampler`: three jit/scan/vmap-safe closures with all
shapes and hyperparameters baked in,

  * ``init()``                          -> params pytree (fixed shapes)
  * ``fit(key, params, view)``          -> params retrained on a realized
                                           :class:`~repro.core.api.SampleView`
  * ``evaluate(params, batch, bcount)`` -> scalar f32 metric on the NEXT
                                           arriving batch (prequential eval:
                                           lower is better for every adapter)

Closed-form adapters (the paper's Sec. 6 applications, from
:mod:`repro.models.simple_ml`):

  ===========  ==========================  ===========================
  name         model                       metric
  ===========  ==========================  ===========================
  linreg       least-squares regression    mean squared error
  naive_bayes  multinomial NB              misclassification fraction
  knn          k-nearest-neighbour         misclassification fraction
  ===========  ==========================  ===========================

plus :func:`make_sgd_adapter`, which wraps any gradient-trained model api
(:func:`repro.train.steps.make_train_step`) so LMs from the zoo run in the
same loop: ``fit`` performs ``retrain_steps`` SGD steps on minibatches
resampled from the sample view.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp

from repro.core.api import SampleView
from repro.models import simple_ml


@dataclasses.dataclass(frozen=True, eq=False)
class ModelAdapter:
    """A model bound to its shapes; see module docstring for the contract.

    ``eq=False`` keeps identity hashing so adapters work as cache keys (the
    manage loop memoizes its compiled programs on (sampler, model, ...))."""

    name: str
    init: Callable[[], Any]
    fit: Callable[[jax.Array, Any, SampleView], Any]
    evaluate: Callable[[Any, Any, jax.Array], jax.Array]
    hyper: Mapping[str, Any]


_REGISTRY: dict[str, Callable[..., ModelAdapter]] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn

    return deco


def available_models() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def make_model(name: str, **hyper) -> ModelAdapter:
    """Construct a registered adapter, e.g. ``make_model("linreg", dim=2)``."""
    try:
        builder = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; available: {available_models()}"
        ) from None
    return builder(**hyper)


def _prefix_mean(values: jax.Array, bcount: jax.Array) -> jax.Array:
    """Mean of values[:bcount] (fixed-shape: mask + safe divide); NaN for an
    empty tick, so zero-size batches can't masquerade as perfect scores."""
    n = values.shape[0]
    w = (jnp.arange(n) < bcount).astype(jnp.float32)
    mean = jnp.sum(values * w) / jnp.maximum(bcount.astype(jnp.float32), 1.0)
    return jnp.where(bcount > 0, mean, jnp.float32(jnp.nan))


@register("linreg")
def _make_linreg(*, dim: int = 2) -> ModelAdapter:
    """Least-squares regression (paper Sec. 6.3). Items: {"x": [dim], "y": []}."""

    def fit(key, params, view: SampleView):
        del key, params
        return simple_ml.linreg_fit(view.items["x"], view.items["y"], view.mask)

    def evaluate(params, batch, bcount):
        pred = simple_ml.linreg_predict(params, batch["x"])
        return _prefix_mean((pred - batch["y"]) ** 2, bcount)

    return ModelAdapter(
        name="linreg",
        init=lambda: jnp.zeros((dim + 1,), jnp.float32),
        fit=fit,
        evaluate=evaluate,
        hyper={"dim": dim},
    )


@register("naive_bayes")
def _make_naive_bayes(*, vocab: int, num_classes: int = 2) -> ModelAdapter:
    """Multinomial NB (paper Sec. 6.4). Items: {"x": [vocab] counts, "y": []}."""

    def fit(key, params, view: SampleView):
        del key, params
        return simple_ml.nb_fit(
            view.items["x"], view.items["y"], view.mask, num_classes=num_classes
        )

    def evaluate(params, batch, bcount):
        pred = simple_ml.nb_predict(params, batch["x"])
        return _prefix_mean((pred != batch["y"]).astype(jnp.float32), bcount)

    return ModelAdapter(
        name="naive_bayes",
        init=lambda: (
            jnp.zeros((num_classes,), jnp.float32),
            jnp.zeros((num_classes, vocab), jnp.float32),
        ),
        fit=fit,
        evaluate=evaluate,
        hyper={"vocab": vocab, "num_classes": num_classes},
    )


@register("knn")
def _make_knn(*, cap: int, dim: int = 2, k: int = 7,
              num_classes: int = 100) -> ModelAdapter:
    """kNN classification (paper Sec. 6.2). Nonparametric: "params" ARE the
    stored sample (x, y, valid), so ``cap`` must match the sampler's buffer
    capacity (n for brs/sw, n+1 for rtbs, the configured cap for t/b-tbs)."""

    def fit(key, params, view: SampleView):
        del key, params
        return {"x": view.items["x"], "y": view.items["y"], "valid": view.mask}

    def evaluate(params, batch, bcount):
        pred = simple_ml.knn_predict(
            params["x"], params["y"], params["valid"], batch["x"],
            k=k, num_classes=num_classes,
        )
        return _prefix_mean((pred != batch["y"]).astype(jnp.float32), bcount)

    return ModelAdapter(
        name="knn",
        init=lambda: {
            "x": jnp.zeros((cap, dim), jnp.float32),
            "y": jnp.zeros((cap,), jnp.int32),
            "valid": jnp.zeros((cap,), bool),
        },
        fit=fit,
        evaluate=evaluate,
        hyper={"cap": cap, "dim": dim, "k": k, "num_classes": num_classes},
    )


def make_sgd_adapter(*, init_params: Callable[[], Any],
                     train_step: Callable[[Any, Any, Any], tuple],
                     init_opt_state: Callable[[Any], Any],
                     loss: Callable[[Any, Any], jax.Array],
                     batch_field: str,
                     train_batch: int,
                     retrain_steps: int,
                     row_loss: Callable[[Any, Any], jax.Array] | None = None,
                     name: str = "sgd") -> ModelAdapter:
    """Adapter for gradient-trained models (the LM path of the paper's loop).

    ``train_step(params, opt_state, batch) -> (params, opt_state, metrics)``
    is a compiled step from :func:`repro.train.steps.make_train_step`;
    ``loss(params, batch) -> scalar`` is the prequential objective. ``fit``
    draws ``retrain_steps`` minibatches of ``train_batch`` rows from the
    sample view (with replacement, proportional to the membership mask) and
    runs one train step on each -- a fixed trip count, so the whole adapter
    stays scan-safe.

    ``evaluate`` caveat: the scalar ``loss`` averages over ALL rows of the
    eval batch, so with the default ``row_loss=None`` every row must be
    valid -- drivers must not zero-pad eval batches (the sharded loop's
    ``shard_stream`` pads per-shard segments whenever |B_t| is not a multiple
    of the shard count; ``launch/train.py`` rounds the tick batch up
    accordingly). Pass ``row_loss(params, batch) -> [rows]`` to get a
    bcount-masked prefix mean instead (same convention as the closed-form
    adapters), which makes padding harmless.
    """

    def init():
        params = init_params()
        return {"params": params, "opt": init_opt_state(params)}

    def fit(key, state, view: SampleView):
        m = view.mask.astype(jnp.float32)
        probs = m / jnp.maximum(m.sum(), 1.0)

        def body(i, carry):
            state, key = carry
            key, k_sel = jax.random.split(key)
            sel = jax.random.choice(
                k_sel, probs.shape[0], shape=(train_batch,), p=probs
            )
            mb = jax.tree_util.tree_map(lambda a: a[sel], view.items)
            params, opt, _ = train_step(
                state["params"], state["opt"], {batch_field: mb}
            )
            return {"params": params, "opt": opt}, key

        def do_fit():
            out, _ = jax.lax.fori_loop(0, retrain_steps, body, (state, key))
            return out

        # empty-sample guard: nothing to train on yet
        return jax.lax.cond(view.size > 0, do_fit, lambda: state)

    if row_loss is None:
        def evaluate(state, batch, bcount):
            del bcount  # scalar loss: caller guarantees no padded rows
            rows = jax.tree_util.tree_leaves(batch)[0].shape[0]
            if rows <= train_batch or rows % train_batch:
                return loss(state["params"], {batch_field: batch})
            # an eval batch wider than a train batch runs in train-batch
            # chunks: activations and logits then never exceed what a train
            # step holds (at 64 x 2048 tokens x a 50k vocab the whole
            # batch's f32 logits alone are 26 GB). Equal chunks, so the
            # mean of chunk means is the batch mean.
            chunks = jax.tree_util.tree_map(
                lambda a: a.reshape((rows // train_batch, train_batch)
                                    + a.shape[1:]), batch)
            return jnp.mean(jax.lax.map(
                lambda c: loss(state["params"], {batch_field: c}), chunks))
    else:
        def evaluate(state, batch, bcount):
            return _prefix_mean(
                row_loss(state["params"], {batch_field: batch}), bcount
            )

    return ModelAdapter(
        name=name,
        init=init,
        fit=fit,
        evaluate=evaluate,
        hyper={"train_batch": train_batch, "retrain_steps": retrain_steps,
               "batch_field": batch_field},
    )
