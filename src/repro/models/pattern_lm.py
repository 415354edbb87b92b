"""Hybrid LM driven by a layer pattern (granite-4.0-h): layer i's mixer is
``cfg.layer_pattern[i]``, "mamba" (a Mamba2 block, ``ssm.apply_ssm``) or
"attention" (GQA self-attention, ``attention.self_attention``), and every
mixer is followed by the MLP (``layers.apply_mlp``). Both are pre-norm
residual branches scaled by ``cfg.residual_multiplier``; the embeddings are
multiplied by ``cfg.embedding_multiplier`` and the logits divided by
``cfg.logits_scaling`` (muP). [huggingface.co/ibm-granite/granite-4.0-h-micro]
Each multiplier multiplies in float32 and the product is rounded once to the
compute dtype, as the published model's bfloat16 arithmetic does: the
multiplier rounded to bfloat16 (0.22 to 0.2197) would scale every branch,
and so every layer's gradient, by the same wrong factor.

The parameters of each run of like layers are stacked on a leading axis and
scanned (``params["layers"]`` holds one stacked tree per run, in order), each
layer remat'd and its mixer remat'd again inside it, so the compiled program
has one loop per run, not per layer.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp

from repro.obs.profile import scope as _scope

from . import attention as A
from . import layers as L
from . import ssm as S
from . import transformer as T


def runs(cfg) -> list[tuple[str, int]]:
    """(mixer kind, number of layers) of each run of like layers."""
    assert len(cfg.layer_pattern) == cfg.num_layers, cfg.layer_pattern
    return [(kind, len(list(g)))
            for kind, g in itertools.groupby(cfg.layer_pattern)]


def _layer_params(cfg, kind, key):
    k1, k2 = jax.random.split(key)
    if kind == "mamba":
        mixer = {"ssm": S.ssm_params(cfg, k1)}
    elif kind == "attention":
        mixer = {"attn": A.attn_params(cfg, k1)}
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return {"ln1": L.norm_params(cfg, cfg.d_model), **mixer,
            "ln2": L.norm_params(cfg, cfg.d_model),
            "mlp": L.mlp_params(cfg, k2, cfg.d_model, cfg.d_ff)}


def init_params(cfg, key):
    ke, kl, ko = jax.random.split(key, 3)
    keys = jax.random.split(kl, cfg.num_layers)
    layers, i = [], 0
    for kind, n in runs(cfg):
        layers.append(jax.vmap(lambda k, kind=kind: _layer_params(cfg, kind, k))(
            keys[i:i + n]))
        i += n
    pd = L.param_dtype(cfg)
    params = {
        "embed": L.embed_init(ke, (cfg.padded_vocab, cfg.d_model), pd),
        "layers": layers,
        "final_norm": L.norm_params(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(
            ko, (cfg.d_model, cfg.padded_vocab), pd, fan_in=cfg.d_model
        )
    return params


def _mamba(cfg, p, u, positions):
    y, _ = S.apply_ssm(cfg, p["ssm"], u)
    return y


def _attention(cfg, p, u, positions):
    with _scope("lm.attn"):
        return A.self_attention(cfg, p["attn"], u, positions)


def _mlp(cfg, p, u):
    with _scope("lm.mlp"):
        return L.apply_mlp(cfg, p, u)


def _times(x, c):
    """``x * c`` at float32, rounded once to ``x``'s dtype."""
    return (x.astype(jnp.float32) * c).astype(x.dtype)


def _layer(cfg, p, h, mix):
    """One layer: ``h + r * mixer(norm(h))``, then ``h + r * mlp(norm(h))``.
    ``mix(p, u) -> (y, cache)``."""
    r = cfg.residual_multiplier
    with _scope("lm.norm"):
        u = L.apply_norm(cfg, p["ln1"], h)
    y, cache = mix(p, u)
    h = h + _times(y, r)
    with _scope("lm.norm"):
        u = L.apply_norm(cfg, p["ln2"], h)
    return h + _times(_mlp(cfg, p["mlp"], u), r), cache


def _embed(cfg, params, batch):
    x, positions = T._embed_inputs(cfg, params, batch)
    return _times(x, cfg.embedding_multiplier), positions


def _logits(cfg, params, x):
    return _times(T.logits_from_hidden(cfg, params, x),
                  1.0 / cfg.logits_scaling)


def forward(cfg, params, batch):
    from . import zoo as _zoo
    params = _zoo.precast(cfg, params)
    x, positions = _embed(cfg, params, batch)

    def mix(p, u):
        fn = _attention if "attn" in p else _mamba
        if cfg.remat:
            # remat'd within the remat'd layer: the backward then holds the
            # mixer's or the MLP's intermediates, never both (what fits the
            # granite cell's retrain on one 16 GB chip)
            fn = jax.checkpoint(fn, static_argnums=(0,))
        return fn(cfg, p, u, positions), None

    def layer(h, p):
        return _layer(cfg, p, h, mix)

    fn = jax.checkpoint(layer) if cfg.remat else layer
    for p in params["layers"]:
        x, _ = T.scan_or_unroll(cfg, fn, x, p)
    with _scope("lm.head"):
        x = L.apply_norm(cfg, params["final_norm"], x)
        return _logits(cfg, params, x)


def prefill(cfg, params, batch, max_len):
    """The prompt through every layer: (last-position logits, one stacked
    cache tree per run: SSMCaches for a Mamba2 run, KVCaches for an
    attention run)."""
    from . import zoo as _zoo
    params = _zoo.precast(cfg, params)
    x, positions = _embed(cfg, params, batch)

    def mix(p, u):
        if "attn" in p:
            return A.prefill_attention(cfg, p["attn"], u, positions, max_len)
        return S.apply_ssm(cfg, p["ssm"], u)

    caches = []
    for p in params["layers"]:
        x, c = T.scan_or_unroll(cfg, lambda h, pl: _layer(cfg, pl, h, mix), x, p)
        caches.append(c)
    x = L.apply_norm(cfg, params["final_norm"], x[:, -1:])
    return _logits(cfg, params, x), caches


def init_decode_state(cfg, batch, max_len, prefill_len=0):
    dt = L.compute_dtype(cfg)
    out = []
    for kind, n in runs(cfg):
        c = (S.init_ssm_cache(cfg, batch, dt) if kind == "mamba"
             else A.init_cache(cfg, batch, max_len, dt, prefill_len))
        out.append(T.stack_layer_tree(cfg, c, n))
    return out


def decode_step(cfg, params, caches, tokens):
    from . import zoo as _zoo
    params = _zoo.precast(cfg, params)
    x = params["embed"].astype(L.compute_dtype(cfg))[tokens]
    x = _times(x, cfg.embedding_multiplier)

    def layer(h, inp):
        p, cache = inp

        def mix(p, u):
            if "attn" in p:
                return A.decode_attention(cfg, p["attn"], u, cache)
            return S.decode_ssm(cfg, p["ssm"], u, cache)

        return _layer(cfg, p, h, mix)

    out = []
    for p, c in zip(params["layers"], caches):
        if isinstance(c, list):
            x, c = T.unrolled_decode(layer, x, p, c)
        else:
            x, c = jax.lax.scan(layer, x, (p, c))
        out.append(c)
    x = L.apply_norm(cfg, params["final_norm"], x)
    return _logits(cfg, params, x), out
