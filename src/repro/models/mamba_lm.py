"""Pure Mamba2 (SSD) language model -- attention-free (mamba2-370m)."""
from __future__ import annotations

import jax

from repro.obs.profile import scope as _scope

from . import layers as L
from . import ssm as S
from . import transformer as T


def init_params(cfg, key):
    ke, km, ko = jax.random.split(key, 3)
    pd = L.param_dtype(cfg)
    params = {
        "embed": L.embed_init(ke, (cfg.padded_vocab, cfg.d_model), pd),
        "blocks": jax.vmap(
            lambda k: {"ln": L.norm_params(cfg, cfg.d_model),
                       "ssm": S.ssm_params(cfg, k)}
        )(jax.random.split(km, cfg.num_layers)),
        "final_norm": L.norm_params(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(
            ko, (cfg.d_model, cfg.padded_vocab), pd, fan_in=cfg.d_model
        )
    return params


def forward(cfg, params, batch):
    from . import zoo as _zoo
    params = _zoo.precast(cfg, params)
    x, _ = T._embed_inputs(cfg, params, batch)

    def layer(h, p):
        with _scope("lm.norm"):
            u = L.apply_norm(cfg, p["ln"], h)
        y, _ = S.apply_ssm(cfg, p["ssm"], u)
        return h + y, None

    fn = jax.checkpoint(layer) if cfg.remat else layer
    x, _ = T.scan_or_unroll(cfg, fn, x, params["blocks"])
    with _scope("lm.head"):
        x = L.apply_norm(cfg, params["final_norm"], x)
        return T.logits_from_hidden(cfg, params, x)


def prefill(cfg, params, batch, max_len):
    """Run the full prompt through the chunked SSD path, returning
    (last-position logits, per-layer SSMCaches). max_len unused: SSM state
    is O(1) in context length."""
    from . import zoo as _zoo
    params = _zoo.precast(cfg, params)
    del max_len
    x, _ = T._embed_inputs(cfg, params, batch)

    def layer(h, p):
        y, cache = S.apply_ssm(cfg, p["ssm"], L.apply_norm(cfg, p["ln"], h))
        return h + y, cache

    x, caches = T.scan_or_unroll(cfg, layer, x, params["blocks"])
    x = L.apply_norm(cfg, params["final_norm"], x[:, -1:])
    return T.logits_from_hidden(cfg, params, x), caches


def init_decode_state(cfg, batch, max_len, prefill_len=0):
    del max_len, prefill_len  # SSM state is O(1) in context length
    dt = L.compute_dtype(cfg)
    c = S.init_ssm_cache(cfg, batch, dt)
    return T.stack_layer_tree(cfg, c, cfg.num_layers)


def decode_step(cfg, params, caches, tokens):
    from . import zoo as _zoo
    params = _zoo.precast(cfg, params)
    dt = L.compute_dtype(cfg)
    x = params["embed"].astype(dt)[tokens]

    def layer(h, inp):
        p, cache = inp
        y, cache = S.decode_ssm(cfg, p["ssm"], L.apply_norm(cfg, p["ln"], h), cache)
        return h + y, cache

    if isinstance(caches, list):
        x, caches = T.unrolled_decode(layer, x, params["blocks"], caches)
    else:
        x, caches = jax.lax.scan(layer, x, (params["blocks"], caches))
    x = L.apply_norm(cfg, params["final_norm"], x)
    return T.logits_from_hidden(cfg, params, x), caches
