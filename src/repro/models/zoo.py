"""Unified model API over the zoo + loss functions + abstract input specs."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ShapeConfig
from repro.obs.profile import scope as _scope

from . import encdec, hybrid, mamba_lm, pattern_lm, transformer

VLM_PATCHES = 256  # stubbed vision prefix length (qwen2-vl dynamic-res stub)


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    cfg: ModelConfig
    init_params: Callable[[jax.Array], Any]
    forward: Callable[[Any, Any], jax.Array]
    prefill: Callable[[Any, Any, int], Any]
    init_decode_state: Callable[..., Any]
    decode_step: Callable[[Any, Any, jax.Array], Any]

    def loss(self, params, batch):
        return loss_fn(self.cfg, self.forward, params, batch)


def build(cfg: ModelConfig) -> ModelAPI:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        mod = transformer
    elif fam == "ssm":
        mod = mamba_lm
    elif fam == "hybrid":
        mod = pattern_lm if cfg.layer_pattern else hybrid
    elif fam == "audio":
        mod = encdec
    else:
        raise ValueError(fam)
    return ModelAPI(
        cfg=cfg,
        init_params=lambda key: mod.init_params(cfg, key),
        forward=lambda params, batch: mod.forward(cfg, params, batch),
        prefill=lambda params, batch, max_len: mod.prefill(
            cfg, params, batch, max_len
        ),
        init_decode_state=lambda batch, max_len, prefill_len=0: mod.init_decode_state(
            cfg, batch, max_len, prefill_len
        ),
        decode_step=lambda params, caches, tokens: mod.decode_step(
            cfg, params, caches, tokens
        ),
    )


def precast(cfg, params):
    """§Perf: pre-cast params to the compute dtype ONCE before the layer stack
    (per-use .astype then no-ops), so FSDP all-gathers move bf16, not f32.
    Gradients still flow to the original (f32) leaves through the cast.

    The optimization barrier pins the cast BEFORE any resharding: without it
    XLA hoists the all-gather above the (elementwise) cast and the gathers
    still move f32 (measured -- EXPERIMENTS.md §Perf H1 iter 1)."""
    if not cfg.cast_params_once:
        return params
    dt = jnp.dtype(cfg.dtype)
    casted = jax.tree_util.tree_map(
        lambda p: p.astype(dt) if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params,
    )
    return jax.lax.optimization_barrier(casted)


def loss_fn(cfg, forward, params, batch):
    """Next-token cross entropy in f32 (padded-vocab logits; labels < vocab)."""
    logits = forward(params, batch)
    tokens = batch["tokens"]
    with _scope("lm.loss"):
        # frontend prefix (vlm): loss only over the text segment
        offset = logits.shape[1] - tokens.shape[1]
        logits = logits[:, offset:]
        logits = logits[:, :-1].astype(jnp.float32)
        labels = tokens[:, 1:]
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        mask = batch.get("loss_mask")
        nll = logz - gold
        if mask is not None:
            m = mask[:, 1:].astype(jnp.float32)
            return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
        return jnp.mean(nll)


# ---------------------------------------------------------------------------
# abstract input specs (the dry-run's ShapeDtypeStruct stand-ins)
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStructs for one global batch of this (arch, shape) cell.

    [vlm]/[audio] entries: the modality frontend is a STUB -- precomputed
    patch/frame embeddings are model inputs, per the assignment."""
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    if shape.kind == "decode":
        specs = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
        return specs
    if cfg.family == "vlm":
        return {
            "tokens": jax.ShapeDtypeStruct((B, S - VLM_PATCHES), jnp.int32),
            "frontend_embeds": jax.ShapeDtypeStruct(
                (B, VLM_PATCHES, cfg.d_model), dt
            ),
        }
    if cfg.family == "audio":
        return {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "frontend_embeds": jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), dt
            ),
        }
    return {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}


def make_demo_batch(cfg: ModelConfig, key, batch: int, seq: int) -> dict:
    """Concrete random batch for smoke tests / examples."""
    k1, k2 = jax.random.split(key)
    out = {
        "tokens": jax.random.randint(k1, (batch, seq), 0, cfg.vocab_size, jnp.int32)
    }
    dt = jnp.dtype(cfg.dtype)
    if cfg.family == "vlm":
        npatch = min(8, seq // 2)
        out["tokens"] = out["tokens"][:, : seq - npatch]
        out["frontend_embeds"] = (
            jax.random.normal(k2, (batch, npatch, cfg.d_model)) * 0.02
        ).astype(dt)
    if cfg.family == "audio":
        out["frontend_embeds"] = (
            jax.random.normal(k2, (batch, cfg.encoder_seq, cfg.d_model)) * 0.02
        ).astype(dt)
    return out
