"""The layer-pattern hybrid (granite-4.0-h) on the CPU: its stack follows
the pattern, the blocked attention with a configured scale and no RoPE is
the dense one, and decoding through the caches reproduces the forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, get_smoke_config
from repro.models import attention as A
from repro.models import pattern_lm, zoo

SMOKE = get_smoke_config("granite-4.0-h-micro")


def test_runs_group_like_layers():
    cfg = get_config("granite-4.0-h-micro")
    assert pattern_lm.runs(cfg) == [("mamba", 5), ("attention", 1),
                                    ("mamba", 9), ("attention", 1),
                                    ("mamba", 9), ("attention", 1),
                                    ("mamba", 9), ("attention", 1),
                                    ("mamba", 4)]
    assert cfg.rope_theta == 0 and cfg.attention_scale == 1 / 64


def test_params_are_stacked_per_run():
    api = zoo.build(SMOKE)
    shapes = jax.eval_shape(api.init_params, jax.random.key(0))
    assert [jax.tree_util.tree_leaves(r)[0].shape[0]
            for r in shapes["layers"]] == [1, 1, 1]
    assert "ssm" in shapes["layers"][0] and "attn" in shapes["layers"][1]
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == SMOKE.param_count() + (SMOKE.padded_vocab
                                       - SMOKE.vocab_size) * SMOKE.d_model


@pytest.mark.parametrize("scale", [0.0, 1 / 64])
def test_chunked_sdpa_with_scale_and_no_rope_is_dense_sdpa(scale):
    cfg = dataclasses.replace(SMOKE, attention_scale=scale, rope_theta=0.0)
    ks = jax.random.split(jax.random.key(0), 3)
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    with jax.default_matmul_precision("highest"):
        dense = A.sdpa(cfg, q, k, v)
        blocked = A.chunked_sdpa(cfg, q, k, v, block_q=16, block_k=16)
        g_dense = jax.grad(lambda q: jnp.sum(A.sdpa(cfg, q, k, v) ** 2))(q)
        g_blocked = jax.grad(lambda q: jnp.sum(A.chunked_sdpa(
            cfg, q, k, v, block_q=16, block_k=16) ** 2))(q)
    np.testing.assert_allclose(blocked, dense, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g_blocked, g_dense, rtol=1e-4, atol=1e-4)
    # the scale is the configured one: a different scale moves the output
    other = A.sdpa(dataclasses.replace(cfg, attention_scale=0.5), q, k, v)
    assert float(jnp.abs(other - dense).max()) > 1e-2


def test_attn_chunk_sends_attention_through_the_blocked_path(monkeypatch):
    cfg = dataclasses.replace(SMOKE, attn_chunk=8)
    api = zoo.build(cfg)
    params = api.init_params(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    calls = []
    blocked = A.chunked_sdpa
    monkeypatch.setattr(A, "chunked_sdpa",
                        lambda *a, **k: calls.append(k) or blocked(*a, **k))
    got = api.forward(params, {"tokens": toks})
    assert calls and calls[0]["block_q"] == 8
    want = zoo.build(dataclasses.replace(cfg, attn_chunk=0)).forward(
        params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_decode_matches_forward():
    """Teacher-forced decode through the SSM and KV caches reproduces the
    forward's logits."""
    cfg = dataclasses.replace(SMOKE, dtype="float32")
    api = zoo.build(cfg)
    params = api.init_params(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(4), (2, 8), 0, cfg.vocab_size)
    full = api.forward(params, {"tokens": toks})
    caches = api.init_decode_state(2, max_len=12, prefill_len=0)
    outs = []
    for t in range(8):
        logits, caches = api.decode_step(params, caches, toks[:, t:t + 1])
        outs.append(logits[:, 0])
    np.testing.assert_allclose(full, jnp.stack(outs, axis=1), rtol=1e-3,
                               atol=1e-4)
    last, _ = api.prefill(params, {"tokens": toks}, 12)
    np.testing.assert_allclose(last[:, 0], full[:, -1], rtol=1e-3, atol=1e-4)


def test_multipliers_with_the_residual_branches_off():
    """With the residual multiplier 0 every layer adds nothing: the logits
    are the scaled embedding's, normed, over the tied embedding, divided by
    the logit scaling."""
    from repro.models import layers as L

    cfg = dataclasses.replace(SMOKE, residual_multiplier=0.0,
                              dtype="float32")
    api = zoo.build(cfg)
    params = api.init_params(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 8), 0, cfg.vocab_size)
    x = params["embed"][toks] * cfg.embedding_multiplier
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        got = api.forward(params, {"tokens": toks})
        want = x @ params["embed"].T / cfg.logits_scaling
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_multipliers_multiply_at_float32():
    # a multiplier rounded to bfloat16 first (0.22 -> 0.2197) would scale
    # every branch by one wrong factor; at float32 the rounding is unbiased
    x = jax.random.normal(jax.random.key(0), (1 << 14,)).astype(jnp.bfloat16)
    exact = x.astype(jnp.float32) * 0.22

    def bias(y):
        return float(jnp.mean(y.astype(jnp.float32) / exact) - 1)

    assert abs(bias(pattern_lm._times(x, 0.22))) < 2e-4
    assert bias(x * jnp.bfloat16(0.22)) < -1e-3
