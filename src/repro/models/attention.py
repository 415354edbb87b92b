"""GQA/MQA attention with causal + sliding-window masking, RoPE/M-RoPE,
contiguous KV caches (ring-buffered under SWA so decode memory is bounded).

Two math paths: ``xla`` (pure jnp, used for dry-run/roofline -- XLA fuses this
well on TPU) and ``pallas`` (the flash_attention kernel in repro/kernels,
validated against the same reference). Selected by cfg.attention_impl.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import layers as L


def attn_params(cfg, key, *, cross=False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    pd = L.param_dtype(cfg)
    p = {
        "wq": L.dense_init(ks[0], (d, H, hd), pd, fan_in=d),
        "wk": L.dense_init(ks[1], (d, KV, hd), pd, fan_in=d),
        "wv": L.dense_init(ks[2], (d, KV, hd), pd, fan_in=d),
        "wo": L.dense_init(ks[3], (H, hd, d), pd, fan_in=H * hd),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((H, hd), pd)
        p["bk"] = jnp.zeros((KV, hd), pd)
        p["bv"] = jnp.zeros((KV, hd), pd)
    return p


def _project_qkv(cfg, p, xq, xkv):
    dt = xq.dtype
    q = jnp.einsum("bsd,dhk->bshk", xq, p["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", xkv, p["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", xkv, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.kv_replication > 1:
        # kv-head replication: duplicate kv heads so caches shard TP-ways and
        # every device's q-head block sees exactly its own kv head (DESIGN.md §6)
        k = jnp.repeat(k, cfg.kv_replication, axis=2)
        v = jnp.repeat(v, cfg.kv_replication, axis=2)
    return q, k, v


def sdpa(cfg, q, k, v, *, q_positions=None, k_positions=None, causal=True,
         window=0, k_valid=None):
    """Scaled-dot-product GQA attention (the `xla` path; also the kernels' oracle).

    q [B,S,H,hd]; k,v [B,T,KV,hd]. Masks: causal (by absolute positions),
    sliding window (0 = full), and k_valid [B,T] (cache validity)."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32)
    scores = scores * jnp.float32(cfg.resolved_attention_scale)
    if q_positions is None:
        q_positions = jnp.arange(S)[None]
    if k_positions is None:
        k_positions = jnp.arange(T)[None]
    qp = q_positions[:, None, None, :, None]  # [B,1,1,S,1]
    kp = k_positions[:, None, None, None, :]  # [B,1,1,1,T]
    mask = jnp.ones((B, 1, 1, S, T), bool)
    if causal:
        mask &= kp <= qp
    if window:
        mask &= kp > qp - window
    if k_valid is not None:
        mask &= k_valid[:, None, None, None, :]
    scores = jnp.where(mask, scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, S, H, hd)


def chunked_sdpa(cfg, q, k, v, *, causal=True, window=0, block_q=1024,
                 block_k=1024):
    """Online-softmax (flash-style) attention in pure lax: scan over query
    blocks, remat'd inner scan over key blocks, each key step remat'd too (on
    a v5e the retrain's gradient through the granite cell's 512-blocks turns
    NaN without it, while the CPU's matches the plain reference). Peak memory
    O(block_q*block_k) instead of O(S*T) -- required for the 32k cells. Same
    math as :func:`sdpa` (tested); block-masked waste on causal lower blocks
    is accounted for in the roofline (EXPERIMENTS.md §Roofline note)."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = min(block_q, S), min(block_k, T)
    nq, nk = S // bq, T // bk
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    scale = jnp.float32(cfg.resolved_attention_scale)
    NEG = jnp.float32(-1e30)

    qb = q.reshape(B, nq, bq, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)

    def one_q_block(args):
        qi, idx = args
        qpos = idx * bq + jnp.arange(bq)

        def kv_step(carry, j):
            acc, m, l = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * bk, bk, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * bk, bk, axis=1)
            s = jnp.einsum("bqkgh,btkh->bkgqt", qi, kj).astype(jnp.float32) * scale
            kpos = j * bk + jnp.arange(bk)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, None, None], s, NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(mask[None, None, None],
                          jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkh->bkgqh", p.astype(q.dtype), vj
            ).astype(jnp.float32)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((B, KV, G, bq, hd), jnp.float32)
        m0 = jnp.full((B, KV, G, bq), NEG)
        l0 = jnp.zeros((B, KV, G, bq), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            jax.checkpoint(kv_step), (acc0, m0, l0), jnp.arange(nk)
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)  # [B,KV,G,bq,hd]

    outs = jax.lax.map(jax.checkpoint(one_q_block), (qb, jnp.arange(nq)))
    # [nq,B,KV,G,bq,hd] -> [B,S,H,hd]
    return outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, H, hd)


def _attend(cfg, q, k, v, **kw):
    if cfg.attention_impl == "pallas":
        from repro.kernels.flash_attention import ops as fa

        if kw.get("k_valid") is None and q.shape[1] == k.shape[1]:
            return fa.flash_attention(
                q, k, v, causal=kw.get("causal", True), window=kw.get("window", 0),
                scale=cfg.resolved_attention_scale,
            )
    S, T = q.shape[1], k.shape[1]
    if cfg.attn_chunk and S >= cfg.attn_chunk and T >= cfg.attn_chunk \
            and kw.get("k_valid") is None:
        return chunked_sdpa(
            cfg, q, k, v,
            causal=kw.get("causal", True), window=kw.get("window", 0),
            block_q=cfg.attn_chunk, block_k=cfg.attn_chunk,
        )
    return sdpa(cfg, q, k, v, **kw)


def self_attention(cfg, p, x, positions, *, causal=True):
    """Full-sequence self-attention (train / prefill / encoder)."""
    q, k, v = _project_qkv(cfg, p, x, x)
    if cfg.rope_theta:
        q = L.apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = L.apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    out = _attend(cfg, q, k, v, causal=causal, window=cfg.sliding_window)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# KV cache (decode). Under SWA the cache is a ring buffer of size `window`.
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    k: jax.Array        # [B, T, KV, hd]
    v: jax.Array        # [B, T, KV, hd]
    length: jax.Array   # int32: absolute number of tokens written so far


def init_cache(cfg, batch, max_len, dtype, prefill_len=0):
    T = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    KV = cfg.num_kv_heads * cfg.kv_replication
    hd = cfg.resolved_head_dim
    return KVCache(
        k=jnp.zeros((batch, T, KV, hd), dtype),
        v=jnp.zeros((batch, T, KV, hd), dtype),
        length=jnp.int32(prefill_len),
    )


def decode_attention(cfg, p, x, cache: KVCache):
    """One-token decode step. x: [B, 1, d]. Keys are stored pre-rotated, so the
    ring buffer needs no position bookkeeping (RoPE is relative)."""
    B = x.shape[0]
    T = cache.k.shape[1]
    pos = cache.length                     # absolute position of the new token
    q, k, v = _project_qkv(cfg, p, x, x)
    if cfg.rope_theta:
        pp = jnp.broadcast_to(pos[None, None], (B, 1))
        q = L.apply_rope(q, pp, cfg.rope_theta, cfg.mrope_sections)
        k = L.apply_rope(k, pp, cfg.rope_theta, cfg.mrope_sections)
    slot = jnp.where(cfg.sliding_window > 0, pos % T, jnp.minimum(pos, T - 1))
    kc = jax.lax.dynamic_update_slice(cache.k, k, (0, slot, 0, 0))
    vc = jax.lax.dynamic_update_slice(cache.v, v, (0, slot, 0, 0))
    filled = jnp.minimum(pos + 1, T)  # ring buffer: slot order is irrelevant
    valid = jnp.arange(T)[None] < filled
    out = sdpa(
        cfg, q, kc, vc,
        causal=False,                 # causality via the validity mask
        window=0,
        k_valid=jnp.broadcast_to(valid, (B, T)),
    )
    new_cache = KVCache(k=kc, v=vc, length=pos + 1)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, new_cache


def prefill_attention(cfg, p, x, positions, max_len=None):
    """Prefill: full self-attention + return the populated cache (padded to
    ``max_len`` slots so decode can append)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, x)
    if cfg.rope_theta:
        q = L.apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = L.apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    out = _attend(cfg, q, k, v, causal=True, window=cfg.sliding_window)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    max_len = max_len or S
    if cfg.sliding_window and cfg.sliding_window < S:
        W = cfg.sliding_window
        k_keep, v_keep = k[:, -W:], v[:, -W:]
        # ring-align: token at absolute position p sits at slot p % W
        shift = S % W
        k_keep = jnp.roll(k_keep, shift, axis=1)
        v_keep = jnp.roll(v_keep, shift, axis=1)
        cache = KVCache(k=k_keep, v=v_keep, length=jnp.int32(S))
    else:
        pad = max(0, max_len - S)
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cache = KVCache(k=k, v=v, length=jnp.int32(S))
    return y, cache


def cross_attention(cfg, p, x, enc_kv, positions=None):
    """Cross-attention against precomputed encoder K/V (whisper decoder)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
    k, v = enc_kv
    out = sdpa(cfg, q, k, v, causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


def encode_cross_kv(cfg, p, enc_out):
    dt = enc_out.dtype
    k = jnp.einsum("btd,dhk->bthk", enc_out, p["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", enc_out, p["wv"].astype(dt))
    if "bk" in p:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return k, v
