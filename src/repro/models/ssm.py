"""Mamba2 block via SSD (state-space duality, arXiv:2405.21060).

Train/prefill use the chunked SSD algorithm: quadratic attention-like math
inside chunks of length Q + a linear state recurrence across chunks (one
lax.scan over S/Q chunks carrying the [B,H,N,P] state). Decode is the O(1)
recurrent update. The in-chunk compute is also available as a Pallas kernel
(repro/kernels/ssd_scan) validated against the jnp path here.

Layout: x [B,S,H,P] (H heads, P=head_dim), B/C [B,S,G,N] (G groups, N=state),
dt [B,S,H], A = -exp(A_log) [H], skip D [H].
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import layers as L


def ssm_params(cfg, key):
    d = cfg.d_model
    din, ns, g, h = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_heads
    conv_dim = din + 2 * g * ns
    ks = jax.random.split(key, 5)
    pd = L.param_dtype(cfg)
    return {
        # fused in-projection: [z (din), xBC (din + 2*g*ns), dt (h)]
        "in_proj": L.dense_init(ks[0], (d, 2 * din + 2 * g * ns + h), pd, fan_in=d),
        "conv_w": L.dense_init(ks[1], (cfg.ssm_conv_width, conv_dim), pd,
                               fan_in=cfg.ssm_conv_width),
        "conv_b": jnp.zeros((conv_dim,), pd),
        "dt_bias": jnp.zeros((h,), pd),
        "A_log": jnp.zeros((h,), pd),
        "D": jnp.ones((h,), pd),
        "norm_scale": jnp.zeros((din,), pd),
        "out_proj": L.dense_init(ks[2], (din, d), pd, fan_in=din),
    }


def _split_proj(cfg, proj):
    din, ns, g, h = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_heads
    z = proj[..., :din]
    xBC = proj[..., din : 2 * din + 2 * g * ns]
    dt = proj[..., 2 * din + 2 * g * ns :]
    return z, xBC, dt


def _split_xbc(cfg, xBC):
    din, ns, g = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_groups
    x = xBC[..., :din]
    Bm = xBC[..., din : din + g * ns]
    Cm = xBC[..., din + g * ns :]
    return x, Bm, Cm


def _causal_conv(cfg, p, xBC):
    """Depthwise causal conv1d + silu over [B, S, conv_dim]."""
    W = cfg.ssm_conv_width
    pad = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + xBC.shape[1], :] * p["conv_w"].astype(xBC.dtype)[i][None, None]
        for i in range(W)
    )
    return jax.nn.silu(out + p["conv_b"].astype(xBC.dtype))


def ssd_chunked(cfg, x, dt, A, Bm, Cm, init_state=None):
    """Chunked SSD. x [B,S,H,P], dt [B,S,H] (post-softplus), A [H] (<0),
    Bm/Cm [B,S,G,N]. Returns (y [B,S,H,P], final_state [B,H,N,P])."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(cfg.ssm_chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    rep = H // G

    def chunk_view(t):  # [B,S,...] -> [B,nc,Q,...]
        return t.reshape((Bsz, nc, Q) + t.shape[2:])

    xc, dtc = chunk_view(x), chunk_view(dt)
    Bc, Cc = chunk_view(Bm), chunk_view(Cm)

    s0 = (jnp.zeros((Bsz, H, N, P), jnp.float32)
          if init_state is None else init_state.astype(jnp.float32))
    ii = jnp.arange(Q)
    tri = ii[:, None] >= ii[None, :]

    def body(state, inp):
        """Process ONE chunk: intra-chunk quadratic part + inter-chunk state.
        All O(Q^2) intermediates live only inside this body (memory-bounded;
        remat'd in the backward pass)."""
        x_n, dt_n, B_n, C_n = inp          # [B,Q,H,P],[B,Q,H],[B,Q,G,N],[B,Q,G,N]
        la = (dt_n * A[None, None, :]).astype(jnp.float32)   # [B,Q,H]
        cl = jnp.cumsum(la, axis=1)                          # [B,Q,H]
        clh = cl.transpose(0, 2, 1)                          # [B,H,Q]
        # intra: scores[i,j] = (C_i.B_j) exp(cl_i - cl_j) dt_j for j<=i
        CB = jnp.einsum("bqgs,bkgs->bgqk", C_n, B_n)         # [B,G,Q,Q]
        CB = jnp.broadcast_to(
            CB[:, :, None], (Bsz, G, rep, Q, Q)
        ).reshape(Bsz, H, Q, Q)
        # mask the exponent, not its result: above the diagonal
        # cl_i - cl_j > 0 grows with the chunk and exp overflows to inf,
        # and a mask after the exp still sends inf * 0 = NaN into the
        # gradient (at Q 256 it does)
        seg = jnp.where(tri[None, None], clh[..., :, None] - clh[..., None, :],
                        -jnp.inf)
        decay = jnp.exp(seg)
        scores = CB.astype(jnp.float32) * decay * dt_n.transpose(0, 2, 1)[:, :, None, :]
        y_intra = jnp.einsum("bhqk,bkhp->bqhp", scores.astype(x.dtype), x_n)
        # inter: y_inter[i] = C_i . (state_prev * exp(cl_i))
        Ch = jnp.broadcast_to(
            C_n.reshape(Bsz, Q, G, 1, N), (Bsz, Q, G, rep, N)
        ).reshape(Bsz, Q, H, N)
        y_inter = jnp.einsum("bqhs,bhsp,bqh->bqhp",
                             Ch.astype(jnp.float32), state, jnp.exp(cl))
        # state update: state = state * exp(cl_last) + sum_j exp(cl_last-cl_j) dt_j B_j x_j
        w = jnp.exp(cl[:, -1:, :] - cl) * dt_n               # [B,Q,H]
        Bh = jnp.broadcast_to(
            B_n.reshape(Bsz, Q, G, 1, N), (Bsz, Q, G, rep, N)
        ).reshape(Bsz, Q, H, N)
        st_n = jnp.einsum("bqh,bqhs,bqhp->bhsp",
                          w.astype(jnp.float32), Bh.astype(jnp.float32),
                          x_n.astype(jnp.float32))
        state = state * jnp.exp(cl[:, -1])[:, :, None, None] + st_n
        return state, (y_intra + y_inter.astype(x.dtype))

    xs = (
        xc.swapaxes(0, 1), dtc.swapaxes(0, 1),
        Bc.swapaxes(0, 1), Cc.swapaxes(0, 1),
    )
    final_state, y = jax.lax.scan(jax.checkpoint(body), s0, xs)
    y = y.swapaxes(0, 1).reshape(Bsz, S, H, P)
    return y, final_state


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SSMCache:
    conv: jax.Array    # [B, W-1, conv_dim] trailing conv inputs
    state: jax.Array   # [B, H, N, P] SSM state (f32)


def init_ssm_cache(cfg, batch, dtype):
    din, ns, g = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_groups
    conv_dim = din + 2 * g * ns
    return SSMCache(
        conv=jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_dim), dtype),
        state=jnp.zeros((batch, cfg.ssm_heads, ns, cfg.ssm_head_dim), jnp.float32),
    )


def apply_ssm(cfg, p, u, *, init_state=None):
    """Full-sequence Mamba2 block: u [B,S,D] -> ([B,S,D], SSMCache).
    The returned cache (final state + conv tail) makes this the prefill path."""
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    G, N = cfg.ssm_groups, cfg.ssm_state
    dt_ = u.dtype
    proj = jnp.einsum("bsd,dk->bsk", u, p["in_proj"].astype(dt_))
    z, xBC_raw, dtv = _split_proj(cfg, proj)
    conv_tail = xBC_raw[:, -(cfg.ssm_conv_width - 1):, :]
    xBC = _causal_conv(cfg, p, xBC_raw)
    x, Bm, Cm = _split_xbc(cfg, xBC)
    Bsz, S = x.shape[0], x.shape[1]
    x = x.reshape(Bsz, S, H, P)
    Bm = Bm.reshape(Bsz, S, G, N)
    Cm = Cm.reshape(Bsz, S, G, N)
    dtv = jax.nn.softplus(dtv.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, final_state = ssd_chunked(cfg, x, dtv, A, Bm, Cm, init_state=init_state)
    y = y + x * p["D"].astype(dt_)[None, None, :, None]
    y = y.reshape(Bsz, S, cfg.ssm_d_inner)
    y = L.rms_norm(y * jax.nn.silu(z), p["norm_scale"], cfg.norm_eps)
    out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"].astype(dt_))
    return out, SSMCache(conv=conv_tail, state=final_state)


def decode_ssm(cfg, p, u, cache: SSMCache):
    """One-token recurrent update. u: [B, 1, D]."""
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    G, N = cfg.ssm_groups, cfg.ssm_state
    dt_ = u.dtype
    Bsz = u.shape[0]
    proj = jnp.einsum("bsd,dk->bsk", u, p["in_proj"].astype(dt_))
    z, xBC, dtv = _split_proj(cfg, proj)
    # conv over [cache | new token]
    window = jnp.concatenate([cache.conv, xBC], axis=1)       # [B, W, conv]
    conv_out = jnp.einsum(
        "bwc,wc->bc", window, p["conv_w"].astype(dt_)
    ) + p["conv_b"].astype(dt_)
    xBC1 = jax.nn.silu(conv_out)[:, None, :]
    x, Bm, Cm = _split_xbc(cfg, xBC1)
    x = x.reshape(Bsz, H, P)
    Bm = Bm.reshape(Bsz, G, N)
    Cm = Cm.reshape(Bsz, G, N)
    dtv = jax.nn.softplus(
        dtv[:, 0].astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
    )                                                         # [B,H]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    da = jnp.exp(dtv * A[None])                                # [B,H]
    rep = H // G
    Bh = jnp.broadcast_to(
        Bm[:, :, None, :], (Bsz, G, rep, N)
    ).reshape(Bsz, H, N).astype(jnp.float32)
    Ch = jnp.broadcast_to(
        Cm[:, :, None, :], (Bsz, G, rep, N)
    ).reshape(Bsz, H, N).astype(jnp.float32)
    state = cache.state * da[:, :, None, None] + jnp.einsum(
        "bh,bhs,bhp->bhsp", dtv, Bh, x.astype(jnp.float32)
    )
    y = jnp.einsum("bhs,bhsp->bhp", Ch, state).astype(dt_)
    y = y + x * p["D"].astype(dt_)[None, :, None]
    y = y.reshape(Bsz, 1, cfg.ssm_d_inner)
    y = L.rms_norm(y * jax.nn.silu(z), p["norm_scale"], cfg.norm_eps)
    out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"].astype(dt_))
    return out, SSMCache(conv=window[:, 1:], state=state)
