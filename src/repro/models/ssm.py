"""Mamba2 block via SSD (state-space duality, arXiv:2405.21060).

Train/prefill use the chunked SSD algorithm: quadratic attention-like math
inside chunks of length Q + a linear state recurrence across chunks. Decode
is the O(1) recurrent update. :func:`ssd` routes the chunked forward: a call
that is not differentiated and is lowered for a TPU runs the fused Pallas
kernel (repro/kernels/ssd_scan); a differentiated call, or one lowered for
any other platform, runs :func:`ssd_chunked` (one lax.scan over S/Q chunks
carrying the [B,H,N,P] state). :func:`_in_proj` routes the in-projection by
differentiation alone: three dots where not differentiated, one dot and its
split where differentiated.

Layout: x [B,S,H,P] (H heads, P=head_dim), B/C [B,S,G,N] (G groups, N=state),
dt [B,S,H], A = -exp(A_log) [H], skip D [H].
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.extend
import jax.numpy as jnp
from jax.interpreters import batching, mlir

from repro.kernels.ssd_scan import ops as _ssd_ops
from repro.obs.profile import scope as _scope

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


def ssd_chunked(cfg, x, dt, A, Bm, Cm):
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

    s0 = jnp.zeros((Bsz, H, N, P), jnp.float32)
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


def _ssd_jnp(cfg, xBC, dt, A, D):
    """:func:`ssd_chunked` on the conv output, with the D skip."""
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    G, N = cfg.ssm_groups, cfg.ssm_state
    x, Bm, Cm = _split_xbc(cfg, xBC)
    Bsz, S = x.shape[0], x.shape[1]
    x = x.reshape(Bsz, S, H, P)
    Bm = Bm.reshape(Bsz, S, G, N)
    Cm = Cm.reshape(Bsz, S, G, N)
    y, final_state = ssd_chunked(cfg, x, dt, A, Bm, Cm)
    y = y + x * D.astype(x.dtype)[None, None, :, None]
    return y.reshape(Bsz, S, cfg.ssm_d_inner), final_state


def _ssd_fused(cfg, xBC, dt, A, D):
    """The fused Pallas kernel on the conv output, with the D skip."""
    with _scope("ssm.ssd_fused"):
        return _ssd_ops.ssd_fused(
            xBC, dt, A, D, head_dim=cfg.ssm_head_dim, groups=cfg.ssm_groups,
            state=cfg.ssm_state, chunk=cfg.ssm_chunk)


# The SSD forward as one primitive whose lowering follows the platform the
# program is lowered for: the fused kernel on a TPU, :func:`_ssd_jnp`
# elsewhere (``lax.platform_dependent`` would choose the same, through a
# ``cond`` whose branch name then lands in every op_name of the jnp path).
_ssd_forward_p = jax.extend.core.Primitive("ssd_forward")
_ssd_forward_p.multiple_results = True


@_ssd_forward_p.def_impl
def _ssd_forward_impl(*args, cfg):
    return jax.jit(functools.partial(_ssd_forward_p.bind, cfg=cfg))(*args)


@_ssd_forward_p.def_abstract_eval
def _ssd_forward_abstract(xBC, dt, A, D, *, cfg):
    Bsz, S = xBC.shape[:2]
    return (jax.core.ShapedArray((Bsz, S, cfg.ssm_d_inner), xBC.dtype),
            jax.core.ShapedArray((Bsz, cfg.ssm_heads, cfg.ssm_state,
                                  cfg.ssm_head_dim), jnp.float32))


def _ssd_forward_batch(args, dims, *, cfg):
    """vmap: rows are independent, so the mapped axis joins the batch; a
    mapped A or D runs the jnp path under vmap."""
    if dims[2] is not None or dims[3] is not None:
        return jax.vmap(functools.partial(_ssd_jnp, cfg), dims)(*args), (0, 0)
    n = next(a.shape[d] for a, d in zip(args, dims) if d is not None)
    xBC, dt = (jnp.broadcast_to(a, (n,) + a.shape) if d is None
               else jnp.moveaxis(a, d, 0) for a, d in zip(args[:2], dims[:2]))
    outs = _ssd_forward_p.bind(xBC.reshape((-1,) + xBC.shape[2:]),
                               dt.reshape((-1,) + dt.shape[2:]), *args[2:],
                               cfg=cfg)
    return [o.reshape((n, -1) + o.shape[1:]) for o in outs], (0, 0)


batching.primitive_batchers[_ssd_forward_p] = _ssd_forward_batch
mlir.register_lowering(_ssd_forward_p, mlir.lower_fun(
    lambda *args, cfg: _ssd_jnp(cfg, *args), multiple_results=True))
mlir.register_lowering(_ssd_forward_p, mlir.lower_fun(
    lambda *args, cfg: _ssd_fused(cfg, *args), multiple_results=True),
    platform="tpu")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def ssd(cfg, xBC, dt, A, D):
    """Chunked SSD forward with the D skip: xBC [B,S,conv_dim] (conv output),
    dt [B,S,H] (post-softplus, f32), A [H] (<0), D [H] -> (y [B,S,d_inner],
    final_state [B,H,N,P] f32). Lowered for a TPU the forward is the fused
    kernel; differentiated, it is ``jax.vjp`` of :func:`ssd_chunked`, so
    the gradient is that path's, bit for bit."""
    return tuple(_ssd_forward_p.bind(xBC, dt, A, D, cfg=cfg))


def _ssd_fwd(cfg, xBC, dt, A, D):
    return jax.vjp(functools.partial(_ssd_jnp, cfg), xBC, dt, A, D)


def _vjp_bwd(cfg, vjp_fn, ct):
    del cfg
    return vjp_fn(ct)


ssd.defvjp(_ssd_fwd, _vjp_bwd)


def _in_proj_fused(cfg, u, w):
    """The in-projection as one dot, split into (z, xBC, dt)."""
    return _split_proj(cfg, jnp.einsum("bsd,dk->bsk", u, w))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _in_proj(cfg, u, w):
    """u [B,S,D] @ w [D, 2*d_inner + 2*G*N + H] -> (z, xBC, dt). Not
    differentiated: three dots on w's column blocks, so each output is laid
    out as its consumer reads it (one dot's output is laid out S-minor for
    dt's consumer, and its z and xBC slices are then copied row-major).
    Differentiated: ``jax.vjp`` of :func:`_in_proj_fused`, so the gradient
    is that path's, bit for bit."""
    with _scope("ssm.in_proj_split"):
        return tuple(jnp.einsum("bsd,dk->bsk", u, wk)
                     for wk in _split_proj(cfg, w))


def _in_proj_fwd(cfg, u, w):
    return jax.vjp(functools.partial(_in_proj_fused, cfg), u, w)


_in_proj.defvjp(_in_proj_fwd, _vjp_bwd)


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


def apply_ssm(cfg, p, u):
    """Full-sequence Mamba2 block: u [B,S,D] -> ([B,S,D], SSMCache).
    The returned cache (final state + conv tail) makes this the prefill path."""
    dt_ = u.dtype
    with _scope("ssm.in_proj"):
        z, xBC_raw, dtv = _in_proj(cfg, u, p["in_proj"].astype(dt_))
    conv_tail = xBC_raw[:, -(cfg.ssm_conv_width - 1):, :]
    with _scope("ssm.conv"):
        xBC = _causal_conv(cfg, p, xBC_raw)
    with _scope("ssm.ssd"):
        dtv = jax.nn.softplus(dtv.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(p["A_log"].astype(jnp.float32))
        y, final_state = ssd(cfg, xBC, dtv, A, p["D"])
    with _scope("ssm.gate_norm"):
        y = L.rms_norm(y * jax.nn.silu(z), p["norm_scale"], cfg.norm_eps)
    with _scope("ssm.out_proj"):
        out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"].astype(dt_))
    return out, SSMCache(conv=conv_tail, state=final_state)


def decode_ssm(cfg, p, u, cache: SSMCache):
    """One-token recurrent update. u: [B, 1, D]."""
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    G, N = cfg.ssm_groups, cfg.ssm_state
    dt_ = u.dtype
    Bsz = u.shape[0]
    z, xBC, dtv = _in_proj_fused(cfg, u, p["in_proj"].astype(dt_))
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
