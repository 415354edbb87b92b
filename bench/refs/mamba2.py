"""Plain Mamba2 language model: weights from a seed, forward, loss, AdamW.

The reference the LM cells are compared with. It imports nothing of the
program. It follows the Mamba2 block of arXiv:2405.21060 (SSD, "state
space duality") as the configuration states it: in-projection to
(z, xBC, dt), a causal depthwise convolution over xBC, the selective state
space recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T, y_t = C_t h_t
+ D x_t, a gated RMSNorm, an out-projection, pre-norm residual blocks, tied
embeddings. Everything is float32 at ``highest`` matmul precision; the SSD
recurrence is evaluated chunk by chunk (exact: the chunked form is an
algebraic rewrite of the recurrence), and losses and gradients in blocks of
rows so that the whole fits next to the optimizer state.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a per-tensor scale before the product, the step below
the bfloat16 the configuration computes in.

Weights use the published initialisation of ``mamba_ssm``'s Mamba2 (dt
log-uniform in [1e-3, 1e-1] stored through the inverse softplus, A from
U(1, 16), D one, out-projection scaled by 1/sqrt(layers)), laid out as the
program's parameter tree: the driver checks the layout against the
program's before it hands the weights over.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
E4M3_MAX = 448.0


def dims(cfg: dict) -> dict:
    d = cfg["d_model"]
    din = cfg["expand"] * d
    H = din // cfg["headdim"]
    G, N = cfg["ngroups"], cfg["d_state"]
    return {"d": d, "din": din, "H": H, "P": cfg["headdim"], "G": G, "N": N,
            "conv_dim": din + 2 * G * N, "W": cfg["d_conv"],
            "L": cfg["n_layer"], "Q": cfg["chunk_size"],
            "Vp": -(-cfg["vocab_size"] // 256) * 256,
            "proj": 2 * din + 2 * G * N + H}


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Seeded float32 weights in the program's parameter layout."""
    k = dims(cfg)
    ks = jax.random.split(key, 8)
    L, d, din, H = k["L"], k["d"], k["din"], k["H"]

    def unif(kk, shape, bound):
        return jax.random.uniform(kk, shape, F32, -bound, bound)

    dt = jnp.exp(jax.random.uniform(ks[4], (L, H), F32, math.log(1e-3),
                                    math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    blocks = {
        "ln": {"scale": jnp.zeros((L, d), F32)},
        "ssm": {
            "in_proj": unif(ks[1], (L, d, k["proj"]), 1 / math.sqrt(d)),
            "conv_w": unif(ks[2], (L, k["W"], k["conv_dim"]),
                           1 / math.sqrt(k["W"])),
            "conv_b": unif(ks[3], (L, k["conv_dim"]), 1 / math.sqrt(k["W"])),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(ks[5], (L, H), F32, 1.0,
                                                16.0)),
            "D": jnp.ones((L, H), F32),
            "norm_scale": jnp.zeros((L, din), F32),
            "out_proj": unif(ks[6], (L, din, d), 1 / math.sqrt(din))
            / math.sqrt(L),
        },
    }
    return {"embed": 0.02 * jax.random.normal(ks[0], (k["Vp"], d), F32),
            "blocks": blocks,
            "final_norm": {"scale": jnp.zeros((d,), F32)}}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _q8(x):
    """Round to float8 e4m3 under a per-tensor scale, back to float32. The
    rounding passes gradients straight through: a cast's own transpose
    would round the cotangent to float8 unscaled and flush it to zero."""
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / E4M3_MAX)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(precision: str):
    def mm(eq, *xs):
        if precision == "fp8":
            xs = [_q8(x) for x in xs]
        return jnp.einsum(eq, *xs)

    return mm


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def ssd(mm, x, dt, A, Bm, Cm, Q):
    """y for x [b,S,H,P], dt [b,S,H], A [H], B/C [b,S,G,N], chunk Q."""
    b, S, H, P = x.shape
    G, N = Bm.shape[2:]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2)                      # [b,S,H,N]
    Ch = jnp.repeat(Cm, rep, axis=2)
    nc = S // Q

    def chunks(t):
        return t.reshape((b, nc, Q) + t.shape[2:]).swapaxes(0, 1)

    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def body(h, c):
        xc, dtc, Bc, Cc = c                               # [b,Q,...]
        cum = jnp.cumsum(dtc * A, axis=1)                 # [b,Q,H]
        diff = cum[:, :, None, :] - cum[:, None, :, :]    # [b,i,j,H]
        Lij = jnp.exp(jnp.where(tri[None, :, :, None], diff, -jnp.inf))
        cb = mm("bihn,bjhn->bijh", Cc, Bc)
        y = mm("bijh,bjhp->bihp", cb * Lij * dtc[:, None], xc)
        y = y + mm("bihn,bhnp->bihp", Cc * jnp.exp(cum)[..., None], h)
        w = jnp.exp(cum[:, -1:, :] - cum) * dtc          # [b,Q,H]
        h = h * jnp.exp(cum[:, -1])[:, :, None, None] \
            + mm("bjhn,bjhp->bhnp", Bc * w[..., None], xc)
        return h, y

    h0 = jnp.zeros((b, H, N, P), F32)
    _, y = jax.lax.scan(body, h0, (chunks(x), chunks(dt), chunks(Bh),
                                   chunks(Ch)))
    return y.swapaxes(0, 1).reshape(b, S, H, P)


def block(cfg, precision, p, x):
    k = dims(cfg)
    mm = _mm(precision)
    eps = cfg["norm_eps"]
    din, G, N, H, P = k["din"], k["G"], k["N"], k["H"], k["P"]
    u = rms_norm(x, p["ln"]["scale"], eps)
    s = p["ssm"]
    proj = mm("bsd,dk->bsk", u, s["in_proj"])
    z = proj[..., :din]
    xbc = proj[..., din:2 * din + 2 * G * N]
    dt = proj[..., 2 * din + 2 * G * N:]
    W = k["W"]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + xbc.shape[1]] * s["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + s["conv_b"])
    b, S = x.shape[:2]
    xs = xbc[..., :din].reshape(b, S, H, P)
    Bm = xbc[..., din:din + G * N].reshape(b, S, G, N)
    Cm = xbc[..., din + G * N:].reshape(b, S, G, N)
    dt = jax.nn.softplus(dt + s["dt_bias"])
    A = -jnp.exp(s["A_log"])
    y = ssd(mm, xs, dt, A, Bm, Cm, k["Q"]) + xs * s["D"][:, None]
    y = rms_norm(y.reshape(b, S, din) * jax.nn.silu(z), s["norm_scale"], eps)
    return x + mm("bsk,kd->bsd", y, s["out_proj"])


def loss(cfg, precision, params, tokens, *, remat: bool):
    """Mean next-token cross entropy over all positions of ``tokens`` [b,S]
    (logits over the padded vocabulary, as the model defines them)."""
    x = params["embed"][tokens]
    layer = functools.partial(block, cfg, precision)
    if remat:
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(lambda h, p: (layer(p, h), None), x,
                        params["blocks"])
    x = rms_norm(x, params["final_norm"]["scale"], cfg["norm_eps"])
    logits = _mm(precision)("bsd,vd->bsv", x, params["embed"])[:, :-1]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


# ---------------------------------------------------------------------------
# blocked evaluation and training
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _block_loss(params, tokens, *, cfg_items, precision):
    return loss(dict(cfg_items), precision, params, tokens, remat=False)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _block_grad(params, tokens, *, cfg_items, precision):
    return jax.value_and_grad(
        lambda p: loss(dict(cfg_items), precision, p, tokens, remat=True))(
            params)


def _items(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def eval_loss(cfg, params, tokens, *, rows: int, precision: str = "f32"):
    """Mean loss of ``tokens`` [B,S], ``rows`` rows at a time (equal blocks,
    so the mean of block means is the mean)."""
    B = tokens.shape[0]
    assert B % rows == 0, (B, rows)
    with jax.default_matmul_precision("highest"):
        out = [_block_loss(params, tokens[i:i + rows], cfg_items=_items(cfg),
                           precision=precision)
               for i in range(0, B, rows)]
    return float(np.mean([float(o) for o in out]))


def grads(cfg, params, tokens, *, rows: int, precision: str = "f32"):
    """(loss, gradient) of the mean loss over ``tokens``, in row blocks."""
    B = tokens.shape[0]
    assert B % rows == 0, (B, rows)
    tot_l, tot_g = 0.0, None
    with jax.default_matmul_precision("highest"):
        for i in range(0, B, rows):
            l, g = _block_grad(params, tokens[i:i + rows],
                               cfg_items=_items(cfg), precision=precision)
            tot_l += float(l)
            tot_g = g if tot_g is None else _accumulate(tot_g, g)
    nb = B // rows
    return tot_l / nb, _scale(tot_g, 1.0 / nb)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _accumulate(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


@functools.partial(jax.jit, donate_argnums=0)
def _scale(a, s):
    return jax.tree_util.tree_map(lambda x: x * s, a)


def adamw_init(params):
    z = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": z, "v": jax.tree_util.tree_map(jnp.zeros_like, params),
            "count": 0}


@functools.partial(jax.jit, static_argnames=("opt_items",),
                   donate_argnums=(0, 1, 2, 3))
def _adamw(params, g, m, v, count, *, opt_items):
    o = dict(opt_items)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
    g = jax.tree_util.tree_map(
        lambda x: x * jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm,
                                                                    1e-12)), g)
    c = jnp.asarray(count, F32)
    warm = jnp.minimum(c / max(o["warmup"], 1), 1.0)
    prog = jnp.clip((c - o["warmup"]) / max(o["total_steps"] - o["warmup"],
                                            1), 0.0, 1.0)
    lr = o["lr"] * warm * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    b1c = 1.0 - o["b1"] ** (c + 1)
    b2c = 1.0 - o["b2"] ** (c + 1)
    m = jax.tree_util.tree_map(lambda m, g: o["b1"] * m + (1 - o["b1"]) * g,
                               m, g)
    v = jax.tree_util.tree_map(
        lambda v, g: o["b2"] * v + (1 - o["b2"]) * g * g, v, g)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / b1c) / (jnp.sqrt(v / b2c) + o["eps"])
                                  + o["weight_decay"] * p), params, m, v)
    return params, m, v


def adamw_step(opt_cfg: dict, params, opt, g):
    items = tuple(sorted(opt_cfg.items()))
    params, m, v = _adamw(params, g, opt["m"], opt["v"], opt["count"],
                          opt_items=items)
    return params, {"m": m, "v": v, "count": opt["count"] + 1}


def retrain(cfg, opt_cfg, params, opt, items, mask, key, *, steps: int,
            batch: int, rows: int, precision: str = "f32"):
    """``steps`` AdamW steps on minibatches of ``batch`` rows drawn with
    replacement from the sample (``mask`` over the reservoir's slots), the
    draws keyed as the loop keys them: per step ``key, k_sel = split(key)``
    then ``choice(k_sel, slots, (batch,), p=mask/|mask|)``. Consumes
    ``params`` and ``opt`` (their buffers are reused in place)."""
    m = mask.astype(F32)
    probs = m / jnp.maximum(m.sum(), 1.0)
    if float(m.sum()) == 0:
        return params, opt
    for _ in range(steps):
        key, k_sel = jax.random.split(key)
        sel = jax.random.choice(k_sel, probs.shape[0], shape=(batch,),
                                p=probs)
        _, g = grads(cfg, params, jnp.asarray(items)[sel], rows=rows,
                     precision=precision)
        params, opt = adamw_step(opt_cfg, params, opt, g)
    return params, opt
