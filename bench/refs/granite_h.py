"""Plain granite-4.0-h language model: weights from a seed, logits, loss,
blocked gradients, AdamW.

The reference the granite cells are compared with. It imports nothing of
the program. It follows ``GraniteMoeHybridForCausalLM`` (Hugging Face
transformers, ``model_type`` granitemoehybrid) with no experts, as the
configuration's keys state it:

  h = embed(tokens) * embedding_multiplier
  per layer:  h += residual_multiplier * mixer(RMSNorm(h))
              h += residual_multiplier * MLP(RMSNorm(h))
  logits = RMSNorm(h) . embed^T / logits_scaling

where ``layer_types[i]`` names layer i's mixer: "mamba", the Mamba2 block of
``bench/refs/mamba2.py`` (in-projection to z, xBC, dt; causal depthwise
conv with bias; SSD; the gated RMSNorm over all of d_inner; out-projection;
no projection bias), or "attention", causal GQA with no positional encoding
(``position_embedding_type`` "nope") and scores scaled by
``attention_multiplier`` in place of 1/sqrt(head_dim). The MLP is the
shared MLP: ``input_linear`` d -> 2 * intermediate_size, ``silu`` of the
first half times the second, ``output_linear`` back to d. Everything is
float32 at ``highest`` matmul precision; the SSD, the norm, the float8
control (``precision="fp8"``) and the AdamW step are ``mamba2.py``'s.

Departures, none of which changes what is computed:

* weights are laid out as the program's parameter tree, which the cell
  checks before it hands them over: ``input_linear`` is split into the
  gate half ``wg`` and the up half ``wi``, attention weights are
  ``[d, heads, head_dim]``, and each run of like layers is stacked;
* RMSNorm weights are stored as ``scale`` with the weight ``1 + scale``;
* weights are random: dense weights uniform in +-1/sqrt(fan_in), the
  embedding normal with std 0.02, dt, A and D as ``mamba_ssm`` initialises
  them (``mamba2.py``).

Gradients are computed in blocks of rows; between AdamW steps the moments
wait in host memory, so that parameters, the gradient and its running sum
fit on one chip beside the activations (moments and all, a training step
at the cell's size needs 16 bytes a parameter, 12.4 GB).
"""
from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs import mamba2 as m2

F32 = jnp.float32


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    din = cfg["mamba_expand"] * d
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    assert H * P == din, (H, P, din)
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    nh, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"d": d, "din": din, "H": H, "P": P, "G": G, "N": N,
            "conv_dim": din + 2 * G * N, "W": cfg["mamba_d_conv"],
            "Q": cfg["mamba_chunk_size"], "nh": nh, "kv": kv, "hd": d // nh,
            "ff": cfg["shared_intermediate_size"],
            "Vp": -(-cfg["vocab_size"] // 256) * 256,
            "proj": 2 * din + 2 * G * N + H}


def runs(cfg: dict) -> list[tuple[str, int]]:
    """(kind, count) of each run of like layers among the model's layers."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    assert len(kinds) == cfg["num_hidden_layers"]
    return [(k, len(list(g))) for k, g in itertools.groupby(kinds)]


def _unif(key, shape, fan_in):
    b = 1 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, F32, -b, b)


def _mamba_params(k: dict, key, n: int) -> dict:
    ks = jax.random.split(key, 6)
    d, din, H, W = k["d"], k["din"], k["H"], k["W"]
    dt = jnp.exp(jax.random.uniform(ks[3], (n, H), F32, math.log(1e-3),
                                    math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "in_proj": _unif(ks[0], (n, d, k["proj"]), d),
        "conv_w": _unif(ks[1], (n, W, k["conv_dim"]), W),
        "conv_b": _unif(ks[2], (n, k["conv_dim"]), W),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(ks[4], (n, H), F32, 1.0, 16.0)),
        "D": jnp.ones((n, H), F32),
        "norm_scale": jnp.zeros((n, din), F32),
        "out_proj": _unif(ks[5], (n, din, d), din),
    }


def _attn_params(k: dict, key, n: int) -> dict:
    ks = jax.random.split(key, 4)
    d, nh, kv, hd = k["d"], k["nh"], k["kv"], k["hd"]
    return {"wq": _unif(ks[0], (n, d, nh, hd), d),
            "wk": _unif(ks[1], (n, d, kv, hd), d),
            "wv": _unif(ks[2], (n, d, kv, hd), d),
            "wo": _unif(ks[3], (n, nh, hd, d), nh * hd)}


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Seeded float32 weights in the program's parameter layout."""
    k = dims(cfg)
    d, ff = k["d"], k["ff"]
    ke, kl = jax.random.split(key)
    layers = []
    for i, (kind, n) in enumerate(runs(cfg)):
        km, kg, ki, ko = jax.random.split(jax.random.fold_in(kl, i), 4)
        mixer = ({"ssm": _mamba_params(k, km, n)} if kind == "mamba"
                 else {"attn": _attn_params(k, km, n)})
        layers.append({
            "ln1": {"scale": jnp.zeros((n, d), F32)}, **mixer,
            "ln2": {"scale": jnp.zeros((n, d), F32)},
            "mlp": {"wg": _unif(kg, (n, d, ff), d),
                    "wi": _unif(ki, (n, d, ff), d),
                    "wo": _unif(ko, (n, ff, d), ff)},
        })
    return {"embed": 0.02 * jax.random.normal(ke, (k["Vp"], d), F32),
            "layers": layers,
            "final_norm": {"scale": jnp.zeros((d,), F32)}}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def mamba_mixer(k, mm, eps, s, u):
    din, G, N, H, P, W = k["din"], k["G"], k["N"], k["H"], k["P"], k["W"]
    proj = mm("bsd,dk->bsk", u, s["in_proj"])
    z = proj[..., :din]
    xbc = proj[..., din:2 * din + 2 * G * N]
    dt = proj[..., 2 * din + 2 * G * N:]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + xbc.shape[1]] * s["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + s["conv_b"])
    b, S = u.shape[:2]
    xs = xbc[..., :din].reshape(b, S, H, P)
    Bm = xbc[..., din:din + G * N].reshape(b, S, G, N)
    Cm = xbc[..., din + G * N:].reshape(b, S, G, N)
    dt = jax.nn.softplus(dt + s["dt_bias"])
    A = -jnp.exp(s["A_log"])
    y = m2.ssd(mm, xs, dt, A, Bm, Cm, k["Q"]) + xs * s["D"][:, None]
    y = m2.rms_norm(y.reshape(b, S, din) * jax.nn.silu(z), s["norm_scale"],
                    eps)
    return mm("bsk,kd->bsd", y, s["out_proj"])


def attention_mixer(k, mm, scale, a, u):
    """Causal GQA, no positional encoding, scores times ``scale``."""
    b, S = u.shape[:2]
    kv, g = k["kv"], k["nh"] // k["kv"]
    q = mm("bsd,dhk->bshk", u, a["wq"]).reshape(b, S, kv, g, k["hd"])
    kk = mm("bsd,dhk->bshk", u, a["wk"])
    v = mm("bsd,dhk->bshk", u, a["wv"])
    s = mm("bskgh,btkh->bkgst", q, kk) * scale
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm("bkgst,btkh->bskgh", p, v).reshape(b, S, k["nh"], k["hd"])
    return mm("bshk,hkd->bsd", o, a["wo"])


def mlp(mm, p, u):
    return mm("bsf,fd->bsd", jax.nn.silu(mm("bsd,df->bsf", u, p["wg"]))
              * mm("bsd,df->bsf", u, p["wi"]), p["wo"])


def layer(cfg, precision, p, h):
    k = dims(cfg)
    mm = m2._mm(precision)
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    u = m2.rms_norm(h, p["ln1"]["scale"], eps)
    if "ssm" in p:
        y = mamba_mixer(k, mm, eps, p["ssm"], u)
    else:
        y = attention_mixer(k, mm, cfg["attention_multiplier"], p["attn"], u)
    h = h + r * y
    return h + r * mlp(mm, p["mlp"], m2.rms_norm(h, p["ln2"]["scale"], eps))


def logits(cfg, precision, params, tokens, *, remat: bool = False):
    """Logits over the padded vocabulary for ``tokens`` [b, S]."""
    x = params["embed"][tokens] * cfg["embedding_multiplier"]
    fn = functools.partial(layer, cfg, precision)
    if remat:
        fn = jax.checkpoint(fn)
    for p in params["layers"]:
        x, _ = jax.lax.scan(lambda h, pl: (fn(pl, h), None), x, p)
    x = m2.rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return m2._mm(precision)("bsd,vd->bsv", x, params["embed"]) \
        / cfg["logits_scaling"]


def loss(cfg, precision, params, tokens, *, remat: bool):
    """Mean next-token cross entropy over all positions of ``tokens``."""
    lg = logits(cfg, precision, params, tokens, remat=remat)[:, :-1]
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)


# ---------------------------------------------------------------------------
# blocked evaluation and training
# ---------------------------------------------------------------------------
def _items(cfg):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, list))))


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _block_loss(params, tokens, *, cfg_items, precision):
    return loss(dict(cfg_items), precision, params, tokens, remat=False)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"),
                   donate_argnums=2)
def _block_grad(params, tokens, acc, *, cfg_items, precision):
    """(loss, acc + gradient) of one block of rows; ``acc`` is donated."""
    l, g = jax.value_and_grad(
        lambda p: loss(dict(cfg_items), precision, p, tokens, remat=True))(
            params)
    return l, jax.tree_util.tree_map(jnp.add, acc, g)


def eval_loss(cfg, params, tokens, *, rows: int, precision: str = "f32"):
    """Mean loss of ``tokens`` [B, S], ``rows`` rows at a time (equal
    blocks, so the mean of block means is the mean)."""
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
    acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    tot = 0.0
    with jax.default_matmul_precision("highest"):
        for i in range(0, B, rows):
            l, acc = _block_grad(params, tokens[i:i + rows], acc,
                                 cfg_items=_items(cfg), precision=precision)
            tot += float(l)
    nb = B // rows
    return tot / nb, m2._scale(acc, 1.0 / nb)


def adamw_init(params):
    """AdamW's state with its moments in host memory."""
    z = jax.tree_util.tree_map(lambda x: np.zeros(x.shape, x.dtype), params)
    return {"m": z, "v": jax.tree_util.tree_map(np.copy, z), "count": 0}


def retrain(cfg, opt_cfg, params, opt, items, mask, key, *, steps: int,
            batch: int, rows: int, precision: str = "f32"):
    """``mamba2.retrain``'s steps and minibatch draws; the moments go to the
    chip for each AdamW step and back to the host after it. Consumes
    ``params``."""
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
        params, dev = m2.adamw_step(opt_cfg, params, {
            "m": jax.device_put(opt["m"]), "v": jax.device_put(opt["v"]),
            "count": opt["count"]}, g)
        del g
        opt = {"m": jax.device_get(dev["m"]), "v": jax.device_get(dev["v"]),
               "count": dev["count"]}
        del dev
    return params, opt
