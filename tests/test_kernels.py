"""Per-kernel validation: shape/dtype sweeps asserting allclose against the
pure-jnp oracles (interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.reservoir_compact import ops as rc_ops, ref as rc_ref
from repro.kernels.ssd_scan import ops as ssd_ops, ref as ssd_ref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "B,S,H,KV,hd,causal,window,dtype",
    [
        (2, 128, 4, 2, 32, True, 0, jnp.float32),
        (1, 256, 4, 1, 16, True, 0, jnp.float32),     # MQA
        (2, 128, 4, 4, 64, False, 0, jnp.float32),    # MHA, bidirectional
        (1, 256, 2, 2, 32, True, 64, jnp.float32),    # sliding window
        (1, 128, 8, 2, 32, True, 0, jnp.bfloat16),    # bf16
        (2, 384, 6, 2, 32, True, 96, jnp.bfloat16),   # swa + gqa + bf16
    ],
)
def test_flash_attention_matches_ref(B, S, H, KV, hd, causal, window, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    got = fa_ops.flash_attention(
        q, k, v, causal=causal, window=window, block_q=64, block_k=64
    )
    want = fa_ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


def test_flash_attention_block_shape_invariance():
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 32))
    k = jax.random.normal(ks[1], (1, 256, 2, 32))
    v = jax.random.normal(ks[2], (1, 256, 2, 32))
    outs = [
        np.asarray(fa_ops.flash_attention(q, k, v, block_q=bq, block_k=bk))
        for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, atol=1e-5)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------
def _ssd_inputs(B, S, H, G, N, P, dtype, key=2):
    """Model-layout SSD inputs: the conv output xBC [B, S, H*P + 2*G*N] and
    its x [B,S,H,P], B and C [B,S,G,N]; dt [B,S,H]; A [H] < 0; D [H]."""
    ks = jax.random.split(jax.random.key(key), 6)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.5
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (B, S, G, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, G, N)) * 0.5).astype(dtype)
    D = jax.random.normal(ks[5], (H,))
    xbc = jnp.concatenate([x.reshape(B, S, H * P), Bm.reshape(B, S, G * N),
                           Cm.reshape(B, S, G * N)], axis=-1)
    return xbc, x, dt, a, Bm, Cm, D


@pytest.mark.parametrize(
    "B,S,H,G,N,P,chunk,dtype,heads_per_block",
    [
        (2, 64, 4, 1, 16, 16, 16, jnp.float32, None),  # one block of 4 heads
        (1, 128, 4, 2, 32, 16, 32, jnp.float32, None),   # 2 groups
        (2, 64, 8, 2, 16, 32, 64, jnp.float32, 2),     # chunk == S, 4 blocks
        (1, 64, 4, 1, 16, 16, 16, jnp.bfloat16, 2),
    ],
)
def test_ssd_scan_matches_recurrence(B, S, H, G, N, P, chunk, dtype,
                                     heads_per_block, monkeypatch):
    """The fused kernel (interpreted) against the per-token recurrence."""
    from repro.kernels.ssd_scan import kernel as ssd_kernel

    if heads_per_block:   # more than one block of heads at these widths
        monkeypatch.setattr(ssd_kernel, "head_block",
                            lambda *shape: heads_per_block)
    xbc, x, dt, a, Bm, Cm, D = _ssd_inputs(B, S, H, G, N, P, dtype)
    y, st = ssd_ops.ssd_fused(xbc, dt, a, D, head_dim=P, groups=G, state=N,
                              chunk=chunk, interpret=True)
    # oracle: exact per-token recurrence with per-head broadcast B/C
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    Ch = jnp.repeat(Cm, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, S)
    af = jnp.tile(a, B)
    y_ref, st_ref = ssd_ref.ssd_ref(xf, dtf, af, Bh, Ch)
    y_ref = (y_ref.reshape(B, H, S, P).transpose(0, 2, 1, 3).astype(jnp.float32)
             + x.astype(jnp.float32) * D[:, None])
    st_ref = st_ref.reshape(B, H, N, P)
    assert y.shape == (B, S, H * P) and y.dtype == dtype
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(
        np.asarray(y, np.float32).reshape(B, S, H, P), np.asarray(y_ref),
        atol=tol, rtol=tol,
    )
    np.testing.assert_allclose(
        np.asarray(st), np.asarray(st_ref), atol=tol, rtol=tol
    )


def _tiny_ssm_cfg(**kw):
    from repro.config import ModelConfig

    return ModelConfig(name="t", family="ssm", num_layers=1, d_model=32,
                       ssm_state=16, ssm_head_dim=16, ssm_groups=1,
                       ssm_chunk=16, **kw)


def test_ssd_model_path_matches_kernel():
    """The model's jnp chunked path and the Pallas kernel agree."""
    from repro.models import ssm as S

    cfg = _tiny_ssm_cfg()
    B, Sq = 2, 64
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    xbc, _, dt, a, _, _, D = _ssd_inputs(B, Sq, H, 1, 16, P, jnp.float32,
                                         key=3)
    y1, st1 = S._ssd_jnp(cfg, xbc, dt, a, D)
    y2, st2 = ssd_ops.ssd_fused(xbc, dt, a, D, head_dim=P, groups=1,
                                state=16, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), atol=2e-3, rtol=2e-3)


def test_ssd_kernel_finite_where_the_chunk_exponent_overflows():
    """At chunk 256 the in-chunk exponent cl_i - cl_j above the diagonal
    reaches ~177 here, past exp's f32 range: the kernel masks it before the
    exp, so the forward stays finite and matches the recurrence."""
    B, S, H, G, N, P = 1, 256, 2, 1, 8, 16
    xbc, x, dt, _, Bm, Cm, D = _ssd_inputs(B, S, H, G, N, P, jnp.float32,
                                           key=5)
    dt = jnp.full((B, S, H), 0.69)          # softplus(0)
    a = -jnp.ones((H,))
    y, st = ssd_ops.ssd_fused(xbc, dt, a, D, head_dim=P, groups=G, state=N,
                              chunk=256, interpret=True)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(st).all())
    y_ref, _ = ssd_ref.ssd_ref(
        x.transpose(0, 2, 1, 3).reshape(B * H, S, P),
        dt.transpose(0, 2, 1).reshape(B * H, S), jnp.tile(a, B),
        jnp.repeat(Bm, H, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, N),
        jnp.repeat(Cm, H, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, N))
    y_ref = y_ref.reshape(B, H, S, P).transpose(0, 2, 1, 3) + x * D[:, None]
    np.testing.assert_allclose(np.asarray(y).reshape(B, S, H, P),
                               np.asarray(y_ref), atol=1e-3, rtol=1e-3)


def test_ssd_custom_vjp_routes_primal_to_kernel_and_gradient_to_jnp(
        monkeypatch):
    """``ssm.ssd``: its gradient is bit for bit the plain ``ssd_chunked``
    path's, and its primal is the kernel's output where the program is
    lowered for the kernel (here: the kernel, interpreted, put in place of
    the CPU lowering by the test)."""
    from jax._src.interpreters import mlir
    from repro.models import ssm as S

    cfg = _tiny_ssm_cfg()
    p = S.ssm_params(cfg, jax.random.key(7))
    u = jax.random.normal(jax.random.key(8), (2, 64, cfg.d_model))

    def loss(p, u):
        return jnp.sum(jnp.tanh(S.apply_ssm(cfg, p, u)[0]))

    routed = jax.jit(jax.grad(loss, argnums=(0, 1)))(p, u)
    # today's path: apply_ssm with the jnp SSD and no custom_vjp
    with monkeypatch.context() as m:
        m.setattr(S, "ssd", S._ssd_jnp)
        plain = jax.jit(jax.grad(loss, argnums=(0, 1)))(p, u)
    for g1, g2 in zip(jax.tree_util.tree_leaves(routed),
                      jax.tree_util.tree_leaves(plain)):
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))

    xbc, _, dt, a, _, _, D = _ssd_inputs(2, 64, cfg.ssm_heads, 1, 16, 16,
                                         jnp.float32, key=9)

    def kernel(*args):
        return ssd_ops.ssd_fused(*args, head_dim=16, groups=1, state=16,
                                 chunk=16, interpret=True)

    cpu = mlir._platform_specific_lowerings["cpu"]
    with monkeypatch.context() as m:
        m.setitem(cpu, S._ssd_forward_p, mlir.LoweringRuleEntry(
            mlir.lower_fun(lambda *args, cfg: kernel(*args)), inline=True))
        y, st = jax.jit(lambda *args: S.ssd(cfg, *args))(xbc, dt, a, D)
    y_k, st_k = jax.jit(kernel)(xbc, dt, a, D)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_k))
    np.testing.assert_array_equal(np.asarray(st), np.asarray(st_k))
    # lowered for the CPU, the primal is the jnp path's
    y_j, _ = jax.jit(lambda *args: S.ssd(cfg, *args))(xbc, dt, a, D)
    y_p, _ = jax.jit(lambda *args: S._ssd_jnp(cfg, *args))(xbc, dt, a, D)
    np.testing.assert_array_equal(np.asarray(y_j), np.asarray(y_p))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_in_proj_custom_vjp_routes_gradient_to_the_fused_dot(monkeypatch,
                                                             dtype):
    """``ssm._in_proj``: differentiated, the in-projection is bit for bit
    the one fused dot and its split (f32 weights cast to the activations'
    dtype, as the model runs them)."""
    from repro.models import ssm as S

    cfg = _tiny_ssm_cfg()
    p = S.ssm_params(cfg, jax.random.key(7))
    u = jax.random.normal(jax.random.key(8), (2, 64, cfg.d_model), dtype)

    def loss(p, u):
        y = S.apply_ssm(cfg, p, u)[0]
        return jnp.sum(jnp.tanh(y.astype(jnp.float32)))

    routed = jax.jit(jax.grad(loss, argnums=(0, 1)))(p, u)
    with monkeypatch.context() as m:
        m.setattr(S, "_in_proj", S._in_proj_fused)
        fused = jax.jit(jax.grad(loss, argnums=(0, 1)))(p, u)
    for g1, g2 in zip(jax.tree_util.tree_leaves(routed),
                      jax.tree_util.tree_leaves(fused)):
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))


@pytest.mark.parametrize("dtype,ulps", [(jnp.float32, 0), (jnp.bfloat16, 1)])
def test_in_proj_primal_splits_the_fused_dot(dtype, ulps):
    """The primal's three dots give the fused dot's slices: exactly in
    float32, on integer-valued data whose sums are exact in any order (on
    random data the CPU's dot sums in an order that follows the output
    width, a last-bit difference); bfloat16 rounds each f32 sum once, so
    random data agree within one ulp."""
    from repro.models import ssm as S

    cfg = _tiny_ssm_cfg()
    din, gn = cfg.ssm_d_inner, 2 * cfg.ssm_groups * cfg.ssm_state
    k = din + din + gn + cfg.ssm_heads
    ku, kw = jax.random.split(jax.random.key(8))
    if dtype == jnp.float32:
        u = jax.random.randint(ku, (2, 64, cfg.d_model), -8, 9).astype(dtype)
        w = jax.random.randint(kw, (cfg.d_model, k), -8, 9).astype(dtype)
    else:
        u = jax.random.normal(ku, (2, 64, cfg.d_model), dtype)
        w = jax.random.normal(kw, (cfg.d_model, k), dtype)
    split = jax.jit(lambda u, w: S._in_proj(cfg, u, w))(u, w)
    want = jax.jit(lambda u, w: S._in_proj_fused(cfg, u, w))(u, w)
    assert [a.shape[-1] for a in split] == [din, din + gn, cfg.ssm_heads]
    for a, b in zip(split, want):
        assert a.shape == b.shape and a.dtype == b.dtype == dtype
        a = np.asarray(a.astype(jnp.float32))
        b = np.asarray(b.astype(jnp.float32))
        # one ulp of the dtype at b: float32's spacing, 2**16 times wider
        # for bfloat16's 7 fraction bits
        ulp = np.spacing(np.abs(b)) * (2.0 ** 16 if dtype == jnp.bfloat16
                                       else 1.0)
        assert np.all(np.abs(a - b) <= ulps * ulp)


def test_ssd_chunked_gradient_finite_at_full_chunk():
    """At the configs' chunk of 256 the in-chunk decay exponent above the
    diagonal overflows exp; the gradient must still be finite (a retrain of
    a full-width mamba2 otherwise turns every param NaN)."""
    from repro.config import ModelConfig
    from repro.models import ssm as S

    cfg = ModelConfig(name="t", family="ssm", num_layers=1, d_model=32,
                      ssm_state=4, ssm_head_dim=16, ssm_groups=1,
                      ssm_chunk=256)
    B, Sq, H, P = 1, 256, cfg.ssm_heads, cfg.ssm_head_dim
    ks = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(ks[0], (B, Sq, H, P))
    Bm = jax.random.normal(ks[1], (B, Sq, 1, 4))
    Cm = jax.random.normal(ks[2], (B, Sq, 1, 4))
    a = -jnp.ones((H,))

    def total(dt):
        return jnp.sum(S.ssd_chunked(cfg, x, dt, a, Bm, Cm)[0])

    g = jax.grad(total)(jnp.full((B, Sq, H), 0.69))   # softplus(0)
    assert bool(jnp.isfinite(g).all())


def test_ssd_forward_under_vmap_equals_a_loop():
    """vmap of ``ssm.ssd``: a mapped axis of xBC and dt joins the batch, a
    mapped A runs the jnp path under vmap; both equal a loop of calls."""
    from repro.models import ssm as S

    cfg = _tiny_ssm_cfg()
    H = cfg.ssm_heads
    xbc, _, dt, a, _, _, D = _ssd_inputs(3 * 2, 32, H, 1, 16, 16,
                                         jnp.float32, key=11)
    xbc, dt = xbc.reshape((3, 2) + xbc.shape[1:]), dt.reshape((3, 2, 32, H))
    a3 = a * jnp.arange(1.0, 4.0)[:, None]
    f = jax.jit(lambda *args: S.ssd(cfg, *args))
    for got, args in (
            (jax.vmap(f, (0, 0, None, None))(xbc, dt, a, D),
             lambda i: (xbc[i], dt[i], a, D)),
            (jax.vmap(f, (0, None, 0, None))(xbc, dt[0], a3, D),
             lambda i: (xbc[i], dt[0], a3[i], D))):
        for i in range(3):
            want = f(*args(i))
            for g, w in zip(got, want):
                np.testing.assert_allclose(np.asarray(g[i]), np.asarray(w),
                                           atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# reservoir compaction
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "cap,D,frac,block,dtype",
    [
        (256, 8, 0.5, 64, jnp.float32),
        (128, 16, 0.0, 128, jnp.float32),   # keep nothing
        (128, 16, 1.0, 32, jnp.float32),    # keep everything
        (512, 4, 0.25, 128, jnp.int32),     # int payload (token ids)
        (256, 8, 0.9, 64, jnp.bfloat16),
        (301, 8, 0.5, 128, jnp.float32),    # cap not a block multiple (padded)
    ],
)
def test_reservoir_compact_matches_ref(cap, D, frac, block, dtype):
    """impl="interpret" executes the kernel BODY on CPU (the auto route is
    the jnp oracle off-TPU, which would test ref against itself)."""
    k1, k2 = jax.random.split(jax.random.key(4))
    if dtype == jnp.int32:
        items = jax.random.randint(k1, (cap, D), 0, 1000, jnp.int32)
    else:
        items = jax.random.normal(k1, (cap, D), dtype)
    mask = jax.random.bernoulli(k2, frac, (cap,))
    got, cnt = rc_ops.reservoir_compact(items, mask, block=block,
                                        impl="interpret")
    want, cnt_ref = rc_ref.compact_ref(items, mask)
    assert int(cnt) == int(cnt_ref) == int(np.asarray(mask).sum())
    np.testing.assert_array_equal(
        np.asarray(got[: int(cnt)]), np.asarray(want[: int(cnt)])
    )


@settings(max_examples=20, deadline=None)
@given(
    cap_blocks=st.integers(1, 4),
    d=st.sampled_from([4, 8]),
    seed=st.integers(0, 2**16),
)
def test_reservoir_compact_property(cap_blocks, d, seed):
    """Property: stable compaction == numpy boolean indexing, any mask."""
    cap = 64 * cap_blocks
    rs = np.random.RandomState(seed)
    items = jnp.asarray(rs.randint(0, 10**6, (cap, d)), jnp.int32)
    mask = jnp.asarray(rs.rand(cap) < rs.rand())
    got, cnt = rc_ops.reservoir_compact(items, mask, block=64,
                                        impl="interpret")
    want = np.asarray(items)[np.asarray(mask)]
    assert int(cnt) == want.shape[0]
    np.testing.assert_array_equal(np.asarray(got[: int(cnt)]), want)
