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
@pytest.mark.parametrize(
    "B,S,H,G,N,P,chunk,dtype",
    [
        (2, 64, 4, 1, 16, 16, 16, jnp.float32),
        (1, 128, 4, 2, 32, 16, 32, jnp.float32),   # 2 groups
        (2, 64, 2, 2, 16, 32, 64, jnp.float32),    # chunk == S
        (1, 64, 4, 1, 16, 16, 16, jnp.bfloat16),
    ],
)
def test_ssd_scan_matches_recurrence(B, S, H, G, N, P, chunk, dtype):
    ks = jax.random.split(jax.random.key(2), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.5
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N), dtype) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N), dtype) * 0.5
    y, st = ssd_ops.ssd_scan(x, dt, a, Bm, Cm, chunk=chunk)
    # oracle: exact per-token recurrence with per-head broadcast B/C
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    Ch = jnp.repeat(Cm, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, S)
    af = jnp.tile(a, B)
    y_ref, st_ref = ssd_ref.ssd_ref(xf, dtf, af, Bh, Ch)
    y_ref = y_ref.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    st_ref = st_ref.reshape(B, H, N, P)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
        atol=tol, rtol=tol,
    )
    np.testing.assert_allclose(
        np.asarray(st), np.asarray(st_ref), atol=tol, rtol=tol
    )


def test_ssd_model_path_matches_kernel():
    """The model's jnp chunked path and the Pallas kernel agree."""
    from repro.config import ModelConfig
    from repro.models import ssm as S

    cfg = ModelConfig(
        name="t", family="ssm", num_layers=1, d_model=32, ssm_state=16,
        ssm_head_dim=16, ssm_groups=1, ssm_chunk=16,
    )
    B, Sq = 2, 64
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    ks = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(ks[0], (B, Sq, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, Sq, H))) * 0.5
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, Sq, 1, 16)) * 0.5
    Cm = jax.random.normal(ks[4], (B, Sq, 1, 16)) * 0.5
    y1, st1 = S.ssd_chunked(cfg, x, dt, a, Bm, Cm)
    y2, st2 = ssd_ops.ssd_scan(x, dt, a, Bm, Cm, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), atol=2e-3, rtol=2e-3)


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
