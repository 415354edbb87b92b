"""The plain references agree with the program on the CPU at small sizes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.refs import mamba2 as ref
from bench.refs import rtbs as rtbs_ref

CFG = {"d_model": 64, "n_layer": 2, "vocab_size": 500, "d_state": 16,
       "headdim": 16, "ngroups": 1, "expand": 2, "d_conv": 4,
       "chunk_size": 8, "norm_eps": 1e-5}
OPT = {"lr": 3e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "clip_norm": 1.0, "warmup": 2, "total_steps": 4000}


@pytest.fixture(scope="module")
def api():
    from repro import config as C
    from repro.models import zoo

    mc = dataclasses.replace(
        C.get_config("mamba2_370m"), num_layers=2, d_model=64,
        vocab_size=500, ssm_state=16, ssm_head_dim=16, ssm_chunk=8,
        dtype="float32")
    return zoo.build(mc)


def test_weights_have_the_programs_layout(api):
    p = ref.init_params(CFG, jax.random.key(0))
    want = jax.eval_shape(api.init_params, jax.random.key(0))
    assert jax.tree_util.tree_structure(p) == \
        jax.tree_util.tree_structure(want)
    assert [x.shape for x in jax.tree_util.tree_leaves(p)] == \
        [x.shape for x in jax.tree_util.tree_leaves(want)]


def test_loss_and_gradient_match_the_program_in_float32(api):
    p = ref.init_params(CFG, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (4, 32), 0, 500)
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(api.loss)(p, {"tokens": toks})
    assert ref.eval_loss(CFG, p, toks, rows=2) == pytest.approx(
        float(want), rel=1e-5)
    got, g = ref.grads(CFG, p, toks, rows=2)
    assert got == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_fp8_control_moves_the_loss_more_than_bfloat16(api):
    p = ref.init_params(CFG, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (4, 32), 0, 500)
    f32 = ref.eval_loss(CFG, p, toks, rows=2)
    fp8 = ref.eval_loss(CFG, p, toks, rows=2, precision="fp8")
    from repro import config as C
    from repro.models import zoo

    bf16 = zoo.build(dataclasses.replace(api.cfg, dtype="bfloat16"))
    prog = float(bf16.loss(p, {"tokens": toks}))
    assert abs(fp8 - f32) > 3 * abs(prog - f32)
    del C


def test_retrain_follows_the_adapter_step_for_step(api):
    from repro.manage import make_sgd_adapter
    from repro.optim import AdamWConfig, adamw_init
    from repro.train.steps import make_train_step

    p0 = ref.init_params(CFG, jax.random.key(0))
    adapter = make_sgd_adapter(
        init_params=lambda: p0,
        train_step=make_train_step(api, AdamWConfig(**{
            k: OPT[k] for k in ("lr", "b1", "b2", "eps", "weight_decay",
                                "clip_norm")}), warmup=OPT["warmup"],
            total_steps=OPT["total_steps"]),
        init_opt_state=adamw_init, loss=api.loss, batch_field="tokens",
        train_batch=4, retrain_steps=3)
    items = jax.random.randint(jax.random.key(2), (17, 32), 0, 500)
    mask = jnp.arange(17) < 13
    from repro.core.api import SampleView

    key = jax.random.key(5)
    with jax.default_matmul_precision("highest"):
        got = adapter.fit(key, adapter.init(),
                          SampleView(items=items, mask=mask, size=13))
    p, opt = ref.retrain(CFG, OPT, p0, ref.adamw_init(p0), np.asarray(items),
                         mask, key, steps=3, batch=4, rows=2)
    for a, b in zip(jax.tree_util.tree_leaves(got["params"]),
                    jax.tree_util.tree_leaves(p)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got["opt"]["m"]),
                    jax.tree_util.tree_leaves(opt["m"])):
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_total_weight_matches_the_sampler():
    from repro.core.api import make_sampler

    s = make_sampler("rtbs", n=20, lam=0.1)
    counts = np.array([5, 0, 7, 9, 3, 0, 0, 11, 4, 6])
    st = s.init(jax.ShapeDtypeStruct((3,), jnp.float32))
    want = rtbs_ref.weights(counts, 0.1)
    for t, c in enumerate(counts):
        st = s.step(jax.random.key(t), st, jnp.ones((12, 3)), jnp.int32(c))
        assert float(st.total_weight) == pytest.approx(want[t], rel=1e-5)
        size = int(s.size(jax.random.key(100 + t), st))
        assert rtbs_ref.sample_size_ok(size, want[t], 20)


def test_sample_size_rule():
    assert rtbs_ref.sample_size_ok(3, 3.4, 10)
    assert rtbs_ref.sample_size_ok(4, 3.4, 10)
    assert not rtbs_ref.sample_size_ok(5, 3.4, 10)
    assert not rtbs_ref.sample_size_ok(2, 3.4, 10)
    assert rtbs_ref.sample_size_ok(10, 50.0, 10)
    assert not rtbs_ref.sample_size_ok(11, 50.0, 10)


def test_age_band_z_separates_the_sampler_from_one_that_never_evicts():
    from repro.core.api import make_sampler

    n, b, lam, T = 64, 8, 0.02, 120
    s = make_sampler("rtbs", n=n, lam=lam)
    ids = jnp.arange(T * b, dtype=jnp.int32).reshape(T, b)

    @jax.jit
    def run(key):
        def body(st, x):
            t, batch = x
            return s.step(jax.random.fold_in(key, t), st, batch,
                          jnp.int32(b)), None

        st = s.init(jax.ShapeDtypeStruct((), jnp.int32))
        return jax.lax.scan(body, st, (jnp.arange(T), ids))[0]

    counts = np.full(T, b)
    for seed in range(8):
        st = run(jax.random.key(seed))
        items, nf = np.asarray(st.lat.items), int(st.lat.nfull)
        c = float(st.lat.weight)
        frac = c - np.floor(c)
        part = (T - 1) - items[nf] // b if frac > 0 else None
        z = rtbs_ref.age_band_z((T - 1) - items[:nf] // b, part, frac,
                                counts, lam, n)
        assert z < 4, (seed, z)
    # the first n arrivals kept, as a sampler that never evicts keeps them
    first = (T - 1) - np.arange(n) // b
    assert rtbs_ref.age_band_z(first, None, 0.0, counts, lam, n) > 20
    # the newest n kept: as far off the other way
    newest = np.arange(n) // b
    assert rtbs_ref.age_band_z(newest, None, 0.0, counts, lam, n) > 8
