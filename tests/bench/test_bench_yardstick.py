"""The benchmark's own arithmetic: peaks, FLOPs, traffic."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, gen
from bench.peaks import peaks_for

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_peaks_are_keyed_by_device_kind():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")


def test_mamba2_param_count_matches_the_model():
    from repro import config as C

    cfg = json.loads((ROOT / "bench/configs/lm_mamba2_370m.json").read_text())
    n = flops.mamba2_param_count({
        "d_model": cfg["d_model"], "num_hidden_layers": cfg["n_layer"],
        "vocab_size": cfg["vocab_size"], "expand": cfg["expand"],
        "state_size": cfg["d_state"], "n_groups": cfg["ngroups"],
        "head_dim": cfg["headdim"], "conv_kernel": cfg["d_conv"]})
    mc = C.get_config("mamba2_370m")
    # the program's analytic count leaves out the conv bias and one of the
    # three per-head vectors (dt_bias, A_log, D)
    extra = mc.num_layers * (mc.ssm_d_inner + 2 * mc.ssm_state
                             + mc.ssm_heads)
    assert n == mc.param_count() + extra
    assert 3.6e8 < n < 3.8e8


def test_model_flops_rule():
    assert flops.model_flops(10, 7, train=True) == 420
    assert flops.model_flops(10, 7, train=False) == 140


def test_token_chains_follow_their_language():
    toks = np.asarray(gen.token_chains(
        jax.random.key(0), ticks=6, per_tick=3, seq_len=12, vocab=50,
        branching=2, flip_every=3))
    assert toks.shape == (6, 3, 12) and toks.min() >= 0 and toks.max() < 50
    # within one language each token has at most `branching` successors
    for lang in (toks[:3], toks[3:]):
        succ = {}
        for row in lang.reshape(-1, 12):
            for a, b in zip(row[:-1], row[1:]):
                succ.setdefault(a, set()).add(b)
        assert max(len(v) for v in succ.values()) <= 2


def test_row_hash_tells_rows_apart():
    a = jnp.arange(24, dtype=jnp.int32).reshape(2, 3, 4)
    h = gen.hash64(np.asarray(gen.row_hash(a, lead=2)))
    assert h.shape == (2, 3) and len(set(h.reshape(-1).tolist())) == 6
    b = a.at[1, 2, 3].add(1)
    h2 = gen.hash64(np.asarray(gen.row_hash(b, lead=2)))
    assert (h2 != h).sum() == 1
    f = {"x": jnp.ones((2, 5), jnp.float32), "y": jnp.zeros((2,), jnp.int32)}
    assert gen.hash64(np.asarray(gen.row_hash(f, lead=1))).shape == (2,)


def test_seed_key_takes_seeds_past_32_bits():
    k1 = gen.seed_key(2**40 + 5, 0)
    k2 = gen.seed_key(5, 0)
    assert not np.array_equal(jax.random.key_data(k1), jax.random.key_data(k2))
