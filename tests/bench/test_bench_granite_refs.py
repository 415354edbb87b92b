"""The granite cell's yardstick at small sizes on the CPU: the plain
reference agrees with the program (logits, loss, every gradient leaf), the
counts agree at the cell's own size, and the per-layer readers find the
scopes ``lm.attn`` and ``lm.mlp`` in the compiled tick."""
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops_granite
from bench import run as R
from bench import scopes as S
from bench import trace as tr
from bench.drivers.lm_granite import Cell
from bench.peaks import peaks_for
from bench.refs import granite_h as ref
from granite_sizes import GRANITE_CELL, SMALL, granite_small

ROOT = pathlib.Path(__file__).resolve().parents[2]
CFG = json.loads((ROOT / "bench/configs/lm_granite4h_micro.json").read_text())


def small_cfg(**kw):
    return dict(CFG, **SMALL, **kw)


@pytest.fixture(scope="module")
def api():
    from repro.models import zoo

    cell = Cell(small_cfg(dtype="float32"), {"per_tick": 8,
                                             "prefill_ticks": 4}, 0, 1)
    return zoo.build(cell._model_config())


def test_the_small_pattern_has_attention_inside_and_multipliers():
    cfg = small_cfg()
    assert cfg["layer_types"][0] == "mamba" and "attention" in \
        cfg["layer_types"][1:]
    for k in ("embedding_multiplier", "residual_multiplier",
              "logits_scaling", "attention_multiplier"):
        assert cfg[k] != 1, k
    assert cfg["attention_multiplier"] != (
        cfg["hidden_size"] // cfg["num_attention_heads"]) ** -0.5


def test_weights_have_the_programs_layout(api):
    p = ref.init_params(small_cfg(), jax.random.key(0))
    want = jax.eval_shape(api.init_params, jax.random.key(0))
    assert jax.tree_util.tree_structure(p) == \
        jax.tree_util.tree_structure(want)
    assert [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(p)] == \
        [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(want)]


def test_logits_loss_and_gradient_match_the_program_in_float32(api):
    cfg = small_cfg()
    p = ref.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (4, 32), 0,
                              cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        logits = api.forward(p, {"tokens": toks})
        want = ref.logits(cfg, "f32", p, toks)
        loss, g = jax.value_and_grad(api.loss)(p, {"tokens": toks})
    np.testing.assert_allclose(logits, want, rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(want).max()))
    assert ref.eval_loss(cfg, p, toks, rows=2) == pytest.approx(
        float(loss), rel=1e-5)
    got, g_want = ref.grads(cfg, p, toks, rows=2)
    assert got == pytest.approx(float(loss), rel=1e-5)
    leaves = jax.tree_util.tree_leaves_with_path(g)
    assert len(leaves) == len(jax.tree_util.tree_leaves(g_want))
    for (path, a), b in zip(leaves, jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=1e-5 * float(jnp.abs(b).max()),
            err_msg=jax.tree_util.keystr(path))


def test_fp8_control_moves_the_loss_more_than_bfloat16(api):
    import dataclasses

    from repro.models import zoo

    cfg = small_cfg()
    p = ref.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (4, 32), 0,
                              cfg["vocab_size"])
    f32 = ref.eval_loss(cfg, p, toks, rows=2)
    fp8 = ref.eval_loss(cfg, p, toks, rows=2, precision="fp8")
    bf16 = zoo.build(dataclasses.replace(api.cfg, dtype="bfloat16"))
    prog = float(bf16.loss(p, {"tokens": toks}))
    assert abs(fp8 - f32) > 3 * abs(prog - f32)


def test_counts_agree_at_the_cells_size():
    # shapes only: nothing of the cell's size is allocated
    want = 772_160_448
    shapes = jax.eval_shape(lambda: ref.init_params(CFG, jax.random.key(0)))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == want
    assert flops_granite.param_count(CFG) == want
    mc = Cell(CFG, {"per_tick": 16, "prefill_ticks": 4}, 0, 1)._model_config()
    assert mc.param_count() == want


def test_model_config_is_the_programs_published_one_cut():
    import dataclasses

    from repro.configs.granite_4_0_h_micro import CONFIG

    mc = Cell(CFG, {"per_tick": 16, "prefill_ticks": 4}, 0, 1)._model_config()
    assert mc == dataclasses.replace(
        CONFIG, num_layers=10, layer_pattern=CONFIG.layer_pattern[:10],
        vocab_size=12544)


def test_window_flops_add_the_attention_scores():
    n = flops_granite.param_count(CFG)
    # one attention layer: 2 * 2048 * 32 * 64 per token forward
    per_token = 2 * 2048 * 32 * 64
    assert flops_granite.window_flops(CFG, 10, 0) == 10 * (2 * n + per_token)
    assert flops_granite.window_flops(CFG, 0, 10) == \
        10 * (6 * n + 3 * per_token)


# ---------------------------------------------------------------------------
# the readers, on the compiled tick of the small cell
# ---------------------------------------------------------------------------
METRICS = ROOT / "bench" / "metrics"
READERS = ("mfu.granite4h", "eval_ms.granite4h", "retrain_ms.granite4h",
           "attn_ms.granite4h", "mlp_ms.granite4h")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def tick():
    """(cell, module name, instruction -> op_name path) of the small
    cell's compiled tick."""
    spec, entry, cfg, traffic = R.load_cell(GRANITE_CELL)
    granite_small(cfg, traffic)
    jax.clear_caches()
    cell = R.driver(cfg)(cfg, traffic, 7, 1)
    cell.setup()
    (fn, args), = cell.programs()
    return (cell,) + tr.scope_map(fn.lower(*args).compile().as_text())


def _paths(tick, *names):
    return [p for p in tick[2].values() if set(names) <= S.path_names(p)]


@pytest.mark.parametrize("scope", ["lm.attn", "lm.mlp"])
def test_scope_runs_in_the_eval_and_the_retrains_backward(tick, scope):
    assert _paths(tick, "manage.eval", scope)
    assert [p for p in _paths(tick, "manage.retrain", scope)
            if "transpose(" in p]


class _Counts:
    cfg = {}

    def __init__(self, cell):
        self.cell = cell
        self.counts = {"ticks": 4, "retrains": 1, "items": 32,
                       "eval_tokens": 1024, "trained_tokens": 256}

    def window_flops(self):
        return flops_granite.window_flops(
            self.cell.cfg, self.counts["eval_tokens"],
            self.counts["trained_tokens"])


def _ctx(tick, ops):
    cell, module, names = tick
    trace = tr.Trace(ops=[tr.Op(0, module, n, a, d) for n, a, d in ops],
                     spans=[], devices=1)
    return R.Context(trace, {module: names}, 0, 10_000, _Counts(cell),
                     peaks_for("TPU v5 lite"), 1)


def _one(tick, scope):
    return next(i for i, p in tick[2].items()
                if scope in S.path_names(p) and "manage.eval" in
                S.path_names(p))


def test_readers(tick):
    ops = [(_one(tick, "lm.attn"), 0, 100), (_one(tick, "lm.mlp"), 200, 40)]
    ctx = _ctx(tick, ops)
    got = {name: reader(name)(ctx) for name in READERS}
    assert got["attn_ms.granite4h"] == pytest.approx(1e-6 * 100 / 4)
    assert got["mlp_ms.granite4h"] == pytest.approx(1e-6 * 40 / 4)
    assert got["eval_ms.granite4h"] == pytest.approx(1e-6 * 140 / 4)
    assert got["retrain_ms.granite4h"] == 0
    assert got["mfu.granite4h"] == pytest.approx(
        100 * ctx.cell.window_flops() / (1e-5 * 197e12))


def test_layer_readers_on_a_program_without_the_scopes(tick):
    trace = tr.Trace(ops=[tr.Op(0, "jit_step", "fusion.1", 0, 10)],
                     spans=[], devices=1)
    ctx = R.Context(trace, {"jit_step": {"fusion.1":
                                         "jit(step)/manage.eval/dot"}},
                    0, 10_000, _Counts(tick[0]), peaks_for("TPU v5 lite"), 1)
    assert reader("attn_ms.granite4h")(ctx) is None
    assert reader("mlp_ms.granite4h")(ctx) is None
