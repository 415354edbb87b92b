"""At a small size, the comparison has to catch a fault of the granite
architecture planted under the timed path (the attention branch added to
the residual stream without the residual multiplier), and the float8
control: the reference with every matmul operand rounded to float8 e4m3 in
the program's place."""
import jax

from bench import run as R
from bench.tools.control import control_edit
from granite_sizes import GRANITE_CELL, granite_small


def test_residual_multiplier_dropped_on_attention_is_not_correct(
        monkeypatch):
    from repro.models import pattern_lm

    attend = pattern_lm._attention

    def unscaled(cfg, p, u, positions):
        return attend(cfg, p, u, positions) / cfg.residual_multiplier

    monkeypatch.setattr(pattern_lm, "_attention", unscaled)
    jax.clear_caches()
    out = R.run(GRANITE_CELL, 31337, 0.2, False, require_tpu=False,
                edit=granite_small)
    assert not out["correct"], out["checks"]
    failed = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert failed and failed <= {"eval_gap", "m_gap", "update_gap"}, failed


def test_float8_control_is_not_correct():
    fp8 = control_edit("fp8")

    def edit(cfg, traffic):
        granite_small(cfg, traffic)
        fp8(cfg, traffic)

    jax.clear_caches()
    out = R.run(GRANITE_CELL, 31337, 0.2, False, require_tpu=False, edit=edit)
    assert not out["correct"], out["checks"]
    failed = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    # the eval loss tells the precision apart; the worst leaf of the moment
    # or of the update does not (tests/bench/granite_sizes.py)
    assert "eval_gap" in failed, out["checks"]
