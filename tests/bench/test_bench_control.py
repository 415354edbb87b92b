"""The comparison's control, at a size a test run holds: the plain
reference put in the program's place one precision step below what the
configuration states makes a whole run come out as not correct."""
import jax

from bench import run as R
from bench.tools.control import control_edit
from small_sizes import LM_CELL, lm_small


def test_lm_fp8_control_fails_the_cells_limits():
    def edit(cfg, traffic):
        lm_small(cfg, traffic)
        control_edit("fp8")(cfg, traffic)

    jax.clear_caches()
    out = R.run(LM_CELL, 31337, 0.2, False, require_tpu=False, edit=edit)
    lim = {k: c["limit"] for k, c in out["checks"].items()}
    assert not out["correct"], out["checks"]
    # the model's numbers fail; the sample, which the control leaves to the
    # program, does not
    failed = {k for k, c in out["checks"].items() if c["value"] > lim[k]}
    assert failed and failed <= {"eval_gap", "m_gap", "update_gap"}, failed
