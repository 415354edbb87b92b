"""A whole LM-cell run at a small size, past the look for a chip: sound, it
comes out correct; with the timed path broken underneath, it does not."""
import jax
import pytest

from bench import run as R
from bench.tools.faults import FAULTS
from small_sizes import LM_CELL, lm_small


def run_small(seed=1234567890123):
    jax.clear_caches()
    return R.run(LM_CELL, seed, 0.2, False, require_tpu=False, edit=lm_small)


def test_sound_run_is_correct():
    out = run_small()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ingest_items_per_s", "staleness_p95_s",
                                   "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run_small()
    assert not out["correct"], out["checks"]
