"""A whole granite-cell run at a small size, past the look for a chip: it
comes out correct, and reports what the cell's entry says it reports."""
import jax

from bench import run as R
from granite_sizes import GRANITE_CELL, granite_small


def test_sound_run_is_correct():
    jax.clear_caches()
    out = R.run(GRANITE_CELL, 2**40 + 16, 0.2, False, require_tpu=False,
                edit=granite_small)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"ingest_items_per_s", "setup_s"} <= set(out["metrics"])
    assert set(out["checks"]) == {"sample_errors", "w_gap", "age_band_z",
                                  "eval_gap", "m_gap", "update_gap"}


def test_cell_reports_its_metrics():
    spec, entry, cfg, traffic = R.load_cell(GRANITE_CELL)
    assert entry["chips"] == 1 and cfg["driver"] == "lm_granite"
    assert set(R.readers(spec, GRANITE_CELL)) == {
        "mfu.granite4h", "eval_ms.granite4h", "retrain_ms.granite4h",
        "attn_ms.granite4h", "mlp_ms.granite4h", "device_idle_share"}
    assert set(R.end_to_end(spec, GRANITE_CELL)) == {
        "ingest_items_per_s", "setup_s"}
