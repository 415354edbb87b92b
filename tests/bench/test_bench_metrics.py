"""Each per-layer metric's reader, on a context with known numbers."""
import importlib.util
import pathlib

import pytest

from bench.peaks import peaks_for

METRICS = pathlib.Path(__file__).resolve().parents[2] / "bench" / "metrics"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class FakeCell:
    cfg = {}

    def model_params(self):
        return 1_000_000


class FakeCtx:
    window_s = 2.0
    busy_s = 1.5
    chips = 1
    peaks = peaks_for("TPU v5 lite")
    cell = FakeCell()
    counts = {"ticks": 100, "retrains": 25, "items": 1600,
              "eval_tokens": 1000, "trained_tokens": 500}

    def scope_s(self, scope):
        return {"manage.eval": 0.4, "manage.retrain": 1.0}[scope]


@pytest.mark.parametrize("name,want", [
    ("eval_ms.lm", 4.0), ("retrain_ms.lm", 40.0),
    ("device_idle_share", 25.0),
    ("mfu.lm", 100.0 * (2e6 * 1000 + 6e6 * 500) / (2.0 * 197e12)),
])
def test_reader(name, want):
    assert reader(name)(FakeCtx()) == pytest.approx(want)


def test_readers_find_nothing_to_read():
    class Empty(FakeCtx):
        counts = dict(FakeCtx.counts, retrains=0)

    assert reader("retrain_ms.lm")(Empty()) is None
