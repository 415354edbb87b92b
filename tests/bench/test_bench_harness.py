"""The benchmark finds every configuration, mix and metric by name, keeps
to the shape of BENCHMARK.json, and refuses to measure without a TPU."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from bench import run as R
from small_sizes import LM_CELL

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_loads_by_name(workload):
    spec, entry, cfg, traffic = R.load_cell(workload)
    assert callable(R.driver(cfg))
    rd = R.readers(spec, workload)
    want = {m["name"] for m in SPEC["per_layer"]
            if workload in m.get("workloads", [workload])}
    assert set(rd) == want and want
    assert all(callable(read) for read, _ in rd.values())
    e2e = set(R.end_to_end(spec, workload))
    assert {"setup_s", "ingest_items_per_s"} <= e2e
    assert len(e2e) >= 2


def test_every_named_file_is_under_paths():
    paths = SPEC["paths"]
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert any(c["file"].startswith(p + "/") for p in paths)
    for w in SPEC["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in SPEC["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_benchmark_json_keeps_its_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][0] == "python3" and len(SPEC["command"]) <= 32
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    configs = {c["name"] for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        for w in m.get("workloads", []):
            assert w in CELLS and m["moves"] in R.end_to_end(SPEC, w)
    # a full check with 24 cells fits its time
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_refuses_to_measure_on_cpu(capsys):
    rc = R.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, *SPEC["command"][1:], "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "repro" in r.stderr


def test_lm_cell_reports_its_metrics():
    spec, entry, cfg, traffic = R.load_cell(LM_CELL)
    assert set(R.readers(spec, LM_CELL)) == {
        "mfu.lm", "retrain_ms.lm", "eval_ms.lm", "device_idle_share"}
    assert set(R.end_to_end(spec, LM_CELL)) == {
        "ingest_items_per_s", "staleness_p95_s", "setup_s"}
    assert set(cfg["limits"]) == {"sample_errors", "w_gap", "age_band_z",
                                  "eval_gap", "m_gap", "update_gap"}
