"""Paper Figures 7/8/9: distributed implementation comparison + scaling.

Fig. 7: per-batch time of the five implementation points on an 8-shard host
mesh. Expected ordering (as in the paper): D-T-TBS < CP+Dist < CP+Cent <
KV+CJ < KV+RJ. Host-mesh wall time measures total work + copies (all shards
share one CPU), so it reflects the paper's *work/traffic* ordering rather
than real network latency -- EXPERIMENTS.md notes the caveat.

Fig. 8 (scale-out): CP+Dist per-batch time vs shard count at fixed global
batch. Fig. 9 (scale-up): per-batch time vs per-shard batch size.

``fig8_fusedloop_*`` is the scale-out of the FULL fused sharded manage loop
(stream -> sample -> retrain -> eval via repro.manage.make_sharded_run_loop)
rather than the bare sampler step -- the Sec. 5 algorithms driving the
Sec. 6 experiment harness in one program (protocol in EXPERIMENTS.md)."""
from __future__ import annotations

import subprocess
import sys

from .common import REPO_ROOT, host_mesh_env


def _worker(shards, bps, impl, timeout=600):
    env = host_mesh_env()
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks._dist_worker",
         str(shards), str(bps), impl],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=str(REPO_ROOT),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    return float(line.split(",")[1])


def run():
    rows = []
    # Fig. 7: implementation comparison at 8 shards
    for impl in ("dttbs", "cp_dist", "cp_cent", "kv_cj", "kv_rj"):
        us = _worker(8, 2048, impl)
        rows.append((f"fig7_impl_{impl}", us, {"shards": 8, "batch/shard": 2048}))
    # Fig. 8: scale-out (fixed global batch = 16384)
    for shards in (1, 2, 4, 8):
        us = _worker(shards, 16384 // shards, "cp_dist")
        rows.append((f"fig8_scaleout_{shards}w", us,
                     {"global_batch": 16384, "shards": shards}))
    # Fig. 9: scale-up (8 shards, growing batch)
    for bps in (512, 2048, 8192):
        us = _worker(8, bps, "cp_dist")
        rows.append((f"fig9_scaleup_b{bps}", us,
                     {"shards": 8, "batch/shard": bps}))
    # Fig. 8 companion: the whole fused manage loop scaling out
    from .manage_loop import _sharded_worker

    for shards in (1, 2, 4, 8):
        us = _sharded_worker(shards, "fused")
        rows.append((f"fig8_fusedloop_{shards}w", us,
                     {"shards": shards, "us_per_tick": round(us, 1)}))
    return rows


if __name__ == "__main__":
    from .common import emit

    emit(run())
