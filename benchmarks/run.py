"""Benchmark harness: one module per paper table/figure (+ the roofline
report). Prints ``name,us_per_call,derived`` CSV.

  fig1   -- sample-size behaviour, T-TBS vs R-TBS (paper Fig. 1)
  table1 -- kNN accuracy + 10% ES across drift patterns (paper Table 1/Fig.10)
  fig12  -- linear regression MSE + ES, saturated/unsaturated (paper Fig. 12)
  fig13  -- Naive Bayes on the Usenet2-like stream (paper Fig. 13)
  fig789 -- distributed impl comparison + scale-out/up (paper Figs. 7-9)
  manage -- fused/superbatched manage loop + sampler-step criterion
            (writes BENCH_manage_loop.json)
  sampler -- sampler-step throughput sweep, fused vs pre-fused reference
            (writes BENCH_sampler_step.json)
  decay  -- static lambda vs polynomial vs adaptive decay on the Sec. 6.2
            drift scenarios (writes BENCH_decay_sweep.json)
  bank   -- keyed multi-tenant bank step vs naive per-key dispatch at
            growing K (writes BENCH_bank_step.json)
  obs    -- in-scan telemetry on/off overhead on the fused manage loop and
            the K=4096 bank step (writes BENCH_obs_overhead.json)
  roofline -- dry-run roofline table (EXPERIMENTS.md §Roofline)

Select with ``python -m benchmarks.run [names...]`` (default: all).
``--smoke`` / BENCH_SMOKE=1 shrinks the json-emitting suites to CI size.
A suite that fails prints an ERROR row and the run exits non-zero. All
suites run in this one process; ``fig789`` and ``manage`` time host-device
meshes in child processes and so run only under ``JAX_PLATFORMS=cpu``.
"""
from __future__ import annotations

import sys
import time

from .common import emit

SUITES = ["fig1", "table1", "fig12", "fig13", "fig789", "manage", "sampler",
          "decay", "bank", "obs", "roofline"]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = [a for a in sys.argv[1:] if a != "--smoke"] or SUITES
    failed = []
    for name in args:
        t0 = time.time()
        if name == "fig1":
            from . import fig1_sample_size as m
        elif name == "table1":
            from . import table1_knn as m
        elif name == "fig12":
            from . import fig12_linreg as m
        elif name == "fig13":
            from . import fig13_nb as m
        elif name == "fig789":
            from . import fig789_distributed as m
        elif name == "manage":
            from . import manage_loop as m
        elif name == "sampler":
            from . import sampler_step as m
        elif name == "decay":
            from . import decay_sweep as m
        elif name == "bank":
            from . import bank_step as m
        elif name == "obs":
            from . import obs_overhead as m
        elif name == "roofline":
            from . import roofline as m
        else:
            raise SystemExit(f"unknown suite {name}; pick from {SUITES}")
        try:
            rows = m.run()
        except Exception as e:  # noqa: BLE001
            print(f"{name},0.0,ERROR={e!r}", flush=True)
            failed.append(name)
            continue
        emit(rows)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if failed:
        raise SystemExit(f"suites failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
