"""Fused vs. unfused online model-management loop (DESIGN.md Secs. 8, 10, 11).

Measures ticks/sec of the paper's stream -> sample -> retrain -> eval loop:

  * ``unfused`` -- the pre-API idiom: one Python iteration per tick calling
    individually jitted step/extract/fit/evaluate (4 dispatches/tick, metrics
    pulled to host each tick).
  * ``fused``   -- :func:`repro.manage.make_run_loop`: the whole stream in a
    single jitted ``lax.scan``.
  * ``fused_sb8``-- the same loop superbatched (G=8 chunked scan body: the
    non-retrain fast path drops the per-tick retrain conditional and scan
    bookkeeping; results bit-identical, asserted before timing).
  * ``farm32``  -- the fused loop ``vmap``-ed over 32 Monte-Carlo trials
    (Fig. 12/13 robustness protocol); throughput counts trials x ticks.

plus the sampler-step hot path at cap 4096 (the headline perf criterion:
fused + argsort-free vs the pre-fused reference, measured in both Alg. 2
phases -- see benchmarks/sampler_step.py for the full sweep), and the
D-R-TBS sharded loop at 1/2/4/8 virtual host devices (subprocess per device
count, see benchmarks/_sharded_loop_worker.py):

  * ``sharded_fused_Sw``   -- :func:`repro.manage.make_sharded_run_loop`:
    the whole stream as one jitted scan under shard_map (shard-resident
    reservoir state).
  * ``sharded_pertick_Sw`` -- :func:`repro.manage.make_sharded_manage_step`:
    one shard_map dispatch per tick (state snapshot round-trips every tick).

Same keys, same trace -- the fused/unfused equivalences are asserted before
timing (and unit-tested in tests/test_api.py / tests/test_sharded_loop.py).
Emits ``BENCH_manage_loop.json`` at the repo root; ``--smoke`` (or
BENCH_SMOKE=1) shrinks everything to CI size and skips the subprocess
points. EXPERIMENTS.md (sharded-loop + sampler-throughput protocols)
documents the host-mesh caveat.
"""
from __future__ import annotations

import subprocess
import sys

import jax
import numpy as np

from repro.core.api import make_sampler
from repro.data.streams import LinRegStream, mode_schedule
from repro.manage import (
    make_manage_step,
    make_model,
    make_run_farm,
    make_run_loop,
    materialize_stream,
)
from repro.manage.loop import item_proto

from .common import REPO_ROOT, host_mesh_env, smoke_mode, time_fn, write_bench_json

T = 200
B = 100
N = 400
LAM = 0.07
TRIALS = 32
SB = 8                      # superbatch chunk size for the fused_sb rows
STEP_CAP = 4096             # sampler-step criterion capacity
STEP_BCAP = 512



def _sharded_worker(shards: int, mode: str, timeout=600) -> float:
    """us/tick of the sharded loop in a subprocess with ``shards`` forced
    host devices (the device count is locked at jax init, so each point
    needs its own process -- same pattern as fig789)."""
    env = host_mesh_env()
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks._sharded_loop_worker",
         str(shards), mode],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=str(REPO_ROOT),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return float(proc.stdout.strip().splitlines()[-1].split(",")[1])


def run():
    smoke = smoke_mode()
    T_, B_, N_ = (40, 16, 64) if smoke else (T, B, N)
    retrain_every = SB  # so the superbatched loop has a cond-free fast path

    sampler = make_sampler("rtbs", n=N_, lam=LAM)
    model = make_model("linreg", dim=2)
    batches, bcounts = materialize_stream(
        LinRegStream(seed=0), T_, batch_size=B_,
        mode=lambda t: mode_schedule("periodic", t),
    )
    key = jax.random.key(0)

    tick = make_manage_step(sampler, model,  # jitted, donates off-CPU
                            retrain_every=retrain_every)
    fused = make_run_loop(sampler, model, retrain_every=retrain_every,
                          superbatch=1)
    fused_sb = make_run_loop(sampler, model, retrain_every=retrain_every,
                             superbatch=SB)
    farm = make_run_farm(sampler, model, retrain_every=retrain_every)

    def unfused(key, batches, bcounts):
        state = sampler.init(item_proto(batches))
        params = model.init()
        metrics = []
        for t in range(T_):
            bt = jax.tree_util.tree_map(lambda a: a[t], batches)
            state, params, m = tick(key, t, state, params, bt, bcounts[t])
            metrics.append(float(m["metric"]))  # host pull, as the old drivers did
        return state, params, np.asarray(metrics)

    # equivalence before timing: same keys => identical metric traces, and
    # the superbatched loop is bit-identical to the per-tick scan
    _, _, trace = fused(key, batches, bcounts)
    _, _, trace_sb = fused_sb(key, batches, bcounts)
    np.testing.assert_array_equal(np.asarray(trace["metric"]),
                                  np.asarray(trace_sb["metric"]))
    _, _, m_unfused = unfused(key, batches, bcounts)
    np.testing.assert_allclose(
        np.asarray(trace["metric"]), m_unfused, rtol=1e-5
    )

    rows = []
    t_unf = time_fn(unfused, key, batches, bcounts, iters=5) / 1e6  # -> s
    rows.append(("manage_loop_unfused", t_unf / T_ * 1e6,
                 {"ticks_per_s": round(T_ / t_unf, 1)}))

    t_fus = time_fn(fused, key, batches, bcounts) / 1e6
    rows.append(("manage_loop_fused", t_fus / T_ * 1e6,
                 {"ticks_per_s": round(T_ / t_fus, 1),
                  "speedup_vs_unfused": round(t_unf / t_fus, 2)}))

    t_sb = time_fn(fused_sb, key, batches, bcounts) / 1e6
    rows.append((f"manage_loop_fused_sb{SB}", t_sb / T_ * 1e6,
                 {"ticks_per_s": round(T_ / t_sb, 1),
                  "superbatch": SB,
                  "speedup_vs_fused": round(t_fus / t_sb, 2)}))

    t_farm = time_fn(farm, key, TRIALS, batches, bcounts) / 1e6
    work = T_ * TRIALS
    rows.append(("manage_loop_farm32", t_farm / work * 1e6,
                 {"trial_ticks_per_s": round(work / t_farm, 1),
                  "trials": TRIALS}))

    # the sampler-step perf criterion: fused + argsort-free vs the pre-fused
    # reference at cap >= 4096 (both Alg. 2 phases; full sweep in
    # benchmarks/sampler_step.py -> BENCH_sampler_step.json)
    from .sampler_step import rtbs_rows

    cap, bcap = (64, 16) if smoke else (STEP_CAP, STEP_BCAP)
    rows += rtbs_rows(cap, bcap, iters=5 if smoke else 30)

    # D-R-TBS sharded loop: fused scan vs per-tick shard_map dispatch
    if not smoke:
        for shards in (1, 2, 4, 8):
            us_tick = _sharded_worker(shards, "per_tick")
            us_fused = _sharded_worker(shards, "fused")
            rows.append((f"sharded_pertick_{shards}w", us_tick,
                         {"shards": shards,
                          "ticks_per_s": round(1e6 / us_tick, 1)}))
            rows.append((f"sharded_fused_{shards}w", us_fused,
                         {"shards": shards,
                          "ticks_per_s": round(1e6 / us_fused, 1),
                          "speedup_vs_pertick": round(us_tick / us_fused, 2)}))
    write_bench_json("manage_loop", rows)
    return rows


if __name__ == "__main__":
    from .common import emit

    emit(run())
