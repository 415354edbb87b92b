#!/usr/bin/env python3
"""Prove the main path runs on a TPU, through the entry points users call.

  python chip_smoke.py             one chip: kernel parity, the keyed bank,
                                   the full-width LM retrain loop
  python chip_smoke.py --chips 4   a four-chip host: the D-R-TBS trainer and
                                   the fused sharded loop against the
                                   per-tick sharded driver

Every phase prints its own lines (compile and run seconds, device memory,
the checks it made). Any failure raises, so the exit code is non-zero and
no result line is printed; so does a run where JAX finds no TPU, or a
directory that holds this script without the repository. The last line is
one JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.

Everything runs in this one process: a chip belongs to one process.
Data is random, made from ``--seed``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

GB = 1e9
VOCAB = 50280          # mamba2_370m's vocabulary: token ids in the payload
LAM = 0.07             # the trainer's default decay rate


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(phase: str, ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"[{phase}] check failed: {what}")
    log(phase, f"ok: {what}")


def memory_line() -> str:
    import jax

    parts = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        parts.append(f"dev{d.id} in_use={st.get('bytes_in_use', 0) / GB:.3f}GB "
                     f"peak={st.get('peak_bytes_in_use', 0) / GB:.3f}GB")
    return "; ".join(parts)


@contextlib.contextmanager
def phase(name: str):
    """Log the phase's wall seconds, the seconds XLA spent compiling in it
    (a persistent-cache hit counts its fetch), and device memory."""
    import jax

    compile_s = []

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    log(name, "start")
    t0 = time.perf_counter()
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    log(name, f"done in {time.perf_counter() - t0:.1f}s (XLA compile "
              f"{sum(compile_s):.1f}s in {len(compile_s)} programs); "
              f"HBM {memory_line()}")


def timed(fn, *args):
    """(result, seconds) of one call, waited on."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# phase 1: the device
# ---------------------------------------------------------------------------
def device_phase(chips: int) -> dict:
    import jax

    with phase("device"):
        devs = jax.devices()
        dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
               "count": len(devs)}
        log("device", f"{dev} jax {jax.__version__}")
        check("device", dev["platform"] == "tpu",
              f"JAX runs on a TPU (platform {dev['platform']!r})")
        check("device", dev["count"] == chips,
              f"{chips} device(s) visible (found {dev['count']})")
    return dev


# ---------------------------------------------------------------------------
# phase 2: the payload kernels, compiled kernel == jnp oracle, bit for bit
# ---------------------------------------------------------------------------
def _bits(x):
    import numpy as np

    a = np.asarray(x)
    return a.view(np.uint32) if a.dtype.itemsize == 4 else a


def _parity(name, fn, impl):
    """Run ``fn(impl)`` and ``fn("ref")`` and demand equal bits."""
    import jax
    import numpy as np

    got, first = timed(lambda: fn(impl))
    got, warm = timed(lambda: fn(impl))
    want = fn("ref")
    same = all(np.array_equal(_bits(a), _bits(b)) for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))
    log("parity", f"{name}: compile+run {first:.2f}s, run {warm * 1e3:.2f}ms")
    check("parity", same, f"{name}: {impl} == ref bit for bit")


def _payload(key, shape, dtype):
    """Random rows: token ids for int32, f32 features salted with the
    values a lossy copy would change (NaN, -0.0, inf, a denormal)."""
    import jax
    import jax.numpy as jnp

    if dtype == jnp.int32:
        return jax.random.randint(key, shape, 0, VOCAB, jnp.int32)
    x = jax.random.normal(key, shape, jnp.float32)
    special = jnp.asarray([jnp.nan, -0.0, jnp.inf, -jnp.inf, 1e-40],
                          jnp.float32)
    flat = x.reshape(-1)
    flat = flat.at[jnp.arange(special.size) * 7].set(special)
    return flat.reshape(shape)


def parity_phase(seed: int, impl: str = "pallas", cases=None,
                 bank_case=(4096, 65, 32)) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.reservoir_compact import ops as rc
    from repro.kernels.tbs_step import ops as ts

    cases = cases or [("int32 tokens", 4096, 64, 2048, jnp.int32),
                      ("f32 rows", 4096, 512, 8, jnp.float32)]
    with phase("parity"):
        key = jax.random.key(seed)
        for label, cap, bcap, D, dtype in cases:
            k1, k2, k3, k4, key = jax.random.split(key, 5)
            items = _payload(k1, (cap, D), dtype)
            batch = _payload(k2, (bcap, D), dtype)
            src = jax.random.randint(k3, (cap,), 0, cap + bcap, jnp.int32)
            mask = jax.random.bernoulli(k4, 0.5, (cap,))
            tag = f"{label} cap={cap} bcap={bcap} D={D}"
            _parity(f"tbs_step_apply {tag}", lambda i: ts.tbs_step_apply(
                items, batch, src, impl=i), impl)
            T = 4
            items_t = jnp.stack([jnp.roll(items, t, axis=0) for t in range(T)])
            batch_t = jnp.stack([jnp.roll(batch, t, axis=0) for t in range(T)])
            src_t = jnp.stack([jnp.roll(src, t) for t in range(T)])
            _parity(f"tbs_step_apply_banked T={T} {tag}",
                    lambda i: ts.tbs_step_apply_banked(items_t, batch_t,
                                                       src_t, impl=i), impl)
            _parity(f"reservoir_compact {tag}", lambda i: rc.reservoir_compact(
                items, mask, impl=i), impl)
        # the bank's own payload pass: many keys, narrow leaves
        T, cap, bcap = bank_case
        for D in (2, 1):
            k1, k2, k3, key = jax.random.split(key, 4)
            items = _payload(k1, (T, cap, D), jnp.float32)
            batch = _payload(k2, (T, bcap, D), jnp.float32)
            src = jax.random.randint(k3, (T, cap), 0, cap + bcap, jnp.int32)
            _parity(f"tbs_step_apply_banked bank leaf T={T} cap={cap} "
                    f"bcap={bcap} D={D}",
                    lambda i: ts.tbs_step_apply_banked(items, batch, src,
                                                       impl=i), impl)


# ---------------------------------------------------------------------------
# phase 3: the keyed bank at a tenant count users run
# ---------------------------------------------------------------------------
def bank_phase(seed: int, num_keys=1_048_576, n=64, bcap=32, b=4096,
               ticks=16, every=4) -> None:
    import jax
    import numpy as np

    from repro.bank import make_bank
    from repro.data.streams import KeyedStream, LinRegStream
    from repro.manage import make_bank_run_loop, make_model, materialize_stream
    from repro.obs import MemorySink, Telemetry, default_monitors, tree_nbytes

    with phase("bank"):
        t0 = time.perf_counter()
        stream = KeyedStream(base=LinRegStream(seed=seed), num_keys=num_keys,
                             seed=seed)
        batches, bcounts = materialize_stream(stream, ticks, batch_size=b,
                                              fields=("key", "x", "y"))
        log("bank", f"stream: {ticks} ticks x {b} items over {num_keys} keys "
                    f"made in {time.perf_counter() - t0:.1f}s")
        bank = make_bank("rtbs", num_keys=num_keys, n=n, bcap=bcap, lam=LAM)
        mem = MemorySink()
        tel = Telemetry([mem], every=every, monitors=default_monitors())
        transport = tel.resolve_transport()
        check("bank", transport == "callback",
              f"telemetry drains by {transport!r} (the off-CPU transport)")
        run = make_bank_run_loop(bank, make_model("linreg", dim=2),
                                 retrain_every=every, train_keys=range(8),
                                 telemetry=tel)
        (state, params, trace), secs = timed(run, jax.random.key(seed),
                                             batches, bcounts)
        log("bank", f"run (compile included): {secs:.1f}s; state "
                    f"{tree_nbytes(state) / GB:.3f}GB on the device")
        tel.flush()
        recs = mem.by_kind("tick")
        check("bank", [r["t"] for r in recs] == list(range(ticks)),
              f"{len(recs)} drained tick records for {ticks} ticks")
        ov_trace = np.asarray(trace["overflow"])
        ov_state = int(np.asarray(state.overflow).sum())
        check("bank", int(ov_trace.sum()) == ov_state,
              f"trace overflow {int(ov_trace.sum())} == state.overflow "
              f"{ov_state}")
        check("bank", [int(r["overflow"]) for r in recs] == ov_trace.tolist(),
              "telemetry overflow column == trace overflow per tick")
        metric = np.asarray(trace["metric"])
        check("bank", bool(np.isfinite(metric).all()),
              f"prequential MSE finite (last {float(metric[-1]):.4f})")
        mem.close()


# ---------------------------------------------------------------------------
# phase 4: the LM retrain loop at full width, through launch/train.py
# ---------------------------------------------------------------------------
LM_ARGS = ["--arch", "mamba2_370m", "--preset", "full", "--seq-len", "2048",
           "--reservoir", "4096", "--batch-per-tick", "64",
           "--train-batch", "8", "--ticks", "6", "--retrain-every", "3",
           "--retrain-steps", "2"]


def trainer_phase(seed: int, lm_args=LM_ARGS) -> None:
    from repro.launch.train import main as train_main

    argv = lm_args + ["--scheme", "rtbs", "--seed", str(seed)]
    with phase("trainer"):
        log("trainer", "launch.train " + " ".join(argv))
        t0 = time.perf_counter()
        rows = train_main(argv)
        log("trainer", f"{len(rows)} ticks in {time.perf_counter() - t0:.1f}s "
                       "(compile included)")
        opt = dict(zip(argv[::2], argv[1::2]))
        ticks, every = int(opt["--ticks"]), int(opt["--retrain-every"])
        n, b = int(opt["--reservoir"]), int(opt["--batch-per-tick"])
        check("trainer", len(rows) == ticks, f"{ticks} ticks logged")
        check("trainer", all(math.isfinite(r["eval_loss"]) for r in rows),
              "eval loss finite every tick: "
              + ", ".join(f"{r['eval_loss']:.4f}" for r in rows))
        retrains = [r for r in rows if math.isfinite(r["train_loss"])]
        check("trainer", len(retrains) == ticks // every,
              f"{len(retrains)} retrains, post-retrain loss finite")
        check("trainer", all(0 < r["sample_size"] <= n for r in rows),
              f"0 < |S| <= {n}: {[r['sample_size'] for r in rows]}")
        d = math.exp(-LAM)
        w = 0.0
        for r in rows:
            w = d * w + b
            if abs(r["total_weight"] - w) > 1e-5 * max(1.0, w):
                raise AssertionError(f"W at tick {r['tick']}: "
                                     f"{r['total_weight']} != {w}")
        check("trainer", True, "W_{t+1} = e^-lam W_t + B every tick")


# ---------------------------------------------------------------------------
# --chips 4: the D-R-TBS paths (paper Sec. 5)
# ---------------------------------------------------------------------------
def sharded_trainer_phase(seed: int, shards: int) -> None:
    import jax

    from repro.launch.train import main as train_main

    argv = LM_ARGS + ["--scheme", "drtbs", "--shards", str(shards),
                      "--seed", str(seed)]
    with phase("drtbs-trainer"):
        log("drtbs-trainer", "launch.train " + " ".join(argv))
        t0 = time.perf_counter()
        rows = train_main(argv)
        log("drtbs-trainer", f"{len(rows)} ticks in "
                             f"{time.perf_counter() - t0:.1f}s (compile "
                             "included)")
        check("drtbs-trainer", len(rows) == 6, "6 ticks logged")
        check("drtbs-trainer",
              all(math.isfinite(r["eval_loss"]) for r in rows),
              "eval loss finite every tick: "
              + ", ".join(f"{r['eval_loss']:.4f}" for r in rows))
        check("drtbs-trainer", all(r["sample_size"] <= 4096 for r in rows),
              f"|S| <= 4096: {[r['sample_size'] for r in rows]}")
        # replicated params and optimizer state, one reservoir shard each:
        # every chip held the run, not device 0 alone
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        check("drtbs-trainer", min(peaks) >= 0.5 * max(peaks) > 0,
              "every device held the run: peak HBM "
              + ", ".join(f"{p / GB:.3f}GB" for p in peaks))
        too_many = jax.device_count() * 2
        try:
            train_main(argv[:-4] + ["--shards", str(too_many)])
        except SystemExit as e:
            check("drtbs-trainer", True, f"--shards {too_many} raises: {e}")
        else:
            raise AssertionError(f"--shards {too_many} on "
                                 f"{jax.device_count()} devices did not raise")


def sharded_loop_phase(seed: int, shards: int, n=4096, b=1024, seq=2048,
                       ticks=8, every=2) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.api import make_sampler
    from repro.data.streams import TokenDriftStream
    from repro.launch.mesh import make_data_mesh
    from repro.manage import (init_sharded_state, make_sharded_manage_step,
                              make_sharded_run_loop, materialize_stream,
                              shard_stream)
    from repro.manage.models import ModelAdapter

    # the fit returns the global view's size: an exact witness of what the
    # retrain saw, so the two drivers can be compared bit for bit
    probe = ModelAdapter(
        name="probe", init=lambda: jnp.float32(-1.0),
        fit=lambda key, params, view: jnp.sum(view.mask).astype(jnp.float32),
        evaluate=lambda params, batch, bcount: jnp.sum(
            batch[:, 0] % 7).astype(jnp.float32),
        hyper={})
    with phase("sharded-loop"):
        stream = TokenDriftStream(seed=seed, vocab=VOCAB, seq_len=seq)
        batches, bcounts = materialize_stream(stream, ticks, batch_size=b)
        batches, bcounts = shard_stream(batches, bcounts, shards)
        mesh = make_data_mesh(shards)
        sampler = make_sampler("drtbs", n=n, lam=LAM,
                               cap_s=n + b // shards)
        key = jax.random.key(seed)
        run = make_sharded_run_loop(sampler, probe, mesh,
                                    retrain_every=every)
        (state_f, params_f, trace_f), secs = timed(run, key, batches,
                                                   bcounts)
        log("sharded-loop", f"fused loop (compile included): {secs:.1f}s")
        tick = make_sharded_manage_step(sampler, probe, mesh,
                                        retrain_every=every)
        state = init_sharded_state(
            sampler, shards, jax.ShapeDtypeStruct((seq,), jnp.int32))
        params = probe.init()
        metrics, sizes = [], []
        t0 = time.perf_counter()
        for t in range(ticks):
            state, params, m = tick(key, jnp.int32(t), state, params,
                                    batches[t], bcounts[t])
            metrics.append(np.asarray(m["metric"]))
            sizes.append(np.asarray(m["size"]))
        log("sharded-loop", f"per-tick driver (compile included): "
                            f"{time.perf_counter() - t0:.1f}s")
        check("sharded-loop",
              np.array_equal(np.asarray(trace_f["metric"]), np.stack(metrics))
              and np.array_equal(np.asarray(trace_f["size"]), np.stack(sizes)),
              f"fused trace == per-tick trace (sizes {np.stack(sizes).tolist()})")
        same = all(np.array_equal(_bits(a), _bits(b_)) for a, b_ in zip(
            jax.tree_util.tree_leaves(state_f),
            jax.tree_util.tree_leaves(state)))
        check("sharded-loop", same and float(params_f) == float(params),
              "fused final state and params == per-tick, bit for bit")
        # the stream is co-partitioned over every chip and each chip fills
        # its own reservoir shard
        in_sh = run.lower(key, batches, bcounts).compile().input_shardings
        bsh = in_sh[0][1]
        part = bsh.shard_shape(batches.shape)
        check("sharded-loop", len(bsh.device_set) == shards
              and part[1] * shards == batches.shape[1],
              f"stream input split over {len(bsh.device_set)} devices: "
              f"{part} per device of {batches.shape}")
        nfull = np.asarray(state_f.nfull)
        check("sharded-loop", nfull.shape == (shards,) and (nfull > 0).all(),
              f"every reservoir shard holds items: nfull {nfull.tolist()}, "
              f"overflow {np.asarray(state_f.overflow).tolist()}")
        check("sharded-loop", int(np.asarray(state_f.overflow).sum()) == 0,
              "no capacity overflow")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    log("setup", f"compile cache: {enable_compile_cache()}")
    dev = device_phase(args.chips)
    if args.chips == 1:
        parity_phase(args.seed)
        bank_phase(args.seed)
        trainer_phase(args.seed)
    else:
        sharded_loop_phase(args.seed, args.chips)
        sharded_trainer_phase(args.seed, args.chips)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
