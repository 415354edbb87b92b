"""Online model management driver (the paper's loop, lifted to LMs):

  stream -> time-biased sample update -> (drift-triggered | periodic)
  retraining on the current sample -> prequential evaluation -> checkpoint.

The sampler is any scheme from the unified registry (``--scheme rtbs|sw|brs|
btbs|ttbs|drtbs|dttbs``, see :mod:`repro.core.api`); retraining runs through
the :mod:`repro.manage` SGD adapter, so the reservoir update and the whole
retrain inner loop are compiled programs. Runs any `--arch` (reduced
`--preset smoke` configs on CPU; full configs are for real pods). Fault
tolerance: `--resume` restarts bit-exactly from the newest checkpoint
(params, optimizer, reservoir, stream position).

The distributed schemes (paper Sec. 5) run the SAME loop sharded: the driver
builds a ``data``-axis mesh over ``--shards`` devices, which must exist (on a
CPU-only run, ``JAX_PLATFORMS=cpu``, the driver re-execs itself with that many
forced host devices before JAX starts; on an accelerator too few devices is
an error), co-partitions the stream, and runs the whole run as ONE
fused :func:`repro.manage.make_sharded_run_loop` program: co-partitioned
reservoir shards, replicated params, one psum per tick. With ``--ckpt-dir``
the stream is consumed in ``--ckpt-every``-tick segments through
:func:`repro.manage.make_sharded_resume_loop` (the ``gather_tree`` snapshot
is what gets serialized), so ``--resume`` restarts the sharded run
bit-exactly too.

Decay (DESIGN.md Sec. 12): ``--decay exp`` (default; rate ``--lam``) or
``--decay poly`` (power-law, exponent ``--beta``); ``--adaptive`` switches to
the closed-loop controller (lambda driven by the prequential loss between
``--lam-min`` and ``--lam-max``, starting at ``--lam``).

Multi-tenant mode (DESIGN.md Sec. 13): ``--num-keys K`` swaps the single
sampler for a :class:`repro.bank.SamplerBank` -- K per-key time-biased
samples over a Zipf-keyed token stream with per-key drift phases, advanced
by the bank's fused key-routed step; the LM retrains on the pooled extract
of the ``--train-keys`` most popular keys (rtbs/ttbs only).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch stablelm_12b \
      --preset smoke --ticks 30 --retrain-every 5 --scheme rtbs
  PYTHONPATH=src python -m repro.launch.train --arch mamba2_370m \
      --preset smoke --ticks 20 --scheme rtbs --num-keys 4096 --train-keys 8
  PYTHONPATH=src python -m repro.launch.train --arch mamba2_370m \
      --preset smoke --ticks 12 --retrain-every 4 --scheme drtbs --shards 8
  PYTHONPATH=src python -m repro.launch.train --arch mamba2_370m \
      --preset smoke --ticks 30 --scheme rtbs --adaptive

Full width on one TPU v5e (16 GB): mamba2_370m is the zoo's model whose
params, AdamW state and a train step fit one chip (``chip_smoke.py`` runs
exactly this; the default ``--arch stablelm_12b`` does not fit at
``--preset full``):
  PYTHONPATH=src python -m repro.launch.train --arch mamba2_370m \
      --preset full --scheme rtbs --seq-len 2048 --reservoir 4096 \
      --batch-per-tick 64 --train-batch 8 --ticks 6 --retrain-every 3 \
      --retrain-steps 2
"""
from __future__ import annotations

import argparse
import contextlib
import math
import os
import sys

import jax
import jax.numpy as jnp

from repro import config as C
from repro import decay as dk
from repro.bank import make_bank
from repro.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.core.api import available_schemes, make_sampler
from repro.data.streams import KeyedStream, TokenDriftStream, mode_schedule
from repro.launch.compile_cache import enable_compile_cache
from repro.manage import (
    init_sharded_state,
    make_bank_run_loop,
    make_sgd_adapter,
    make_sharded_resume_loop,
    make_sharded_run_loop,
    materialize_stream,
    shard_stream,
)
from repro.models import zoo
from repro.obs import make_telemetry, profile_span
from repro.obs import probe as obs_probe
from repro.optim import AdamWConfig, adamw_init
from repro.train.steps import make_train_step

DISTRIBUTED_SCHEMES = ("drtbs", "dttbs")
DECAY_FREE_SCHEMES = ("sw", "brs")


def build_sampler(scheme: str, *, n: int, lam: float, batch_per_tick: int,
                  shards: int = 1, decay=None):
    """Map the driver's knobs onto each scheme's hyperparameters. ``decay``
    (a DecaySchedule) replaces the scalar ``lam`` when given; ``lam`` still
    sizes the B-TBS capacity bound (a rough steady-state proxy for
    time-varying schedules)."""
    dkw = {"lam": lam} if decay is None else {"decay": decay}
    if scheme == "rtbs":
        return make_sampler("rtbs", n=n, **dkw)
    if scheme in DECAY_FREE_SCHEMES:
        return make_sampler(scheme, n=n)
    if scheme == "btbs":
        # B-TBS has NO size control (paper Alg. 4): steady-state E|S| is
        # b/(1-e^-lam), not --reservoir. Provision 3x that so the capacity
        # bound never silently distorts the time bias.
        steady = batch_per_tick / max(1.0 - math.exp(-lam), 1e-6)
        return make_sampler("btbs", cap=max(n, int(3 * steady) + 1), **dkw)
    if scheme == "ttbs":
        return make_sampler("ttbs", n=n, batch_size=batch_per_tick, **dkw)
    if scheme == "drtbs":
        # cap_s covers the worst transient: every global full item plus this
        # shard's incoming batch landing on one shard before the downsample
        return make_sampler("drtbs", n=n, cap_s=n + batch_per_tick, **dkw)
    if scheme == "dttbs":
        # per-shard targets: n/S sample rows fed by b/S arrivals per shard
        n_s = max(1, -(-n // shards))
        b_s = max(1.0, batch_per_tick / shards)
        return make_sampler("dttbs", n=n_s, batch_size=b_s, **dkw)
    raise ValueError(f"unsupported scheme {scheme!r}; see {available_schemes()}")


def build_decay(args):
    """(DecaySchedule | None for the lam sugar, AdaptiveDecay | None)."""
    if args.scheme in DECAY_FREE_SCHEMES:
        if args.adaptive or args.decay != "exp":
            raise SystemExit(
                f"--scheme {args.scheme} has no decay to configure"
            )
        return None, None
    controller = None
    if args.adaptive:
        lam_min = args.lam_min if args.lam_min is not None else args.lam / 20
        lam_max = args.lam_max if args.lam_max is not None else \
            min(1.5, args.lam * 20)
        controller = dk.loss_ratio(lam0=args.lam, lam_min=lam_min,
                                   lam_max=lam_max)
    sched = None
    if args.decay == "poly":
        sched = dk.polynomial(args.beta)
    return sched, controller


def build_telemetry(args):
    """The run's :class:`repro.obs.Telemetry` handle from the CLI knobs
    (None when telemetry is off -- the loops then compile the historical,
    drain-free programs)."""
    if not (args.telemetry_dir or args.telemetry_stdout):
        return None
    return make_telemetry(args.telemetry_dir, stdout=args.telemetry_stdout,
                          every=args.telemetry_every)


def profile_cm(args):
    """A profiler span over whatever it wraps: the whole fused program, or
    the first ``--profile-ticks`` ticks of the per-tick driver."""
    if not args.profile_dir:
        return contextlib.nullcontext()
    return profile_span(args.profile_dir)


def _log_sharded_trace(trace, t0, mode_of, log, telemetry=None):
    metric = jax.device_get(trace["metric"])
    size = jax.device_get(trace["size"])
    dec = jax.device_get(trace["decay"]) if "decay" in trace else None
    for i in range(len(size)):
        t = t0 + i
        row = {"tick": t, "mode": mode_of(t), "eval_loss": float(metric[i]),
               "sample_size": int(size[i])}
        extra = ""
        if dec is not None:
            row["lam"] = float(-math.log(max(float(dec[i]), 1e-30)))
            extra = f" lam={row['lam']:6.4f}"
        log.append(row)
        if telemetry is not None:  # ckpt-segmented path: host-side records
            telemetry.emit({"kind": "tick", "t": t,
                            "metric": float(metric[i]),
                            "size": int(size[i]),
                            **({"decay": float(dec[i])}
                               if dec is not None else {})})
        print(f"[train] tick={t:4d} mode={mode_of(t)} "
              f"eval={float(metric[i]):7.4f} |S|={int(size[i]):5d}{extra}",
              flush=True)
    if telemetry is not None:
        telemetry.flush()


def run_sharded(args, adapter, stream, sampler, controller=None):
    """The Sec.-5 path: the run as fused sharded-loop program(s).

    Co-partitions every tick's batch over the ``data`` mesh, then executes
    stream -> per-shard sample update -> periodic retrain on the global view
    -> prequential eval as jitted scans (no per-tick dispatch). Without
    ``--ckpt-dir`` the whole stream is ONE program; with it, the stream is
    consumed in ``--ckpt-every``-tick segments through the resume entry
    point (:func:`repro.manage.make_sharded_resume_loop`), serializing the
    replicated ``gather_tree`` snapshot after each segment -- ``--resume``
    restarts bit-exactly (segmented and unsegmented runs produce identical
    traces; tests/test_sharded_loop.py asserts the equivalence).
    """
    from repro.launch.mesh import make_data_mesh

    S = args.shards
    # main() already rounded batch_per_tick up to a multiple of S (the
    # sampler's rates and the padding-free shard segments both depend on it)
    assert args.batch_per_tick % S == 0

    def mode_of(t):
        return 0 if args.drift == "none" else mode_schedule(args.drift, t)

    batches, bcounts = materialize_stream(stream, args.ticks,
                                          batch_size=args.batch_per_tick,
                                          mode=mode_of)
    batches, bcounts = shard_stream(batches, bcounts, S)
    mesh = make_data_mesh(S)
    key = jax.random.key(args.seed)
    log = []

    telemetry = build_telemetry(args)
    if not args.ckpt_dir:
        run = make_sharded_run_loop(sampler, adapter, mesh,
                                    retrain_every=args.retrain_every,
                                    superbatch=args.superbatch,
                                    controller=controller,
                                    telemetry=telemetry)
        print(f"[train] sharded {args.scheme} loop: {S} shards, "
              f"{args.ticks} ticks, one fused program", flush=True)
        with profile_cm(args):
            _, _, trace = run(key, batches, bcounts)
        _log_sharded_trace(trace, 0, mode_of, log)
        if telemetry is not None:
            telemetry.close()
        return log

    # checkpointed: ckpt_every-tick segments through the resume entry point
    seg = -(-args.ckpt_every // args.retrain_every) * args.retrain_every
    resume = make_sharded_resume_loop(sampler, adapter, mesh,
                                      retrain_every=args.retrain_every,
                                      superbatch=args.superbatch,
                                      controller=controller)
    from repro.manage.loop import item_proto

    state = init_sharded_state(sampler, S, item_proto(batches))
    params = adapter.init()
    cstate = controller.init() if controller is not None else None
    start_tick = 0
    ckpt = AsyncCheckpointer(args.ckpt_dir)
    if args.resume:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            like = (state, params, cstate, 0) if controller is not None \
                else (state, params, 0)
            tree = restore_checkpoint(args.ckpt_dir, last, like)
            tree = jax.tree_util.tree_map(jnp.asarray, tree[:-1]) + (tree[-1],)
            if controller is not None:
                state, params, cstate = tree[:-1]
            else:
                state, params = tree[:-1]
            start_tick = int(tree[-1])
            print(f"[train] resumed sharded run from step {last} "
                  f"(tick {start_tick})")
    print(f"[train] sharded {args.scheme} loop: {S} shards, "
          f"{args.ticks} ticks, {seg}-tick checkpointed segments", flush=True)
    if telemetry is not None:
        telemetry.open_run({"scheme": args.scheme, "ticks": args.ticks,
                            "segment": seg, "every": telemetry.every,
                            "backend": jax.default_backend(),
                            "jax": jax.__version__, "state_bytes": None})

    def cut(tree, lo, hi):
        return jax.tree_util.tree_map(lambda a: a[lo:hi], tree)

    for t0 in range(start_tick, args.ticks, seg):
        t1 = min(t0 + seg, args.ticks)
        if controller is not None:
            state, params, cstate, trace = resume(
                key, state, params, cstate, cut(batches, t0, t1),
                bcounts[t0:t1], t0)
            snap = (state, params, cstate, t1)
        else:
            state, params, trace = resume(
                key, state, params, cut(batches, t0, t1), bcounts[t0:t1], t0)
            snap = (state, params, t1)
        _log_sharded_trace(trace, t0, mode_of, log, telemetry=telemetry)
        # only retrain-cadence-aligned ticks are valid resume points (the
        # resume loop requires t0 % G == 0 and G | retrain_every): skip a
        # misaligned final partial segment -- a later --resume with more
        # --ticks restarts from the last aligned save and replays the few
        # tail ticks bit-exactly instead of failing the alignment check
        if t1 % args.retrain_every == 0:
            ckpt.save(t1, snap)
    ckpt.wait()
    if telemetry is not None:
        telemetry.close()
    return log


def run_bank(args, adapter, cfg):
    """Multi-tenant mode (``--num-keys``, DESIGN.md Sec. 13): one
    :class:`repro.bank.SamplerBank` maintains a per-key time-biased sample
    for every entity; the shared LM retrains on the pooled extract of the
    ``--train-keys`` most popular keys. The whole run is one fused
    :func:`repro.manage.make_bank_run_loop` scan over a Zipf-keyed token
    stream with per-key drift phases."""
    if args.ckpt_dir or args.resume:
        raise SystemExit(
            "--num-keys has no checkpoint/resume path yet (ROADMAP bank "
            "follow-up (c)); drop --ckpt-dir/--resume for bank runs"
        )
    K, Q = args.num_keys, min(args.train_keys, args.num_keys)
    stream = KeyedStream(
        base=TokenDriftStream(seed=args.seed, vocab=cfg.vocab_size,
                              seq_len=args.seq_len),
        num_keys=K, seed=args.seed,
        flip_every=0 if args.drift == "none" else 5 * args.retrain_every,
    )
    batches, bcounts = materialize_stream(
        stream, args.ticks, batch_size=args.batch_per_tick,
        fields=("key", "tokens"),
    )
    bcap = args.bank_bcap or args.batch_per_tick
    dkw = {"lam": args.lam}
    sched, controller = build_decay(args)
    if controller is not None:
        raise SystemExit("--adaptive drives per-key farms "
                         "(manage.make_bank_run_loop(per_key=True)); the "
                         "shared-model --num-keys driver runs the bank's "
                         "own schedule")
    if sched is not None:
        dkw = {"decay": sched}
    if args.scheme == "rtbs":
        bank = make_bank("rtbs", num_keys=K, n=args.reservoir, bcap=bcap,
                         **dkw)
    elif args.scheme == "ttbs":
        # per-key mean arrivals per touched tick ~ 1 sub-batch row; the
        # popular keys see ~ b * P(0) of the tick
        bank = make_bank("ttbs", num_keys=K, n=args.reservoir,
                         batch_size=max(1.0, args.batch_per_tick / K),
                         bcap=bcap, **dkw)
    else:
        raise SystemExit(
            f"--num-keys supports the local time-biased schemes rtbs/ttbs; "
            f"got --scheme {args.scheme}"
        )
    telemetry = build_telemetry(args)
    run = make_bank_run_loop(bank, adapter, retrain_every=args.retrain_every,
                             train_keys=range(Q),
                             superbatch=args.superbatch,
                             telemetry=telemetry)
    print(f"[train] bank {args.scheme} loop: K={K} keys, top-{Q} trained, "
          f"{args.ticks} ticks, one fused program", flush=True)
    with profile_cm(args):
        state, _, trace = run(jax.random.key(args.seed), batches, bcounts)
    metric = jax.device_get(trace["metric"])
    sizes = jax.device_get(trace["size"])
    overflow = jax.device_get(trace["overflow"])
    log = []
    for t in range(args.ticks):
        row = {"tick": t, "eval_loss": float(metric[t]),
               "train_key_sizes": [int(s) for s in sizes[t]],
               "overflow": int(overflow[t])}
        log.append(row)
        print(f"[train] tick={t:4d} eval={float(metric[t]):7.4f} "
              f"|S|(top-{Q})={sizes[t].tolist()}", flush=True)
    ov = int(jax.device_get(state.overflow).sum())
    print(f"[train] bank done: routed-overflow={ov} items "
          f"(per-key bcap={bcap})", flush=True)
    if telemetry is not None:
        telemetry.close()
    return log


def _ensure_devices(shards: int, *, reexec: bool) -> None:
    """Make sure ``shards`` devices exist for the ``data`` mesh.

    A CPU-only run (``JAX_PLATFORMS=cpu``) launched from the command line
    re-execs itself with that many forced host devices; the decision is
    made from the environment, before JAX starts a backend. Anything else
    with too few devices is an error: the driver never clamps the mesh or
    moves a run off its accelerator."""
    flag = "--xla_force_host_platform_device_count"
    cpu_only = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if reexec and cpu_only and flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + f" {flag}={shards}"
        ).strip()
        os.execv(sys.executable, [sys.executable] + sys.argv)
    have = jax.device_count()
    if have < shards:
        raise SystemExit(
            f"--shards {shards} needs {shards} devices; the "
            f"{jax.default_backend()} backend has {have}"
        )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_12b")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--scheme", default="rtbs",
                    choices=["rtbs", "sw", "brs", "btbs", "ttbs",
                             "drtbs", "dttbs"])
    ap.add_argument("--shards", type=int, default=8,
                    help="data-axis width for the distributed schemes")
    ap.add_argument("--num-keys", type=int, default=0,
                    help="multi-tenant mode: maintain one per-key "
                         "time-biased sample for this many entities "
                         "(repro.bank; rtbs/ttbs only, DESIGN.md Sec. 13)")
    ap.add_argument("--train-keys", type=int, default=8,
                    help="bank mode: retrain on / log the pooled sample of "
                         "this many most-popular keys")
    ap.add_argument("--bank-bcap", type=int, default=None,
                    help="bank mode: static per-key sub-batch capacity "
                         "(default: the whole tick batch, no routing drops)")
    ap.add_argument("--ticks", type=int, default=30)
    ap.add_argument("--batch-per-tick", type=int, default=32)
    ap.add_argument("--reservoir", type=int, default=256)
    ap.add_argument("--lam", type=float, default=0.07)
    ap.add_argument("--decay", default="exp", choices=["exp", "poly"],
                    help="decay schedule: exp (rate --lam) or poly "
                         "(power-law, exponent --beta; DESIGN.md Sec. 12)")
    ap.add_argument("--beta", type=float, default=0.8,
                    help="polynomial-decay exponent (--decay poly)")
    ap.add_argument("--adaptive", action="store_true",
                    help="closed-loop decay: drive lambda from the "
                         "prequential loss (starts at --lam, clipped to "
                         "[--lam-min, --lam-max])")
    ap.add_argument("--lam-min", type=float, default=None)
    ap.add_argument("--lam-max", type=float, default=None)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--retrain-every", type=int, default=5)
    ap.add_argument("--superbatch", type=int, default=None,
                    help="manage-loop chunk size G (divisor of "
                         "--retrain-every; default: 8 on TPU, 1 elsewhere "
                         "-- DESIGN.md Sec. 11)")
    ap.add_argument("--retrain-steps", type=int, default=8)
    ap.add_argument("--train-batch", type=int, default=16)
    ap.add_argument("--drift", default="periodic", choices=["periodic", "single", "none"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--telemetry-dir", default=None,
                    help="write in-loop telemetry (one JSONL record per "
                         "tick + health-monitor warnings) under this "
                         "directory (repro.obs, DESIGN.md Sec. 14)")
    ap.add_argument("--telemetry-every", type=int, default=64,
                    help="telemetry drain period in ticks (fused loops "
                         "round it to whole superbatch chunks)")
    ap.add_argument("--telemetry-stdout", action="store_true",
                    help="echo telemetry records to stdout")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace (TensorBoard/"
                         "Perfetto-loadable) under this directory")
    ap.add_argument("--profile-ticks", type=int, default=8,
                    help="per-tick driver: ticks to bracket with the "
                         "profiler (fused loops capture the whole program)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.scheme in DISTRIBUTED_SCHEMES:
        _ensure_devices(args.shards, reexec=argv is None)
        # pad the tick batch to a multiple of the mesh BEFORE the sampler is
        # built: dttbs calibrates its acceptance rates p/q on the per-shard
        # arrival rate, and the SGD adapter's LM loss needs padding-free
        # shard segments (see run_sharded)
        b = -(-args.batch_per_tick // args.shards) * args.shards
        if b != args.batch_per_tick:
            print(f"[train] batch-per-tick {args.batch_per_tick} -> {b} "
                  f"(multiple of {args.shards} shards)")
            args.batch_per_tick = b

    cfg = (C.get_smoke_config(args.arch) if args.preset == "smoke"
           else C.get_config(args.arch))
    api = zoo.build(cfg)
    stream = TokenDriftStream(seed=args.seed, vocab=cfg.vocab_size,
                              seq_len=args.seq_len)

    # fixed schedule horizon: must NOT depend on --ticks, or an interrupted
    # run would train under a different LR curve than the run it resumes
    adapter = make_sgd_adapter(
        init_params=lambda: api.init_params(jax.random.key(args.seed)),
        train_step=make_train_step(
            api, AdamWConfig(lr=args.lr), microbatches=1,
            warmup=2, total_steps=4000,
        ),
        init_opt_state=adamw_init,
        loss=api.loss,
        batch_field="tokens",
        train_batch=args.train_batch,
        retrain_steps=args.retrain_steps,
        name=args.arch,
    )
    if args.num_keys:
        return run_bank(args, adapter, cfg)

    sched, controller = build_decay(args)
    sampler = build_sampler(args.scheme, n=args.reservoir, lam=args.lam,
                            batch_per_tick=args.batch_per_tick,
                            shards=args.shards, decay=sched)
    if args.scheme in DISTRIBUTED_SCHEMES:
        return run_sharded(args, adapter, stream, sampler, controller)

    # the retrain consumes the model state it replaces: donating it keeps
    # one copy of params + AdamW state on the device, not two
    fit = jax.jit(adapter.fit, donate_argnums=1)
    eval_fn = jax.jit(adapter.evaluate)
    step = jax.jit(sampler.step)
    step_decayed = jax.jit(sampler.step_decayed)
    extract = jax.jit(sampler.extract)
    proto = jax.ShapeDtypeStruct((args.seq_len,), jnp.int32)
    st = sampler.init(proto)
    model_state = adapter.init()
    cstate = controller.init() if controller is not None else None
    start_tick = 0

    ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    if args.resume and args.ckpt_dir:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            like = (model_state, st, cstate, 0) if controller is not None \
                else (model_state, st, 0)
            tree = restore_checkpoint(args.ckpt_dir, last, like)
            if controller is not None:
                model_state, st, cstate, start_tick = tree
                cstate = jax.tree_util.tree_map(jnp.asarray, cstate)
            else:
                model_state, st, start_tick = tree
            model_state = jax.tree_util.tree_map(jnp.asarray, model_state)
            st = jax.tree_util.tree_map(jnp.asarray, st)
            start_tick = int(start_tick)
            print(f"[train] resumed from step {last} (tick {start_tick})")

    telemetry = build_telemetry(args)
    state_stats = obs_probe.make_state_stats(sampler)
    d_static = obs_probe.static_decay(sampler)
    if telemetry is not None:
        telemetry.open_run({"scheme": args.scheme, "ticks": args.ticks,
                            "superbatch": 1, "every": telemetry.every,
                            "backend": jax.default_backend(),
                            "jax": jax.__version__,
                            "state_bytes": obs_probe.tree_nbytes(st)})
    prof = contextlib.ExitStack()

    log = []
    for t in range(start_tick, args.ticks):
        if args.profile_dir and t == start_tick:
            prof.enter_context(profile_span(args.profile_dir))
        if args.profile_dir and t == start_tick + args.profile_ticks:
            prof.close()
        mode = 0 if args.drift == "none" else mode_schedule(args.drift, t)
        batch = jnp.asarray(stream.batch(t, args.batch_per_tick, mode))

        # prequential eval BEFORE the model sees this data
        eval_loss = float(eval_fn(model_state, batch, args.batch_per_tick))

        # sample update (the paper's technique); with --adaptive the
        # controller's current rate drives the step and the prequential loss
        # feeds back (adjustment gated on retrain ticks, as in the fused loop)
        key_t = jax.random.fold_in(jax.random.key(args.seed + 1), t)
        if controller is not None:
            d_t = controller.rate(cstate)
            st = step_decayed(key_t, st, batch,
                              jnp.int32(args.batch_per_tick), d_t)
            cstate = controller.observe(
                cstate, jnp.float32(eval_loss),
                (t + 1) % args.retrain_every == 0)
        else:
            st = step(key_t, st, batch, jnp.int32(args.batch_per_tick))

        # ONE realization per tick: the logged |S| is the sample fit trains on
        k_ex, k_fit = jax.random.split(
            jax.random.fold_in(jax.random.key(args.seed + 2), t)
        )
        view = extract(k_ex, st)
        size = int(view.size)

        # periodic retraining on the realized time-biased sample
        train_loss = float("nan")
        if (t + 1) % args.retrain_every == 0 and size >= args.train_batch:
            model_state = fit(k_fit, model_state, view)
            train_loss = float(
                eval_fn(model_state, batch, args.batch_per_tick)
            )

        # every scheme's state carries W_t (decayed weight for rtbs/ttbs/btbs,
        # item count for brs/sw); time-varying schedules wrap it
        raw = st.inner if isinstance(st, dk.DecayedState) else st
        total_w = float(raw.total_weight)
        row = {"tick": t, "mode": mode, "eval_loss": eval_loss,
               "train_loss": train_loss, "sample_size": size,
               "total_weight": total_w}
        extra = ""
        if controller is not None:
            row["lam"] = float(jnp.exp(cstate.loglam))
            extra = f" lam={row['lam']:6.4f}"
        log.append(row)
        if telemetry is not None:
            rec = {"kind": "tick", "t": t,
                   "bcount": args.batch_per_tick,
                   "metric": eval_loss, "size": size,
                   "retrain": (t + 1) % args.retrain_every == 0}
            rec.update({k: float(v) for k, v in state_stats(st).items()})
            if controller is not None:
                rec["decay"] = float(d_t)
                rec["lam"] = row["lam"]
            elif d_static is not None:
                rec["decay"] = d_static
            telemetry.emit(rec)
        print(f"[train] tick={t:4d} mode={mode} eval={eval_loss:7.4f} "
              f"train={train_loss:7.4f} |S|={size:5d} W={total_w:8.2f}"
              f"{extra}", flush=True)

        if ckpt and (t + 1) % args.ckpt_every == 0:
            snap = (model_state, st, cstate, t + 1) \
                if controller is not None else (model_state, st, t + 1)
            ckpt.save(t + 1, snap)
    prof.close()
    if ckpt:
        ckpt.wait()
    if telemetry is not None:
        telemetry.close()
    return log


if __name__ == "__main__":
    main()
