"""Batched serving driver: prefill a batch of prompts, decode greedily.

With ``--telemetry-dir`` (or a :class:`repro.obs.Telemetry` handle passed
programmatically) the driver emits one ``kind="query"`` record per served
prompt -- prompt/generated lengths, prefill and decode wall time, cumulative
tokens served -- through the same sinks the manage loops drain into
(DESIGN.md Sec. 14), so a serving fleet and a training loop can share one
telemetry stream.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2_370m \
      --preset smoke --prompts 4 --prompt-len 16 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import config as C
from repro.launch.compile_cache import enable_compile_cache
from repro.models import zoo
from repro.obs import make_telemetry
from repro.obs.profile import annotation
from repro.train.steps import make_decode_step


def main(argv=None, telemetry=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2_370m")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry-dir", default=None,
                    help="write per-query serving telemetry (JSONL) under "
                         "this directory (repro.obs)")
    ap.add_argument("--telemetry-stdout", action="store_true",
                    help="echo telemetry records to stdout")
    args = ap.parse_args(argv)
    enable_compile_cache()
    own_telemetry = False
    if telemetry is None and (args.telemetry_dir or args.telemetry_stdout):
        telemetry = make_telemetry(args.telemetry_dir,
                                   stdout=args.telemetry_stdout, monitors=())
        own_telemetry = True

    cfg = (C.get_smoke_config(args.arch) if args.preset == "smoke"
           else C.get_config(args.arch))
    api = zoo.build(cfg)
    params = api.init_params(jax.random.key(args.seed))
    if telemetry is not None:
        telemetry.open_run({"mode": "serve", "arch": args.arch,
                            "prompts": args.prompts,
                            "prompt_len": args.prompt_len, "gen": args.gen,
                            "backend": jax.default_backend(),
                            "jax": jax.__version__})

    batch = zoo.make_demo_batch(
        cfg, jax.random.key(args.seed + 1), args.prompts, args.prompt_len
    )
    max_len = args.prompt_len + args.gen + 1
    t0 = time.time()
    with annotation("serve.prefill"):
        logits, caches = jax.jit(
            lambda p, b: api.prefill(p, b, max_len)
        )(params, batch)
        tok = jnp.argmax(logits[:, :, : cfg.vocab_size], axis=-1)
        tok = tok.astype(jnp.int32)
        tok.block_until_ready()
    prefill_s = time.time() - t0
    print(f"[serve] prefill: {prefill_s:.2f}s")

    # NOTE: prefill caches were built at prompt length; decode appends.
    decode = jax.jit(make_decode_step(api))
    outs = [tok]
    t0 = time.time()
    with annotation("serve.decode"):
        for _ in range(args.gen):
            tok, caches = decode(params, caches, tok)
            outs.append(tok)
    gen = np.concatenate([np.asarray(t) for t in outs], axis=1)
    dt = time.time() - t0
    print(f"[serve] decoded {args.gen} tokens x {args.prompts} seqs "
          f"in {dt:.2f}s ({args.gen*args.prompts/dt:.1f} tok/s)")
    print("[serve] first sequence:", gen[0].tolist())
    if telemetry is not None:
        served = 0
        for q in range(args.prompts):
            served += int(gen.shape[1])
            telemetry.emit({
                "kind": "query", "query": q,
                "prompt_len": args.prompt_len,
                "gen_tokens": int(gen.shape[1]),
                "tokens_served": served,  # cumulative across the batch
                "prefill_s": prefill_s / args.prompts,
                "decode_s": dt / args.prompts,
                "tok_per_s": args.gen * args.prompts / max(dt, 1e-9),
            })
        telemetry.flush()
        if own_telemetry:
            telemetry.close()
    return gen


if __name__ == "__main__":
    main()
