"""Record the small device trace that tests/bench checks the reduction on.

    python3 bench/tools/record_trace.py --out <dir>

Runs a tiny jitted program with three named scopes and the repository's
payload kernel, under host spans, and writes the profiler's trace and the
compiled program's HLO text (``step.hlo.txt``) beside it. Prints every
plane and line of the trace, with the stats of a few events of each, so the
layout the reduction relies on can be read by eye.
"""
from __future__ import annotations

import argparse
import glob
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--dump", type=int, default=6, help="events shown per line")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from repro.kernels.tbs_step import ops as ts

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")

    @jax.jit
    def step(items, batch, src, x):
        with jax.named_scope("manage.eval"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("bank.payload"):
            out = ts.tbs_step_apply(items, batch, src)
        with jax.named_scope("manage.retrain"):
            z = (y @ y).sum()
        return out, z

    k = jax.random.key(0)
    items = jax.random.randint(k, (512, 256), 0, 100, jnp.int32)
    batch = jax.random.randint(k, (64, 256), 0, 100, jnp.int32)
    src = jax.random.randint(k, (512,), 0, 576, jnp.int32)
    x = jax.random.normal(k, (1024, 1024), jnp.bfloat16)
    jax.block_until_ready(step(items, batch, src, x))
    jax.profiler.start_trace(args.out)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.tick"):
            jax.block_until_ready(step(items, batch, src, x))
        with jax.profiler.TraceAnnotation("bench.host_wait"):
            jnp.zeros(()).block_until_ready()
    jax.profiler.stop_trace()
    hlo = step.lower(items, batch, src, x).compile().as_text()
    pathlib.Path(args.out, "step.hlo.txt").write_text(hlo)
    path = sorted(glob.glob(f"{args.out}/**/*.xplane.pb", recursive=True))[-1]
    print("trace:", path)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), "lines", len(lines),
              "stats", list(getattr(plane, "stats", []))[:10])
        for line in lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), "events", len(evs))
            for e in evs[: args.dump]:
                print("    EV", repr(e.name), e.start_ns, e.duration_ns,
                      {k: v for k, v in e.stats})


if __name__ == "__main__":
    main()
