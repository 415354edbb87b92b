"""Subprocess worker for the distributed benchmarks (Figs. 7-9).

Usage: python -m benchmarks._dist_worker <shards> <batch_per_shard> <impl>
Prints: ``<impl>,<us_per_batch>``.

Implementations (paper Fig. 7, Spark design points mapped to the mesh --
DESIGN.md Sec. 3):
  cp_dist  -- co-partitioned reservoir + distributed decisions (D-R-TBS prod)
  cp_cent  -- co-partitioned reservoir + centralized decisions (replicated
              global slot permutation, master-style)
  kv_cj    -- key-value-store reservoir emulation w/ co-located join: insert
              payloads cross the network once (all_gather of half the batch)
  kv_rj    -- key-value emulation w/ repartition join: payloads cross twice
  dttbs    -- D-T-TBS (embarrassingly parallel)
"""
import os
import sys

SHARDS = int(sys.argv[1])
BPS = int(sys.argv[2])
IMPL = sys.argv[3]

os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={SHARDS}"

import functools  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import distributed as dist  # noqa: E402
from repro.core import rng, simple  # noqa: E402

N_GLOBAL = 4 * SHARDS * BPS          # reservoir target (scaled w/ stream rate)
CAP_S = 8 * BPS
LAM = 0.07
D = 8                                 # item payload: D int32s ~ a record


def main():
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(SHARDS)
    step = functools.partial(dist.drtbs_shard_step, n=N_GLOBAL, lam=LAM)

    def shard_fn(key, items, nfull, partial, weight, tweight, oflow, bi, bc):
        st = dist.DRTBSShard(
            items=items, nfull=nfull[0], partial_item=partial,
            weight=weight, total_weight=tweight, overflow=oflow[0],
        )
        me = jax.lax.axis_index(dist.AXIS)
        if IMPL == "cp_cent":
            # centralized decisions: a master-style GLOBAL slot permutation is
            # computed (replicated) before the step
            gperm = jax.random.permutation(key, N_GLOBAL)
            bi = bi + (gperm[0] * 0)
        if IMPL in ("kv_cj", "kv_rj"):
            # key-value reservoir: inserted payloads must cross the network to
            # hash-owned slots; RJ crosses twice (repartition join)
            gathered = jax.lax.all_gather(bi, dist.AXIS)
            bi = bi + 0 * gathered.sum(axis=0)
            if IMPL == "kv_rj":
                gathered2 = jax.lax.all_gather(bi, dist.AXIS)
                bi = bi + 0 * gathered2.sum(axis=0)
        if IMPL == "dttbs":
            import math

            p = math.exp(-LAM)
            q = min(1.0, N_GLOBAL * (1 - p) / (SHARDS * BPS))
            bst = simple.BufferState(
                items=items, count=nfull[0],
                total_weight=weight, overflow=oflow[0],
            )
            bst = dist.dttbs_shard_step(
                key, bst, bi, bc[0], p=jnp.float32(p), q=jnp.float32(q)
            )
            return (bst.items, bst.count[None], partial, weight,
                    bst.total_weight, bst.overflow[None])
        st = step(key, st, bi, bc[0])
        return (st.items, st.nfull[None], st.partial_item, st.weight,
                st.total_weight, st.overflow[None])

    smapped = jax.jit(
        jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(), P(dist.AXIS), P(dist.AXIS), P(), P(), P(),
                      P(dist.AXIS), P(dist.AXIS), P(dist.AXIS)),
            out_specs=(P(dist.AXIS), P(dist.AXIS), P(), P(), P(),
                       P(dist.AXIS)),
            check_vma=False,
        )
    )

    items = jnp.zeros((SHARDS * CAP_S, D), jnp.int32)
    nfull = jnp.zeros((SHARDS,), jnp.int32)
    partial = jnp.zeros((D,), jnp.int32)
    weight = jnp.float32(0.0)
    tweight = jnp.float32(0.0)
    oflow = jnp.zeros((SHARDS,), jnp.int32)
    bi = jnp.ones((SHARDS * BPS, D), jnp.int32)
    bc = jnp.full((SHARDS,), BPS, jnp.int32)

    state = (items, nfull, partial, weight, tweight, oflow)
    # warmup (fills the reservoir, compiles)
    for t in range(3):
        key = jax.random.fold_in(jax.random.key(0), t)
        state = smapped(key, *state, bi, bc)
    jax.block_until_ready(state)
    ts = []
    for t in range(10):
        key = jax.random.fold_in(jax.random.key(1), t)
        t0 = time.perf_counter()
        state = smapped(key, *state, bi, bc)
        jax.block_until_ready(state)
        ts.append(time.perf_counter() - t0)
    print(f"{IMPL},{np.median(ts)*1e6:.1f}")


if __name__ == "__main__":
    main()
