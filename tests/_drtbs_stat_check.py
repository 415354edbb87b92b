"""Subprocess statistical check for D-R-TBS on an 8-shard host mesh.

Invoked by tests/test_distributed.py with XLA_FLAGS forcing 8 host devices
(pytest's own process keeps the default single device). Validates, on a real
multi-device mesh with uneven/empty per-shard batches:

  * Theorem 4.2 invariant  Pr[i in S_t] = (C_t/W_t) w_t(i)  (Monte Carlo)
  * the global sample-size bound  sum_s nfull_s (+ partial) <= n
  * deterministic W_t / C_t trajectories == the analytic recurrence
  * zero capacity overflow for the sized buffers
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import distributed as dist

S = 8          # shards
CAP_S = 24     # per-shard reservoir capacity
BCAP_S = 8     # per-shard batch capacity
N = 16         # global max sample size
LAM = 0.3
TRIALS = 6000

# global batch sizes per step; deliberately uneven across shards (incl. empty)
GLOBAL_BATCHES = [24, 8, 0, 40, 16, 8, 8, 4]
T = len(GLOBAL_BATCHES)


def split_counts(total, s=S):
    """Deterministic uneven split of `total` items over s shards."""
    base = np.zeros(s, np.int32)
    rs = np.random.RandomState(total * 7 + 13)
    for _ in range(total):
        base[rs.randint(0, max(1, s // 2 + total % s))] += 1  # skewed
    while base.max() > BCAP_S:  # respect per-shard capacity
        src = base.argmax()
        dst = base.argmin()
        base[src] -= 1
        base[dst] += 1
    return base


def main():
    mesh = jax.make_mesh((S,), (dist.AXIS,))
    step = functools.partial(dist.drtbs_shard_step, n=N, lam=LAM)

    def shard_fn(keys, items, nfull, partial, weight, tweight, oflow, bitems, bcnt):
        # per-shard views: items [TRIALS, CAP_S], nfull [TRIALS, 1] -> squeeze
        def one(key, it, nf, pa, w, tw, of, bi, bc):
            st = dist.DRTBSShard(
                items=it, nfull=nf, partial_item=pa, weight=w,
                total_weight=tw, overflow=of,
            )
            st = step(key, st, bi, bc)
            return (st.items, st.nfull, st.partial_item, st.weight,
                    st.total_weight, st.overflow)

        return jax.vmap(one)(
            keys, items, nfull[:, 0], partial, weight, tweight,
            oflow[:, 0], bitems, bcnt[:, 0],
        )

    in_specs = (P(), P(None, dist.AXIS), P(None, dist.AXIS), P(), P(), P(),
                P(None, dist.AXIS), P(None, dist.AXIS), P(None, dist.AXIS))
    out_specs = (P(None, dist.AXIS), P(None, dist.AXIS), P(), P(), P(),
                 P(None, dist.AXIS))

    # shard_map out_specs concatenate per-shard outputs along the spec'd dim;
    # per-shard nfull/overflow are [TRIALS] -> need [TRIALS, 1] locally.
    def fix_dims_post(outs):
        items, nfull, partial, weight, tweight, oflow = outs
        return items, nfull[:, None], partial, weight, tweight, oflow[:, None]

    smapped = jax.jit(
        jax.shard_map(
            lambda *a: fix_dims_post(shard_fn(*a)),
            mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
    )

    # ---- build the stream ----------------------------------------------------
    batch_items = np.zeros((T, TRIALS, S * BCAP_S), np.int32)
    batch_counts = np.zeros((T, TRIALS, S), np.int32)
    for t, g in enumerate(GLOBAL_BATCHES):
        counts = split_counts(g)
        batch_counts[t, :, :] = counts
        nid = 0
        for s in range(S):
            for j in range(counts[s]):
                batch_items[t, :, s * BCAP_S + j] = 1000 * (t + 1) + nid
                nid += 1

    items = jnp.zeros((TRIALS, S * CAP_S), jnp.int32)
    nfull = jnp.zeros((TRIALS, S), jnp.int32)
    partial = jnp.zeros((TRIALS,), jnp.int32)
    weight = jnp.zeros((TRIALS,), jnp.float32)
    tweight = jnp.zeros((TRIALS,), jnp.float32)
    oflow = jnp.zeros((TRIALS, S), jnp.int32)

    w_traj = []
    for t in range(T):
        keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.key(17 + t), i))(
            jnp.arange(TRIALS)
        )
        items, nfull, partial, weight, tweight, oflow = smapped(
            keys, items, nfull, partial, weight, tweight, oflow,
            jnp.asarray(batch_items[t]), jnp.asarray(batch_counts[t]),
        )
        w_traj.append((float(tweight[0]), float(weight[0])))

    # ---- checks ---------------------------------------------------------------
    items_np = np.asarray(items)
    nfull_np = np.asarray(nfull)
    weight_np = np.asarray(weight)
    tw_np = np.asarray(tweight)
    assert int(np.asarray(oflow).sum()) == 0, "capacity overflow"

    # deterministic trajectories
    w = 0.0
    for t, g in enumerate(GLOBAL_BATCHES):
        w = math.exp(-LAM) * w + g
        assert abs(w_traj[t][0] - w) < 1e-3 * max(1.0, w), (t, w_traj[t][0], w)
        assert abs(w_traj[t][1] - min(N, w)) < 1e-3 * max(1.0, w)
    W_T = w
    C_T = min(N, W_T)

    # global bound
    tot_full = nfull_np.sum(axis=1)
    assert (tot_full <= N).all(), tot_full.max()
    assert (np.floor(weight_np + 1e-4) >= tot_full).all()

    # Theorem 4.2: membership per batch (count full items + partial contribution)
    frac = weight_np - np.floor(weight_np)
    rs = np.random.RandomState(0)
    take_partial = rs.rand(TRIALS) < frac
    hits = np.zeros(T + 1)
    # valid-mask per shard slot
    slot = np.arange(S * CAP_S) % CAP_S
    shard = np.arange(S * CAP_S) // CAP_S
    valid = slot < nfull_np[:, shard]
    bidx = np.where(valid, items_np // 1000, 0)
    for t in range(1, T + 1):
        hits[t] = (bidx == t).sum()
    pidx = np.asarray(partial) // 1000
    for t in range(1, T + 1):
        hits[t] += ((pidx == t) & take_partial).sum()

    bad = []
    for j, g in enumerate(GLOBAL_BATCHES):
        if g == 0:
            continue
        emp = hits[j + 1] / TRIALS / g
        expect = (C_T / W_T) * math.exp(-LAM * (T - 1 - j))
        if abs(emp - expect) > 0.03:
            bad.append((j, emp, expect))
    assert not bad, bad

    print("D-R-TBS statistical checks passed:",
          f"W_T={W_T:.3f} C_T={C_T:.3f} trials={TRIALS}")


if __name__ == "__main__":
    main()
