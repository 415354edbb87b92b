"""R-TBS: Reservoir-based Time-Biased Sampling (paper Algorithm 2).

The first sampling scheme that simultaneously (i) enforces the exponential
relative-inclusion criterion (paper eq. (1)) at all times, (ii) guarantees
|S_t| <= n, and (iii) tolerates unknown / arbitrarily varying arrival rates.
Invariant maintained (Theorem 4.2):  Pr[i in S_t] = (C_t / W_t) * w_t(i).

Fixed-shape JAX formulation. State:
  * ``lat`` -- the latent fractional sample (capacity n+1 slots)
  * ``total_weight`` -- W_t = sum_j B_j e^{-lambda (t-j)}

Each :func:`step` consumes one arriving batch (valid prefix of a fixed-capacity
buffer) and is fully jit/scan-safe; `vmap` over steps gives Monte-Carlo farms for
the statistical tests.

Two step implementations share the scalar bookkeeping (identical C_t/W_t
trajectories, asserted in tests):

  * :func:`step` -- the FUSED hot path (DESIGN.md Sec. 11). Every branch of
    Alg. 2 (decay-downsample, batch insert, overshoot-downsample, victim
    replacement) is computed as a slot-index map over the two sources
    (reservoir, batch) and composed in O(cap) integer ops with argsort-free
    randomness (:func:`repro.core.rng.prefix_permutation_fast`); the payload
    then moves in ONE two-source pass via
    :func:`repro.kernels.tbs_step.ops.tbs_step_apply` (Pallas kernel on TPU,
    jnp oracle elsewhere).
  * :func:`step_ref` -- the pre-fused reference: per-stage buffer rewrites
    (downsample gather, widened-buffer insert, second gather) with exact
    argsort permutations. Kept for parity tests and as the benchmark
    baseline (benchmarks/manage_loop.py, BENCH_sampler_step.json).

Step structure mirrors Alg. 2 exactly:
  unsaturated (W < n):  decay+downsample, accept all arrivals, then downsample
                        to n on overshoot (lines 5-12)
  saturated  (W >= n):  decay W; still saturated -> replace StochRound(B*n/W)
                        victims with batch items (lines 16-17); undershoot ->
                        downsample to W - B and accept all arrivals (lines 19-20)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.tbs_step import ops as tbs_ops
from repro.obs.profile import scope as _scope

from . import latent as lt
from . import rng


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RTBSState:
    lat: lt.Latent
    total_weight: jax.Array  # float32 scalar, W_t

    @property
    def sample_weight(self) -> jax.Array:  # C_t = min(n, W_t) implicitly == lat.weight
        return self.lat.weight


def init(item_proto: Any, n: int) -> RTBSState:
    """Empty R-TBS state with max sample size n (buffer capacity n+1)."""
    return RTBSState(
        lat=lt.make_empty(item_proto, n + 1), total_weight=jnp.float32(0.0)
    )


# ---------------------------------------------------------------------------
# the fused step: one composed slot map, one payload pass (DESIGN.md Sec. 11)
# ---------------------------------------------------------------------------
def tick_map(key, nfull, weight, total_weight, bcount, decay, *,
             cap: int, bcap: int, n: int):
    """Compose the whole tick's buffer rewrite into ONE slot map.

    Returns ``(src[cap] int32, new_sample_weight, w_new)`` where ``src``
    values in [0, cap) read the old reservoir and values in [cap, cap + bcap)
    read the arriving batch (slot ``cap + j`` = batch row j). The caller
    applies it in a single two-source payload pass; all the work here is
    O(cap + bcap) integer/scalar ops and at most two swap-or-not PRP
    evaluations -- no argsort, no intermediate payload buffers.

    Scalar-operand form (``nfull``/``weight``/``total_weight``/``bcount``/
    ``decay`` traced, ``cap``/``bcap``/``n`` static) so that
    :mod:`repro.bank` can ``vmap`` it over the touched keys of a keyed batch
    with per-key composed decay factors (DESIGN.md Sec. 13);
    :func:`step` feeds it a single :class:`RTBSState`.
    """
    bf = jnp.asarray(bcount, jnp.float32)
    bcnt = jnp.asarray(bcount, jnp.int32)
    w_prev = total_weight
    C = weight
    k0 = nfull
    was_unsat = w_prev < n
    # the select keeps the product rounded on its own: a bare d*W + B may
    # or may not be contracted into one fused multiply-add depending on how
    # the surrounding program is fused, so the bank and the single-reservoir
    # step would round W differently (W >= 0, so the select changes nothing)
    w_dec = jnp.where(w_prev > 0, decay * w_prev, 0.0)
    w_new = w_dec + bf                # both Alg. 2 branches decay then add B
    still_sat = (~was_unsat) & (w_new >= n)

    k_ds, k_over, k_m, k_vic, k_pick = jax.random.split(key, 5)
    nf = jnp.float32(n)

    def insert_path():
        """Alg. 2 lines 5-12 / 19-20: (maybe) downsample, accept all arrivals,
        (maybe) downsample the widened virtual buffer back to n."""
        V = cap + bcap
        # stage 1: decay downsample (unsat lines 6-8) or undershoot downsample
        # to W - B (sat lines 19-20)
        t1 = jnp.where(was_unsat, w_dec, w_new - bf)
        apply1 = jnp.where(was_unsat, (w_dec > 0) & (w_dec < C), True)
        # delete-complement fast path: a decay/undershoot trim removes
        # C - t1 ~ (1 - d) C items -- usually far fewer than bcap -- so the
        # map costs O(bcap) instead of a full-domain PRP evaluation (the
        # fill-up-phase hot spot; falls back past bcap deletions at runtime)
        src1 = jnp.where(
            apply1,
            lt.downsample_map(k_ds, cap, k0, C, t1, max_deleted=bcap),
            jnp.arange(cap, dtype=jnp.int32),
        )
        C1 = jnp.where(
            apply1,
            jnp.minimum(t1, C),
            jnp.minimum(C, jnp.maximum(t1, 0.0)),
        )
        k1, _ = lt.floor_frac(C1)

        # stage 2: insert the batch as full items on the widened virtual
        # buffer [0, V): slots [k1, k1+bcnt) <- batch rows, partial relocated
        # to k1+bcnt (lt.insert_full's layout, as a map)
        j = jnp.arange(V, dtype=jnp.int32)
        src1_at = src1[jnp.minimum(j, cap - 1)]
        mid = jnp.where(
            j < k1,
            src1_at,
            jnp.where(
                j < k1 + bcnt,
                cap + (j - k1),
                jnp.where(j == k1 + bcnt, src1[jnp.minimum(k1, cap - 1)], j),
            ),
        )
        C2 = C1 + bf

        # stage 3: overshoot downsample to n (unsat lines 11-12 only)
        overshoot = was_unsat & (C2 > nf)
        # an overshoot trims C2 - n <= B <= bcap items: always the fast map
        src2 = jax.lax.cond(
            overshoot,
            lambda: lt.downsample_map(k_over, V, k1 + bcnt, C2, nf,
                                      max_deleted=bcap),
            lambda: jnp.arange(V, dtype=jnp.int32),
        )
        src = mid[src2[:cap]]          # compose: one gather of int32 maps
        C3 = jnp.where(overshoot, nf, C2)
        return src, C3

    def replace_path():
        """Alg. 2 lines 16-17: replace m = StochRound(B*n/W) victims."""
        m = rng.stochastic_round(k_m, bf * n / jnp.maximum(w_new, 1e-30))
        victims = rng.prefix_permutation_fast(k_vic, cap, k0, k=bcap)
        picks = rng.prefix_permutation_fast(k_pick, bcap, bcnt, k=bcap)
        i = jnp.arange(bcap, dtype=jnp.int32)
        dest = jnp.where(i < m, victims, cap)          # cap => dropped
        src = (
            jnp.arange(cap, dtype=jnp.int32)
            .at[dest]
            .set(cap + picks, mode="drop")
        )
        return src, nf

    src, C3 = jax.lax.cond(still_sat, replace_path, insert_path)
    return src, C3, w_new


def _tick_map(key, state: RTBSState, bcount, bcap: int, *, n: int, decay):
    """:func:`tick_map` on an :class:`RTBSState` (the fused step's view)."""
    return tick_map(
        key, state.lat.nfull, state.lat.weight, state.total_weight, bcount,
        decay, cap=state.lat.cap, bcap=bcap, n=n,
    )


def _resolve_decay(lam, decay) -> jax.Array:
    """The per-tick multiplicative decay factor d_t from either a rate
    (``lam`` -> e^{-lam}) or the factor itself (``decay``, as produced by a
    :mod:`repro.decay` schedule / controller). Exactly one must be given;
    both may be traced scalars (DESIGN.md Sec. 12)."""
    if (lam is None) == (decay is None):
        raise ValueError(
            f"pass exactly one of lam= or decay=; got lam={lam!r}, "
            f"decay={decay!r}"
        )
    if decay is None:
        return jnp.exp(-jnp.asarray(lam, jnp.float32))
    return jnp.asarray(decay, jnp.float32)


@functools.partial(jax.jit, static_argnames=("n", "impl"))
def step(
    key: jax.Array,
    state: RTBSState,
    batch_items: Any,
    bcount: jax.Array,
    *,
    n: int,
    lam: float | jax.Array | None = None,
    decay: float | jax.Array | None = None,
    impl: str | None = None,
) -> RTBSState:
    """Advance R-TBS by one batch arrival (paper Algorithm 2), fused.

    ``batch_items``: pytree, leaves [bcap, ...]; valid prefix length ``bcount``.
    ``lam`` may be a traced scalar; elapsed time between batches is 1 (use
    lam * dt for irregular arrivals, per paper Sec. 2). ``decay`` gives the
    per-tick multiplicative factor d_t directly instead (the
    :mod:`repro.decay` schedules and the adaptive controller feed this form;
    pass exactly one of the two). ``impl`` routes the payload pass (None =
    auto: Pallas kernel on TPU, jnp oracle elsewhere; see
    :mod:`repro.kernels.tbs_step.ops`).

    Identical C_t/W_t trajectories and sampling distribution as
    :func:`step_ref` (asserted in tests/test_tbs_step.py); the RNG stream
    differs (DESIGN.md Sec. 11).
    """
    decay = _resolve_decay(lam, decay)
    bcount = jnp.asarray(bcount, jnp.int32)
    bcap = jax.tree_util.tree_leaves(batch_items)[0].shape[0]

    with _scope("rtbs.tick_map"):
        src, C3, w_new = _tick_map(key, state, bcount, bcap, n=n, decay=decay)
        k3, _ = lt.floor_frac(C3)
    with _scope("rtbs.payload"):
        new_items = tbs_ops.tbs_step_apply(state.lat.items, batch_items, src,
                                           impl=impl)
    return RTBSState(
        lat=lt.Latent(items=new_items, nfull=k3, weight=C3),
        total_weight=w_new,
    )


# ---------------------------------------------------------------------------
# the reference step: per-stage buffer rewrites, exact argsort permutations
# ---------------------------------------------------------------------------
def _unsaturated_path(key, lat, w_prev, batch_items, bcount, n, decay):
    """Paper Alg. 2 lines 5-12 (previously unsaturated: C == W < n)."""
    k_ds, k_over = jax.random.split(key)
    w_dec = decay * w_prev
    # lines 6-8: decay weight, downsample the latent to the decayed weight
    lat = jax.lax.cond(
        (w_dec > 0) & (w_dec < lat.weight),
        lambda: lt.downsample(k_ds, lat, w_dec, exact=True),
        lambda: dataclasses.replace(
            lat, weight=jnp.minimum(lat.weight, jnp.maximum(w_dec, 0.0))
        ),
    )
    # lines 9-10: accept ALL batch items (on a widened temp buffer)
    cap = lat.cap
    wide = lt.Latent(
        items=lt.concat_items(
            lat.items,
            jax.tree_util.tree_map(lambda b: jnp.zeros_like(b), batch_items),
        ),
        nfull=lat.nfull,
        weight=lat.weight,
    )
    wide = lt.insert_full(wide, batch_items, bcount)
    w_new = w_dec + jnp.asarray(bcount, jnp.float32)
    # lines 11-12: overshoot -> downsample to n (sample becomes saturated)
    wide = jax.lax.cond(
        wide.weight > n,
        lambda: lt.downsample(k_over, wide, jnp.float32(n), exact=True),
        lambda: wide,
    )
    out = lt.Latent(
        items=lt.truncate_items(wide.items, cap), nfull=wide.nfull, weight=wide.weight
    )
    return out, w_new


def _saturated_path(key, lat, w_prev, batch_items, bcount, n, decay):
    """Paper Alg. 2 lines 14-20 (previously saturated: C == n <= W)."""
    k_m, k_vic, k_pick, k_ds = jax.random.split(key, 4)
    bcapf = jnp.asarray(bcount, jnp.float32)
    w_new = decay * w_prev + bcapf
    cap = lat.cap
    bcap = jax.tree_util.tree_leaves(batch_items)[0].shape[0]

    def still_saturated():
        # lines 16-17: replace m = StochRound(B*n/W) victims with batch items
        m = rng.stochastic_round(k_m, bcapf * n / jnp.maximum(w_new, 1e-30))
        victims = rng.prefix_permutation(k_vic, cap, lat.nfull)
        picks = rng.prefix_permutation(k_pick, bcap, bcount)
        i = jnp.arange(bcap, dtype=jnp.int32)
        dest = jnp.where(i < m, victims[jnp.minimum(i, cap - 1)], cap)  # cap => drop
        payload = lt.gather(batch_items, picks)
        items = jax.tree_util.tree_map(
            lambda a, b: a.at[dest].set(b, mode="drop"), lat.items, payload
        )
        return lt.Latent(items=items, nfull=lat.nfull, weight=jnp.float32(n))

    def undershoot():
        # lines 19-20: downsample to W' = W - B, then accept all batch items
        l2 = lt.downsample(k_ds, lat, w_new - bcapf, exact=True)
        return lt.insert_full(l2, batch_items, bcount)

    out = jax.lax.cond(w_new >= n, still_saturated, undershoot)
    return out, w_new


@functools.partial(jax.jit, static_argnames=("n",))
def step_ref(
    key: jax.Array,
    state: RTBSState,
    batch_items: Any,
    bcount: jax.Array,
    *,
    n: int,
    lam: float | jax.Array | None = None,
    decay: float | jax.Array | None = None,
) -> RTBSState:
    """The pre-fused R-TBS step: per-stage buffer rewrites with exact argsort
    permutations -- 2-4 full sorts + multi-gather slot remaps per tick. Kept
    as the parity oracle and the benchmark baseline; use :func:`step`."""
    decay = _resolve_decay(lam, decay)
    bcount = jnp.asarray(bcount, jnp.int32)
    was_unsat = state.total_weight < n
    lat, w_new = jax.lax.cond(
        was_unsat,
        lambda: _unsaturated_path(
            key, state.lat, state.total_weight, batch_items, bcount, n, decay
        ),
        lambda: _saturated_path(
            key, state.lat, state.total_weight, batch_items, bcount, n, decay
        ),
    )
    return RTBSState(lat=lat, total_weight=w_new)


def realize(key: jax.Array, state: RTBSState) -> tuple[jax.Array, jax.Array]:
    """Draw the actual sample S_t: (mask over the n+1 slots, |S_t|)."""
    return lt.realize(key, state.lat)


def run_stream(
    key: jax.Array,
    state: RTBSState,
    batches: Any,
    bcounts: jax.Array,
    *,
    n: int,
    lam: float,
    impl: str | None = None,
    use_ref: bool = False,
) -> tuple[RTBSState, dict]:
    """Scan ``step`` over a stream of T batches; returns final state + per-step
    trace (sample weight C_t, total weight W_t, realized size E via C).
    ``use_ref`` scans :func:`step_ref` instead (parity tests, benchmarks)."""

    def body(carry, inp):
        st = carry
        items_t, cnt_t, key_t = inp
        if use_ref:
            st = step_ref(key_t, st, items_t, cnt_t, n=n, lam=lam)
        else:
            st = step(key_t, st, items_t, cnt_t, n=n, lam=lam, impl=impl)
        return st, {"C": st.lat.weight, "W": st.total_weight}

    T = bcounts.shape[0]
    keys = jax.random.split(key, T)
    final, trace = jax.lax.scan(body, state, (batches, bcounts, keys))
    return final, trace
