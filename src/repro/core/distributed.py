"""D-R-TBS and D-T-TBS: the paper's Section-5 distributed algorithms on a JAX mesh.

Spark -> TPU mapping (see DESIGN.md Sec. 3):

  * co-partitioned reservoir  -> reservoir shard s lives with incoming-batch
    shard s along the ``data`` mesh axis; item payloads NEVER cross shards
    (except the single fractional item, whose payload is replicated).
  * distributed decisions     -> every shard computes the identical global
    bookkeeping from the same PRNG key, splits global insert/delete counts
    with an exact multivariate hypergeometric over per-shard counts
    (Sec. 5.3 / Fig. 6(b)), then acts only on its own shard.
  * master aggregation of |B_t| -> one scalar ``psum`` per step.

The module is written against *per-shard* views: every public ``*_shard_step``
function is meant to be called inside ``jax.shard_map`` over the ``data`` axis
(helpers to build those wrappers are provided at the bottom). All global
bookkeeping (W, C, branch choices, count splits) is computed identically on
every shard from the replicated scalars + shared key, so no scalar needs to be
exchanged beyond the |B_t| psum and the tiny all_gather of per-shard counts.

Variants kept for the paper's Figure-7 comparison:
  * centralized decisions (global permutation over virtual slots, replicated)
  * key-value-store reservoir emulation (hash-partitioned: batch payloads must
    cross the network -- modeled with an all_gather of insert payloads)
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from . import latent as lt
from . import rng

AXIS = "data"  # mesh axis the reservoir is co-partitioned over


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DRTBSShard:
    """Per-shard slice of the distributed latent sample.

    Global latent = union of shard full-item prefixes + one replicated partial.
    ``weight``/``total_weight``/``partial_*`` are replicated scalars (identical
    on every shard -- enforced by construction, all derived from shared keys).
    """

    items: Any                # pytree, leaves [cap_s, ...] -- full items at [0, nfull)
    nfull: jax.Array          # int32, this shard's full-item count
    partial_item: Any         # pytree of ONE item (replicated payload)
    weight: jax.Array         # float32, global C
    total_weight: jax.Array   # float32, global W
    overflow: jax.Array       # int32, capacity-dropped inserts (should stay 0)


def init_shard(item_proto: Any, cap_s: int) -> DRTBSShard:
    """Empty per-shard state (call under shard_map or vmap over shards)."""
    items = jax.tree_util.tree_map(
        lambda p: jnp.zeros((cap_s,) + tuple(p.shape), p.dtype), item_proto
    )
    one = jax.tree_util.tree_map(
        lambda p: jnp.zeros(tuple(p.shape), p.dtype), item_proto
    )
    return DRTBSShard(
        items=items,
        nfull=jnp.int32(0),
        partial_item=one,
        weight=jnp.float32(0.0),
        total_weight=jnp.float32(0.0),
        overflow=jnp.int32(0),
    )


def _payload_bcast(payload: Any, flag) -> Any:
    """Zero out payload unless flag; psum over shards -> replicated broadcast."""
    return jax.tree_util.tree_map(
        lambda p: jax.lax.psum(p * jnp.asarray(flag, p.dtype), AXIS), payload
    )


# ---------------------------------------------------------------------------
# the global downsample, executed shard-locally (paper Alg. 3, distributed)
# ---------------------------------------------------------------------------
def _dist_downsample(key, st: DRTBSShard, new_weight) -> DRTBSShard:
    """Distributed Algorithm 3: scale every item's inclusion prob by C'/C.

    All shards derive the same branch decisions and count split from ``key``;
    each then compacts only its local prefix. The new partial item's payload is
    broadcast with one psum. Old-partial-as-full lands on the donor shard."""
    cap_s = jax.tree_util.tree_leaves(st.items)[0].shape[0]
    me = jax.lax.axis_index(AXIS)
    nshards = jax.lax.psum(1, AXIS)

    cw = st.weight
    nw = jnp.minimum(jnp.asarray(new_weight, jnp.float32), cw)
    k, f = lt.floor_frac(cw)
    kp, fp = lt.floor_frac(nw)
    safe_c = jnp.maximum(cw, 1e-30)

    k_u, k_split, k_donor, k_local = jax.random.split(key, 4)
    u = jax.random.uniform(k_u, dtype=jnp.float32)

    counts = jax.lax.all_gather(st.nfull, AXIS)  # [S] replicated

    # --- shared branch logic -------------------------------------------------
    case0 = kp == 0
    case_eq = (kp == k) & ~case0
    # case_lt otherwise
    p1 = (nw / safe_c) * f
    b1 = (u <= p1) & (f > 0)                      # case_lt branch 1
    rho = (1.0 - (nw / safe_c) * f) / jnp.maximum(1.0 - fp, 1e-30)
    do_swap = u > rho                             # case_eq swap?
    keep_old_partial = u <= f / safe_c            # case0

    # Number of items to select globally from the full-item pool:
    #   case0: 1 (only if a full item becomes the partial)
    #   case_eq: 1 (only to swap)   case_lt b1: kp   case_lt b2: kp + 1
    sel_total = jnp.where(
        case0,
        jnp.where(keep_old_partial, 0, 1),
        jnp.where(
            case_eq,
            jnp.where(do_swap, 1, 0),
            jnp.where(b1, kp, kp + 1),
        ),
    )
    split = rng.multivariate_hypergeometric(
        k_split, sel_total, counts, max_support=cap_s
    )  # [S] replicated
    x_s = split[me]

    # Donor shard for the new partial: w.p. x_s / sel_total.
    donor_shard = rng.categorical_from_counts(k_donor, split)
    is_donor = (me == donor_shard) & (sel_total > 0)

    # --- local compaction ----------------------------------------------------
    perm = rng.prefix_permutation_fast(
        jax.random.fold_in(k_local, me), cap_s, st.nfull
    )
    # fulls kept locally:
    #   case0: 0.     case_eq: nfull (swap only replaces one slot -- see below)
    #   case_lt: x_s, minus 1 on the donor (its last selected becomes partial;
    #            if fp==0 that extracted item is simply dropped, which matches
    #            Alg. 3 lines 19-20 exactly -- see tests).
    keep_s = jnp.where(
        case0,
        0,
        jnp.where(case_eq, st.nfull, x_s - jnp.where(is_donor, 1, 0)),
    ).astype(jnp.int32)
    keep_s = jnp.maximum(keep_s, 0)

    # new partial payload (uniform over the globally selected items):
    #   donor contributes its perm[keep_s] item (for case_lt) / perm[0] (case0/eq)
    donor_slot = jnp.where(case0 | case_eq, perm[0], perm[jnp.minimum(keep_s, cap_s - 1)])
    donor_payload = jax.tree_util.tree_map(lambda a: a[donor_slot], st.items)
    new_partial_from_full = _payload_bcast(donor_payload, is_donor)
    new_partial = jax.tree_util.tree_map(
        lambda old, new: jnp.where(
            _b(case0 & keep_old_partial | (case_eq & ~do_swap), old), old, new
        ),
        st.partial_item,
        new_partial_from_full,
    )

    # --- rebuild local buffer -------------------------------------------------
    # case_lt / case0: gather-compact to the first keep_s of perm order
    compacted = lt.gather(st.items, perm)

    # case_eq swap: replace slot perm[0] (the item that became partial) with the
    # old partial payload, keep everything else in place.
    swapped = jax.tree_util.tree_map(
        lambda a, p: a.at[perm[0]].set(
            jnp.where(_b(is_donor & (f > 0), p), p, a[perm[0]])
        ),
        st.items,
        st.partial_item,
    )
    items = jax.tree_util.tree_map(
        lambda comp, sw: jnp.where(_b2(case_eq, comp), sw, comp), compacted, swapped
    )
    nfull_new = jnp.where(case_eq, st.nfull, keep_s).astype(jnp.int32)

    # case_lt branch1: old partial becomes a FULL item -> append on donor shard.
    append_old_partial = (~case0) & (~case_eq) & b1 & (f > 0)
    items = jax.tree_util.tree_map(
        lambda a, p: a.at[jnp.where(append_old_partial & is_donor, nfull_new, cap_s)]
        .set(p, mode="drop"),
        items,
        st.partial_item,
    )
    nfull_new = nfull_new + jnp.where(append_old_partial & is_donor, 1, 0)

    # identity shortcut when no shrink requested
    noop = nw >= cw
    items = jax.tree_util.tree_map(
        lambda old, new: jnp.where(_b2(noop, old), old, new), st.items, items
    )
    nfull_new = jnp.where(noop, st.nfull, nfull_new)
    new_partial = jax.tree_util.tree_map(
        lambda old, new: jnp.where(_b(noop, old), old, new),
        st.partial_item,
        new_partial,
    )

    return dataclasses.replace(
        st, items=items, nfull=nfull_new, partial_item=new_partial, weight=nw
    )


def _b(pred, like):
    """broadcast scalar bool for a single-item payload leaf"""
    return jnp.reshape(pred, (1,) * like.ndim) if like.ndim else pred


def _b2(pred, like):
    """broadcast scalar bool for a [cap, ...] buffer leaf"""
    return jnp.reshape(pred, (1,) * like.ndim)


def _local_insert_full(st: DRTBSShard, batch_items, bcount, add_weight) -> DRTBSShard:
    """Append local batch items as full items (weight bump is the GLOBAL batch
    size; item placement is purely local -- co-partitioned reservoir)."""
    cap_s = jax.tree_util.tree_leaves(st.items)[0].shape[0]
    bcap = jax.tree_util.tree_leaves(batch_items)[0].shape[0]
    i = jnp.arange(bcap, dtype=jnp.int32)
    dest = jnp.where(i < bcount, st.nfull + i, cap_s)
    dropped = jnp.maximum(st.nfull + bcount - cap_s, 0)
    items = jax.tree_util.tree_map(
        lambda a, b: a.at[dest].set(b, mode="drop"), st.items, batch_items
    )
    return dataclasses.replace(
        st,
        items=items,
        nfull=jnp.minimum(st.nfull + bcount, cap_s),
        weight=st.weight + jnp.asarray(add_weight, jnp.float32),
        overflow=st.overflow + dropped,
    )


# ---------------------------------------------------------------------------
# the per-batch step (paper Alg. 2, distributed; call under shard_map)
# ---------------------------------------------------------------------------
def drtbs_shard_step(
    key: jax.Array,
    st: DRTBSShard,
    batch_items: Any,
    bcount_local: jax.Array,
    *,
    n: int,
    lam=None,
    decay=None,
) -> DRTBSShard:
    """One D-R-TBS step for this shard. ``key`` must be IDENTICAL across shards
    (replicated); shard-local draws fold in the shard index. ``decay`` gives
    the per-tick multiplicative factor d_t directly (replicated, possibly
    traced -- the :mod:`repro.decay` form) instead of the rate ``lam``;
    exactly one of the two must be passed."""
    from . import rtbs as _rtbs

    me = jax.lax.axis_index(AXIS)
    decay = _rtbs._resolve_decay(lam, decay)
    bcount_local = jnp.asarray(bcount_local, jnp.int32)
    B = jax.lax.psum(bcount_local, AXIS)            # the ONE aggregation (Sec. 5.1)
    Bf = B.astype(jnp.float32)
    cap_s = jax.tree_util.tree_leaves(st.items)[0].shape[0]
    bcap = jax.tree_util.tree_leaves(batch_items)[0].shape[0]

    k_ds, k_over, k_m, k_split_v, k_split_i, k_loc = jax.random.split(key, 6)
    was_unsat = st.total_weight < n

    def unsat_path(st: DRTBSShard) -> DRTBSShard:
        w_dec = decay * st.total_weight
        st1 = jax.lax.cond(
            (w_dec > 0) & (w_dec < st.weight),
            lambda: _dist_downsample(k_ds, st, w_dec),
            lambda: dataclasses.replace(
                st, weight=jnp.minimum(st.weight, jnp.maximum(w_dec, 0.0))
            ),
        )
        st2 = _local_insert_full(st1, batch_items, bcount_local, Bf)
        w_new = w_dec + Bf
        st3 = jax.lax.cond(
            st2.weight > n,
            lambda: _dist_downsample(k_over, st2, jnp.float32(n)),
            lambda: st2,
        )
        return dataclasses.replace(st3, total_weight=w_new)

    def sat_path(st: DRTBSShard) -> DRTBSShard:
        w_new = decay * st.total_weight + Bf

        def still_saturated():
            m = rng.stochastic_round(k_m, Bf * n / jnp.maximum(w_new, 1e-30))
            counts = jax.lax.all_gather(st.nfull, AXIS)
            bcounts = jax.lax.all_gather(bcount_local, AXIS)
            # paper Fig. 6(b): split delete AND insert counts hypergeometrically
            del_s = rng.multivariate_hypergeometric(
                k_split_v, m, counts, max_support=cap_s
            )[me]
            ins_s = rng.multivariate_hypergeometric(
                k_split_i, m, bcounts, max_support=bcap
            )[me]
            k_vic, k_pick = jax.random.split(jax.random.fold_in(k_loc, me))
            # delete del_s local victims by compaction to (nfull - del_s) ...
            vperm = rng.prefix_permutation_fast(k_vic, cap_s, st.nfull)
            keep = st.nfull - del_s
            compacted = lt.gather(st.items, vperm)
            # ... then append ins_s local batch picks
            picks = rng.prefix_permutation_fast(k_pick, bcap, bcount_local)
            i = jnp.arange(bcap, dtype=jnp.int32)
            dest = jnp.where(i < ins_s, keep + i, cap_s)
            dropped = jnp.maximum(keep + ins_s - cap_s, 0)
            payload = lt.gather(batch_items, picks)
            items = jax.tree_util.tree_map(
                lambda a, b: a.at[dest].set(b, mode="drop"), compacted, payload
            )
            return dataclasses.replace(
                st,
                items=items,
                nfull=jnp.minimum(keep + ins_s, cap_s),
                weight=jnp.float32(n),
                overflow=st.overflow + dropped,
            )

        def undershoot():
            st1 = _dist_downsample(k_ds, st, w_new - Bf)
            return _local_insert_full(st1, batch_items, bcount_local, Bf)

        st2 = jax.lax.cond(w_new >= n, still_saturated, undershoot)
        return dataclasses.replace(st2, total_weight=w_new)

    return jax.lax.cond(was_unsat, unsat_path, sat_path, st)


def drtbs_realize_shard(key: jax.Array, st: DRTBSShard):
    """Realize S_t on this shard: (mask [cap_s], local size). The partial item is
    included (on shard 0 only) w.p. frac(C), using the replicated key.

    Callers that materialize the realized sample must also materialize the
    partial payload whenever ``take_partial`` is True -- ``st.partial_item`` is
    NOT covered by ``mask``/``st.items`` (it is a separate replicated payload).
    The unified API does this by reserving slot ``cap_s``; see
    :func:`repro.core.api._make_drtbs`."""
    me = jax.lax.axis_index(AXIS)
    _, take, _ = lt.partial_draw(key, st.weight)
    take_partial = take & (me == 0)
    cap_s = jax.tree_util.tree_leaves(st.items)[0].shape[0]
    mask = jnp.arange(cap_s) < st.nfull
    return mask, st.nfull + take_partial.astype(jnp.int32), take_partial


def drtbs_realize_global(key: jax.Array, st: DRTBSShard):
    """Assemble the realized GLOBAL sample, replicated on every shard.

    Returns ``(items, mask, size)`` where item leaves are [S*cap_s + 1, ...]:
    the all-gathered per-shard full-item buffers followed by ONE reserved slot
    holding the replicated partial payload. Slot ``S*cap_s`` is selected w.p.
    frac(C) with the replicated key, so -- unlike the bare per-shard realize --
    the fractional item's payload is materialized whenever it is counted and
    ``mask.sum() == size`` holds globally. One all_gather of the shard buffers
    (the only time payloads cross shards: model fitting needs them anyway) plus
    one psum of the counts."""
    cap_s = jax.tree_util.tree_leaves(st.items)[0].shape[0]
    _, take_partial, _ = lt.partial_draw(key, st.weight)
    mask_s = jnp.arange(cap_s) < st.nfull
    items = jax.tree_util.tree_map(
        lambda a: jax.lax.all_gather(a, AXIS, tiled=True), st.items
    )
    mask = jax.lax.all_gather(mask_s, AXIS, tiled=True)
    items = jax.tree_util.tree_map(
        lambda g, p: jnp.concatenate([g, p[None]], axis=0), items, st.partial_item
    )
    mask = jnp.concatenate([mask, take_partial[None]])
    size = jax.lax.psum(st.nfull, AXIS) + take_partial.astype(jnp.int32)
    return items, mask, size


def drtbs_global_size(key: jax.Array, st: DRTBSShard) -> jax.Array:
    """|S_t| as :func:`drtbs_realize_global` would report it (same key => same
    partial-item draw), without touching the item buffers: the cheap size-only
    path the fused loop logs on non-retrain ticks."""
    _, take_partial, _ = lt.partial_draw(key, st.weight)
    return jax.lax.psum(st.nfull, AXIS) + take_partial.astype(jnp.int32)


def buffer_realize_global(state):
    """Global view of a per-shard :class:`repro.core.simple.BufferState` (the
    D-T-TBS path): all-gathered buffers + prefix masks, psum'd size. Replicated
    on every shard; deterministic membership, so no key."""
    from . import simple

    mask_s, _ = simple.realize_all(state)
    items = jax.tree_util.tree_map(
        lambda a: jax.lax.all_gather(a, AXIS, tiled=True), state.items
    )
    mask = jax.lax.all_gather(mask_s, AXIS, tiled=True)
    size = jax.lax.psum(state.count, AXIS)
    return items, mask, size


def gather_tree(tree: Any, axis: str = AXIS) -> Any:
    """Replicated global snapshot of per-shard state: every leaf gains a
    leading [S] axis (scalars become [S] vectors). ``tree_map(lambda a: a[me],
    snapshot)`` inside shard_map restores the per-shard view bit-exactly, which
    is how the per-tick sharded driver round-trips state between dispatches."""
    return jax.tree_util.tree_map(lambda a: jax.lax.all_gather(a, axis), tree)


# ---------------------------------------------------------------------------
# D-T-TBS: embarrassingly parallel (paper Sec. 5.1)
# ---------------------------------------------------------------------------
def dttbs_shard_step(key, state, batch_items, bcount_local, *, p, q):
    """Each shard runs T-TBS on its own partition -- zero coordination."""
    from . import simple

    me = jax.lax.axis_index(AXIS)
    return simple.ttbs_step(
        jax.random.fold_in(key, me), state, batch_items, bcount_local, p=p, q=q
    )


# ---------------------------------------------------------------------------
# mesh-level wrappers
# ---------------------------------------------------------------------------
def make_drtbs_step(mesh, item_spec, *, n: int, lam: float, axis: str = AXIS):
    """Build a pjit-able whole-mesh D-R-TBS step via shard_map over ``axis``.

    item_spec: PartitionSpec for item buffers' leading (global slot) dim."""
    from jax.sharding import PartitionSpec as P

    def sharded(key, st, batch_items, bcounts):
        return drtbs_shard_step(key, st, batch_items, bcounts, n=n, lam=lam)

    state_specs = DRTBSShard(
        items=item_spec,
        nfull=P(axis),
        partial_item=P(),
        weight=P(),
        total_weight=P(),
        overflow=P(axis),
    )
    return jax.jit(
        jax.shard_map(
            sharded,
            mesh=mesh,
            in_specs=(P(), state_specs, item_spec, P(axis)),
            out_specs=state_specs,
            check_vma=False,
        )
    )
