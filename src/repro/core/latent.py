"""Latent ("fractional") samples -- the core data structure of R-TBS (paper Sec. 4.1).

A latent sample L = (A, pi, C) holds floor(C) "full" items and at most one
"partial" item; realizing a sample S from L includes every full item and the
partial item with probability frac(C), so E[|S|] = C exactly (paper eq. (3)).

Fixed-shape JAX encoding (jit/scan/shard_map-safe):
  * ``items``   -- a pytree whose leaves have leading dim ``cap``
  * ``nfull``   -- int32, floor(C): slots [0, nfull) hold the full items
  * ``weight``  -- float32, the sample weight C; if frac(C) > 0 the partial item
                   lives at slot ``nfull``; slots above are garbage.

The key operator is :func:`downsample` (paper Algorithm 3), which rescales every
item's inclusion probability by exactly C'/C (Theorem 4.1). We implement it as a
branch-selected gather: each branch produces a slot-index map ``src`` (new slot ->
old slot), exposed on its own as :func:`downsample_map` so the fused R-TBS step
can compose a whole tick's rewrites into ONE two-source payload pass (the
``tbs_step`` Pallas kernel; DESIGN.md Sec. 11). :func:`realize_compact` packs a
realized sample to the buffer head via the ``reservoir_compact`` kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from . import rng


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def floor_frac(c):
    """(floor(C) as int32, frac(C) in [0,1)) with float-noise clipping."""
    c = _f32(c)
    k = jnp.floor(c)
    return k.astype(jnp.int32), jnp.clip(c - k, 0.0, 1.0)


def partial_draw(key: jax.Array, weight) -> tuple[jax.Array, jax.Array, jax.Array]:
    """THE fractional-item realization draw: (floor(C), take_partial, frac(C)).

    ``take_partial`` is True w.p. frac(C) (False when frac == 0). Every
    realization path -- :func:`realize`, the Samplers' size-only fast paths,
    and the distributed per-shard/global realizes -- MUST consume the key
    through this one helper so ``mask.sum() == size`` and size == extract's
    ``view.size`` stay structural invariants rather than five copies of the
    same bernoulli."""
    k, f = floor_frac(weight)
    take = jax.random.bernoulli(key, f) & (f > 0)
    return k, take, f


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Latent:
    """Latent fractional sample; see module docstring for slot conventions."""

    items: Any            # pytree, leaves [cap, ...]
    nfull: jax.Array      # int32 scalar
    weight: jax.Array     # float32 scalar (C)

    @property
    def cap(self) -> int:
        return jax.tree_util.tree_leaves(self.items)[0].shape[0]

    def has_partial(self) -> jax.Array:
        _, f = floor_frac(self.weight)
        return f > 0


def gather(items: Any, idx: jax.Array) -> Any:
    """tree-wide items[idx] (fill_value semantics unused: callers keep idx in range)."""
    return jax.tree_util.tree_map(lambda a: jnp.take(a, idx, axis=0), items)


def make_empty(item_proto: Any, cap: int) -> Latent:
    """Empty latent sample with capacity ``cap``; item_proto gives leaf shapes/dtypes
    (a pytree of arrays or ShapeDtypeStructs describing ONE item)."""
    items = jax.tree_util.tree_map(
        lambda p: jnp.zeros((cap,) + tuple(p.shape), p.dtype), item_proto
    )
    return Latent(items=items, nfull=jnp.int32(0), weight=jnp.float32(0.0))


def realize(key: jax.Array, lat: Latent) -> tuple[jax.Array, jax.Array]:
    """Draw S from L per paper eq. (2): returns (mask[cap] bool, size int32).

    Full slots are always included; the partial slot is included w.p. frac(C).
    """
    k, take_partial, _ = partial_draw(key, lat.weight)
    slot = jnp.arange(lat.cap, dtype=jnp.int32)
    mask = (slot < k) | ((slot == k) & take_partial)
    return mask, k + take_partial.astype(jnp.int32)


def compact_items(items: Any, mask: jax.Array) -> Any:
    """Tree-wide stable pack of the masked rows to the buffer head via
    :func:`repro.kernels.reservoir_compact.ops.reservoir_compact` (Pallas
    row gather on TPU, jnp oracle elsewhere). Leaves may have any trailing
    shape; rows past ``mask.sum()`` come back zero. THE pack primitive behind
    every materialization path (:func:`realize_compact` here,
    :func:`repro.core.api.materialize_view` and through it the distributed
    ``extract_global`` closures)."""
    from repro.kernels.reservoir_compact import ops as rc_ops

    return rc_ops.reservoir_compact(items, mask)[0]


def realize_compact(key: jax.Array, lat: Latent) -> tuple[Any, jax.Array]:
    """Materialize S: draw the realization mask and pack the selected rows to
    the buffer head (:func:`compact_items`). Returns ``(items, size)`` where
    item leaves are [cap, ...] with rows [0, size) the sample and the rest
    zero. Consumes the key exactly like :func:`realize` (same partial draw).
    """
    mask, size = realize(key, lat)
    return compact_items(lat.items, mask), size


def _downsample_map_small(key: jax.Array, cap: int, k, f, kp, fp, nw, cw,
                          D: int) -> jax.Array:
    """Delete-complement construction of the Alg. 3 slot map: O(D) random
    work instead of a full-domain PRP evaluation (DESIGN.md Sec. 12).

    Valid when at most ``D`` full items leave the full set (``k - kp <= D``)
    or when no full item is deleted at all (kp == 0 / kp == k, which need
    only ONE uniform full-slot draw). Instead of drawing a length-``cap``
    prefix permutation and *keeping* its head, delete the complement: repeat
    ``d`` times "remove a uniform slot of the current prefix [0, m) by
    moving the item at m-1 into it" -- the classic swap-with-last deletion,
    each step uniform over the remaining items, so the surviving set is an
    exact uniform (k-d)-subset. A final uniform swap positions the new
    partial item uniformly among the survivors. Full items are exchangeable
    beyond the full/partial split, so survivor ORDER is free -- exactly the
    freedom the full-permutation construction also exploits.

    Same distribution as the ``prefix_permutation_fast`` path (Theorem 4.1
    re-verified in tests), different RNG stream.
    """
    kperm, ku = jax.random.split(key)
    u = jax.random.uniform(ku, dtype=jnp.float32)
    # D victim draws + one uniform-full draw + one survivor draw, all from
    # raw bits (modulo bias <= m / 2^32: orders below the MC tolerance of
    # every Thm 4.1/4.2 check, same budget as rng.swap_or_not)
    rb = jax.random.bits(kperm, (D + 2,), jnp.uint32)
    slot = jnp.arange(cap, dtype=jnp.int32)
    identity = slot
    safe_c = jnp.maximum(cw, 1e-30)

    def unif(bits, m):  # uniform int32 in [0, m), m >= 1 traced
        return (bits % jnp.maximum(m, 1).astype(jnp.uint32)).astype(jnp.int32)

    unif_full = unif(rb[D], k)                    # one uniform full slot

    # ---- case kp == 0 (paper Alg.3 lines 5-8): no loop needed ----
    keep_old = u <= f / safe_c
    src_case0 = identity.at[0].set(jnp.where(keep_old, k, unif_full))

    # ---- case 0 < kp == k (lines 9-11): swap partial <-> uniform full ----
    rho = (1.0 - (nw / safe_c) * f) / jnp.maximum(1.0 - fp, 1e-30)
    do_swap = u > rho
    src_swap = identity.at[unif_full].set(k).at[k].set(unif_full)
    src_case_eq = jnp.where(do_swap, src_swap, identity)

    # ---- case 0 < kp < k (lines 12-18): delete-complement ----
    p1 = (nw / safe_c) * f
    b1 = u <= p1
    # branch1 keeps kp of the k fulls (old partial joins as a full);
    # branch2 keeps kp + 1 (one of them becomes the new partial)
    d = jnp.where(b1, k - kp, k - kp - 1)

    def delete(i, src):
        m = k - i                                  # current prefix length
        v = unif(rb[jnp.minimum(i, D - 1)], m)
        return src.at[v].set(src[jnp.clip(m - 1, 0, cap - 1)])

    # dynamic trip count: only the ACTUAL deletions run (a decay tick trims
    # ~(1-d_t)C items, typically far below the static bound D); zero trips
    # for the loop-free cases
    trips = jnp.where((kp > 0) & (kp < k), jnp.clip(d, 0, D), 0)
    src_lt = jax.lax.fori_loop(0, trips, delete, identity)
    # branch2: survivors at [0, kp+1); uniform one of them becomes the
    # partial at slot kp (swap j <-> kp)
    j2 = unif(rb[D + 1], kp + 1)
    sj2, sk2 = src_lt[j2], src_lt[jnp.minimum(kp, cap - 1)]
    src_b2 = src_lt.at[kp].set(sj2).at[j2].set(sk2)
    # branch1: survivors at [0, kp); uniform one becomes the partial at slot
    # kp, its hole filled by the last survivor, old partial lands at kp-1
    kp_m1 = jnp.maximum(kp - 1, 0)
    j1 = unif(rb[D + 1], kp)
    sj1, slast = src_lt[j1], src_lt[kp_m1]
    src_b1 = src_lt.at[kp].set(sj1).at[j1].set(slast).at[kp_m1].set(k)
    src_case_lt = jnp.where(b1, src_b1, src_b2)

    src = jnp.where(
        kp == 0,
        src_case0,
        jnp.where(kp == k, src_case_eq, src_case_lt),
    )
    return jnp.where(nw >= cw, identity, src)


def downsample_map(
    key: jax.Array, cap: int, nfull, weight, new_weight, *,
    exact: bool = False, max_deleted: int | None = None
) -> jax.Array:
    """Slot-index map of paper Algorithm 3: ``src[cap]`` (new slot -> old slot)
    such that gathering the old buffer through ``src`` realizes the
    C -> C' downsample (Theorem 4.1). Map-only form so the fused R-TBS step
    (:func:`repro.core.rtbs.step`) can compose several buffer rewrites into a
    single payload pass; :func:`downsample` is map + gather.

    Randomness defaults to the argsort-free
    :func:`repro.core.rng.prefix_permutation_fast`; ``exact=True`` restores
    the exact-but-O(cap log cap) argsort draw (the pre-fused RNG stream --
    see DESIGN.md Sec. 11 -- used by the reference step and parity tests).

    ``max_deleted`` (static) enables the delete-complement fast path
    (:func:`_downsample_map_small`): whenever at most ``max_deleted`` full
    items leave the full set -- the common fill-up-phase case, where each
    tick's decay trims a sliver off a large sample -- the map is built with
    O(max_deleted) random work under a ``lax.cond`` instead of evaluating
    the PRP over the whole domain; larger trims fall back to the full
    construction at runtime. Identical distribution either way (different
    RNG stream); ignored when ``exact=True``.
    """
    del nfull  # the map depends on floor(weight) only; kept for signature clarity
    cw = _f32(weight)
    nw = jnp.minimum(_f32(new_weight), cw)
    k, f = floor_frac(cw)
    kp, fp = floor_frac(nw)

    if not exact and max_deleted is not None and max_deleted > 0:
        D = min(int(max_deleted), cap)
        can_fast = (kp == 0) | (kp == k) | (k - kp <= D)
        return jax.lax.cond(
            can_fast,
            lambda: _downsample_map_small(key, cap, k, f, kp, fp, nw, cw, D),
            lambda: _downsample_map_full(key, cap, k, f, kp, fp, nw, cw,
                                         exact),
        )
    return _downsample_map_full(key, cap, k, f, kp, fp, nw, cw, exact)


def _downsample_map_full(key, cap: int, k, f, kp, fp, nw, cw,
                         exact: bool) -> jax.Array:
    """The full-domain construction: one length-``cap`` prefix permutation,
    branch maps selected with jnp.where."""
    kperm, ku = jax.random.split(key)
    perm_fn = rng.prefix_permutation if exact else rng.prefix_permutation_fast
    perm = perm_fn(kperm, cap, k)  # random order over full slots
    u = jax.random.uniform(ku, dtype=jnp.float32)
    slot = jnp.arange(cap, dtype=jnp.int32)
    identity = slot

    safe_c = jnp.maximum(cw, 1e-30)

    # ---- case kp == 0 (no full items retained; paper Alg.3 lines 5-8) ----
    # keep old partial as partial w.p. f/C, else a uniform full item becomes partial.
    keep_old = u <= f / safe_c
    src_case0 = identity.at[0].set(jnp.where(keep_old, k, perm[0]))

    # ---- case 0 < kp == k (no items deleted; lines 9-11) ----
    # swap partial<->random-full w.p. 1-rho, rho = (1-(C'/C)f)/(1-f').
    rho = (1.0 - (nw / safe_c) * f) / jnp.maximum(1.0 - fp, 1e-30)
    do_swap = u > rho
    swap_a = perm[0]          # the full slot that becomes partial
    src_swap = identity.at[swap_a].set(k).at[k].set(swap_a)
    src_case_eq = jnp.where(do_swap, src_swap, identity)

    # ---- case 0 < kp < k (items deleted; lines 12-18) ----
    # branch1 (w.p. (C'/C)f): old partial becomes full; fulls = {pi} + perm[:kp-1];
    #                          partial = perm[kp-1].
    # branch2 (else):          fulls = perm[:kp]; partial = perm[kp].
    p1 = (nw / safe_c) * f
    b1 = u <= p1
    kp_m1 = jnp.maximum(kp - 1, 0)
    # branch1 map: new slot j<kp-1 -> perm[j]; slot kp-1 -> k (old partial);
    #              slot kp -> perm[kp-1]
    src_b1 = jnp.where(slot < kp_m1, perm[slot], identity)
    src_b1 = src_b1.at[kp_m1].set(k)
    src_b1 = src_b1.at[kp].set(perm[kp_m1])
    # branch2 map: new slot j<kp -> perm[j]; slot kp -> perm[kp]
    src_b2 = jnp.where(slot <= kp, perm[jnp.minimum(slot, cap - 1)], identity)
    src_case_lt = jnp.where(b1, src_b1, src_b2)

    src = jnp.where(
        kp == 0,
        src_case0,
        jnp.where(kp == k, src_case_eq, src_case_lt),
    )
    # C' == C shortcut (also covers the k==0,f==0 empty edge): identity.
    return jnp.where(nw >= cw, identity, src)


def downsample(key: jax.Array, lat: Latent, new_weight, *, exact: bool = False,
               max_deleted: int | None = None) -> Latent:
    """Paper Algorithm 3: rescale inclusion probabilities by C'/C (Theorem 4.1).

    Requires 0 < C' <= C (C' == C is an identity shortcut). All branches are
    computed as slot-index maps (:func:`downsample_map`, which also documents
    ``max_deleted``) and selected with jnp.where, so the whole operation is
    one gather regardless of branch.
    """
    cw = _f32(lat.weight)
    nw = jnp.minimum(_f32(new_weight), cw)
    kp, _ = floor_frac(nw)
    src = downsample_map(key, lat.cap, lat.nfull, lat.weight, new_weight,
                         exact=exact, max_deleted=max_deleted)
    new_items = gather(lat.items, src)
    return Latent(items=new_items, nfull=kp, weight=nw)


def insert_full(lat: Latent, batch_items: Any, bcount) -> Latent:
    """Insert ``bcount`` batch items (valid prefix of ``batch_items``) as FULL items,
    preserving the partial item (relocated above the inserted block).

    Paper Alg. 2 lines 9/20: arriving items are accepted with probability 1.
    Caller guarantees nfull + bcount + 1 <= cap.
    """
    bcap = jax.tree_util.tree_leaves(batch_items)[0].shape[0]
    k = lat.nfull
    bcount = jnp.asarray(bcount, jnp.int32)

    # read the (possible) partial payload BEFORE scattering over its slot
    partial_payload = jax.tree_util.tree_map(lambda a: a[k], lat.items)

    bpos = jnp.arange(bcap, dtype=jnp.int32)
    dest = jnp.where(bpos < bcount, k + bpos, lat.cap)  # cap => dropped
    items = jax.tree_util.tree_map(
        lambda a, b: a.at[dest].set(b, mode="drop"), lat.items, batch_items
    )
    # relocate the partial item to the new top slot
    items = jax.tree_util.tree_map(
        lambda a, p: a.at[k + bcount].set(
            jnp.where(_bcast(lat.has_partial(), p), p, a[k + bcount])
        ),
        items,
        jax.tree_util.tree_map(lambda p: p, partial_payload),
    )
    return Latent(
        items=items,
        nfull=k + bcount,
        weight=lat.weight + bcount.astype(jnp.float32),
    )


def _bcast(pred, like):
    """Broadcast a scalar bool against an item payload leaf."""
    return jnp.reshape(pred, (1,) * like.ndim) if like.ndim else pred


def concat_items(a: Any, b: Any) -> Any:
    return jax.tree_util.tree_map(lambda x, y: jnp.concatenate([x, y], axis=0), a, b)


def truncate_items(items: Any, cap: int) -> Any:
    return jax.tree_util.tree_map(lambda x: x[:cap], items)
