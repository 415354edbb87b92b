"""repro.bank -- keyed multi-tenant sampler banks (DESIGN.md Sec. 13):

  * routing: segment bookkeeping vs a numpy reference, static per-key bcap
    overflow accounting, invalid-row exclusion;
  * the banked payload kernel's grid dimension on the interpret route is
    bit-identical to the vmap-of-ref parity oracle;
  * a bank tick is BIT-identical to vmapping the standalone fused step over
    the routed sub-batches (rtbs and ttbs), untouched keys taking exactly
    the pure-decay pending multiply;
  * per-key marginal equivalence (the acceptance criterion): key k's
    reservoir in a K-key bank under a Zipf keyed stream is distributionally
    identical to a standalone R-TBS fed only key-k arrivals -- lazily
    (wall-clock dt gaps) or eagerly (empty ticks) -- and all three match
    the Theorem 4.1/4.2 inclusion probabilities;
  * extract/size consistency (mask.sum() == size, size path == extract
    sizes) including pending-decay settling, K >= 4096 in one jitted scan,
    and the bank-level manage loops (shared pool, per-key farm, per-key
    controller, key-sharded mesh).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import decay as dk
from repro.bank import make_bank, route, subbatches
from repro.core import latent as lt
from repro.core import rtbs, simple
from repro.data.streams import KeyedStream, LinRegStream
from repro.kernels.tbs_step import ops as tbs_ops
from repro.kernels.tbs_step import ref as tbs_ref
from repro.manage import (
    make_bank_run_loop,
    make_model,
    make_sharded_bank_loop,
    materialize_stream,
    shard_keyed_stream,
)

PROTO = jax.ShapeDtypeStruct((2,), jnp.float32)


def _zipf_keys(rs, K, shape, alpha=1.2):
    w = (1.0 + np.arange(K)) ** -alpha
    return rs.choice(K, size=shape, p=w / w.sum()).astype(np.int32)


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------
def test_route_matches_numpy_reference():
    K, b, bcap = 11, 32, 4
    rs = np.random.RandomState(0)
    keys = rs.randint(0, K, size=b).astype(np.int32)
    bcount = 23
    r = route(jnp.asarray(keys), jnp.int32(bcount), num_keys=K, bcap=bcap)

    valid = keys[:bcount]
    uniq = np.unique(valid)
    nt = int(r.ntouched)
    assert nt == len(uniq)
    np.testing.assert_array_equal(np.asarray(r.touched)[:nt], uniq)
    assert (np.asarray(r.touched)[nt:] == K).all()
    order = np.asarray(r.order)
    sorted_keys = np.where(np.arange(b) < bcount, keys, K)[order]
    # stable key sort: ascending keys, arrival order within a key
    assert (np.diff(sorted_keys) >= 0).all()
    total_drop = 0
    for i, k in enumerate(uniq):
        raw = int((valid == k).sum())
        want = min(raw, bcap)
        assert int(r.counts[i]) == want
        assert int(r.dropped[i]) == raw - want
        total_drop += raw - want
        s = int(r.starts[i])
        seg = order[s:s + want]
        np.testing.assert_array_equal(
            seg, np.nonzero(valid == k)[0][:want]
        )  # first-bcap in arrival order
    assert int(r.overflow) == total_drop
    # rows past ntouched carry zero counts
    assert (np.asarray(r.counts)[nt:] == 0).all()


def test_route_discards_out_of_range_keys():
    """Out-of-range ids are dropped and counted, NEVER clipped onto a real
    tenant (clipping would silently corrupt key num_keys-1's reservoir)."""
    K, bcap = 4, 4
    keys = jnp.asarray([0, 7, 3, -1, 3, K, 2, 1], jnp.int32)
    r = route(keys, jnp.int32(6), num_keys=K, bcap=bcap)  # row 6,7 invalid
    assert int(r.invalid) == 3            # 7, -1, K within the valid prefix
    nt = int(r.ntouched)
    np.testing.assert_array_equal(np.asarray(r.touched)[:nt], [0, 3])
    np.testing.assert_array_equal(np.asarray(r.counts)[:nt], [1, 2])
    assert int(r.overflow) == 0


def test_subbatches_windows_are_prefix_valid():
    K, b, bcap = 5, 16, 3
    rs = np.random.RandomState(1)
    keys = rs.randint(0, K, size=b).astype(np.int32)
    payload = rs.randn(b, 2).astype(np.float32)
    r = route(jnp.asarray(keys), jnp.int32(b), num_keys=K, bcap=bcap)
    sub = subbatches(r, jnp.asarray(payload), bcap=bcap)
    for i in range(int(r.ntouched)):
        k = int(r.touched[i])
        c = int(r.counts[i])
        rows = np.nonzero(keys == k)[0][:c]
        np.testing.assert_array_equal(np.asarray(sub)[i, :c], payload[rows])


# ---------------------------------------------------------------------------
# the banked kernel grid dimension: interpret route == vmap-of-ref oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("T,cap,bcap,D", [(5, 16, 8, 4), (3, 33, 5, 2)])
def test_banked_apply_interpret_matches_vmap_of_ref(T, cap, bcap, D):
    rs = np.random.RandomState(2)
    items = jnp.asarray(rs.randn(T, cap, D), jnp.float32)
    batch = jnp.asarray(rs.randn(T, bcap, D), jnp.float32)
    src = jnp.asarray(rs.randint(0, cap + bcap, size=(T, cap)), jnp.int32)
    want = tbs_ref.apply_banked_ref(items, batch, src)
    got = tbs_ops.tbs_step_apply_banked(items, batch, src, impl="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_ref = tbs_ops.tbs_step_apply_banked(items, batch, src, impl="ref")
    np.testing.assert_array_equal(np.asarray(got_ref), np.asarray(want))
    # int payloads widen and cast back
    ii = jnp.asarray(rs.randint(-5, 5, size=(T, cap, D)), jnp.int8)
    bb = jnp.asarray(rs.randint(-5, 5, size=(T, bcap, D)), jnp.int8)
    gi = tbs_ops.tbs_step_apply_banked(ii, bb, src, impl="interpret")
    wi = tbs_ref.apply_banked_ref(
        ii.astype(jnp.int32), bb.astype(jnp.int32), src
    )
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    assert gi.dtype == jnp.int8


# ---------------------------------------------------------------------------
# bank tick == vmap-of-single over the routed sub-batches, bit for bit
# ---------------------------------------------------------------------------
def test_bank_rtbs_step_bit_parity_with_vmap_of_single():
    K, n, bcap, b, T = 8, 5, 4, 16, 5
    lam = 0.3
    d = jnp.float32(math.exp(-lam))
    bank = make_bank("rtbs", num_keys=K, n=n, lam=lam, bcap=bcap)
    bstep = jax.jit(bank.step)
    # both sides are compiled programs: the reference is ONE jitted vmap of
    # the single-reservoir step over the touched keys, so the weight update
    # W <- d W + c is rounded as in the fused bank program (an eager
    # per-key step rounds it differently)
    ref_step = jax.jit(jax.vmap(
        lambda k, s, b, c, p: rtbs.step(k, s, b, c, n=n, decay=p * d)))
    st = bank.init(PROTO)
    rs = np.random.RandomState(3)
    key0 = jax.random.key(7)
    for t in range(T):
        keys = jnp.asarray(rs.randint(0, K, size=b), jnp.int32)
        payload = jnp.asarray(rs.randn(b, 2), jnp.float32)
        kt = jax.random.fold_in(key0, t)

        pend = np.array(st.pending * d, np.float32)
        r = route(keys, jnp.int32(b), num_keys=K, bcap=bcap)
        sub = subbatches(r, payload, bcap=bcap)
        exp_items = np.asarray(jax.tree_util.tree_leaves(st.items)[0]).copy()
        nfull = np.asarray(st.nfull).copy()
        C = np.asarray(st.weight).copy()
        W = np.asarray(st.total_weight).copy()
        nt = int(r.ntouched)
        ids = np.asarray(r.touched)[:nt]
        st_t = rtbs.RTBSState(
            lat=lt.Latent(items=jnp.asarray(exp_items[ids]),
                          nfull=jnp.asarray(nfull[ids]),
                          weight=jnp.asarray(C[ids])),
            total_weight=jnp.asarray(W[ids]),
        )
        out = ref_step(
            jax.vmap(lambda k_id: jax.random.fold_in(kt, k_id))(
                jnp.asarray(ids)),
            st_t, jax.tree_util.tree_map(lambda a: a[:nt], sub),
            r.counts[:nt], st.pending[ids],
        )
        exp_items[ids] = np.asarray(out.lat.items)
        nfull[ids] = np.asarray(out.lat.nfull)
        C[ids] = np.asarray(out.lat.weight)
        W[ids] = np.asarray(out.total_weight)
        pend[ids] = 1.0

        st = bstep(kt, st, keys, payload, jnp.int32(b))
        np.testing.assert_array_equal(np.asarray(st.items), exp_items)
        np.testing.assert_array_equal(np.asarray(st.nfull), nfull)
        np.testing.assert_array_equal(np.asarray(st.weight), C)
        np.testing.assert_array_equal(np.asarray(st.total_weight), W)
        np.testing.assert_array_equal(np.asarray(st.pending), pend)
    # something actually decayed lazily at some point
    assert (np.asarray(st.pending) <= 1.0).all()


def test_bank_ttbs_step_bit_parity_with_vmap_of_single():
    K, n, cap, bcap, b, T = 6, 4, 8, 4, 12, 5
    lam = 0.3
    batch_size = 2.0
    d = jnp.float32(math.exp(-lam))
    q = jnp.float32(np.clip(n * (1.0 - np.float32(math.exp(-lam)))
                            / np.float32(batch_size), 0.0, 1.0))
    bank = make_bank("ttbs", num_keys=K, n=n, lam=lam,
                     batch_size=batch_size, cap=cap, bcap=bcap)
    bstep = jax.jit(bank.step)
    st = bank.init(PROTO)
    rs = np.random.RandomState(4)
    key0 = jax.random.key(11)
    for t in range(T):
        keys = jnp.asarray(rs.randint(0, K, size=b), jnp.int32)
        payload = jnp.asarray(rs.randn(b, 2), jnp.float32)
        kt = jax.random.fold_in(key0, t)

        pend = np.array(st.pending * d, np.float32)
        r = route(keys, jnp.int32(b), num_keys=K, bcap=bcap)
        sub = subbatches(r, payload, bcap=bcap)
        exp_items = np.asarray(jax.tree_util.tree_leaves(st.items)[0]).copy()
        cnt = np.asarray(st.nfull).copy()
        W = np.asarray(st.total_weight).copy()
        for i in range(int(r.ntouched)):
            k_id = int(r.touched[i])
            bs = simple.BufferState(
                items=jnp.asarray(exp_items[k_id]),
                count=jnp.int32(cnt[k_id]),
                total_weight=jnp.float32(W[k_id]),
                overflow=jnp.int32(0),
            )
            out = simple.ttbs_step(
                jax.random.fold_in(kt, k_id), bs,
                jax.tree_util.tree_map(lambda a: a[i], sub), r.counts[i],
                p=jnp.float32(pend[k_id]), q=q,
            )
            exp_items[k_id] = np.asarray(out.items)
            cnt[k_id] = int(out.count)
            W[k_id] = np.float32(out.total_weight)
            pend[k_id] = 1.0

        st = bstep(kt, st, keys, payload, jnp.int32(b))
        np.testing.assert_array_equal(np.asarray(st.items), exp_items)
        np.testing.assert_array_equal(np.asarray(st.nfull), cnt)
        # W is bookkeeping-only for T-TBS (never read by the algorithm);
        # XLA's fma contraction of p*W + b differs between the bank's
        # vectorized compile and the scalar step, so allow 1 ulp there
        np.testing.assert_allclose(np.asarray(st.total_weight), W,
                                   rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(st.pending), pend)


# ---------------------------------------------------------------------------
# per-key marginal equivalence: the acceptance criterion
# ---------------------------------------------------------------------------
def test_bank_per_key_theorem_4_1_equivalence():
    """Key k's reservoir in a K-key bank under a non-trivial Zipf keyed
    stream is distributionally identical to a standalone R-TBS fed only
    key-k arrivals -- Theorem 4.1/4.2 re-run per key.

    Three executions over the SAME fixed keyed stream, Monte-Carlo'd over
    sampler randomness: (a) the fused bank (lazy pending decay, composed
    tick maps), (b) a standalone sampler fed only the key's arrival ticks
    with wall-clock gaps (``decay = e^{-lam dt}``), (c) a standalone
    sampler stepped EVERY tick (empty batches on non-arrival ticks) -- the
    eager chain the lazy composition must match. All three must reproduce
    the analytic inclusion probabilities Pr[i in S] = (C_T/W_T) e^{-lam a}
    for item age a, for a saturated (popular) and an unsaturated (rare,
    irregular) key."""
    K, n, T, b, lam, trials = 8, 6, 8, 16, 0.25, 10000
    bcap = b  # no routing drops: every arrival reaches its reservoir
    d = math.exp(-lam)
    rs = np.random.RandomState(5)
    keys = _zipf_keys(rs, K, (T, b))
    # payload encodes the arrival tick: (t+1)*100 + row
    payload = (np.arange(1, T + 1)[:, None] * 100
               + np.arange(b)[None, :]).astype(np.float32)
    payload = np.repeat(payload[:, :, None], 2, axis=2)
    keys_j, payload_j = jnp.asarray(keys), jnp.asarray(payload)

    bank = make_bank("rtbs", num_keys=K, n=n, lam=lam, bcap=bcap)

    def run_bank(trial_key, focal):
        st = bank.init(PROTO)

        def body(c, t):
            return bank.step(jax.random.fold_in(trial_key, t), c,
                             keys_j[t], payload_j[t], jnp.int32(b)), None

        st, _ = jax.lax.scan(body, st, jnp.arange(T))
        view = bank.extract(jax.random.fold_in(trial_key, 777), st,
                            jnp.asarray([focal]))
        ticks = (view.items[0, :, 0] // 100).astype(jnp.int32)
        counts = jnp.zeros((T + 1,), jnp.float32).at[ticks].add(
            view.mask[0].astype(jnp.float32), mode="drop")
        return counts[1:]

    def run_standalone(trial_key, focal, lazy):
        """Feed only key-``focal``'s arrivals; ``lazy`` composes gaps into
        one decay factor (dt form), else steps every tick (eager chain)."""
        st = rtbs.init(PROTO, n)
        arrived = keys == focal        # [T, b] (numpy, fixed stream)
        prev = -1
        for t in range(T):
            c_t = int(arrived[t].sum())
            if not lazy or c_t > 0:
                gap = t - prev
                rows = np.nonzero(arrived[t])[0]
                bt = np.zeros((bcap, 2), np.float32)
                bt[:c_t] = payload[t, rows]
                st = rtbs.step(
                    jax.random.fold_in(jax.random.fold_in(trial_key, t),
                                       focal),
                    st, jnp.asarray(bt), jnp.int32(c_t), n=n,
                    decay=jnp.float32(d ** (gap if lazy else 1)),
                )
                prev = t
        # trailing gap settles exactly as the bank extract does
        kk = jax.random.fold_in(jax.random.fold_in(trial_key, 777), focal)
        k_ds, k_re = jax.random.split(kk)
        w_eff = jnp.float32(d ** (T - 1 - prev)) * st.total_weight
        lat = lt.downsample(k_ds, st.lat,
                            jnp.minimum(st.lat.weight, w_eff),
                            max_deleted=bcap)
        mask, _ = lt.realize(k_re, lat)
        ticks = (lat.items[:, 0] // 100).astype(jnp.int32)
        counts = jnp.zeros((T + 1,), jnp.float32).at[ticks].add(
            mask.astype(jnp.float32), mode="drop")
        return counts[1:]

    tkeys = jax.random.split(jax.random.key(0), trials)
    for focal in (0, 5):               # popular/saturated and rare/irregular
        c = (keys == focal).sum(axis=1).astype(np.float64)  # arrivals/tick
        assert c.sum() > 0
        if focal == 5:
            assert (c == 0).any()      # genuinely irregular: skipped ticks
        W = 0.0
        for t in range(T):
            W = d * W + c[t]
        C = min(n, W)
        expect = np.array([
            (C / W) * d ** (T - 1 - t) if c[t] else 0.0 for t in range(T)
        ])

        got = {}
        got["bank"] = np.asarray(
            jax.jit(jax.vmap(lambda k: run_bank(k, focal)))(tkeys)
        ).mean(axis=0)
        lazy_fn = jax.jit(jax.vmap(lambda k: run_standalone(k, focal, True)))
        eager_fn = jax.jit(jax.vmap(lambda k: run_standalone(k, focal,
                                                             False)))
        got["lazy"] = np.asarray(lazy_fn(tkeys)).mean(axis=0)
        got["eager"] = np.asarray(eager_fn(tkeys)).mean(axis=0)
        denom = np.where(c > 0, c, 1.0)
        for name, counts in got.items():
            probs = counts / denom
            for t in range(T):
                assert abs(probs[t] - expect[t]) < 0.03, (
                    focal, name, t, probs[t], expect[t]
                )
        # the bank and the dt-fed standalone agree with each other too
        np.testing.assert_allclose(got["bank"] / denom,
                                   got["lazy"] / denom, atol=0.03)


# ---------------------------------------------------------------------------
# extract / size / overflow / validation
# ---------------------------------------------------------------------------
def test_bank_extract_size_consistent_and_settles_pending():
    K, n, bcap, b = 12, 6, 8, 24
    bank = make_bank("rtbs", num_keys=K, n=n, lam=0.4, bcap=bcap)
    bstep = jax.jit(bank.step)
    st = bank.init(PROTO)
    rs = np.random.RandomState(6)
    key0 = jax.random.key(2)
    for t in range(6):
        keys = jnp.asarray(_zipf_keys(rs, K, b), jnp.int32)
        st = bstep(jax.random.fold_in(key0, t), st, keys,
                   jnp.asarray(rs.randn(b, 2), jnp.float32),
                   jnp.int32(b))
    # several more empty ticks: pure pending decay, NO payload movement
    items_before = np.asarray(st.items).copy()
    for t in range(6, 10):
        st = bstep(jax.random.fold_in(key0, t), st,
                   jnp.zeros((b,), jnp.int32),
                   jnp.zeros((b, 2), jnp.float32), jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(st.items), items_before)
    assert (np.asarray(st.pending) < 1.0).all()

    all_keys = jnp.arange(K)
    view = jax.jit(bank.extract)(jax.random.key(9), st, all_keys)
    sizes = jax.jit(bank.size)(jax.random.key(9), st, all_keys)
    np.testing.assert_array_equal(np.asarray(view.mask.sum(axis=1)),
                                  np.asarray(view.size))
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(view.size))
    # the deferred decay is visible: effective sizes are bounded by the
    # decayed weight, not the stored one
    w_eff = np.asarray(st.pending * st.total_weight)
    assert (np.asarray(sizes) <= np.ceil(np.minimum(n, w_eff) + 1e-6)).all()
    assert (np.asarray(sizes) <= n).all()

    # ttbs: same consistency contract
    bank2 = make_bank("ttbs", num_keys=K, n=4, lam=0.4, batch_size=2.0,
                      bcap=bcap)
    bstep2 = jax.jit(bank2.step)
    st2 = bank2.init(PROTO)
    for t in range(6):
        keys = jnp.asarray(_zipf_keys(rs, K, b), jnp.int32)
        st2 = bstep2(jax.random.fold_in(key0, t), st2, keys,
                     jnp.asarray(rs.randn(b, 2), jnp.float32),
                     jnp.int32(b))
    v2 = jax.jit(bank2.extract)(jax.random.key(3), st2, all_keys)
    s2 = jax.jit(bank2.size)(jax.random.key(3), st2, all_keys)
    np.testing.assert_array_equal(np.asarray(v2.mask.sum(axis=1)),
                                  np.asarray(v2.size))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(v2.size))


def test_bank_routing_overflow_accounting_through_step():
    K, n, bcap, b = 4, 8, 2, 16
    bank = make_bank("rtbs", num_keys=K, n=n, lam=0.1, bcap=bcap)
    st = bank.init(PROTO)
    # every item hits key 0: 16 arrivals, 2 accepted per tick
    keys = jnp.zeros((b,), jnp.int32)
    payload = jnp.ones((b, 2), jnp.float32)
    bstep = jax.jit(bank.step)
    for t in range(3):
        st = bstep(jax.random.fold_in(jax.random.key(0), t), st, keys,
                   payload, jnp.int32(b))
    assert int(st.overflow[0]) == 3 * (b - bcap)
    assert (np.asarray(st.overflow)[1:] == 0).all()
    # the accepted-only weight accounting: W counts the bcap accepted items
    d = math.exp(-0.1)
    W = 0.0
    for _ in range(3):
        W = d * W + bcap
    np.testing.assert_allclose(float(st.total_weight[0]), W, rtol=1e-5)


def test_bank_step_dt_consumes_wallclock_gaps():
    """ROADMAP decay follow-up (b) at the bank level: one step spanning
    dt=3 equals three unit steps, up to f32 rounding of d^3 (exponential
    schedules are exact in the gap: e^{-lam dt})."""
    K, n, bcap, b = 6, 5, 4, 8
    bank = make_bank("rtbs", num_keys=K, n=n, lam=0.2, bcap=bcap)
    rs = np.random.RandomState(7)
    keys = jnp.asarray(rs.randint(0, K, size=b), jnp.int32)
    payload = jnp.asarray(rs.randn(b, 2), jnp.float32)
    key0 = jax.random.key(1)
    bstep = jax.jit(bank.step)
    st = bank.init(PROTO)
    st = bstep(jax.random.fold_in(key0, 0), st, keys, payload,
               jnp.int32(b))

    empty_k = jnp.zeros((b,), jnp.int32)
    empty_p = jnp.zeros((b, 2), jnp.float32)
    st_unit = st
    for t in range(1, 4):
        st_unit = bstep(jax.random.fold_in(key0, t), st_unit, empty_k,
                        empty_p, jnp.int32(0))
    st_dt = bank.step(jax.random.fold_in(key0, 9), st, empty_k, empty_p,
                      jnp.int32(0), dt=jnp.float32(3.0))
    np.testing.assert_allclose(np.asarray(st_dt.pending),
                               np.asarray(st_unit.pending), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(st_dt.items),
                                  np.asarray(st_unit.items))
    np.testing.assert_array_equal(np.asarray(st_dt.total_weight),
                                  np.asarray(st_unit.total_weight))


def test_make_bank_validation():
    with pytest.raises(ValueError, match="unknown bank scheme"):
        make_bank("nope", num_keys=4, n=2)
    with pytest.raises(ValueError, match="num_keys"):
        make_bank("rtbs", num_keys=0, n=2, lam=0.1)
    with pytest.raises(ValueError, match="exactly one"):
        make_bank("rtbs", num_keys=4, n=2)
    b = make_bank("rtbs", num_keys=4, n=2,
                  decay=dk.polynomial(0.8))
    st = b.init(PROTO)
    assert st.dstate is not None        # time-varying bookkeeping carried
    assert "SamplerBank(rtbs" in repr(b)
    # out-of-range key ids fail eagerly instead of silently aliasing the
    # last key's reservoir (the global-vs-local id foot-gun of sharded banks)
    with pytest.raises(ValueError, match="key_ids"):
        b.extract(jax.random.key(0), st, jnp.asarray([0, 4]))
    with pytest.raises(ValueError, match="key_ids"):
        b.size(jax.random.key(0), st, jnp.asarray([-1]))
    from repro.manage import make_bank_run_loop as mkloop
    from repro.manage import make_model as mkmodel
    with pytest.raises(ValueError, match="train_keys"):
        mkloop(b, mkmodel("linreg", dim=2), train_keys=range(9))


def test_bank_4096_keys_one_jitted_scan():
    """The acceptance shape: K >= 4096 keys advanced by one jitted scan
    (both schemes), with only the touched keys paying payload work."""
    K, n, bcap, b, T = 4096, 8, 8, 64, 4
    rs = np.random.RandomState(8)
    keys = jnp.asarray(_zipf_keys(rs, K, (T, b)), jnp.int32)
    payload = jnp.asarray(rs.randn(T, b, 2), jnp.float32)
    for scheme, hyper in [("rtbs", dict(n=n)),
                          ("ttbs", dict(n=n, batch_size=1.0, cap=n + 1))]:
        bank = make_bank(scheme, num_keys=K, lam=0.1, bcap=bcap, **hyper)

        @jax.jit
        def run(key, bank=bank):
            def body(c, t):
                return bank.step(jax.random.fold_in(key, t), c, keys[t],
                                 payload[t], jnp.int32(b)), None

            st, _ = jax.lax.scan(body, bank.init(PROTO), jnp.arange(T))
            return st

        st = run(jax.random.key(0))
        touched = np.unique(np.asarray(keys))
        w = np.asarray(st.total_weight)
        assert (w[touched] > 0).any()
        untouched = np.setdiff1d(np.arange(K), touched)
        assert (w[untouched] == 0).all()
        assert jax.tree_util.tree_leaves(st.items)[0].shape[0] == K


# ---------------------------------------------------------------------------
# bank-level manage loops
# ---------------------------------------------------------------------------
def _keyed_stream(K=32, T=12, b=24):
    stream = KeyedStream(base=LinRegStream(seed=0), num_keys=K, alpha=1.2,
                         flip_every=6)
    return materialize_stream(stream, T, batch_size=b,
                              fields=("key", "x", "y"))


def test_bank_run_loop_shared_and_superbatch_bit_identity():
    K, Q = 32, 4
    batches, bcounts = _keyed_stream(K=K)
    bank = make_bank("rtbs", num_keys=K, n=10, lam=0.1, bcap=8)
    model = make_model("linreg", dim=2)
    run = make_bank_run_loop(bank, model, retrain_every=3,
                             train_keys=range(Q))
    assert run is make_bank_run_loop(bank, model, retrain_every=3,
                                     train_keys=range(Q))
    out1 = run(jax.random.key(0), batches, bcounts)
    assert out1[2]["metric"].shape == (12,)
    assert out1[2]["size"].shape == (12, Q)
    assert np.isfinite(np.asarray(out1[2]["metric"])[1:]).all()
    run_sb = make_bank_run_loop(bank, model, retrain_every=3,
                                train_keys=range(Q), superbatch=3)
    out3 = run_sb(jax.random.key(0), batches, bcounts)
    _assert_trees_equal(out1, out3)


def test_bank_run_loop_per_key_farm_and_controller():
    K, Q = 32, 4
    batches, bcounts = _keyed_stream(K=K)
    bank = make_bank("rtbs", num_keys=K, n=10, lam=0.1, bcap=8)
    model = make_model("linreg", dim=2)
    run = make_bank_run_loop(bank, model, retrain_every=3,
                             train_keys=range(Q), per_key=True)
    state, params, trace = run(jax.random.key(0), batches, bcounts)
    assert trace["metric"].shape == (12, Q)
    assert np.asarray(params).shape == (Q, 3)
    m = np.asarray(trace["metric"])
    # per-key prequential eval: NaN exactly on ticks the key has no arrivals
    arrive = np.zeros((12, Q), bool)
    kk = np.asarray(batches["key"])
    for q in range(Q):
        arrive[:, q] = (kk == q).any(axis=1)
    np.testing.assert_array_equal(np.isfinite(m), arrive)
    # the popular keys' models actually differ (trained per key)
    assert len({np.asarray(params)[q].tobytes() for q in range(Q)}) > 1

    ctrl = dk.loss_ratio(lam0=0.1, lam_min=0.01, lam_max=1.0)
    runc = make_bank_run_loop(bank, model, retrain_every=3,
                              train_keys=range(Q), per_key=True,
                              controller=ctrl)
    state, params, trace = runc(jax.random.key(0), batches, bcounts)
    assert trace["metric"].shape == (12, Q)
    assert np.isfinite(np.asarray(trace["metric"])).any()


def test_per_key_eval_windows_never_leak_other_tenants():
    """The farm mode's per-key eval windows are zero-padded past each key's
    count: an adapter that ignores bcount must still never see another
    tenant's rows."""
    from repro.manage.bank_loop import _train_windows

    bank = make_bank("rtbs", num_keys=8, n=4, lam=0.1, bcap=4)
    keys = jnp.asarray([0, 1, 0, 2, 1, 5, 0, 0], jnp.int32)
    payload = (jnp.arange(8, dtype=jnp.float32)[:, None]
               * jnp.ones((1, 2)) + 1.0)
    tk = jnp.asarray([0, 1, 3], jnp.int32)
    windows, counts = _train_windows(bank, keys, payload, jnp.int32(6), tk)
    # rows 6-7 sit past bcount: key 0 has valid arrivals at rows 0 and 2
    np.testing.assert_array_equal(np.asarray(counts), [2, 2, 0])
    w = np.asarray(windows)
    np.testing.assert_array_equal(w[0, :2, 0], [1, 3])     # key 0 arrivals
    np.testing.assert_array_equal(w[1, :2, 0], [2, 5])     # key 1 arrivals
    assert (w[0, 2:] == 0).all() and (w[1, 2:] == 0).all()
    assert (w[2] == 0).all()                               # key 3: no rows


def test_sharded_bank_loop_one_shard_matches_local():
    from repro.launch.mesh import make_data_mesh

    K, Q = 32, 4
    batches, bcounts = _keyed_stream(K=K)
    bank = make_bank("rtbs", num_keys=K, n=10, lam=0.1, bcap=8)
    model = make_model("linreg", dim=2)
    local = make_bank_run_loop(bank, model, retrain_every=3,
                               train_keys=range(Q))
    _, _, trace_l = local(jax.random.key(0), batches, bcounts)

    sb, sc = shard_keyed_stream(batches, bcounts, 1, K)
    run = make_sharded_bank_loop(bank, model, make_data_mesh(1),
                                 retrain_every=3, train_keys=range(Q))
    state, params, trace = run(jax.random.key(0), sb, sc)
    assert np.asarray(trace["metric"]).shape[0] == 1  # gathered [S, T]
    np.testing.assert_allclose(np.asarray(trace["metric"])[0],
                               np.asarray(trace_l["metric"]), rtol=1e-6)


def test_sharded_bank_loop_multi_shard_runs():
    """Key-sharded scale-out on every available device (the CI distributed
    job runs this on a real 8-virtual-device mesh): each shard owns a
    contiguous key range with its own local bank; the psum'd metric is
    replicated and finite, reservoirs stay shard-local."""
    from repro.launch.mesh import make_data_mesh

    S = jax.device_count()
    K, Q = 8 * S, 2
    stream = KeyedStream(base=LinRegStream(seed=1), num_keys=K, alpha=1.1,
                         flip_every=4)
    batches, bcounts = materialize_stream(stream, 8, batch_size=4 * S,
                                          fields=("key", "x", "y"))
    sb, sc = shard_keyed_stream(batches, bcounts, S, K)
    bank = make_bank("rtbs", num_keys=K // S, n=6, lam=0.2, bcap=4)
    model = make_model("linreg", dim=2)
    run = make_sharded_bank_loop(bank, model, make_data_mesh(S),
                                 retrain_every=2, train_keys=range(Q))
    state, params, trace = run(jax.random.key(3), sb, sc)
    m = np.asarray(trace["metric"])
    assert m.shape == (S, 8)
    # the psum'd global metric is replicated: every shard logs the same row
    for s in range(1, S):
        np.testing.assert_array_equal(m[0], m[s])
    assert np.isfinite(m[0, 1:]).all()
    assert jax.tree_util.tree_leaves(state.items)[0].shape[0] == S
    assert (np.asarray(state.nfull).sum(axis=1) > 0).any()


def test_shard_keyed_stream_partitions_by_key_ownership():
    K, S = 12, 3
    batches, bcounts = _keyed_stream(K=K, T=5, b=16)
    sb, sc = shard_keyed_stream(batches, bcounts, S, K)
    ks = K // S
    bcap_s = sb["key"].shape[1] // S
    np.testing.assert_array_equal(np.asarray(sc).sum(axis=1),
                                  np.asarray(bcounts))
    for t in range(5):
        seen = []
        for s in range(S):
            c = int(sc[t, s])
            local = np.asarray(sb["key"])[t, s * bcap_s:s * bcap_s + c]
            assert ((0 <= local) & (local < ks)).all()
            seen.append(local + s * ks)
            # payload rides with its key, in arrival order
            x_seg = np.asarray(sb["x"])[t, s * bcap_s:s * bcap_s + c]
            glob = np.asarray(batches["key"])[t, : int(bcounts[t])]
            rows = np.nonzero((glob // ks) == s)[0]
            np.testing.assert_array_equal(
                x_seg, np.asarray(batches["x"])[t, rows]
            )
        got = np.sort(np.concatenate(seen))
        want = np.sort(np.asarray(batches["key"])[t, : int(bcounts[t])])
        np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="divide"):
        shard_keyed_stream(batches, bcounts, 5, K)
