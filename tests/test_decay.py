"""The repro.decay subsystem (DESIGN.md Sec. 12):

  * scalar-``lam`` sugar is BIT-identical to ``decay=exponential(lam)`` for
    every registered scheme, local and sharded (the acceptance criterion of
    the subsystem: the sugar constructs the schedule, so this guards the
    construction staying shared);
  * schedule algebra: per-tick factors match the analytic forms, and the
    cumulative-product weights drive R-TBS exactly as Theorem 4.2 predicts
    under POLYNOMIAL decay (the journal extension's generalization);
  * the closed-loop adaptive controller converges on the single-shift
    scenario -- post-shift prequential loss beats every static lambda on the
    grid -- while running inside the jitted (super)batched scan with no
    per-tick re-trace;
  * the delete-complement downsample map satisfies Theorem 4.1 at any
    ``max_deleted`` (fast path AND runtime fallback);
  * ``batch_size_schedule``'s decaying regime floors at one item.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import decay as dk
from repro.core import latent as lt
from repro.core.api import make_sampler
from repro.data.streams import GMMStream, batch_size_schedule, mode_schedule
from repro.manage import make_model, make_run_farm, make_run_loop, \
    materialize_stream

PROTO = jax.ShapeDtypeStruct((), jnp.int32)

LOCAL_DECAYED = {
    "rtbs": dict(n=10),
    "ttbs": dict(n=10, batch_size=8),
    "btbs": dict(cap=64),
}
SHARDED_DECAYED = {
    "drtbs": dict(n=8, cap_s=16),
    "dttbs": dict(n=4, batch_size=4),
}


def _drive(sampler, T=6, b=8, bcap=16, seed=0):
    state = sampler.init(PROTO)
    step = jax.jit(sampler.step)
    for t in range(T):
        items = jnp.full((bcap,), 1000 * (t + 1), jnp.int32) + jnp.arange(bcap)
        state = step(jax.random.fold_in(jax.random.key(seed), t), state,
                     items, jnp.int32(b))
    return state


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# lam sugar == exponential schedule, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scheme", sorted(LOCAL_DECAYED))
def test_lam_sugar_bit_identical_local(scheme):
    lam = 0.3
    a = make_sampler(scheme, lam=lam, **LOCAL_DECAYED[scheme])
    b = make_sampler(scheme, decay=dk.exponential(lam), **LOCAL_DECAYED[scheme])
    sa, sb = _drive(a), _drive(b)
    _assert_trees_equal(sa, sb)
    va = a.extract(jax.random.key(9), sa)
    vb = b.extract(jax.random.key(9), sb)
    _assert_trees_equal(va, vb)
    assert int(a.size(jax.random.key(9), sa)) == int(b.size(jax.random.key(9), sb))
    # and the exponential fast path adds NO schedule state to the pytree
    assert len(jax.tree_util.tree_leaves(sa)) == len(jax.tree_util.tree_leaves(sb))


@pytest.mark.parametrize("scheme", sorted(SHARDED_DECAYED))
def test_lam_sugar_bit_identical_sharded(scheme):
    from jax.sharding import PartitionSpec as P

    from repro.core import distributed as dist

    lam = 0.3
    nsh = jax.device_count()
    mesh = jax.make_mesh((nsh,), (dist.AXIS,))
    bcap_s = 8
    bitems = jnp.arange(4 * nsh * bcap_s, dtype=jnp.int32).reshape(
        4, nsh * bcap_s) + 1
    bcounts = jnp.full((4, nsh), 3, jnp.int32)

    def run_with(sampler):
        def body(key, bitems, bcounts):
            state = sampler.init(PROTO)
            for t in range(4):
                state = sampler.step(jax.random.fold_in(key, t), state,
                                     bitems[t], bcounts[t, 0])
            gview = sampler.extract_global(jax.random.fold_in(key, 9), state)
            return dist.gather_tree(state), gview.items, gview.size[None]

        f = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(None, dist.AXIS), P(None, dist.AXIS)),
            out_specs=(P(), P(), P()),
            check_vma=False,
        ))
        return f(jax.random.key(2), bitems, bcounts)

    a = run_with(make_sampler(scheme, lam=lam, **SHARDED_DECAYED[scheme]))
    b = run_with(make_sampler(scheme, decay=dk.exponential(lam),
                              **SHARDED_DECAYED[scheme]))
    _assert_trees_equal(a, b)


def test_resolve_rejects_ambiguous_decay():
    with pytest.raises(ValueError, match="exactly one"):
        make_sampler("rtbs", n=8)
    with pytest.raises(ValueError, match="exactly one"):
        make_sampler("rtbs", n=8, lam=0.1, decay=dk.exponential(0.1))
    with pytest.raises(TypeError, match="DecaySchedule"):
        make_sampler("rtbs", n=8, decay=0.9)


# ---------------------------------------------------------------------------
# schedule algebra
# ---------------------------------------------------------------------------
def test_schedule_profiles_match_analytic():
    T, beta, t0 = 8, 1.3, 1.0
    prof = np.asarray(dk.decay_profile(dk.polynomial(beta, t0=t0), T))
    want = [(max(t - 1 + t0, 0.0) / (t + t0)) ** beta for t in range(T)]
    np.testing.assert_allclose(prof, want, rtol=1e-6)
    # cumulative products telescope to the power law in arrival time
    D = np.cumprod(prof[1:])  # D_t / D_0 for t >= 1
    np.testing.assert_allclose(
        D, [((t0) / (t + t0)) ** beta for t in range(1, T)], rtol=1e-5
    )

    prof = np.asarray(dk.decay_profile(dk.piecewise((2, 4), (0.1, 0.5, 0.2)), 6))
    want = [math.exp(-v) for v in (0.1, 0.1, 0.5, 0.5, 0.2, 0.2)]
    np.testing.assert_allclose(prof, want, rtol=1e-6)

    prof = np.asarray(dk.decay_profile(
        dk.from_callable(lambda t: jnp.exp(-0.05 * (t + 1.0))), 4))
    np.testing.assert_allclose(
        prof, [math.exp(-0.05 * (t + 1)) for t in range(4)], rtol=1e-6)


def test_schedule_dt_wallclock_gaps():
    """ROADMAP decay follow-up (b): ``tick(dstate, dt=...)`` consumes
    wall-clock gaps. Exponential is exact (e^{-lam dt} for any real dt);
    polynomial's telescoping ratio closes exactly over integral gaps;
    schedules without a native dt form fall back to d^dt (documented)."""
    lam = 0.3
    e = dk.exponential(lam)
    ds = e.init()
    d3, ds3 = e.tick(ds, dt=3.0)
    np.testing.assert_allclose(float(d3), math.exp(-3 * lam), rtol=1e-6)
    assert int(ds3) == 3                    # counter advances by the gap
    d_half, _ = e.tick(ds, dt=0.5)          # fractional gaps: still exact
    np.testing.assert_allclose(float(d_half), math.exp(-0.5 * lam),
                               rtol=1e-6)

    p = dk.polynomial(1.3, t0=1.0)
    ds = p.step(p.step(p.init()))           # counter at t=2
    d2, ds2 = p.tick(ds, dt=2.0)
    # exact telescoping: factor over [t, t+2) == d_t * d_{t+1}
    want = float(p.rate(jnp.int32(2))) * float(p.rate(jnp.int32(3)))
    np.testing.assert_allclose(float(d2), want, rtol=1e-6)
    assert int(ds2) == 4

    c = dk.from_callable(lambda t: jnp.float32(0.9))
    d4, ds4 = c.tick(c.init(), dt=4.0)      # fallback: rate ** dt
    np.testing.assert_allclose(float(d4), 0.9 ** 4, rtol=1e-6)
    assert int(ds4) == 4
    # sub-unit gaps ACCUMULATE (no round-away freeze): 3 gaps of 0.4 move
    # the elapsed-time counter to 1.2, and a time-varying rate moves with it
    ds = p.init()
    for _ in range(3):
        _, ds = p.tick(ds, dt=0.4)
    np.testing.assert_allclose(float(ds), 1.2, rtol=1e-6)
    assert float(p.rate(ds)) != float(p.rate(p.init()))
    # dt=None keeps the historical unit-tick behaviour bit-for-bit
    d1a, s1a = e.tick(e.init())
    d1b, s1b = e.tick(e.init(), dt=None)
    assert float(d1a) == float(d1b) and int(s1a) == int(s1b)


def test_schedule_validation():
    with pytest.raises(ValueError, match="lam >= 0"):
        dk.exponential(-0.1)
    with pytest.raises(ValueError, match="beta >= 0"):
        dk.polynomial(-1.0)
    with pytest.raises(ValueError, match="len"):
        dk.piecewise((2,), (0.1,))
    with pytest.raises(ValueError, match="increasing"):
        dk.piecewise((4, 2), (0.1, 0.2, 0.3))
    assert dk.exponential(0.2).static_rate == pytest.approx(math.exp(-0.2))
    assert dk.polynomial(1.0).static_rate is None


# ---------------------------------------------------------------------------
# Theorem 4.2 under polynomial decay: Pr[i in S_T] = (C_T/W_T) w_T(i),
# with w_T(i) = D_T / D_{t_i} the cumulative-product weight
# ---------------------------------------------------------------------------
def test_rtbs_theorem_4_2_polynomial_decay():
    beta, n, T, b, trials = 1.5, 8, 8, 4, 25000
    sched = dk.polynomial(beta)
    sampler = make_sampler("rtbs", n=n, decay=sched)
    prof = np.asarray(dk.decay_profile(sched, T), np.float64)

    # analytic W_t = d_t W_{t-1} + b and item weights w_T(j) = prod d_{j+1..T-1}
    W = 0.0
    for t in range(T):
        W = prof[t] * W + b
    w_item = [float(np.prod(prof[j + 1:T])) for j in range(T)]
    C = min(n, W)

    bcap = b
    batches = np.zeros((T, bcap), np.int32)
    for t in range(T):
        batches[t] = 1000 * (t + 1) + np.arange(b)
    batches = jnp.asarray(batches)
    bcounts = jnp.full((T,), b, jnp.int32)

    def one(kk):
        state = sampler.init(PROTO)

        def body(state, inp):
            bt, ct, k = inp
            return sampler.step(k, state, bt, ct), None

        keys = jax.random.split(kk, T + 1)
        state, _ = jax.lax.scan(body, state, (batches, bcounts, keys[:T]))
        mask, _ = lt.realize(keys[T], state.inner.lat)
        batch_of = state.inner.lat.items // 1000
        counts = jnp.zeros((T + 1,), jnp.float32)
        counts = counts.at[batch_of].add(mask.astype(jnp.float32))
        return counts[1:], state.inner.lat.weight, state.inner.total_weight

    keys = jax.random.split(jax.random.key(0), trials)
    counts, Cs, Ws = jax.vmap(one)(keys)
    # the scalar trajectories are deterministic and match the analytic ones
    np.testing.assert_allclose(float(Cs[0]), C, rtol=1e-4)
    np.testing.assert_allclose(float(Ws[0]), W, rtol=1e-4)
    probs = np.asarray(counts.mean(axis=0)) / b
    for j in range(T):
        expect = (C / W) * w_item[j]
        assert abs(probs[j] - expect) < 0.02, (j, probs[j], expect)
    # eq.-(1) analogue: relative inclusion is the POLYNOMIAL weight ratio
    # ((t_i + t0) / (t_j + t0))^beta, not an exponential in age
    ratio = probs[2] / probs[5]
    want = w_item[2] / w_item[5]
    assert abs(ratio - want) < 0.12, (ratio, want)


# ---------------------------------------------------------------------------
# delete-complement downsample map: Theorem 4.1 at any max_deleted
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "c,cp,max_deleted",
    [
        (5.6, 3.2, 1),    # deletion count 2-3 > D: runtime fallback path
        (5.6, 3.2, 16),   # fast path, partial cases
        (5.0, 3.4, 4),    # integral C
        (5.6, 5.2, 4),    # kp == k swap case (loop-free)
        (5.6, 0.7, 4),    # kp == 0 corner (loop-free)
        (9.3, 8.9, 2),    # single deletion
    ],
)
def test_downsample_delete_complement_theorem_4_1(c, cp, max_deleted):
    cap, trials = 10, 30000
    k = math.floor(c)
    f = c - k
    ids = jnp.arange(cap, dtype=jnp.int32)
    base = lt.Latent(items=ids, nfull=jnp.int32(k), weight=jnp.float32(c))

    def one(kk):
        k1, k2 = jax.random.split(kk)
        out = lt.downsample(k1, base, jnp.float32(cp),
                            max_deleted=max_deleted)
        mask, _ = lt.realize(k2, out)
        member = jnp.zeros((cap,), jnp.float32)
        member = member.at[out.items].add(mask.astype(jnp.float32))
        return member

    keys = jax.random.split(jax.random.key(3), trials)
    probs = np.asarray(jax.vmap(one)(keys).mean(axis=0))
    scale = cp / c
    for i in range(k):
        assert abs(probs[i] - scale) < 0.015, (i, probs[i], scale)
    if f > 0:
        assert abs(probs[k] - scale * f) < 0.015, (probs[k], scale * f)
    for i in range(k + 1 if f > 0 else k, cap):
        assert probs[i] < 1e-9


# ---------------------------------------------------------------------------
# the closed-loop adaptive controller
# ---------------------------------------------------------------------------
def test_adaptive_beats_best_static_lambda_on_single_shift():
    """The convergence criterion: on the Sec. 6.2 single-shift kNN/GMM
    scenario the controller's post-shift prequential loss beats the endpoint
    of EVERY static lambda on the grid.

    Scenario design (the dial must have no static sweet spot): a sharp
    class-frequency flip (ratio=25) makes stale samples costly, and
    b = 50 << n = 400 makes every fast-flushing static rate run with a
    shrunken steady-state sample (E W = b/(1-e^-lam) < n for lam >= 0.2).
    So the static grid trades pollution against coverage, while the
    controller cruises at lam_min with a full sample, pulses lambda at the
    shift, and anneals back -- getting both.  Margins measured at +0.02..
    +0.035 across 5 stream/key seed combos; the assertion is strict
    inequality against the best of the grid."""
    warm, T, b, n, trials, skip = 30, 40, 50, 400, 8, 3
    grid = (0.005, 0.05, 0.2, 0.5)
    batches, bcounts = materialize_stream(
        GMMStream(seed=0, ratio=25), warm + T, batch_size=b,
        mode=lambda t: 0 if t < warm else 1,
    )
    model = make_model("knn", cap=n + 1, dim=2, k=7, num_classes=100)

    def post_shift_miss(controller, lam):
        sampler = make_sampler("rtbs", n=n, lam=lam)
        farm = make_run_farm(sampler, model, retrain_every=1,
                             controller=controller)
        trace = farm(jax.random.key(11), trials, batches, bcounts)
        return float(np.asarray(trace["metric"])[:, warm + skip:].mean()), trace

    static = {lam: post_shift_miss(None, lam)[0] for lam in grid}
    ctrl = dk.loss_ratio(lam0=0.05, lam_min=0.005, lam_max=0.5)
    adaptive, trace = post_shift_miss(ctrl, 0.05)

    best = min(static.values())
    assert adaptive < best, (adaptive, static)
    # the controller actually moved: lambda pulsed after the shift and came
    # back down once the retrained model recovered
    lam_path = -np.log(np.maximum(np.asarray(trace["decay"]), 1e-30))
    assert lam_path[:, warm:warm + 10].max() > 0.4, lam_path[:, warm:].max()
    assert lam_path[:, -1].mean() < 0.05, lam_path[:, -1]
    # and cruised at lam_min pre-shift (stationary stream -> max sample)
    assert lam_path[:, warm - 5:warm].mean() < 0.01


def test_controller_no_retrace_and_superbatch_bit_identity():
    """The controller runs inside the jitted scan: repeated dispatches hit
    the jit cache (no per-tick or per-call re-trace), and superbatched
    chunking stays bit-identical with the controller in the carry."""
    from repro.data.streams import LinRegStream

    sampler = make_sampler("rtbs", n=40, lam=0.1)
    model = make_model("linreg", dim=2)
    ctrl = dk.loss_ratio(lam0=0.1, lam_min=0.01, lam_max=1.0)
    batches, bcounts = materialize_stream(LinRegStream(seed=0), 12,
                                          batch_size=16)
    r1 = make_run_loop(sampler, model, retrain_every=4, controller=ctrl)
    assert r1 is make_run_loop(sampler, model, retrain_every=4,
                               controller=ctrl)
    assert r1 is not make_run_loop(sampler, model, retrain_every=4)
    out1 = r1(jax.random.key(0), batches, bcounts)
    r1(jax.random.key(1), batches, bcounts)
    assert r1._cache_size() == 1

    r4 = make_run_loop(sampler, model, retrain_every=4, superbatch=4,
                       controller=ctrl)
    out4 = r4(jax.random.key(0), batches, bcounts)
    _assert_trees_equal(out1, out4)
    assert "decay" in out1[2]


def test_controller_rejects_decay_free_schemes():
    model = make_model("linreg", dim=2)
    ctrl = dk.loss_ratio(lam0=0.1, lam_min=0.01, lam_max=1.0)
    for scheme in ("brs", "sw"):
        with pytest.raises(ValueError, match="no decay"):
            make_run_loop(make_sampler(scheme, n=8), model, controller=ctrl)


def test_controller_pulses_relaxes_and_ignores_nan():
    ctrl = dk.loss_ratio(lam0=0.1, lam_min=0.05, lam_max=0.8, warmup=1)
    c = ctrl.init()
    # stationary loss: lambda relaxes to lam_min (max sample)
    for _ in range(10):
        c = ctrl.observe(c, jnp.float32(1.0), jnp.bool_(True))
    assert float(jnp.exp(c.loglam)) == pytest.approx(0.05, rel=1e-5)
    # a loss jump fires ONE pulse straight to lam_max...
    c = ctrl.observe(c, jnp.float32(100.0), jnp.bool_(True))
    assert float(jnp.exp(c.loglam)) == pytest.approx(0.8, rel=1e-5)
    assert int(c.hold) == 8
    # ...and even a sustained plateau cannot keep lambda there: once the
    # slow EMA absorbs the new level the ratio signal dies, the refractory
    # window spaces out the re-fires meanwhile, and the relax leak anneals
    # lambda back down (the stuck-high guard)
    for _ in range(60):
        c = ctrl.observe(c, jnp.float32(100.0), jnp.bool_(True))
    assert float(jnp.exp(c.loglam)) == pytest.approx(0.05, rel=1e-5)
    # NaN losses (empty ticks) change nothing
    c_nan = ctrl.observe(c, jnp.float32(float("nan")), jnp.bool_(True))
    assert float(c_nan.loglam) == float(c.loglam)
    assert int(c_nan.seen) == int(c.seen)
    # non-adjust ticks update the EMAs but never lambda
    c2 = ctrl.observe(c, jnp.float32(500.0), jnp.bool_(False))
    assert float(c2.loglam) == float(c.loglam)
    assert float(c2.fast) != float(c.fast)


def test_adaptive_validation():
    with pytest.raises(ValueError, match="lam_min <= lam0 <= lam_max"):
        dk.loss_ratio(lam0=0.5, lam_min=0.01, lam_max=0.1)
    with pytest.raises(ValueError, match="slow_alpha <= fast_alpha"):
        dk.loss_ratio(lam0=0.1, lam_min=0.01, lam_max=1.0,
                      fast_alpha=0.1, slow_alpha=0.5)


# ---------------------------------------------------------------------------
# streams satellite: one batch_size_schedule branch, floored at 1
# ---------------------------------------------------------------------------
def test_batch_size_schedule_decaying_floors_at_one():
    sizes = [batch_size_schedule("decaying", t, b=100, phi=0.9, t0=0)
             for t in range(200)]
    assert sizes[0] == 100
    assert min(sizes) == 1          # never a permanently-zero bcount tail
    assert sizes[-1] == 1
    assert all(a >= b for a, b in zip(sizes, sizes[1:]))
    grow = [batch_size_schedule("growing", t, b=100, phi=1.002, t0=0)
            for t in range(50)]
    assert grow[0] == 100 and grow[-1] == int(round(100 * 1.002 ** 49))
    with pytest.raises(ValueError):
        batch_size_schedule("nope", 0)


def test_polynomial_decay_in_manage_loop():
    """A time-varying schedule drives the fused loop end to end (wrapped
    state through the scan; mode_schedule sanity on the GMM stream)."""
    stream = GMMStream(seed=1)
    batches, bcounts = materialize_stream(
        stream, 10, batch_size=30,
        mode=lambda t: mode_schedule("periodic", t, delta=3, eta=3))
    n = 80
    sampler = make_sampler("rtbs", n=n, decay=dk.polynomial(1.0))
    model = make_model("knn", cap=n + 1, dim=2, k=3, num_classes=100)
    state, params, trace = make_run_loop(sampler, model, retrain_every=2)(
        jax.random.key(4), batches, bcounts)
    assert isinstance(state, dk.DecayedState)
    assert int(state.dstate) == 10
    m = np.asarray(trace["metric"])
    assert ((m[1:] >= 0) & (m[1:] <= 1)).all()
    assert (np.asarray(trace["size"]) <= n).all()
