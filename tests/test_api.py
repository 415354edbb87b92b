"""Unified Sampler API + manage-loop contracts (DESIGN.md Sec. 8):

  * every registered scheme constructs and satisfies the protocol
  * init/step/extract round-trip under jit (local) / shard_map (distributed)
  * the fused manage loop is bit-identical to stepping the sampler directly
    with the documented key discipline
  * model adapters fit/evaluate on realized sample views
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributed as dist
from repro.core.api import (
    SampleView,
    Sampler,
    available_schemes,
    make_sampler,
)
from repro.data.streams import LinRegStream, UsenetLikeStream
from repro.manage import (
    make_manage_step,
    make_model,
    make_run_farm,
    make_run_loop,
    make_sgd_adapter,
    materialize_stream,
    tick_keys,
)
from repro.manage.loop import item_proto

PROTO = jax.ShapeDtypeStruct((), jnp.int32)

LOCAL = {
    "rtbs": dict(n=10, lam=0.3),
    "ttbs": dict(n=10, lam=0.3, batch_size=8),
    "btbs": dict(lam=0.3, cap=64),
    "brs": dict(n=10),
    "sw": dict(n=10),
}
DISTRIBUTED = {
    "drtbs": dict(n=8, lam=0.3, cap_s=16),
    "dttbs": dict(n=4, lam=0.3, batch_size=4),
}


def _stream_ids(T=6, bcap=16, b=8):
    """Deterministic id stream: item id encodes its batch (1000*(t+1)+j)."""
    batches = np.zeros((T, bcap), np.int32)
    for t in range(T):
        batches[t, :b] = 1000 * (t + 1) + np.arange(b)
    return jnp.asarray(batches), jnp.full((T,), b, jnp.int32)


# ---------------------------------------------------------------------------
# registry + protocol
# ---------------------------------------------------------------------------
def test_registry_covers_all_schemes():
    assert set(available_schemes()) == set(LOCAL) | set(DISTRIBUTED)


def test_unknown_scheme_raises():
    with pytest.raises(ValueError, match="unknown sampling scheme"):
        make_sampler("nope")


def test_ttbs_rejects_invalid_q():
    with pytest.raises(ValueError, match="q ="):
        make_sampler("ttbs", n=100, lam=2.0, batch_size=1)  # q >> 1


@pytest.mark.parametrize("scheme", sorted(LOCAL) + sorted(DISTRIBUTED))
def test_protocol_shape(scheme):
    s = make_sampler(scheme, **{**LOCAL, **DISTRIBUTED}[scheme])
    assert isinstance(s, Sampler)
    assert s.scheme == scheme
    assert callable(s.init) and callable(s.step) and callable(s.extract)
    assert s.distributed == (scheme in DISTRIBUTED)
    assert dict(s.hyper)  # hyperparameters recorded


# ---------------------------------------------------------------------------
# local schemes: init/step/extract round-trip under jit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scheme", sorted(LOCAL))
def test_local_roundtrip_under_jit(scheme):
    s = make_sampler(scheme, **LOCAL[scheme])
    batches, bcounts = _stream_ids()
    state = s.init(PROTO)
    step = jax.jit(s.step)
    for t in range(batches.shape[0]):
        state = step(jax.random.fold_in(jax.random.key(0), t), state,
                     batches[t], bcounts[t])
    view = jax.jit(s.extract)(jax.random.key(99), state)
    assert isinstance(view, SampleView)
    cap = view.mask.shape[0]
    assert jax.tree_util.tree_leaves(view.items)[0].shape[0] == cap
    assert int(view.size) == int(view.mask.sum())
    # every selected slot holds a genuinely streamed item id
    got = np.asarray(view.items)[np.asarray(view.mask)]
    assert got.size == int(view.size)
    assert ((got >= 1000) & (got < 1000 * 8)).all(), got


@pytest.mark.parametrize("scheme", sorted(LOCAL))
def test_local_step_scans(scheme):
    """The same step composes with lax.scan (fixed shapes end to end)."""
    s = make_sampler(scheme, **LOCAL[scheme])
    batches, bcounts = _stream_ids()
    keys = jax.random.split(jax.random.key(3), batches.shape[0])

    @jax.jit
    def run(batches, bcounts, keys):
        def body(state, inp):
            b, c, k = inp
            return s.step(k, state, b, c), None

        state, _ = jax.lax.scan(body, s.init(PROTO), (batches, bcounts, keys))
        return s.extract(jax.random.key(9), state)

    view = run(batches, bcounts, keys)
    assert int(view.size) == int(view.mask.sum())


@pytest.mark.parametrize("scheme", sorted(LOCAL))
def test_extract_mask_sum_equals_size_local(scheme):
    """Regression: an item counted in view.size must be materialized in the
    view (mask.sum() == size) for EVERY realization key -- R-TBS's fractional
    item is drawn per extract, so multiple keys hit both branches."""
    s = make_sampler(scheme, **LOCAL[scheme])
    batches, bcounts = _stream_ids()
    state = s.init(PROTO)
    for t in range(batches.shape[0]):
        state = s.step(jax.random.fold_in(jax.random.key(5), t), state,
                       batches[t], bcounts[t])
    for k in range(10):
        view = s.extract(jax.random.key(100 + k), state)
        assert int(view.mask.sum()) == int(view.size)
        assert int(s.size(jax.random.key(100 + k), state)) == int(view.size)


@pytest.mark.parametrize("scheme", sorted(DISTRIBUTED))
def test_extract_mask_sum_equals_size_distributed(scheme):
    """The dropped-fractional-item regression (D-R-TBS): the partial payload
    occupies the reserved slot whenever it is counted, per shard AND in the
    global view. Hyperparameters keep C fractional (unsaturated stream) so
    the partial-item branch is actually exercised."""
    from jax.sharding import PartitionSpec as P

    nsh = jax.device_count()
    hyper = dict(DISTRIBUTED[scheme])
    if scheme == "drtbs":
        # 3 ticks of 2 items/shard: W = 2*nsh*(d^2+d+1) < n at any mesh
        # width, so C = W keeps a fraction of ~0.6 and ~24 keys hit both
        # partial-item branches with overwhelming probability
        hyper.update(n=5 * nsh + 5, lam=0.3)
    s = make_sampler(scheme, **hyper)
    mesh = jax.make_mesh((nsh,), (dist.AXIS,))
    bcap_s = 8
    nkeys = 24

    def run(key, bitems, bcounts):
        state = s.init(PROTO)
        for t in range(3):
            state = s.step(jax.random.fold_in(key, t), state,
                           bitems[t], bcounts[t, 0])
        outs = []
        for k in range(nkeys):
            kk = jax.random.fold_in(key, 50 + k)
            view = s.extract(kk, state)
            gview = s.extract_global(kk, state)
            outs.append((view.mask, view.size[None],
                         s.size(kk, state)[None],
                         gview.mask, gview.size[None],
                         s.size_global(kk, state)[None]))
        return outs

    f = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), P(None, dist.AXIS), P(None, dist.AXIS)),
        out_specs=[(P(dist.AXIS), P(dist.AXIS), P(dist.AXIS),
                    P(), P(), P())] * nkeys,
        check_vma=False,
    ))
    bitems = jnp.arange(3 * nsh * bcap_s, dtype=jnp.int32).reshape(
        3, nsh * bcap_s) + 1
    bcounts = jnp.full((3, nsh), 2, jnp.int32)
    sizes_seen = set()
    for mask, size_s, fast_s, gmask, gsize, gfast in f(jax.random.key(2),
                                                       bitems, bcounts):
        # per-shard: every counted item is selected by the mask
        assert int(mask.sum()) == int(size_s.sum()) == int(fast_s.sum())
        # global view: ditto, and it agrees with the per-shard realization
        assert int(gmask.sum()) == int(gsize[0]) == int(gfast[0])
        assert int(gsize[0]) == int(size_s.sum())
        sizes_seen.add(int(gsize[0]))
    if scheme == "drtbs":  # both partial-item branches must have been hit
        assert len(sizes_seen) == 2, sizes_seen


def test_bounded_schemes_respect_n():
    for scheme in ("rtbs", "brs", "sw"):
        s = make_sampler(scheme, **LOCAL[scheme])
        batches, bcounts = _stream_ids(T=8, bcap=32, b=30)
        state = s.init(PROTO)
        for t in range(8):
            state = s.step(jax.random.fold_in(jax.random.key(1), t), state,
                           batches[t], bcounts[t])
        view = s.extract(jax.random.key(2), state)
        assert int(view.size) <= s.hyper["n"], scheme


# ---------------------------------------------------------------------------
# distributed schemes under shard_map
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scheme", sorted(DISTRIBUTED))
def test_distributed_roundtrip_under_shard_map(scheme):
    if jax.device_count() < 1:
        pytest.skip("no devices")
    from jax.sharding import PartitionSpec as P

    s = make_sampler(scheme, **DISTRIBUTED[scheme])
    nsh = jax.device_count()
    mesh = jax.make_mesh((nsh,), (dist.AXIS,))
    bcap_s = 8

    def run(key, bitems, bcounts):
        state = s.init(PROTO)
        for t in range(3):
            state = s.step(jax.random.fold_in(key, t), state,
                           bitems[t], bcounts[t, 0])
        view = s.extract(jax.random.fold_in(key, 9), state)
        return view.mask, view.size[None]

    f = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), P(None, dist.AXIS), P(None, dist.AXIS)),
        out_specs=(P(dist.AXIS), P(dist.AXIS)),
        check_vma=False,
    ))
    bitems = jnp.arange(3 * nsh * bcap_s, dtype=jnp.int32).reshape(
        3, nsh * bcap_s) + 1
    bcounts = jnp.full((3, nsh), 4, jnp.int32)
    mask, sizes = f(jax.random.key(0), bitems, bcounts)
    assert mask.shape[0] % nsh == 0
    assert int(sizes.sum()) >= 0
    if scheme == "drtbs":
        # global bound: full items across shards never exceed n
        assert int(mask.sum()) <= s.hyper["n"]


# ---------------------------------------------------------------------------
# manage loop: fused == stepping the sampler directly
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scheme", ["rtbs", "sw"])
def test_manage_loop_matches_direct_stepping(scheme):
    n = 50
    sampler = make_sampler("rtbs", n=n, lam=0.1) if scheme == "rtbs" \
        else make_sampler("sw", n=n)
    model = make_model("linreg", dim=2)
    batches, bcounts = materialize_stream(LinRegStream(seed=0), 12,
                                          batch_size=20)
    key = jax.random.key(42)
    run = make_run_loop(sampler, model, retrain_every=1)
    state_fused, params_fused, trace = run(key, batches, bcounts)

    # drive the raw sampler with the loop's documented key discipline
    state = sampler.init(item_proto(batches))
    for t in range(12):
        k_step, _, _ = tick_keys(key, t)
        bt = jax.tree_util.tree_map(lambda a: a[t], batches)
        state = sampler.step(k_step, state, bt, bcounts[t])

    for a, b in zip(jax.tree_util.tree_leaves(state_fused),
                    jax.tree_util.tree_leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the traced metric/size are well-formed
    assert np.isfinite(np.asarray(trace["metric"])[1:]).all()
    assert (np.asarray(trace["size"]) <= n).all()


def test_manage_step_composes_with_fused_loop():
    """Tick-by-tick driving via make_manage_step reproduces the fused trace."""
    sampler = make_sampler("brs", n=40)
    model = make_model("linreg", dim=2)
    batches, bcounts = materialize_stream(LinRegStream(seed=1), 10,
                                          batch_size=16)
    key = jax.random.key(7)
    _, _, trace = make_run_loop(sampler, model, retrain_every=2)(
        key, batches, bcounts)

    tick = make_manage_step(sampler, model, retrain_every=2)  # jitted
    state, params = sampler.init(item_proto(batches)), model.init()
    metrics = []
    for t in range(10):
        bt = jax.tree_util.tree_map(lambda a: a[t], batches)
        state, params, m = tick(key, t, state, params, bt, bcounts[t])
        metrics.append(float(m["metric"]))
    np.testing.assert_allclose(np.asarray(trace["metric"]), metrics,
                               rtol=1e-6)


def test_manage_step_donates_and_keeps_reservoir_on_device():
    """ROADMAP PR-3 follow-up (c): the LOCAL per-tick driver donates the
    reservoir state (off-CPU, matching the sharded driver) and never forces
    a per-tick host copy of it -- the whole per-tick drive runs under a
    device-to-host transfer guard; metrics are pulled only afterwards."""
    sampler = make_sampler("rtbs", n=32, lam=0.1)
    model = make_model("linreg", dim=2)
    batches, bcounts = materialize_stream(LinRegStream(seed=2), 8,
                                          batch_size=16)
    tick = make_manage_step(sampler, model, retrain_every=2)
    assert tick is make_manage_step(sampler, model, retrain_every=2)
    key = jax.random.key(0)
    state, params = sampler.init(item_proto(batches)), model.init()
    ts = [jnp.int32(t) for t in range(8)]
    bts = [jax.tree_util.tree_map(lambda a: a[t], batches) for t in range(8)]
    metrics = []
    with jax.transfer_guard_device_to_host("disallow"):
        for t in range(8):
            prev = state
            state, params, m = tick(key, ts[t], state, params, bts[t],
                                    bcounts[t])
            metrics.append(m["metric"])
            if jax.default_backend() != "cpu":
                # donation: the consumed snapshot's buffers are reused
                assert all(a.is_deleted()
                           for a in jax.tree_util.tree_leaves(prev))
    assert np.isfinite(np.asarray(jnp.stack(metrics))[1:]).all()


def test_manage_loop_learns_linreg():
    """On a stationary stream the managed model reaches the noise floor."""
    sampler = make_sampler("rtbs", n=200, lam=0.1)
    model = make_model("linreg", dim=2)
    batches, bcounts = materialize_stream(LinRegStream(seed=3), 25,
                                          batch_size=80)
    _, _, trace = make_run_loop(sampler, model)(jax.random.key(0),
                                                batches, bcounts)
    tail = np.asarray(trace["metric"])[-5:]
    assert tail.mean() < 1.5, tail  # noise floor is 1.0


def test_manage_farm_shapes_and_variation():
    sampler = make_sampler("rtbs", n=30, lam=0.2)
    model = make_model("linreg", dim=2)
    batches, bcounts = materialize_stream(LinRegStream(seed=4), 8,
                                          batch_size=20)
    trace = make_run_farm(sampler, model)(jax.random.key(5), 6,
                                          batches, bcounts)
    assert trace["metric"].shape == (6, 8)
    # independent trials -> sampler randomness actually varies
    assert len(np.unique(np.asarray(trace["metric"])[:, -1])) > 1


def test_naive_bayes_adapter_on_manage_loop():
    s = UsenetLikeStream(seed=0)
    batches, bcounts = materialize_stream(s, 6, batch_size=50)
    model = make_model("naive_bayes", vocab=s.vocab)
    _, _, trace = make_run_loop(make_sampler("sw", n=250), model)(
        jax.random.key(0), batches, bcounts)
    m = np.asarray(trace["metric"])
    assert ((m >= 0) & (m <= 1)).all()
    assert m[1:3].mean() < 0.5  # within the first context, NB fits well


def test_knn_adapter_round_trip():
    model = make_model("knn", cap=51, dim=2, k=3, num_classes=5)
    params = model.init()
    view = SampleView(
        items={"x": jnp.ones((51, 2)), "y": jnp.zeros((51,), jnp.int32)},
        mask=jnp.arange(51) < 20,
        size=jnp.int32(20),
    )
    params = model.fit(jax.random.key(0), params, view)
    batch = {"x": jnp.ones((4, 2)), "y": jnp.zeros((4,), jnp.int32)}
    miss = model.evaluate(params, batch, jnp.int32(4))
    assert float(miss) == 0.0


def test_sgd_adapter_is_scan_safe():
    """The gradient adapter jits and trains on a toy quadratic model."""
    def loss(params, batch):
        pred = batch["tokens"][:, 0] * params["w"]
        return jnp.mean((pred - batch["tokens"][:, 1]) ** 2)

    def train_step(params, opt, batch):
        g = jax.grad(loss)(params, batch)
        params = jax.tree_util.tree_map(lambda p, d: p - 0.1 * d, params, g)
        return params, opt, {"loss": loss(params, batch)}

    adapter = make_sgd_adapter(
        init_params=lambda: {"w": jnp.float32(0.0)},
        train_step=train_step,
        init_opt_state=lambda p: jnp.int32(0),
        loss=loss,
        batch_field="tokens",
        train_batch=8,
        retrain_steps=20,
    )
    state = adapter.init()
    # sample: y = 3x pairs
    xs = jnp.linspace(1.0, 2.0, 32)
    view = SampleView(
        items=jnp.stack([xs, 3.0 * xs], axis=1),
        mask=jnp.ones((32,), bool),
        size=jnp.int32(32),
    )
    state = jax.jit(adapter.fit)(jax.random.key(0), state, view)
    assert abs(float(state["params"]["w"]) - 3.0) < 0.2
    # empty-sample guard: fit is a no-op
    empty = SampleView(items=view.items, mask=jnp.zeros((32,), bool),
                       size=jnp.int32(0))
    state2 = jax.jit(adapter.fit)(jax.random.key(1), state, empty)
    assert float(state2["params"]["w"]) == float(state["params"]["w"])


def test_run_loop_memoized_no_retrace():
    """run_loop/run_farm one-shot wrappers must not rebuild + re-jit the scan
    per call: make_run_loop is memoized on (sampler, model, retrain_every)
    and the jit cache shows exactly one trace for repeat same-shape runs."""
    from repro.manage import run_loop

    sampler = make_sampler("rtbs", n=20, lam=0.1)
    model = make_model("linreg", dim=2)
    r1 = make_run_loop(sampler, model)
    assert r1 is make_run_loop(sampler, model)
    assert r1 is not make_run_loop(sampler, model, retrain_every=2)
    assert make_run_farm(sampler, model) is make_run_farm(sampler, model)
    # an equivalent-but-fresh sampler is a different program (identity hash)
    assert make_run_loop(make_sampler("rtbs", n=20, lam=0.1), model) is not r1

    batches, bcounts = materialize_stream(LinRegStream(seed=2), 5,
                                          batch_size=8)
    run_loop(jax.random.key(0), sampler, model, batches, bcounts)
    run_loop(jax.random.key(1), sampler, model, batches, bcounts)
    assert r1._cache_size() == 1  # second call hit the jit cache, no retrace


def test_sgd_adapter_row_loss_masks_padding():
    """With row_loss, evaluate is a bcount-masked prefix mean: zero-padded
    eval rows (e.g. sharded per-shard segments) cannot skew the metric."""
    def row_loss(params, batch):
        return (batch["tokens"][:, 0] * params["w"] - batch["tokens"][:, 1]) ** 2

    adapter = make_sgd_adapter(
        init_params=lambda: {"w": jnp.float32(3.0)},
        train_step=lambda p, o, b: (p, o, {}),
        init_opt_state=lambda p: jnp.int32(0),
        loss=lambda p, b: jnp.mean(row_loss(p, b)),
        row_loss=row_loss,
        batch_field="tokens",
        train_batch=4,
        retrain_steps=1,
    )
    state = adapter.init()
    valid = jnp.asarray([[1.0, 3.0], [2.0, 6.0]])          # exact fit: loss 0
    garbage = jnp.zeros((2, 2)).at[:, 1].set(99.0)          # would blow up
    batch = jnp.concatenate([valid, garbage])
    assert float(adapter.evaluate(state, batch, jnp.int32(2))) == 0.0
    assert float(adapter.evaluate(state, batch, jnp.int32(4))) > 1.0


def test_manage_loop_rejects_distributed_samplers():
    """Per-shard schemes must fail fast, not die inside jax with an
    unbound-axis error."""
    model = make_model("linreg", dim=2)
    for scheme in sorted(DISTRIBUTED):
        s = make_sampler(scheme, **DISTRIBUTED[scheme])
        with pytest.raises(ValueError, match="per-shard"):
            make_run_loop(s, model)
        with pytest.raises(ValueError, match="per-shard"):
            make_manage_step(s, model)


def test_empty_tick_metric_is_nan():
    """bcount == 0 must not report a perfect score."""
    model = make_model("linreg", dim=2)
    batch = {"x": jnp.ones((4, 2)), "y": jnp.ones((4,))}
    m = model.evaluate(model.init(), batch, jnp.int32(0))
    assert np.isnan(float(m))
    assert np.isfinite(float(model.evaluate(model.init(), batch, jnp.int32(3))))


def test_model_registry():
    from repro.manage import available_models

    assert {"linreg", "naive_bayes", "knn"} <= set(available_models())
    with pytest.raises(ValueError, match="unknown model"):
        make_model("nope")
