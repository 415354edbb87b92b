"""Bank-level model-management loops (DESIGN.md Sec. 13).

The paper's stream -> sample -> retrain -> eval loop, lifted to a
:class:`repro.bank.SamplerBank`: one jitted ``lax.scan`` consumes a KEYED
stream (every tick a ``(keys, payload)`` batch) and maintains K per-key
time-biased samples concurrently. Two retraining regimes:

  * **shared model** (default): one model, periodically retrained on the
    POOLED extract of a key subset (``train_keys``) -- the multi-tenant
    analogue of the paper's single loop, where the model serves all keys
    but the sample it trains on is per-key time-biased;
  * **per-key farm** (``per_key=True``): a ``vmap``-ed model per train key,
    each fit on ITS key's sample and prequentially evaluated on ITS key's
    arrivals (the Fig. 12/13 scenarios replayed per key -- keyed streams
    give every key its own drift phase). Optionally a vmapped
    :func:`repro.decay.loss_ratio` controller per key closes the loop
    between each key's prequential loss and its decay rate, through the
    bank's ``step_decayed`` with a per-key [K] factor vector.

``make_sharded_bank_loop`` splits the KEYS over the mesh instead of the
batch: each shard owns a contiguous key range (its own local bank + model
farm), the stream is co-partitioned by key ownership
(:func:`shard_keyed_stream`), and the only cross-device traffic is the
per-tick psum of the prequential metric -- key-sharded scale-out rides the
same ``shard_map`` skeleton as the Sec.-5 schemes with NO payload
collectives at all.

Superbatching, tick-key discipline, and builder memoization are shared with
:mod:`repro.manage.loop` (same ``tick_keys``, same chunked-scan skeleton,
bit-identical for any G).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.bank import SamplerBank, route
from repro.core import distributed
from repro.core.api import SampleView
from repro.manage.loop import (
    _effective_superbatch,
    _memoized,
    _psum_metric,
    _superbatched_scan,
    _telemetry_fetch_scan,
    _telemetry_scan,
    _wrap_run_header,
    item_proto,
    tick_keys,
)
from repro.manage.models import ModelAdapter
from repro.obs import probe as _obs_probe

KEY_FIELD = "key"


def _split_keyed(batch: Any):
    """A keyed tick batch is a dict with the ``"key"`` column plus payload
    fields; a SINGLE payload field is unwrapped to its bare leaf (the
    convention bare-batch adapters like the SGD/LM adapter expect)."""
    keys = batch[KEY_FIELD]
    payload = {k: v for k, v in batch.items() if k != KEY_FIELD}
    if len(payload) == 1:
        payload = next(iter(payload.values()))
    return keys, payload


def keyed_item_proto(batches: Any) -> Any:
    """ONE-item payload prototype from stacked keyed-stream arrays (the
    ``"key"`` column excluded)."""
    keys, payload = _split_keyed(batches)
    del keys
    return item_proto(payload)


def pooled_view(view: SampleView) -> SampleView:
    """Flatten a stacked per-key :class:`SampleView` ([Q, cap, ...] leaves)
    into one pooled view ([Q*cap, ...]): the union of the keys' realized
    samples, which mask-weighted model fits consume directly."""
    items = jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), view.items
    )
    return SampleView(items=items, mask=view.mask.reshape(-1),
                      size=view.size.sum())


def _train_windows(bank: SamplerBank, keys, payload, bcount, train_keys):
    """Each train key's slice of the tick: ``(windows, counts)`` with window
    leaves [Q, bcap, ...] whose first counts[q] rows are that key's arrivals
    (0 when the key did not arrive) -- prefix-valid batches for vmapped
    prequential eval, rows past the count ZEROED (the raw windows are
    slices of the key-sorted batch whose tails belong to OTHER tenants; an
    adapter that ignores ``bcount`` must never see another key's data).
    Recomputes the same :func:`repro.bank.route` the bank step runs
    internally on identical inputs (pure, inside one jitted scan body, so
    XLA's CSE normally merges the two sorts; the bank step's closure does
    not take a precomputed Routing)."""
    r = route(keys, bcount, num_keys=bank.num_keys, bcap=bank.bcap)
    b = r.order.shape[0]
    pos = jnp.clip(jnp.searchsorted(r.touched, train_keys), 0, b - 1)
    found = r.touched[pos] == train_keys
    counts = jnp.where(found, r.counts[pos], 0)
    starts = jnp.where(found, r.starts[pos], 0)
    idx = jnp.clip(
        starts[:, None] + jnp.arange(bank.bcap, dtype=jnp.int32)[None, :],
        0, b - 1,
    )
    valid = jnp.arange(bank.bcap, dtype=jnp.int32)[None, :] < counts[:, None]

    def one(a):
        w = jnp.take(jnp.take(a, r.order, axis=0), idx, axis=0)
        return jnp.where(valid.reshape(valid.shape + (1,) * (w.ndim - 2)),
                         w, jnp.zeros_like(w))

    return jax.tree_util.tree_map(one, payload), counts


def _as_train_keys(train_keys, num_keys: int) -> jnp.ndarray:
    tk = np.asarray(train_keys, np.int32).reshape(-1)
    if tk.shape[0] < 1:
        raise ValueError("train_keys must be a non-empty key list")
    if tk.min() < 0 or tk.max() >= num_keys:
        raise ValueError(
            f"train_keys must lie in [0, {num_keys}); got range "
            f"[{tk.min()}, {tk.max()}] -- the sharded bank loop takes "
            "LOCAL ids (see shard_keyed_stream)"
        )
    return jnp.asarray(tk)


def _memo_key(train_keys) -> tuple:
    return tuple(int(k) for k in np.asarray(train_keys).reshape(-1))


def _make_bank_ticks(bank: SamplerBank, model: ModelAdapter,
                     retrain_every: int, train_keys, per_key: bool,
                     controller, metric_fn: Callable | None = None,
                     with_obs: bool = False) -> tuple[Callable, Callable]:
    """(full, fast) opaque-carry ticks for the bank loop, in the
    :func:`repro.manage.loop._superbatched_scan` contract. The fast tick is
    the full tick minus the retrain conditional and minus any controller
    adjustment (``adjust=False`` arithmetic), so superbatched runs stay
    bit-identical to G=1.

    The ticks step through the bank's ``step_stats`` closures so per-tick
    routing overflow (dropped items) is surfaced as the ``"overflow"``
    metrics column; ``with_obs=True`` additionally diverts the remaining
    routing gauges (touched keys, invalid ids, the applied decay factor)
    into the reserved ``"_obs"`` entry for :func:`_telemetry_scan`."""
    tk = _as_train_keys(train_keys, bank.num_keys)
    Q = tk.shape[0]
    shared_eval = metric_fn or (lambda p, b, c: model.evaluate(p, b, c))

    def eval_and_step(key, t, state, params, cstate, batch, bcount, adjust):
        k_step, k_extract, k_fit = tick_keys(key, t)
        keys_t, payload = _split_keyed(batch)
        if per_key:
            windows, counts = _train_windows(bank, keys_t, payload, bcount,
                                             tk)
            metric = jax.vmap(model.evaluate)(params, windows, counts)
        else:
            metric = shared_eval(params, payload, bcount)
        if controller is None:
            state, bstats = bank.step_stats(k_step, state, keys_t, payload,
                                            bcount)
        elif per_key:
            d_q = jax.vmap(controller.rate)(cstate)
            d_full = jnp.full((bank.num_keys,), bank.base_rate(state),
                              jnp.float32).at[tk].set(d_q)
            state, bstats = bank.step_decayed_stats(k_step, state, keys_t,
                                                    payload, bcount, d_full)
            cstate = jax.vmap(controller.observe, in_axes=(0, 0, None))(
                cstate, metric, adjust
            )
        else:
            d = controller.rate(cstate)
            state, bstats = bank.step_decayed_stats(k_step, state, keys_t,
                                                    payload, bcount, d)
            cstate = controller.observe(cstate, metric, adjust)
        return state, cstate, metric, bstats, (k_extract, k_fit)

    def tick_metrics(k_extract, state, metric, bstats):
        m = {"metric": metric, "size": bank.size(k_extract, state, tk),
             "overflow": bstats["overflow"]}
        if with_obs:
            m["_obs"] = {"ntouched": bstats["ntouched"],
                         "invalid": bstats["invalid"],
                         "decay": bstats["decay"]}
        return m

    def fit(k_extract, k_fit, state, params):
        view = bank.extract(k_extract, state, tk)
        if per_key:
            return jax.vmap(model.fit, in_axes=(0, 0, 0))(
                jax.random.split(k_fit, Q), params, view
            )
        return model.fit(k_fit, params, pooled_view(view))

    def full(key, t, carry, batch, bcount):
        state, params, *cs = carry
        cstate = cs[0] if cs else None
        do_fit = (t + 1) % retrain_every == 0
        state, cstate, metric, bstats, (k_extract, k_fit) = eval_and_step(
            key, t, state, params, cstate, batch, bcount, do_fit
        )
        params = jax.lax.cond(
            do_fit,
            lambda: fit(k_extract, k_fit, state, params),
            lambda: params,
        )
        m = tick_metrics(k_extract, state, metric, bstats)
        out = (state, params) + ((cstate,) if cs else ())
        return out, m

    def fast(key, t, carry, batch, bcount):
        state, params, *cs = carry
        cstate = cs[0] if cs else None
        state, cstate, metric, bstats, (k_extract, _) = eval_and_step(
            key, t, state, params, cstate, batch, bcount, False
        )
        m = tick_metrics(k_extract, state, metric, bstats)
        out = (state, params) + ((cstate,) if cs else ())
        return out, m

    return full, fast


def _make_bank_stats(bank: SamplerBank, controller, per_key: bool,
                     retrain_every: int, probe_key: int) -> Callable:
    """The bank loop's telemetry row (DESIGN.md Sec. 14): per-tick routing
    gauges (touched keys, invalid ids, overflow drops), the probed tenant's
    Thm 4.1 self-check columns (:func:`repro.obs.probe.
    make_bank_probe_stats`), the pending-decay magnitude across the bank
    (min composed factor -- how much deferred decay the laziest key is
    carrying), and the controller gauges (the probe/first train key's lane
    under ``per_key``)."""
    probe = _obs_probe.make_bank_probe_stats(bank, probe_key)
    cstats = getattr(controller, "stats", None)

    def stats_fn(t, batch, bcount, carry, m, obs):
        t = jnp.asarray(t, jnp.int32)
        keys_t, _ = _split_keyed(batch)
        state = carry[0]
        row = {
            "t": t,
            "bcount": jnp.asarray(bcount, jnp.int32),
            "metric": jnp.asarray(m["metric"], jnp.float32),
            "size": jnp.asarray(m["size"], jnp.int32),
            "overflow": jnp.asarray(m["overflow"], jnp.int32),
            "retrain": (t + 1) % retrain_every == 0,
            "ntouched": jnp.asarray(obs["ntouched"], jnp.int32),
            "invalid": jnp.asarray(obs["invalid"], jnp.int32),
        }
        d = jnp.asarray(obs["decay"], jnp.float32)
        # a [K] per-key factor vector reports the probed tenant's lane
        row["decay"] = d if d.ndim == 0 else d[probe_key]
        row.update(probe(state, keys_t, bcount))
        row["pending_min"] = jnp.asarray(state.pending.min(), jnp.float32)
        if cstats is not None:
            cs = carry[2]
            if per_key:
                cs = jax.tree_util.tree_map(lambda a: a[0], cs)
            row.update(cstats(cs))
        return row

    return stats_fn


def _init_carry(bank: SamplerBank, model: ModelAdapter, batches,
                train_keys, per_key: bool, controller):
    Q = _as_train_keys(train_keys, bank.num_keys).shape[0]
    state = bank.init(keyed_item_proto(batches))
    params = model.init()
    if per_key:
        params = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (Q,) + a.shape), params
        )
    carry = (state, params)
    if controller is not None:
        cstate = controller.init()
        if per_key:
            cstate = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(
                    jnp.asarray(a)[None], (Q,) + jnp.asarray(a).shape
                ),
                cstate,
            )
        carry = carry + (cstate,)
    return carry


def make_bank_run_loop(bank: SamplerBank, model: ModelAdapter, *,
                       retrain_every: int = 1, train_keys,
                       per_key: bool = False, superbatch: int | None = None,
                       controller=None, telemetry=None) -> Callable:
    """Compile the keyed-stream management loop once.

    Returns ``run(key, batches, bcounts) -> (state, params, trace)``:

      * ``batches``: a dict with the int32 ``"key"`` column [T, b] plus the
        payload fields (leaves [T, b, ...]) -- the layout
        :func:`repro.manage.materialize_stream` produces for a
        :class:`repro.data.streams.KeyedStream` with
        ``fields=("key", ...)``;
      * ``train_keys``: the key subset that is retrained on / traced (for
        Zipf streams ``range(Q)`` are the popular keys);
      * shared-model mode: ``trace = {"metric" f32[T], "size" i32[T, Q]}``,
        fit consumes the POOLED extract of ``train_keys``;
      * ``per_key=True``: params gain a leading [Q] axis (one model per
        train key), ``trace["metric"]`` is [T, Q] -- each key's prequential
        loss on its own arrivals (NaN on ticks it did not arrive). The
        per-key eval windows are zero-padded ``bcap`` batches with a
        per-key ``bcount``: adapters must honor ``bcount`` for a correct
        metric (all closed-form adapters do; for
        :func:`repro.manage.make_sgd_adapter` pass ``row_loss=`` -- its
        default scalar loss averages the zero padding in, the same caveat
        as the sharded loop's padded shard segments);
      * ``controller``: a :func:`repro.decay.loss_ratio` driven globally
        (shared mode, scalar metric) or vmapped per key (``per_key=True``:
        each key's loss drives its own lambda; untrained keys follow the
        bank's schedule).

    Memoized like :func:`repro.manage.make_run_loop`; ``superbatch`` chunks
    the scan with the same divisor rule, bit-identically.

    ``telemetry``: an optional :class:`repro.obs.Telemetry` handle -- the
    loop drains per-tick records (routing gauges + the probed tenant's
    Thm 4.1 columns, ``telemetry.probe_key`` defaulting to key 0) at
    ``telemetry.every``-tick boundaries, with the SAME ``(state, params,
    trace)`` outputs bit-for-bit as ``telemetry=None``.
    """

    def build():
        G = _effective_superbatch(superbatch, retrain_every)
        full, fast = _make_bank_ticks(bank, model, retrain_every, train_keys,
                                      per_key, controller,
                                      with_obs=telemetry is not None)
        if telemetry is None:
            scan = _superbatched_scan(full, fast, G)
        else:
            pk = 0 if telemetry.probe_key is None else int(telemetry.probe_key)
            stats = _make_bank_stats(bank, controller, per_key, retrain_every,
                                     pk)
            if telemetry.resolve_transport() == "fetch":
                scan = _telemetry_fetch_scan(full, fast, G, telemetry, stats)
            else:
                scan = _telemetry_scan(full, fast, G, telemetry, stats)

        @jax.jit
        def run(key, batches, bcounts):
            carry0 = _init_carry(bank, model, batches, train_keys, per_key,
                                 controller)
            if telemetry is None:
                carry, trace = scan(key, carry0, batches, bcounts)
                return carry[0], carry[1], trace
            carry, trace, aux = scan(key, carry0, batches, bcounts)
            return carry[0], carry[1], trace, aux

        if telemetry is None:
            return run
        return _wrap_run_header(run, telemetry,
                                scheme=f"bank.{bank.scheme}", G=G,
                                init=bank.init, proto_of=keyed_item_proto)

    return _memoized(
        "bank_run_loop",
        (bank, model, retrain_every, _memo_key(train_keys), per_key,
         superbatch, controller, telemetry),
        build,
    )


def make_sharded_bank_loop(bank: SamplerBank, model: ModelAdapter, mesh, *,
                           retrain_every: int = 1, train_keys,
                           per_key: bool = False,
                           superbatch: int | None = None,
                           telemetry=None) -> Callable:
    """The key-sharded bank loop: keys split across devices, zero payload
    collectives.

    ``bank`` is the LOCAL per-shard bank (``num_keys`` = K/S keys, key ids
    localized); ``batches``/``bcounts`` are the co-partitioned keyed stream
    from :func:`shard_keyed_stream` (leaves [T, S*b_s, ...] with shard s
    owning slots [s*b_s, (s+1)*b_s), local key ids; bcounts [T, S]).
    ``train_keys`` are LOCAL ids, the same subset on every shard (each
    shard's models train on its own keys). Per tick the ONLY cross-device
    traffic is the scalar psum of the |B_t|-weighted prequential metric
    (the per-key metrics of ``per_key=True`` stay shard-local); reservoirs,
    routing, payload movement, and fits are all shard-resident.

    Returns ``run(key, batches, bcounts) -> (state, params, trace)`` with
    every output in replicated gathered form (leading [S] axis via
    :func:`repro.core.distributed.gather_tree`): ``state[s]`` is shard s's
    local bank, ``params[s]`` its model (farm), ``trace`` leaves [S, T, ...]
    (the shared-mode metric rows are identical across shards -- it is the
    psum'd global metric).
    """
    from jax.sharding import PartitionSpec as P

    axis = distributed.AXIS

    def build():
        G = _effective_superbatch(superbatch, retrain_every)
        metric_fn = None if per_key else _psum_metric(model)
        full, fast = _make_bank_ticks(bank, model, retrain_every, train_keys,
                                      per_key, None, metric_fn=metric_fn,
                                      with_obs=telemetry is not None)
        if telemetry is None:
            scan = _superbatched_scan(full, fast, G)
        else:
            # the drained columns are shard 0's local view (its bank, its
            # key range); the host keeps only shard 0's stream
            pk = 0 if telemetry.probe_key is None else int(telemetry.probe_key)
            stats = _make_bank_stats(bank, None, per_key, retrain_every, pk)
            if telemetry.resolve_transport() == "fetch":
                scan = _telemetry_fetch_scan(full, fast, G, telemetry, stats)
            else:
                scan = _telemetry_scan(full, fast, G, telemetry, stats,
                                       shard_axis=axis)

        def body(key, batches, bcounts):
            carry0 = _init_carry(bank, model, batches, train_keys, per_key,
                                 None)
            if telemetry is None:
                carry, trace = scan(key, carry0, batches, bcounts[:, 0])
                tail = ()
            else:
                carry, trace, aux = scan(key, carry0, batches, bcounts[:, 0])
                tail = (aux,)
            return tuple(
                distributed.gather_tree(x) for x in (carry[0], carry[1],
                                                     trace)
            ) + tail

        jitted = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(None, axis), P(None, axis)),
            out_specs=tuple(P() for _ in range(3 if telemetry is None
                                              else 4)),
            check_vma=False,
        ))
        if telemetry is None:
            return jitted
        return _wrap_run_header(jitted, telemetry,
                                scheme=f"bank.{bank.scheme}", G=G,
                                init=bank.init, proto_of=keyed_item_proto)

    return _memoized(
        "sharded_bank_loop",
        (bank, model, mesh, retrain_every, _memo_key(train_keys), per_key,
         superbatch, telemetry),
        build,
    )


def shard_keyed_stream(batches: Any, bcounts, num_shards: int,
                       num_keys: int, *, bcap_s: int | None = None):
    """Re-pack a materialized KEYED stream into the key-ownership layout
    :func:`make_sharded_bank_loop` consumes.

    Keys are split into ``num_shards`` contiguous ranges of
    ``num_keys // num_shards`` (must divide); each tick's valid items move
    into their owning shard's segment (arrival order preserved) with key
    ids LOCALIZED to the shard's range. Returns ``(batches, bcounts)`` with
    leaves [T, S*bcap_s, ...] / [T, S] int32, zero-padded per segment;
    ``bcap_s`` defaults to the max per-shard count.
    """
    if num_keys % num_shards:
        raise ValueError(
            f"num_keys={num_keys} must divide evenly over "
            f"num_shards={num_shards} contiguous key ranges"
        )
    ks = num_keys // num_shards
    keys = np.asarray(batches[KEY_FIELD])
    bcounts = np.asarray(bcounts)
    T = bcounts.shape[0]
    S = num_shards
    owner = np.clip(keys // ks, 0, S - 1)
    counts = np.zeros((T, S), np.int32)
    sel = []
    for t in range(T):
        b = int(bcounts[t])
        rows = [np.nonzero(owner[t, :b] == s)[0] for s in range(S)]
        counts[t] = [len(r) for r in rows]
        sel.append(rows)
    need = int(counts.max()) if T else 0
    bcap_s = max(need, 1) if bcap_s is None else bcap_s
    if need > bcap_s:
        raise ValueError(f"per-shard keyed batch {need} exceeds "
                         f"bcap_s={bcap_s}")

    def repack(leaf, localize=False):
        leaf = np.asarray(leaf)
        out = np.zeros((T, S * bcap_s) + leaf.shape[2:], leaf.dtype)
        for t in range(T):
            for s in range(S):
                rows = sel[t][s]
                seg = leaf[t, rows]
                if localize:
                    seg = seg - s * ks
                out[t, s * bcap_s:s * bcap_s + len(rows)] = seg
        return jnp.asarray(out)

    out = {
        f: repack(v, localize=(f == KEY_FIELD)) for f, v in batches.items()
    }
    return out, jnp.asarray(counts, jnp.int32)
