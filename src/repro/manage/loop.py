"""The paper's online model-management loop, fused into one compiled program.

This is the connective tissue the headline claim needs (paper Sec. 1, Fig. 2):
maintain a time-biased sample over the stream, periodically retrain a model on
the realized sample, and evaluate/serve the freshest model -- here as a single
``lax.scan`` over stream batches so the whole loop compiles once and never
leaves the device (DESIGN.md Sec. 8):

    for each tick t (scanned):
      1. metric_t = model.evaluate(params, B_t)     # prequential: eval BEFORE
      2. state    = sampler.step(key_t, state, B_t) # the model/sampler see B_t
      3. if (t+1) % retrain_every == 0:
           params = model.fit(key_t', params, sampler.extract(key_t'', state))

Entry points:
  * :func:`make_run_loop`  -- compile the loop once for a (sampler, model,
                              retrain cadence); reuse across streams/seeds.
  * :func:`run_loop`       -- convenience one-shot wrapper.
  * :func:`make_run_farm` / :func:`run_farm` -- ``vmap`` the whole loop over
    Monte-Carlo trials (the paper's Fig. 12/13 robustness protocol: many
    sampler realizations over one stream, metric quantiles over trials).
  * :func:`materialize_stream` -- stack a host-side generator from
    :mod:`repro.data.streams` into the fixed-shape [T, bcap, ...] arrays the
    scan consumes.

The ``make_*`` builders are memoized on (sampler, model, retrain cadence), so
the one-shot wrappers and the Fig. 12/13 drivers never recompile an identical
program (Samplers/ModelAdapters hash by identity).

Distributed schemes (the paper's Sec. 5 D-R-TBS / D-T-TBS) run the SAME loop
at cluster scale (DESIGN.md Sec. 10):
  * :func:`make_sharded_run_loop` -- the identical tick structure, with the
    whole scan running under ``shard_map`` over the ``data`` mesh axis:
    co-partitioned batches, replicated params, one psum per tick, and a
    global-:class:`~repro.core.api.SampleView` assembly (all_gather of shard
    prefixes + the reserved fractional-item slot) feeding ``model.fit``.
  * :func:`make_sharded_manage_step` -- the unfused per-tick shard_map driver
    (one dispatch per tick, state round-tripped through its replicated
    :func:`~repro.core.distributed.gather_tree` snapshot); bit-identical to
    the fused loop, and the benchmark's comparison point.
  * :func:`make_sharded_run_farm` -- Monte-Carlo trials ``vmap``-ed INSIDE the
    shard_map over replicated trial keys, sharing one co-partitioned stream.
  * :func:`make_sharded_resume_loop` -- checkpoint/resume for the fused
    sharded run: consume a ``gather_tree`` snapshot + a global start tick and
    continue bit-exactly (the key discipline below makes this trivial).
  * :func:`shard_stream` -- re-pack a :func:`materialize_stream` output into
    co-partitioned per-shard segments ([T, S*bcap_s, ...] / [T, S]).

Closed-loop adaptive decay (DESIGN.md Sec. 12): every loop builder accepts
``controller=`` (a :class:`repro.decay.AdaptiveDecay`); the controller's rate
drives ``sampler.step_decayed`` each tick, the prequential metric feeds the
controller back, and the rate adjustment is gated on retrain ticks -- all
inside the same compiled scan, superbatch-compatible, with the applied
factor logged in the trace under ``"decay"``.

Key discipline (bit-exact replays, and what tests assert): tick t uses
``fold_in(key, t)`` split into (step, extract, fit) subkeys, so a fused run,
an unfused per-tick driver, and a checkpoint-resumed run all see identical
randomness. Sharded runs pass the SAME replicated key to every shard (the
samplers fold in the shard index where shard-local draws are needed), so the
discipline carries over unchanged. On non-retrain ticks only the cheap
``sampler.size`` path runs -- ``extract`` (a prefix permutation + RNG draw for
R-TBS) happens under the retrain ``lax.cond``, with identical traces because
size and extract consume the same fold_in subkey.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributed
from repro.core.api import Sampler
from repro.manage.models import ModelAdapter
from repro.obs import probe as _obs_probe
from repro.obs.profile import scope as _scope


def tick_keys(key: jax.Array, t) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The loop's per-tick (step, extract, fit) keys -- public so unfused
    drivers and tests can reproduce the fused loop exactly."""
    return tuple(jax.random.split(jax.random.fold_in(key, t), 3))


def item_proto(batches: Any) -> Any:
    """ONE-item prototype from stacked stream arrays (leaves [T, bcap, ...])."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[2:], a.dtype), batches
    )


def _check_local(sampler: Sampler) -> None:
    if sampler.distributed:
        raise ValueError(
            f"sampler {sampler.scheme!r} is a per-shard scheme: its step/extract "
            f"must run under jax.shard_map over the {distributed.AXIS!r} axis "
            "and cannot drive the single-host manage loop directly -- use "
            "make_sharded_run_loop(sampler, model, mesh)"
        )


def _check_sharded(sampler: Sampler) -> None:
    if not sampler.distributed or sampler.extract_global is None:
        raise ValueError(
            f"sampler {sampler.scheme!r} is a local scheme: the sharded manage "
            "loop needs per-shard step/extract_global closures (drtbs/dttbs) "
            "-- use make_run_loop for local schemes"
        )


def _check_controllable(sampler: Sampler) -> None:
    if sampler.step_decayed is None:
        raise ValueError(
            f"sampler {sampler.scheme!r} has no decay to control (no "
            "step_decayed closure) -- the adaptive controller drives the "
            "time-biased schemes (rtbs/ttbs/btbs/drtbs/dttbs), not the "
            "decay-free baselines"
        )


def _effective_superbatch(superbatch: int | None, retrain_every: int) -> int:
    """Resolve the superbatch chunk size G: the largest divisor of
    ``retrain_every`` not exceeding the requested size. G must divide
    ``retrain_every`` so that within a G-tick chunk only the LAST tick can be
    a retrain tick -- the first G-1 ticks then compile with no fit branch at
    all (DESIGN.md Sec. 11).

    Default (None): 8 on TPU, 1 elsewhere. On CPU the XLA while-loop already
    optimizes the small per-tick body best and a per-tick ``lax.cond`` is
    free, so unrolling REGRESSES throughput ~2x (measured, recorded in
    BENCH_manage_loop.json's ``manage_loop_fused_sb8`` row); on TPU the
    chunked body amortizes per-iteration dispatch and carry double-buffering.
    """
    if superbatch is None:
        superbatch = 8 if jax.default_backend() == "tpu" else 1
    want = max(int(superbatch), 1)
    g = min(want, retrain_every)
    while retrain_every % g:
        g -= 1
    return g


def _make_fast_tick(sampler: Sampler, model: ModelAdapter) -> Callable:
    """The non-retrain fast path of a superbatched chunk: evaluate + step +
    the payload-free size metric, with NO fit conditional in the trace.
    Bit-identical to :func:`make_manage_step`'s tick on ticks where
    ``(t+1) % retrain_every != 0`` (same tick_keys, same op order)."""

    def fast(key, t, state, params, batch_items, bcount):
        k_step, k_extract, _ = tick_keys(key, t)
        with _scope("manage.eval"):
            metric = model.evaluate(params, batch_items, bcount)
        with _scope("manage.sampler_step"):
            state = sampler.step(k_step, state, batch_items, bcount)
        with _scope("manage.size"):
            size = sampler.size(k_extract, state)
        return state, {"metric": metric, "size": size}

    return fast


def _make_controlled_ticks(sampler: Sampler, model: ModelAdapter,
                           controller, retrain_every: int,
                           metric_fn: Callable | None = None,
                           extract_attr: str = "extract",
                           size_attr: str = "size") -> tuple[Callable, Callable]:
    """Carry-form (full, fast) ticks with a closed-loop decay controller
    (:mod:`repro.decay.adaptive`) in the loop: carry is ``(state, params,
    cstate)``.  Per tick the controller's current rate feeds
    ``sampler.step_decayed`` and the prequential metric feeds
    ``controller.observe``; the lambda *adjustment* is gated on retrain ticks
    (``adjust = do_fit``), so the controller only reacts at the cadence where
    the loss can actually respond to a rate change.  The fast tick passes a
    static ``adjust=False`` -- same arithmetic as the full tick's traced
    False, so superbatched runs stay bit-identical to G=1.  The per-tick
    factor ``d_t`` is logged in the trace under ``"decay"``.

    ``metric_fn``/``extract_attr``/``size_attr`` let the sharded loop reuse
    this skeleton with its psum'd metric and global extract closures.
    """
    metric_of = metric_fn or (
        lambda params, b, c: model.evaluate(params, b, c)
    )
    extract = getattr(sampler, extract_attr)
    size = getattr(sampler, size_attr)

    def full(key, t, carry, batch_items, bcount):
        state, params, cstate = carry
        k_step, k_extract, k_fit = tick_keys(key, t)
        with _scope("manage.eval"):
            metric = metric_of(params, batch_items, bcount)
        with _scope("manage.sampler_step"):
            d = controller.rate(cstate)
            state = sampler.step_decayed(k_step, state, batch_items, bcount,
                                         d)
        do_fit = (t + 1) % retrain_every == 0
        cstate = controller.observe(cstate, metric, do_fit)
        with _scope("manage.retrain"):
            params = jax.lax.cond(
                do_fit,
                lambda: model.fit(k_fit, params, extract(k_extract, state)),
                lambda: params,
            )
        with _scope("manage.size"):
            m = {"metric": metric, "size": size(k_extract, state), "decay": d}
        return (state, params, cstate), m

    def fast(key, t, carry, batch_items, bcount):
        state, params, cstate = carry
        k_step, k_extract, _ = tick_keys(key, t)
        with _scope("manage.eval"):
            metric = metric_of(params, batch_items, bcount)
        with _scope("manage.sampler_step"):
            d = controller.rate(cstate)
            state = sampler.step_decayed(k_step, state, batch_items, bcount,
                                         d)
        cstate = controller.observe(cstate, metric, False)
        with _scope("manage.size"):
            m = {"metric": metric, "size": size(k_extract, state), "decay": d}
        return (state, params, cstate), m

    return full, fast


def _superbatched_scan(tick: Callable, fast: Callable, G: int) -> Callable:
    """The chunked-scan skeleton shared by the local and sharded loops:
    ``scan(key, carry0, batches, bcounts, t0=0) -> (carry, trace)``.

    ``tick``/``fast`` operate on an opaque loop carry -- ``(key, t, carry,
    batch, bcount) -> (carry, metrics)`` -- so the same skeleton serves the
    plain (state, params) loops and the controller-augmented ones. ``t0``
    offsets the global tick index (checkpoint/resume: the resumed segment
    replays ``fold_in(key, t0 + i)`` exactly as the unbroken run would);
    callers must keep ``t0 % G == 0`` so chunk boundaries stay aligned with
    the retrain cadence.

    Scans T//G chunks of G ticks; within a chunk the first G-1 ticks run the
    cond-free ``fast`` path (G divides the retrain cadence, so only the last
    tick of a chunk can retrain -- :func:`_effective_superbatch`) and the
    last runs the full ``tick``. Tail ticks (T % G) run ``tick`` unrolled
    after the scan. Bit-identical to the G=1 per-tick scan for any G."""

    def scan(key, carry0, batches, bcounts, t0=0):
        T = bcounts.shape[0]
        nchunks = T // G
        Tm = nchunks * G
        t0 = jnp.asarray(t0, jnp.int32)

        def at(tree, idx):
            return jax.tree_util.tree_map(lambda a: a[idx], tree)

        def chunk(a):
            return a[:Tm].reshape((nchunks, G) + a.shape[1:])

        def chunk_body(carry, inp):
            ct, cb, cc = inp
            ms = []
            for g in range(G - 1):       # unrolled, no retrain conditional
                carry, m = fast(key, ct[g], carry, at(cb, g), cc[g])
                ms.append(m)
            carry, m = tick(key, ct[G - 1], carry, at(cb, G - 1), cc[G - 1])
            ms.append(m)
            metrics = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ms)
            return carry, metrics

        carry, trace = jax.lax.scan(
            chunk_body, carry0,
            (chunk(t0 + jnp.arange(T, dtype=jnp.int32)),
             jax.tree_util.tree_map(chunk, batches), chunk(bcounts)),
        )
        trace = jax.tree_util.tree_map(
            lambda a: a.reshape((Tm,) + a.shape[2:]), trace
        )
        tails = []
        for t in range(Tm, T):
            carry, m = tick(key, t0 + jnp.int32(t), carry,
                            at(batches, t), bcounts[t])
            tails.append(m)
        if tails:
            tailm = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *tails)
            trace = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b]), trace, tailm
            )
        return carry, trace

    return scan


def _wrap_stats(fn: Callable, stats_fn: Callable) -> Callable:
    """Wrap a loop tick so its metrics become ``(m, row)``: the trace entry
    plus one fixed-shape telemetry stats row. A tick's metrics dict may
    carry a reserved ``"_obs"`` entry (telemetry-only columns, e.g. bank
    routing stats): it is diverted to ``stats_fn`` and stripped from the
    trace."""

    def wrapped(key, t, carry, batch, bcount):
        carry, m = fn(key, t, carry, batch, bcount)
        obs = {}
        if isinstance(m, dict) and "_obs" in m:
            m = dict(m)
            obs = m.pop("_obs")
        with _scope("obs.stats"):
            row = stats_fn(t, batch, bcount, carry, m, obs)
        return carry, (m, row)

    return wrapped


def _telemetry_fetch_scan(tick: Callable, fast: Callable, G: int, telem,
                          stats_fn: Callable) -> Callable:
    """The ``"fetch"`` drain transport (DESIGN.md Sec. 14): the plain
    :func:`_superbatched_scan` with the per-tick stats rows riding the scan
    ys next to the trace -- NO host callback anywhere in the compiled
    module. ``scan(...) -> (carry, trace, rows)`` where ``rows`` is the
    [T]-stacked column dict; the run wrapper (:func:`_wrap_run_header`)
    fetches it after the jitted call and feeds ``telem.every``-tick blocks
    to :meth:`repro.obs.Telemetry._drain_cb`, preserving the callback
    transport's tick-record stream (same records, same order; only the
    trailing partial block may coalesce where the callback transport
    drains rem-chunks and unrolled tails separately). Fast ticks do zero
    host transfers; the one fetch at the end
    is the explicitly-allowed drain (the wrapper opts it out of
    ``jax.transfer_guard_device_to_host``)."""
    tick_w, fast_w = _wrap_stats(tick, stats_fn), _wrap_stats(fast, stats_fn)
    inner = _superbatched_scan(tick_w, fast_w, G)

    def scan(key, carry0, batches, bcounts, t0=0):
        carry, (trace, rows) = inner(key, carry0, batches, bcounts, t0)
        return carry, trace, rows

    return scan


def _telemetry_scan(tick: Callable, fast: Callable, G: int, telem,
                    stats_fn: Callable,
                    shard_axis: str | None = None) -> Callable:
    """The :func:`_superbatched_scan` skeleton with in-scan telemetry
    (DESIGN.md Sec. 14): every tick additionally computes one fixed-shape
    stats row (``stats_fn(t, batch, bcount, carry, m, obs) -> {col:
    scalar}``), rows accumulate on-device in the scan stack, and blocks of
    ``telem.every`` ticks (rounded down to whole G-chunks, floor one chunk)
    drain to :meth:`repro.obs.Telemetry._drain_cb` at chunk-group
    boundaries -- the fast ticks inside a chunk never touch the host, and
    the drain does not trip ``jax.transfer_guard_device_to_host`` (asserted
    in tests/test_obs.py).

    The drain transport is ``jax.pure_callback`` with a token chained
    through every drain, NOT the effectful callbacks: any effect-carrying
    host callback (``io_callback`` ordered or not, ``debug.callback``) in
    the compiled module serializes XLA:CPU thunk execution and was measured
    to cost ~40% on the cap-4096 fused loop REGARDLESS of drain frequency
    -- even a single top-level drain per run; ``pure_callback`` keeps the
    concurrent executor and measures in the noise (benchmarks/
    obs_overhead.py). Each drain consumes the previous drain's token and
    returns the next, so the data dependency forces drains to run in stream
    order, and the final token is threaded out of the jitted program by
    every caller so the chain is never dead-code-eliminated. The callback
    mutates host state behind a nominally pure op -- sanctioned here because
    nothing in the computation reads it back: worst case under exotic
    re-execution is a duplicated telemetry block, never a wrong sample.

    Structure: the T//G chunks are grouped into periods of P = every // G
    chunks; an outer scan over whole periods runs an inner scan of P chunks
    then drains the period's P*G rows; leftover chunks (< P) run in one more
    scan with their own drain; tail ticks (T % G) run unrolled and drain
    last. The tick composition -- G-1 fast + 1 full per chunk, tails full --
    is IDENTICAL to :func:`_superbatched_scan`, so the returned ``(carry,
    trace)`` is bit-identical to the telemetry-off loop for any (G, every).
    Returns ``scan(key, carry0, batches, bcounts, t0=0) -> (carry, trace,
    token)``.

    A tick's metrics dict may carry a reserved ``"_obs"`` entry (telemetry-
    only columns, e.g. bank routing stats): it is diverted to ``stats_fn``
    and stripped from the trace. Under ``shard_map`` pass ``shard_axis``:
    every shard drains (the callback fires per shard) but the host keeps
    only shard 0's stream -- the stats columns are replicated or shard-0
    quantities by construction, and so is the returned token.
    """
    P = max(int(telem.every) // G, 1)

    def _host_drain(me, rows, tok):
        telem._drain_cb(me, rows)
        return np.int32(int(tok) + 1)

    tick_w, fast_w = _wrap_stats(tick, stats_fn), _wrap_stats(fast, stats_fn)

    def scan(key, carry0, batches, bcounts, t0=0):
        T = bcounts.shape[0]
        nchunks = T // G
        Tm = nchunks * G
        t0 = jnp.asarray(t0, jnp.int32)
        nper = nchunks // P
        Tp = nper * P * G
        me = (jax.lax.axis_index(shard_axis) if shard_axis is not None
              else jnp.int32(0))
        ticks = t0 + jnp.arange(T, dtype=jnp.int32)

        def at(tree, idx):
            return jax.tree_util.tree_map(lambda a: a[idx], tree)

        def part(tree, lo, hi, prefix):
            return jax.tree_util.tree_map(
                lambda a: a[lo:hi].reshape(prefix + a.shape[1:]), tree
            )

        def chunk_body(carry, inp):
            ct, cb, cc = inp
            outs = []
            for g in range(G - 1):
                carry, o = fast_w(key, ct[g], carry, at(cb, g), cc[g])
                outs.append(o)
            carry, o = tick_w(key, ct[G - 1], carry, at(cb, G - 1), cc[G - 1])
            outs.append(o)
            return carry, jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *outs
            )

        def drain(rows_flat, tok):
            return jax.pure_callback(
                _host_drain, jax.ShapeDtypeStruct((), jnp.int32),
                me, rows_flat, tok,
            )

        def flat2(tree, n):
            return jax.tree_util.tree_map(
                lambda a: a.reshape((n,) + a.shape[2:]), tree
            )

        traces = []
        carry = carry0
        tok = jnp.int32(0)

        if nper:
            inp = (part(ticks, 0, Tp, (nper, P, G)),
                   part(batches, 0, Tp, (nper, P, G)),
                   part(bcounts, 0, Tp, (nper, P, G)))

            def period_body(ct, pin):
                carry, tok = ct
                carry, (m, rows) = jax.lax.scan(chunk_body, carry, pin)
                tok = drain(flat2(rows, P * G), tok)
                return (carry, tok), m

            (carry, tok), m = jax.lax.scan(period_body, (carry, tok), inp)
            traces.append(jax.tree_util.tree_map(
                lambda a: a.reshape((Tp,) + a.shape[3:]), m
            ))

        rem = nchunks - nper * P
        if rem:
            inp = (part(ticks, Tp, Tm, (rem, G)),
                   part(batches, Tp, Tm, (rem, G)),
                   part(bcounts, Tp, Tm, (rem, G)))
            carry, (m, rows) = jax.lax.scan(chunk_body, carry, inp)
            tok = drain(flat2(rows, rem * G), tok)
            traces.append(flat2(m, rem * G))

        tails_m, tails_r = [], []
        for t in range(Tm, T):
            carry, (m, row) = tick_w(key, t0 + jnp.int32(t), carry,
                                     at(batches, t), bcounts[t])
            tails_m.append(m)
            tails_r.append(row)
        if tails_r:
            tok = drain(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                               *tails_r), tok)
            traces.append(jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *tails_m
            ))

        if not traces:  # T == 0: an empty scan still shapes the trace
            carry, (m, _) = jax.lax.scan(
                chunk_body, carry,
                (part(ticks, 0, 0, (0, G)), part(batches, 0, 0, (0, G)),
                 part(bcounts, 0, 0, (0, G))),
            )
            return carry, flat2(m, 0), tok

        trace = traces[0] if len(traces) == 1 else jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs), *traces
        )
        return carry, trace, tok

    return scan


def _make_loop_stats(sampler: Sampler, controller,
                     retrain_every: int) -> Callable:
    """The single-sampler loops' telemetry row: per-tick sample size, the
    stored mass C / decayed weight W gauges (:func:`repro.obs.probe.
    make_state_stats`), the retrain flag, the applied decay factor (from the
    controller trace entry, else the schedule's static rate), and the
    controller's lambda/hold/pulse gauges when one is in the carry."""
    state_stats = _obs_probe.make_state_stats(sampler)
    d0 = _obs_probe.static_decay(sampler)
    cstats = getattr(controller, "stats", None)

    def stats_fn(t, batch, bcount, carry, m, obs):
        del batch, obs
        t = jnp.asarray(t, jnp.int32)
        row = {
            "t": t,
            "bcount": jnp.asarray(bcount, jnp.int32),
            "metric": jnp.asarray(m["metric"], jnp.float32),
            "size": jnp.asarray(m["size"], jnp.int32),
            "retrain": (t + 1) % retrain_every == 0,
        }
        row.update(state_stats(carry[0]))
        if "decay" in m:
            row["decay"] = jnp.asarray(m["decay"], jnp.float32)
        elif d0 is not None:
            row["decay"] = jnp.float32(d0)
        if cstats is not None:
            row.update(cstats(carry[2]))
        return row

    return stats_fn


def _wrap_run_header(jitted: Callable, telemetry, *, scheme: str, G: int,
                     init: Callable, proto_of: Callable) -> Callable:
    """Wrap a compiled loop so each invocation opens a telemetry run: one
    ``kind="run"`` header record (static facts incl. the reservoir-state
    bytes gauge via ``jax.eval_shape``, computed once per loop -- nothing
    materializes), then the jitted call. The jitted program returns the
    user outputs plus a transport-dependent aux: the drain-chain token
    (:func:`_telemetry_scan` -- blocking on it guarantees every drained
    record has reached the sinks) or the stacked rows dict
    (:func:`_telemetry_fetch_scan` -- drained here, in ``telemetry.every``
    blocks, through the same ``_drain_cb``). Either way the aux is stripped
    from what the caller sees."""
    cache: dict = {}

    def run(key, batches, bcounts):
        if "state_bytes" not in cache:
            try:
                cache["state_bytes"] = _obs_probe.state_nbytes(
                    init, proto_of(batches))
            except Exception:
                cache["state_bytes"] = None  # e.g. init needs a collective
        telemetry.open_run({
            "scheme": scheme,
            "ticks": int(bcounts.shape[0]),
            "superbatch": G,
            "every": telemetry.every,
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "state_bytes": cache["state_bytes"],
        })
        *out, aux = jitted(key, batches, bcounts)
        if isinstance(aux, dict):  # fetch transport: drain the stacked rows
            with jax.transfer_guard_device_to_host("allow"):
                cols = {k: np.asarray(v) for k, v in aux.items()}
            n = min((c.shape[0] for c in cols.values()), default=0)
            every = max(telemetry.every // G, 1) * G
            for s in range(0, n, every):
                telemetry._drain_cb(
                    0, {k: c[s:s + every] for k, c in cols.items()})
        else:
            jax.block_until_ready(aux)  # the chain: all drains have landed
        telemetry.flush()
        return tuple(out)

    return run


def _pair_carry(tick: Callable, fast: Callable) -> tuple[Callable, Callable]:
    """Adapt the public (state, params)-signature tick builders to the
    opaque-carry contract of :func:`_superbatched_scan`."""

    def tick_c(key, t, carry, batch_items, bcount):
        state, params, m = tick(key, t, carry[0], carry[1], batch_items, bcount)
        return (state, params), m

    def fast_c(key, t, carry, batch_items, bcount):
        state, m = fast(key, t, carry[0], carry[1], batch_items, bcount)
        return (state, carry[1]), m

    return tick_c, fast_c


def _make_local_tick(sampler: Sampler, model: ModelAdapter,
                     retrain_every: int) -> Callable:
    """The raw (unjitted) local tick body shared by :func:`make_run_loop`'s
    scan and the jitted per-tick driver :func:`make_manage_step`."""

    def step(key, t, state, params, batch_items, bcount):
        k_step, k_extract, k_fit = tick_keys(key, t)
        with _scope("manage.eval"):
            metric = model.evaluate(params, batch_items, bcount)
        # the retrain takes the model state only once the eval has read it:
        # unordered, XLA copies the parameters into and out of the retrain
        # `cond` (a copy of the parameters each, every tick)
        metric, params = jax.lax.optimization_barrier((metric, params))
        with _scope("manage.sampler_step"):
            state = sampler.step(k_step, state, batch_items, bcount)

        # extract (full prefix permutation + realization draw) only runs on
        # retrain ticks; the per-tick size metric takes the payload-free path.
        # Both consume k_extract, so sizes/views agree and traces are
        # unchanged vs. extracting every tick.
        do_fit = (t + 1) % retrain_every == 0
        with _scope("manage.retrain"):
            params = jax.lax.cond(
                do_fit,
                lambda: model.fit(k_fit, params,
                                  sampler.extract(k_extract, state)),
                lambda: params,
            )
        with _scope("manage.size"):
            metrics = {"metric": metric,
                       "size": sampler.size(k_extract, state)}
        return state, params, metrics

    return step


def make_manage_step(sampler: Sampler, model: ModelAdapter, *,
                     retrain_every: int = 1) -> Callable:
    """One tick of the loop as its own jitted dispatch: ``(key, t, state,
    params, batch, bcount) -> (state, params, metrics)``. Composable: the
    same tick body is what :func:`make_run_loop` scans, so driving it
    tick-by-tick (checkpointing, serving, human-in-the-loop) stays
    bit-identical to the fused run.

    The sampler ``state`` (arg 2) is DONATED on backends that support
    donation (not CPU), matching the sharded per-tick driver: the driver
    round-trips the reservoir every dispatch, so donation lets XLA reuse its
    buffers in place instead of double-buffering -- do not reuse a state
    after passing it in. The reservoir stays device-resident across ticks:
    nothing in the tick forces a host copy (asserted under a
    device-to-host transfer guard in tests/test_api.py)."""
    _check_local(sampler)

    def build():
        donate = () if jax.default_backend() == "cpu" else (2,)
        return jax.jit(_make_local_tick(sampler, model, retrain_every),
                       donate_argnums=donate)

    return _memoized(
        "manage_step",
        (sampler, model, retrain_every, jax.default_backend()),
        build,
    )


_BUILD_CACHE: OrderedDict[tuple, Callable] = OrderedDict()
_BUILD_CACHE_MAX = 64


def _memoized(kind: str, key: tuple, build: Callable[[], Callable]) -> Callable:
    """Memoize compiled-loop builders on (kind, sampler, model, ...): repeat
    calls (the one-shot wrappers, the Fig. 12/13 drivers re-dispatching per
    scheme/seed) return the SAME jitted callable, so jax's jit cache is hit
    instead of re-tracing an identical program.

    LRU-bounded: Samplers/ModelAdapters hash by identity, so a sweep that
    builds a fresh sampler per configuration gets no hits and would otherwise
    pin every compiled program for process lifetime."""
    full = (kind, *key)
    hit = _BUILD_CACHE.get(full)
    if hit is None:
        hit = _BUILD_CACHE[full] = build()
        if len(_BUILD_CACHE) > _BUILD_CACHE_MAX:
            _BUILD_CACHE.popitem(last=False)
    else:
        _BUILD_CACHE.move_to_end(full)
    return hit


def make_run_loop(sampler: Sampler, model: ModelAdapter, *,
                  retrain_every: int = 1,
                  superbatch: int | None = None,
                  controller=None, telemetry=None) -> Callable:
    """Compile the full-stream loop once.

    Returns ``run(key, batches, bcounts) -> (state, params, trace)`` where
    ``batches`` leaves are [T, bcap, ...], ``bcounts`` is [T] int32, and
    ``trace`` holds per-tick {"metric" f32[T], "size" i32[T]}. The whole
    stream is consumed by ONE jitted ``lax.scan`` -- no per-tick dispatch.

    ``superbatch`` coalesces G consecutive ticks into one chunked scan body
    (G = largest divisor of ``retrain_every`` <= superbatch; default: 8 on
    TPU, 1 elsewhere -- see :func:`_effective_superbatch`): the first G-1
    ticks of each chunk are unrolled WITHOUT the retrain conditional, so the
    non-retrain fast path pays scan bookkeeping (carry double-buffering,
    per-iteration dispatch) once per chunk instead of once per tick. Results
    are bit-identical for any G (asserted in tests).

    ``controller`` (a :class:`repro.decay.AdaptiveDecay`) closes the loop
    between the prequential metric and the sampler's decay rate INSIDE the
    same compiled scan (DESIGN.md Sec. 12): each tick the controller's
    current rate drives ``sampler.step_decayed`` and the metric updates the
    controller; the rate adjustment itself is gated on retrain ticks. The
    trace gains a per-tick ``"decay"`` entry (the applied factor d_t). The
    sampler must be decay-capable (rtbs/ttbs/btbs); without a controller the
    program is exactly the historical one.

    ``telemetry`` (a :class:`repro.obs.Telemetry`) threads in-scan
    observability (DESIGN.md Sec. 14): every tick computes a stats row
    on-device and ``telemetry.every``-tick blocks drain to the host sinks
    over the handle's transport (fetched as jit outputs after the run, or
    live at chunk-group boundaries through a token-chained
    ``pure_callback``); each invocation additionally emits a ``kind="run"``
    header. The returned ``(state,
    params, trace)`` stays bit-identical to the telemetry-off program
    (asserted in tests/test_obs.py); ``telemetry=None`` compiles exactly
    the historical loop.

    Memoized on ``(sampler, model, retrain_every, superbatch, controller,
    telemetry)``: repeat calls return the same compiled callable.
    """
    return _memoized(
        "run_loop",
        (sampler, model, retrain_every, superbatch, controller, telemetry),
        lambda: _build_run_loop(sampler, model, retrain_every, superbatch,
                                controller, telemetry),
    )


def _build_run_loop(sampler: Sampler, model: ModelAdapter,
                    retrain_every: int, superbatch: int | None,
                    controller=None, telemetry=None) -> Callable:
    _check_local(sampler)
    if controller is None:
        tick, fast = _pair_carry(
            _make_local_tick(sampler, model, retrain_every),
            _make_fast_tick(sampler, model),
        )
    else:
        _check_controllable(sampler)
        tick, fast = _make_controlled_ticks(sampler, model, controller,
                                            retrain_every)
    G = _effective_superbatch(superbatch, retrain_every)
    if telemetry is None:
        scan = _superbatched_scan(tick, fast, G)
    else:
        stats = _make_loop_stats(sampler, controller, retrain_every)
        if telemetry.resolve_transport() == "fetch":
            scan = _telemetry_fetch_scan(tick, fast, G, telemetry, stats)
        else:
            scan = _telemetry_scan(tick, fast, G, telemetry, stats)

    @jax.jit
    def run(key, batches, bcounts):
        carry0 = (sampler.init(item_proto(batches)), model.init())
        if controller is not None:
            carry0 = carry0 + (controller.init(),)
        if telemetry is None:
            carry, trace = scan(key, carry0, batches, bcounts)
            return carry[0], carry[1], trace
        carry, trace, aux = scan(key, carry0, batches, bcounts)
        return carry[0], carry[1], trace, aux

    if telemetry is None:
        return run
    return _wrap_run_header(run, telemetry, scheme=sampler.scheme, G=G,
                            init=sampler.init, proto_of=item_proto)


def run_loop(key: jax.Array, sampler: Sampler, model: ModelAdapter,
             batches: Any, bcounts: jax.Array, *, retrain_every: int = 1,
             superbatch: int | None = None, controller=None):
    """One-shot convenience wrapper over :func:`make_run_loop`."""
    return make_run_loop(sampler, model, retrain_every=retrain_every,
                         superbatch=superbatch,
                         controller=controller)(key, batches, bcounts)


def make_run_farm(sampler: Sampler, model: ModelAdapter, *,
                  retrain_every: int = 1,
                  superbatch: int | None = None,
                  controller=None) -> Callable:
    """Monte-Carlo farm: ``farm(key, trials, batches, bcounts) -> trace``.

    ``vmap`` of the fused loop over ``trials`` independent sampler/model
    randomness streams sharing one data stream; trace leaves gain a leading
    [trials] axis. This is the Fig. 12/13 robustness protocol (mean + expected
    shortfall over realizations) as one compiled program. Memoized like
    :func:`make_run_loop`; ``controller`` is threaded through unchanged (each
    trial carries its own controller state).
    """

    def build():
        run = make_run_loop(sampler, model, retrain_every=retrain_every,
                            superbatch=superbatch, controller=controller)

        def farm(key, trials: int, batches, bcounts):
            keys = jax.random.split(key, trials)
            _, _, trace = jax.vmap(lambda k: run(k, batches, bcounts))(keys)
            return trace

        return farm

    return _memoized(
        "run_farm", (sampler, model, retrain_every, superbatch, controller),
        build
    )


def run_farm(key: jax.Array, trials: int, sampler: Sampler,
             model: ModelAdapter, batches: Any, bcounts: jax.Array, *,
             retrain_every: int = 1, superbatch: int | None = None,
             controller=None):
    """One-shot convenience wrapper over :func:`make_run_farm`."""
    return make_run_farm(sampler, model, retrain_every=retrain_every,
                         superbatch=superbatch,
                         controller=controller)(key, trials, batches, bcounts)


# ---------------------------------------------------------------------------
# the sharded loop: the same tick, run per-shard under shard_map (paper Sec. 5)
# ---------------------------------------------------------------------------
def _make_sharded_tick(sampler: Sampler, model: ModelAdapter,
                       retrain_every: int) -> Callable:
    """The per-shard tick body shared by the fused loop and the per-tick
    driver. Mirrors :func:`make_manage_step` exactly, with the three global
    touch points of the paper's Fig. 6(b) protocol:

      * the prequential metric is the |B_t|-weighted psum of per-shard metrics
        (NaN only when the GLOBAL tick is empty). The weighting assumes
        ``model.evaluate`` honors ``bcount`` (all closed-form adapters do);
        an adapter that averages over every row -- the SGD adapter's default
        scalar LM loss -- additionally needs padding-free shard segments, see
        :func:`repro.manage.models.make_sgd_adapter`,
      * ``model.fit`` consumes ``sampler.extract_global`` -- the replicated
        whole-mesh :class:`~repro.core.api.SampleView` -- so params stay
        replicated by construction,
      * the per-tick size metric takes the payload-free ``size_global`` path
        (extract_global's all_gather only runs on retrain ticks).
    """
    metric_of = _psum_metric(model)

    def tick(key, t, state, params, batch_items, bcount):
        k_step, k_extract, k_fit = tick_keys(key, t)
        with _scope("manage.eval"):
            metric = metric_of(params, batch_items, bcount)

        with _scope("manage.sampler_step"):
            state = sampler.step(k_step, state, batch_items, bcount)

        do_fit = (t + 1) % retrain_every == 0
        with _scope("manage.retrain"):
            params = jax.lax.cond(
                do_fit,
                lambda: model.fit(
                    k_fit, params, sampler.extract_global(k_extract, state)
                ),
                lambda: params,
            )
        with _scope("manage.size"):
            size = sampler.size_global(k_extract, state)
        return state, params, {"metric": metric, "size": size}

    return tick


def _psum_metric(model: ModelAdapter) -> Callable:
    """The sharded loops' prequential metric: |B_t|-weighted psum of
    per-shard metrics over the data axis (NaN only when the GLOBAL tick is
    empty)."""
    axis = distributed.AXIS

    def metric_of(params, batch_items, bcount):
        m_s = model.evaluate(params, batch_items, bcount)
        w_s = jnp.asarray(bcount, jnp.float32)
        num = jax.lax.psum(jnp.where(bcount > 0, m_s, 0.0) * w_s, axis)
        den = jax.lax.psum(w_s, axis)
        return jnp.where(den > 0, num / jnp.maximum(den, 1.0),
                         jnp.float32(jnp.nan))

    return metric_of


def _make_sharded_fast_tick(sampler: Sampler, model: ModelAdapter) -> Callable:
    """Sharded analogue of :func:`_make_fast_tick`: the per-shard tick without
    the retrain conditional (no extract_global all_gather in the trace) --
    the superbatched chunk's non-retrain fast path."""
    metric_of = _psum_metric(model)

    def fast(key, t, state, params, batch_items, bcount):
        k_step, k_extract, _ = tick_keys(key, t)
        with _scope("manage.eval"):
            metric = metric_of(params, batch_items, bcount)
        with _scope("manage.sampler_step"):
            state = sampler.step(k_step, state, batch_items, bcount)
        with _scope("manage.size"):
            size = sampler.size_global(k_extract, state)
        return state, {"metric": metric, "size": size}

    return fast


def _sharded_in_specs(axis):
    from jax.sharding import PartitionSpec as P

    # (key replicated, batch leaves [T, S*bcap_s, ...] split on dim 1,
    #  bcounts [T, S] split on dim 1); P(None, axis) broadcasts over the
    # batches pytree as a spec prefix.
    return (P(), P(None, axis), P(None, axis))


def _make_controlled_sharded_ticks(sampler: Sampler, model: ModelAdapter,
                                   controller,
                                   retrain_every: int) -> tuple[Callable, Callable]:
    """Sharded controller ticks: the :func:`_make_controlled_ticks` skeleton
    with the psum'd metric and the global extract/size closures. The metric
    fed to ``controller.observe`` is the replicated global one and the
    controller update is deterministic, so the controller state stays
    replicated across shards by construction."""
    return _make_controlled_ticks(
        sampler, model, controller, retrain_every,
        metric_fn=_psum_metric(model),
        extract_attr="extract_global",
        size_attr="size_global",
    )


def make_sharded_run_loop(sampler: Sampler, model: ModelAdapter, mesh, *,
                          retrain_every: int = 1,
                          superbatch: int | None = None,
                          controller=None, telemetry=None) -> Callable:
    """Compile the paper's model-management loop for a sharded sampler.

    Returns ``run(key, batches, bcounts) -> (state, params, trace)``:

      * ``batches``: pytree, leaves [T, S*bcap_s, ...] -- tick t's arrivals,
        co-partitioned so shard s owns slots [s*bcap_s, (s+1)*bcap_s)
        (:func:`shard_stream` builds this layout from a materialized stream);
      * ``bcounts``: [T, S] int32 valid-prefix counts per shard (empty shards
        are fine -- the schemes psum the global |B_t|);
      * ``state``: the final sampler state as the replicated
        :func:`~repro.core.distributed.gather_tree` snapshot (every leaf
        gains a leading [S] axis);
      * ``params``/``trace``: replicated, identical shapes and key discipline
        as :func:`make_run_loop`.

    The whole stream runs as ONE jitted ``lax.scan`` executing inside
    ``shard_map`` over the ``data`` axis, so reservoir shards stay resident on
    their devices for the entire stream: per tick there is exactly one scalar
    psum (|B_t|) plus the sampler's own tiny count collectives, and payloads
    cross shards only inside ``extract_global`` on retrain ticks.
    ``superbatch`` chunks the scan exactly as in :func:`make_run_loop` (the
    non-retrain fast ticks additionally drop the retrain-gated all_gather
    from their trace). ``controller`` threads the closed-loop decay
    controller exactly as in :func:`make_run_loop` -- it observes the psum'd
    global metric, so its state stays replicated. ``telemetry`` threads
    in-scan observability exactly as in :func:`make_run_loop`; every shard
    reaches the drain callback with its own axis index and the host
    keeps only shard 0's stream (the drained columns are replicated or
    shard-0 gauges). Memoized on ``(sampler, model, mesh, retrain_every,
    superbatch, controller, telemetry)``.
    """
    _check_sharded(sampler)
    if controller is not None:
        _check_controllable(sampler)

    def build():
        jitted = jax.jit(jax.shard_map(
            _sharded_loop_body(sampler, model, retrain_every, superbatch,
                               controller, telemetry),
            mesh=mesh,
            in_specs=_sharded_in_specs(distributed.AXIS),
            out_specs=_replicated_out_specs(3 if telemetry is None else 4),
            check_vma=False,
        ))
        if telemetry is None:
            return jitted
        return _wrap_run_header(
            jitted, telemetry, scheme=sampler.scheme,
            G=_effective_superbatch(superbatch, retrain_every),
            init=sampler.init, proto_of=item_proto,
        )

    return _memoized(
        "sharded_run_loop",
        (sampler, model, mesh, retrain_every, superbatch, controller,
         telemetry),
        build,
    )


def _replicated_out_specs(n: int = 3):
    from jax.sharding import PartitionSpec as P

    # gathered state / params / trace (+ the drain token under telemetry,
    # identical on every shard) are replicated by construction
    return tuple(P() for _ in range(n))


def _sharded_loop_body(sampler: Sampler, model: ModelAdapter,
                       retrain_every: int,
                       superbatch: int | None = None,
                       controller=None, telemetry=None) -> Callable:
    """Per-shard whole-stream program: superbatched scan of the sharded tick
    (the :func:`_superbatched_scan` skeleton, same chunking contract as
    :func:`_build_run_loop`). With ``telemetry`` the scan drains stats rows
    per shard (the host filters to shard 0 via the axis index)."""
    if controller is None:
        tick, fast = _pair_carry(
            _make_sharded_tick(sampler, model, retrain_every),
            _make_sharded_fast_tick(sampler, model),
        )
    else:
        tick, fast = _make_controlled_sharded_ticks(sampler, model,
                                                    controller, retrain_every)
    G = _effective_superbatch(superbatch, retrain_every)
    if telemetry is None:
        scan = _superbatched_scan(tick, fast, G)
    else:
        stats = _make_loop_stats(sampler, controller, retrain_every)
        if telemetry.resolve_transport() == "fetch":
            # rows ride out as replicated-or-shard-0 outputs (out_spec P())
            scan = _telemetry_fetch_scan(tick, fast, G, telemetry, stats)
        else:
            scan = _telemetry_scan(tick, fast, G, telemetry, stats,
                                   shard_axis=distributed.AXIS)

    def loop(key, batches, bcounts):
        # per-shard views: batch leaves [T, bcap_s, ...], bcounts [T, 1]
        carry0 = (sampler.init(item_proto(batches)), model.init())
        if controller is not None:
            carry0 = carry0 + (controller.init(),)
        if telemetry is None:
            carry, trace = scan(key, carry0, batches, bcounts[:, 0])
            return distributed.gather_tree(carry[0]), carry[1], trace
        carry, trace, aux = scan(key, carry0, batches, bcounts[:, 0])
        return distributed.gather_tree(carry[0]), carry[1], trace, aux

    return loop


def make_sharded_manage_step(sampler: Sampler, model: ModelAdapter, mesh, *,
                             retrain_every: int = 1,
                             controller=None) -> Callable:
    """ONE tick of the sharded loop as its own dispatch: ``(key, t, state,
    params, batch_t, bcount_t) -> (state, params, metrics)``.

    ``state`` is the replicated :func:`~repro.core.distributed.gather_tree`
    snapshot (leading [S] axis on every leaf) -- the same form the fused loop
    returns -- so fused and per-tick runs compose/resume bit-exactly; each
    shard slices its own row back out on entry. ``batch_t`` leaves are
    [S*bcap_s, ...], ``bcount_t`` is [S]. This is the unfused comparison
    point: per-tick dispatch + the snapshot all_gather every tick, which the
    fused scan amortizes away (see benchmarks/manage_loop.py).

    ``controller`` (a :class:`repro.decay.AdaptiveDecay`) threads the
    closed-loop decay controller exactly as in
    :func:`make_sharded_run_loop` -- the signature becomes ``(key, t, state,
    params, cstate, batch_t, bcount_t) -> (state, params, cstate, metrics)``
    with the replicated controller state round-tripped alongside, and the
    per-tick arithmetic (rate -> step_decayed -> observe, adjustment gated
    on retrain ticks) is the SAME controlled tick the fused loop scans, so
    fused and per-tick controlled runs stay bit-identical (asserted in
    tests/test_sharded_loop.py).

    The ``state_g`` snapshot is DONATED on backends that support donation
    (not CPU): the driver round-trips it every dispatch, so donation lets
    XLA reuse the reservoir buffers in place instead of double-buffering
    them -- do not reuse a snapshot after passing it in.
    """
    _check_sharded(sampler)
    if controller is not None:
        _check_controllable(sampler)

    def build():
        from jax.sharding import PartitionSpec as P

        axis = distributed.AXIS
        donate = () if jax.default_backend() == "cpu" else (2,)

        if controller is None:
            tick = _make_sharded_tick(sampler, model, retrain_every)

            def step(key, t, state_g, params, batch_items, bcount):
                me = jax.lax.axis_index(axis)
                state = jax.tree_util.tree_map(lambda a: a[me], state_g)
                state, params, metrics = tick(key, t, state, params,
                                              batch_items, bcount[0])
                return distributed.gather_tree(state), params, metrics

            return jax.jit(jax.shard_map(
                step, mesh=mesh,
                in_specs=(P(), P(), P(), P(), P(axis), P(axis)),
                out_specs=_replicated_out_specs(),
                check_vma=False,
            ), donate_argnums=donate)

        ctick, _ = _make_controlled_sharded_ticks(sampler, model, controller,
                                                  retrain_every)

        def cstep(key, t, state_g, params, cstate, batch_items, bcount):
            me = jax.lax.axis_index(axis)
            state = jax.tree_util.tree_map(lambda a: a[me], state_g)
            (state, params, cstate), metrics = ctick(
                key, t, (state, params, cstate), batch_items, bcount[0]
            )
            return distributed.gather_tree(state), params, cstate, metrics

        return jax.jit(jax.shard_map(
            cstep, mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(), P(axis), P(axis)),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        ), donate_argnums=donate)

    return _memoized(
        "sharded_manage_step",
        (sampler, model, mesh, retrain_every, controller), build
    )


def make_sharded_run_farm(sampler: Sampler, model: ModelAdapter, mesh, *,
                          retrain_every: int = 1,
                          superbatch: int | None = None,
                          controller=None) -> Callable:
    """Monte-Carlo farm of the sharded loop: ``farm(key, trials, batches,
    bcounts) -> (states, params, trace)`` with a leading [trials] axis on
    every output leaf.

    Trials are ``vmap``-ed INSIDE the shard_map over replicated trial keys
    (one co-partitioned stream shared by all trials), so the collectives
    batch across trials instead of re-entering the mesh per trial -- the
    Fig. 12/13 robustness protocol at cluster scale. ``controller`` threads
    the closed-loop decay controller per trial, as in :func:`make_run_farm`.
    """
    _check_sharded(sampler)
    if controller is not None:
        _check_controllable(sampler)

    def build():
        loop = _sharded_loop_body(sampler, model, retrain_every, superbatch,
                                  controller)

        def farm_shard(keys, batches, bcounts):
            return jax.vmap(lambda k: loop(k, batches, bcounts))(keys)

        run = jax.jit(jax.shard_map(
            farm_shard, mesh=mesh,
            in_specs=_sharded_in_specs(distributed.AXIS),
            out_specs=_replicated_out_specs(),
            check_vma=False,
        ))

        def farm(key, trials: int, batches, bcounts):
            keys = jax.random.split(key, trials)
            return run(keys, batches, bcounts)

        return farm

    return _memoized(
        "sharded_run_farm",
        (sampler, model, mesh, retrain_every, superbatch, controller),
        build
    )


def make_sharded_resume_loop(sampler: Sampler, model: ModelAdapter, mesh, *,
                             retrain_every: int = 1,
                             superbatch: int | None = None,
                             controller=None) -> Callable:
    """The sharded loop's checkpoint/resume entry point: continue a fused
    sharded run from its replicated :func:`~repro.core.distributed.gather_tree`
    snapshot.

    Returns ``run(key, snapshot, params, batches, bcounts, t0) -> (snapshot,
    params, trace)`` (with ``controller``: ``run(key, snapshot, params,
    cstate, batches, bcounts, t0) -> (snapshot, params, cstate, trace)``):

      * ``snapshot``: the replicated gathered sampler state exactly as the
        fused run / :func:`init_sharded_state` return it (leading [S] axis on
        every leaf; each shard slices its own row back out on entry);
      * ``batches``/``bcounts``: the co-partitioned SEGMENT to consume, laid
        out as for :func:`make_sharded_run_loop`;
      * ``t0``: the global tick index of the segment's first batch -- the
        loop replays ``fold_in(key, t0 + i)``, so running ``[0, T)`` in one
        go and running ``[0, T1) + [T1, T)`` through this entry point are
        bit-identical (asserted in tests/test_sharded_loop.py). ``t0`` must
        be a concrete int and a multiple of the superbatch chunk G (checked
        here; keep checkpoint boundaries on the retrain cadence and this
        holds for free).

    Serialize ``(snapshot, params[, cstate], next_tick)`` with
    :mod:`repro.checkpoint` for durable restarts -- ``launch/train.py``
    wires exactly that for ``--scheme drtbs|dttbs --ckpt-dir``. Memoized
    like the other builders; ``t0`` is a traced operand, so resuming from
    different ticks reuses one compiled program.
    """
    _check_sharded(sampler)
    if controller is not None:
        _check_controllable(sampler)

    def build():
        from jax.sharding import PartitionSpec as P

        G = _effective_superbatch(superbatch, retrain_every)
        axis = distributed.AXIS
        if controller is None:
            tick, fast = _pair_carry(
                _make_sharded_tick(sampler, model, retrain_every),
                _make_sharded_fast_tick(sampler, model),
            )
        else:
            tick, fast = _make_controlled_sharded_ticks(
                sampler, model, controller, retrain_every
            )
        scan = _superbatched_scan(tick, fast, G)

        def body(key, snapshot, params, aux, batches, bcounts, t0):
            me = jax.lax.axis_index(axis)
            state = jax.tree_util.tree_map(lambda a: a[me], snapshot)
            carry0 = (state, params) + aux
            carry, trace = scan(key, carry0, batches, bcounts[:, 0], t0)
            return (distributed.gather_tree(carry[0]),) + carry[1:] + (trace,)

        nout = 3 if controller is None else 4
        jitted = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(None, axis), P(None, axis), P()),
            out_specs=(P(),) * nout,
            check_vma=False,
        ))

        def run(key, snapshot, params, *rest):
            *aux, batches, bcounts, t0 = rest
            if int(t0) % G:
                raise ValueError(
                    f"resume tick t0={int(t0)} must be a multiple of the "
                    f"superbatch chunk G={G}, or chunk boundaries would "
                    "drift off the retrain cadence"
                )
            return jitted(key, snapshot, params, tuple(aux), batches,
                          bcounts, jnp.int32(t0))

        return run

    return _memoized(
        "sharded_resume_loop",
        (sampler, model, mesh, retrain_every, superbatch, controller),
        build
    )


def init_sharded_state(sampler: Sampler, num_shards: int, proto: Any) -> Any:
    """The t=0 state in the replicated gathered form the per-tick driver
    round-trips: ``sampler.init`` per shard, stacked on a leading [S] axis
    (bit-identical to ``gather_tree`` of S freshly-initialized shards)."""
    state0 = sampler.init(proto)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (num_shards,) + a.shape), state0
    )


def shard_stream(batches: Any, bcounts: jax.Array, num_shards: int, *,
                 bcap_s: int | None = None):
    """Re-pack a :func:`materialize_stream` output into the co-partitioned
    layout the sharded loop consumes.

    Tick t's ``bcounts[t]`` valid items are split contiguously and evenly
    over ``num_shards`` (shard s of tick t gets ``floor(b/S) + (s < b mod S)``
    items -- uneven and empty shards are fine). Returns ``(batches, bcounts)``
    with leaves [T, S*bcap_s, ...] / [T, S] int32, zero-padded per shard
    segment; ``bcap_s`` defaults to the max per-shard count.
    """
    bcounts = np.asarray(bcounts)
    T = bcounts.shape[0]
    S = num_shards
    counts = np.zeros((T, S), np.int32)
    for t in range(T):
        b = int(bcounts[t])
        counts[t] = b // S + (np.arange(S) < b % S)
    need = int(counts.max()) if T else 0
    bcap_s = max(need, 1) if bcap_s is None else bcap_s
    if need > bcap_s:
        raise ValueError(f"per-shard batch {need} exceeds bcap_s={bcap_s}")

    def repack(leaf):
        leaf = np.asarray(leaf)
        out = np.zeros((T, S * bcap_s) + leaf.shape[2:], leaf.dtype)
        for t in range(T):
            off = 0
            for s in range(S):
                c = int(counts[t, s])
                out[t, s * bcap_s:s * bcap_s + c] = leaf[t, off:off + c]
                off += c
        return jnp.asarray(out)

    return (
        jax.tree_util.tree_map(repack, batches),
        jnp.asarray(counts, jnp.int32),
    )


def materialize_stream(stream: Any, T: int, *, batch_size: int | Callable,
                       mode: int | Callable = 0, bcap: int | None = None,
                       fields: tuple[str, ...] = ("x", "y")):
    """Stack ``stream.batch(t, size, mode)`` for t in [0, T) into scan inputs.

    ``batch_size`` / ``mode`` may be ints or ``t -> int`` schedules (compose
    with :func:`repro.data.streams.batch_size_schedule` / ``mode_schedule``).
    Generators returning tuples are zipped into a dict over ``fields``; a
    single-array stream (e.g. token sequences) stays a bare array. Returns
    ``(batches, bcounts)`` with leaves [T, bcap, ...] / [T] int32, batches
    zero-padded up to ``bcap`` (default: the max tick size).
    """
    size_of = batch_size if callable(batch_size) else (lambda t: batch_size)
    mode_of = mode if callable(mode) else (lambda t: mode)
    sizes = [int(size_of(t)) for t in range(T)]
    bcap = max(sizes) if bcap is None else bcap
    if max(sizes) > bcap:
        raise ValueError(f"batch size {max(sizes)} exceeds bcap={bcap}")

    raw = [stream.batch(t, sizes[t], mode_of(t)) for t in range(T)]
    as_dict = isinstance(raw[0], tuple)
    if as_dict:
        raw = [dict(zip(fields, r)) for r in raw]

    def pad_stack(leaves):
        out = np.zeros((T, bcap) + leaves[0].shape[1:], leaves[0].dtype)
        for t, leaf in enumerate(leaves):
            out[t, : leaf.shape[0]] = leaf
        return jnp.asarray(out)

    if as_dict:
        batches = {
            f: pad_stack([r[f] for r in raw]) for f in raw[0]
        }
    else:
        batches = pad_stack(raw)
    return batches, jnp.asarray(sizes, jnp.int32)
