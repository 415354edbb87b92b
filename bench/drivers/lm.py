"""LM cells: a language model retrained on a time-biased reservoir.

The window drives ``repro.manage.make_manage_step`` over an R-TBS sampler
and the SGD adapter, one dispatch per tick: prequential eval of the tick's
sequences, the sample update, and every ``retrain_every`` ticks a retrain of
``retrain_steps`` AdamW steps on minibatches drawn from the sample. Each
tick's metrics, read back, are its acknowledgement.

Set-up fills the reservoir with the sampler's own steps over the mix's
``prefill_ticks`` earlier ticks, then drives the same compiled step from the
seed through two retrain periods; those ticks compile the step, and the
comparison follows them (:meth:`Cell.check`).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen
from bench.refs import mamba2 as ref
from bench.refs import rtbs as rtbs_ref


def leaf_norms(tree) -> np.ndarray:
    return np.asarray(jax.jit(lambda t: jnp.stack(
        [jnp.linalg.norm(x.astype(jnp.float32).reshape(-1))
         for x in jax.tree_util.tree_leaves(t)]))(tree), np.float64)


def diff_norms(a, b) -> np.ndarray:
    return np.asarray(jax.jit(lambda a, b: jnp.stack(
        [jnp.linalg.norm((x - y).astype(jnp.float32).reshape(-1))
         for x, y in zip(jax.tree_util.tree_leaves(a),
                         jax.tree_util.tree_leaves(b))]))(a, b), np.float64)


def worst_leaf_gap(prog: np.ndarray, want: np.ndarray, grad: np.ndarray):
    """max over leaves of |prog - want| / max(want, median of want), over
    the leaves whose reference gradient is above a thousandth of the
    median leaf's (a gradient nought to rounding moves a leaf under Adam
    by round-off alone)."""
    keep = grad >= 1e-3 * np.median(grad)
    if not np.any(keep & (want > 0)):
        return float("inf")          # the reference moved nothing
    scale = np.maximum(want, np.median(want[keep]))
    return float(np.max(np.abs(prog - want)[keep] / scale[keep]))


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, chips: int):
        self.cfg, self.traffic, self.seed, self.chips = cfg, traffic, seed, chips
        self.E = cfg["retrain_every"]
        self.b = traffic["per_tick"]
        self.P = traffic["prefill_ticks"]
        if self.P % self.E:
            raise ValueError("prefill_ticks must be whole retrain periods")

    # ------------------------------------------------------------------ set-up
    def _model_config(self):
        from repro import config as C

        c = self.cfg
        return dataclasses.replace(
            C.get_config(c["arch"]), num_layers=c["n_layer"],
            d_model=c["d_model"], vocab_size=c["vocab_size"],
            ssm_state=c["d_state"], ssm_head_dim=c["headdim"],
            ssm_groups=c["ngroups"], ssm_expand=c["expand"],
            ssm_conv_width=c["d_conv"], ssm_chunk=c["chunk_size"],
            norm_eps=c["norm_eps"], dtype=c["dtype"],
            param_dtype=c["param_dtype"], remat=c["remat"],
            tie_embeddings=True)

    def setup(self) -> None:
        from repro.core.api import make_sampler
        from repro.manage import make_manage_step, make_sgd_adapter, tick_keys
        from repro.models import zoo
        from repro.optim import AdamWConfig, adamw_init
        from repro.train.steps import make_train_step

        c, t = self.cfg, self.traffic
        o = c["optimizer"]
        api = zoo.build(self._model_config())
        self.key = gen.seed_key(self.seed, 0)
        init = jax.jit(functools.partial(ref.init_params, c))
        params0 = init(gen.seed_key(self.seed, 1))
        want = jax.eval_shape(api.init_params, jax.random.key(0))

        def layout(tree):
            return (jax.tree_util.tree_structure(tree),
                    [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(
                        tree)])

        if layout(want) != layout(params0):
            raise RuntimeError("the program's parameter layout differs from "
                               "the one bench/refs/mamba2.py makes")
        # the adapter keeps its init for the process's life: hand it the
        # weights through a holder that set-up empties
        holder = [params0]
        adapter = make_sgd_adapter(
            init_params=lambda: holder[0],
            train_step=make_train_step(
                api, AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"],
                                 eps=o["eps"], weight_decay=o["weight_decay"],
                                 clip_norm=o["clip_norm"]),
                microbatches=1, warmup=o["warmup"],
                total_steps=o["total_steps"]),
            init_opt_state=adamw_init, loss=api.loss, batch_field="tokens",
            train_batch=c["train_batch"], retrain_steps=c["retrain_steps"],
            name=c["arch"])
        sampler = make_sampler("rtbs", n=c["reservoir_n"], lam=t["lam"])
        # make_manage_step donates the sampler state only; with the model
        # state copied in and out as well the tick does not fit one 16 GB
        # chip, so the model state is donated too, as a deployment would
        self.step = jax.jit(
            make_manage_step(sampler, adapter, retrain_every=self.E),
            donate_argnums=(2, 3))

        R = t["ring_ticks"]
        ring = gen.token_chains(
            gen.seed_key(self.seed, 2), ticks=R, per_tick=self.b,
            seq_len=c["seq_len"], vocab=c["vocab_size"],
            branching=t["branching"], flip_every=t["flip_every"])
        self.ring = ring
        self.ticks = list(ring)
        self.bcount = jnp.int32(self.b)

        @jax.jit
        def prefill(key, state, batches):
            def body(st, x):
                tt, batch = x
                return sampler.step(tick_keys(key, tt)[0], st, batch,
                                    jnp.int32(batch.shape[0])), None

            return jax.lax.scan(body, state, (jnp.arange(self.P), batches))[0]

        state = sampler.init(jax.ShapeDtypeStruct((c["seq_len"],), jnp.int32))
        self.state = prefill(self.key, state, ring[:self.P])
        self.model = adapter.init()
        holder.clear()
        del params0
        # two retrain periods through the window's own compiled step: they
        # compile it, and the comparison follows them
        self.records = []
        for i in range(2 * self.E):
            tt = self.P + i
            self.state, self.model, m = self.step(
                self.key, jnp.int32(tt), self.state, self.model,
                self.ticks[tt], self.bcount)
            m = jax.device_get(m)
            self.records.append((tt, float(m["metric"]), int(m["size"])))
            if i == self.E - 1:      # after the first retrain
                st = self.state
                self.view = {"items": np.asarray(st.lat.items),
                             "weight": float(st.lat.weight)}
                self.prog_m = leaf_norms(self.model["opt"]["m"])
                # the first tick consumed the weights it was given: make
                # them again from the seed
                self.prog_dp = diff_norms(self.model["params"],
                                          init(gen.seed_key(self.seed, 1)))
        self.t = self.P + 2 * self.E
        jax.block_until_ready((self.state, self.model))

    # ------------------------------------------------------------------ window
    def window(self, seconds: float) -> dict:
        ann = jax.profiler.TraceAnnotation
        starts, acks, ts = [], [], []
        t0 = time.perf_counter()
        with ann("bench.window"):
            while True:
                if self.t + self.E > len(self.ticks):
                    raise RuntimeError(
                        f"the mix's ring of {len(self.ticks)} ticks ran out "
                        f"{time.perf_counter() - t0:.1f}s into the window")
                for _ in range(self.E):
                    tt = self.t
                    s = time.perf_counter()
                    with ann("bench.tick"):
                        with ann("bench.dispatch"):
                            self.state, self.model, m = self.step(
                                self.key, jnp.int32(tt), self.state,
                                self.model, self.ticks[tt], self.bcount)
                        with ann("bench.ack"):
                            m = jax.device_get(m)
                    e = time.perf_counter()
                    starts.append(s)
                    acks.append(e)
                    ts.append(tt)
                    self.records.append((tt, float(m["metric"]),
                                         int(m["size"])))
                    self.t += 1
                if e - t0 >= seconds:
                    break
        window_s = acks[-1] - t0
        # items of tick i wait until the retrain that closes i's period ends
        stale = [acks[(i // self.E + 1) * self.E - 1] - starts[i]
                 for i in range(len(ts))]
        n = len(ts)
        self.counts = {"ticks": n, "retrains": n // self.E,
                       "items": n * self.b,
                       "eval_tokens": n * self.b * self.cfg["seq_len"],
                       "trained_tokens": (n // self.E) * self.cfg[
                           "retrain_steps"] * self.cfg["train_batch"]
                       * self.cfg["seq_len"]}
        return {
            "window_s": window_s,
            "attempted": n * self.b,
            "failed": 0,
            "metrics": {
                "ingest_items_per_s": (n * self.b / window_s, "items/s"),
                "staleness_p95_s": (float(np.percentile(
                    np.repeat(stale, self.b), 95)), "s"),
            },
        }

    def programs(self):
        """(jitted, args) of the programs the window drives, for the scope
        map of a trace."""
        return [(self.step, (self.key, jnp.int32(self.t), self.state,
                             self.model, self.ticks[0], self.bcount))]

    def model_params(self) -> int:
        from bench.flops import mamba2_param_count

        c = self.cfg
        return mamba2_param_count({
            "d_model": c["d_model"], "num_hidden_layers": c["n_layer"],
            "vocab_size": c["vocab_size"], "expand": c["expand"],
            "state_size": c["d_state"], "n_groups": c["ngroups"],
            "head_dim": c["headdim"], "conv_kernel": c["d_conv"]})

    # ------------------------------------------------------------ comparison
    def finish(self) -> None:
        """Keep what the comparison needs, free the program's state."""
        st = self.state
        last = self.t - 1
        self.final = {
            "W": float(st.total_weight), "C": float(st.lat.weight),
            "nfull": int(st.lat.nfull),
            "hash": gen.hash64(np.asarray(gen.row_hash(st.lat.items,
                                                       lead=1))),
            "offered": gen.hash64(np.asarray(gen.row_hash(
                self.ring[:last + 1], lead=2))).reshape(-1),
            "last": last,
        }
        P, E = self.P, self.E
        self.eval_ticks = [P, P + 1, P + E, P + E + 1]
        self.eval_tokens = {tt: np.asarray(self.ticks[tt])
                            for tt in self.eval_ticks}
        del self.state, self.model, self.step, self.ticks, self.ring, st

    def sample_errors(self) -> int:
        c, f = self.cfg, self.final
        n = c["reservoir_n"]
        w = rtbs_ref.weights(np.full(f["last"] + 1, self.b), self.traffic[
            "lam"])
        bad = sum(not rtbs_ref.sample_size_ok(size, w[tt], n)
                  for tt, _, size in self.records)
        k = f["nfull"] + (1 if f["C"] - math.floor(f["C"]) > 0 else 0)
        bad += not rtbs_ref.sample_size_ok(k, w[-1], n)
        stored = f["hash"][:k]
        bad += int(np.sum(~np.isin(stored, f["offered"])))
        bad += int(k - np.unique(stored).size)
        self.w_gap = abs(f["W"] - w[-1]) / max(w[-1], 1.0)
        # the stored items' ages, from the position of their arrival
        order = np.argsort(f["offered"], kind="stable")
        at = order[np.searchsorted(f["offered"], stored, sorter=order)
                   .clip(0, order.size - 1)]
        known = f["offered"][at] == stored
        ages = f["last"] - at // self.b
        nf = min(f["nfull"], k)
        partial = ages[nf] if k > nf and known[nf] else None
        self.age_z = rtbs_ref.age_band_z(
            ages[:nf][known[:nf]], partial, f["C"] - math.floor(f["C"]),
            np.full(f["last"] + 1, self.b), self.traffic["lam"], n)
        return int(bad)

    def reference(self, precision: str) -> dict:
        """The reference's readings over set-up's first two periods:
        eval losses at four ticks, the first moment and the parameters'
        change after the first retrain."""
        from repro.manage import tick_keys

        c = self.cfg
        rows = c["ref_block_rows"]
        init = jax.jit(functools.partial(ref.init_params, c))
        p0 = init(gen.seed_key(self.seed, 1))
        E, P = self.E, self.P
        losses = {}
        for tt in self.eval_ticks[:2]:
            losses[tt] = ref.eval_loss(c, p0, self.eval_tokens[tt], rows=rows,
                                       precision=precision)
        # the first retrain: the view the tick's extract realised, the
        # minibatches keyed from the tick's fit key
        _, k_extract, k_fit = tick_keys(self.key, P + E - 1)
        cap = self.view["items"].shape[0]
        wgt = self.view["weight"]
        k0, frac = math.floor(wgt), wgt - math.floor(wgt)
        take = bool(jax.random.bernoulli(k_extract, frac)) and frac > 0
        mask = (np.arange(cap) < k0) | ((np.arange(cap) == k0) & take)
        opt = ref.adamw_init(p0)
        p1, opt = ref.retrain(c, c["optimizer"], p0, opt, self.view["items"],
                              jnp.asarray(mask), k_fit,
                              steps=c["retrain_steps"],
                              batch=c["train_batch"],
                              rows=c["ref_train_rows"], precision=precision)
        for tt in self.eval_ticks[2:]:
            losses[tt] = ref.eval_loss(c, p1, self.eval_tokens[tt], rows=rows,
                                       precision=precision)
        out = {"loss": losses, "m": leaf_norms(opt["m"])}
        del opt
        # the retrain consumed the weights it started from: make them again
        out["dp"] = diff_norms(p1, init(gen.seed_key(self.seed, 1)))
        return out

    def compare(self, got: dict, want: dict) -> dict:
        eval_gap = max(abs(got["loss"][tt] - want["loss"][tt])
                       / abs(want["loss"][tt]) for tt in self.eval_ticks)
        return {"eval_gap": eval_gap,
                "m_gap": worst_leaf_gap(got["m"], want["m"], want["m"]),
                "update_gap": worst_leaf_gap(got["dp"], want["dp"],
                                             want["m"])}

    def program_readings(self) -> dict:
        """What the program computed; with ``control`` set in the
        configuration, the reference at that precision in its place."""
        if self.cfg.get("control"):
            return self.reference(self.cfg["control"])
        metric = {tt: m for tt, m, _ in self.records}
        return {"loss": {tt: metric[tt] for tt in self.eval_ticks},
                "m": self.prog_m, "dp": self.prog_dp}

    def check(self) -> list:
        lim = self.cfg["limits"]
        errors = self.sample_errors()
        gaps = self.compare(self.program_readings(), self.reference("f32"))
        return [("sample_errors", errors, lim["sample_errors"]),
                ("w_gap", self.w_gap, lim["w_gap"]),
                ("age_band_z", self.age_z, lim["age_band_z"])] + \
            [(k, v, lim[k]) for k, v in gaps.items()]
