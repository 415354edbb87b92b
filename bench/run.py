#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip, and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file (``bench/configs/<config>.json``, which names its driver
in ``bench/drivers/``), its traffic mix (``bench/traffic/<traffic>.json``)
and, with ``--trace 1``, one reader per per-layer metric
(``bench/metrics/<metric>.py``).

A run loads and warms up (``setup_s``), measures for ``--seconds``, then
compares what the window's programs produced with the plain references in
``bench/refs/``. The last lines of standard error are the numbers compared,
each with its limit; the last line of standard output is one JSON object.
A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_cell(workload: str, root: pathlib.Path = ROOT):
    """(spec, cell entry, configuration, traffic mix) of a named cell."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no cell {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return spec, cell, cfg, traffic


def driver(cfg: dict):
    return importlib.import_module(f"bench.drivers.{cfg['driver']}").Cell


def readers(spec: dict, workload: str, root: pathlib.Path = ROOT) -> dict:
    """The per-layer metrics this cell reports, name -> (reader, unit)."""
    moved = {m["name"]: m for m in spec["end_to_end"]}
    out = {}
    for m in spec["per_layer"]:
        cells = m.get("workloads")
        if cells is None:
            e2e = moved[m["moves"]]
            if workload not in e2e.get("workloads", [workload]):
                continue
        elif workload not in cells:
            continue
        path = root / "bench" / "metrics" / f"{m['name']}.py"
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_metric_{m['name'].replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        out[m["name"]] = (mod.read, m["unit"])
    return out


def end_to_end(spec: dict, workload: str) -> list[str]:
    return [m["name"] for m in spec["end_to_end"]
            if workload in m.get("workloads", [workload])]


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX finds {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def memory_in_use(chips: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.devices()[:chips])


class Context:
    """What a per-layer metric's reader gets: the reduced trace of the
    traced window, the cell (its counts and configuration) and the chip's
    peaks."""

    def __init__(self, trace, scopes, w0, w1, cell, peaks, chips):
        from bench import trace as tr

        self.tr, self.trace, self.scopes = tr, trace, scopes
        self.w0, self.w1 = w0, w1
        self.window_s = (w1 - w0) / 1e9
        self.busy_s = tr.busy_s(trace, w0, w1)
        self.cell, self.counts, self.cfg = cell, cell.counts, cell.cfg
        self.peaks, self.chips = peaks, chips

    def scope_s(self, scope: str) -> float:
        """Device seconds in ``scope`` per chip."""
        return self.tr.scope_s(self.trace, self.scopes, scope, self.w0,
                               self.w1) / max(self.trace.devices, 1)

    def kernel_s(self, kernel: str) -> float:
        return self.tr.kernel_s(self.trace, kernel, self.w0, self.w1) / max(
            self.trace.devices, 1)

    def module_s(self, module: str) -> float:
        return self.tr.module_s(self.trace, module, self.w0, self.w1) / max(
            self.trace.devices, 1)


def traced_window(cell, seconds: float, spec: dict, workload: str,
                  chips: int, kind: str):
    """The window under the profiler, reduced to per-layer metrics."""
    import jax

    from bench import trace as tr
    from bench.peaks import peaks_for

    peaks = peaks_for(kind)
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            res = cell.window(seconds)
        finally:
            jax.profiler.stop_trace()
        scopes = {}
        for fn, args in cell.programs():
            module, names = tr.scope_map(fn.lower(*args).compile().as_text())
            scopes[module] = names
        path = sorted(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True))[-1]
        trace = tr.load(path)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    w0, w1 = tr.window_of(trace)
    ctx = Context(trace, scopes, w0, w1, cell, peaks, chips)
    metrics = {}
    for name, (read, unit) in readers(spec, workload).items():
        v = read(ctx)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": unit}
    breakdown = {"device_ops": tr.top_ops(trace, scopes, w0, w1),
                 "idle_gaps": tr.idle_gaps(trace, w0, w1)}
    return res, metrics, {"busy_s": ctx.busy_s, "window_s": ctx.window_s}, \
        breakdown


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: pathlib.Path = ROOT, require_tpu: bool = True,
        edit=None) -> dict:
    """One run of one cell; returns the result line's object. ``edit``
    (tests only) may change the configuration and mix before the run."""
    spec, entry, cfg, traffic = load_cell(workload, root)
    import repro  # noqa: F401  (the system under test: fail first without it)

    if edit is not None:
        edit(cfg, traffic)
    chips = entry["chips"]
    dev = devices(chips, require_tpu)
    if require_tpu:
        enable_compile_cache()
    cell = driver(cfg)(cfg, traffic, seed, chips)
    log(f"{workload} seed {seed}: {dev}")
    cell.setup()
    setup_s = time.perf_counter() - T_START
    log(f"set-up done: {setup_s:.1f}s")
    if trace:
        res, metrics, extra, breakdown = traced_window(
            cell, min(seconds, traffic["trace_seconds"]), spec, workload,
            chips, dev["kind"])
        dev.update(extra)
    else:
        res = cell.window(seconds)
        metrics = {k: {"value": float(v), "unit": u}
                   for k, (v, u) in res["metrics"].items()}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        missing = set(end_to_end(spec, workload)) - set(metrics)
        if missing:
            raise RuntimeError(f"cell {workload} did not report {missing}")
    dev["memory_peak_bytes"] = memory_peak(chips)
    log(f"window done: {res['window_s']:.1f}s, "
        f"{res['attempted']} items; peak {dev['memory_peak_bytes']} B")
    cell.finish()
    log(f"state freed: {memory_in_use(chips)} B in use")
    checks = cell.check()
    log("comparison done")
    out = {"correct": all(v <= lim for _, v, lim in checks),
           "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics, "device": dev}
    if trace:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
