"""Shared benchmark helpers."""
from __future__ import annotations

import json
import os
import pathlib
import time

import numpy as np

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

def host_mesh_env() -> dict:
    """Environment for a child process that times a mesh of forced host
    (CPU) devices. A chip belongs to one process, and the parent of such a
    child has already touched JAX, so these suites run only where JAX is
    pinned to the CPU (``JAX_PLATFORMS=cpu``); anywhere else they refuse
    rather than start a child that would need the parent's device."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise SystemExit(
            "host-device mesh suites run with JAX_PLATFORMS=cpu only: their "
            "child processes would need the device the parent holds"
        )
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        str(REPO_ROOT / "src") + os.pathsep + str(REPO_ROOT)
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    env.pop("XLA_FLAGS", None)  # the worker sets its own device count
    return env


#: every BENCH_*.json written by a suite follows this shape (validated by
#: benchmarks/check_bench.py and the CI bench-smoke job):
#:   {"benchmark": str, "generated_unix": float, "jax": str, "backend": str,
#:    "smoke": bool, "provenance": {...}, "rows": [{"name": str,
#:    "us_per_call": float, ...derived}]}
BENCH_SCHEMA_KEYS = ("benchmark", "generated_unix", "jax", "backend", "smoke",
                     "provenance", "rows")

#: the run-provenance block every BENCH_*.json must carry so a number can be
#: traced to the software + hardware + tree that produced it
PROVENANCE_KEYS = ("jax", "backend", "device_kind", "commit", "timestamp")


def run_provenance() -> dict:
    """Where/when/what produced this benchmark run: jax version, backend and
    device kind, the repo commit (None outside a git checkout), and a UTC
    timestamp. Embedded in every BENCH_*.json (and usable by any other
    artifact writer)."""
    import datetime
    import subprocess

    import jax

    try:
        proc = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=str(REPO_ROOT),
            capture_output=True, text=True, timeout=10,
        )
        commit = proc.stdout.strip() if proc.returncode == 0 else None
    except Exception:  # noqa: BLE001 -- no git binary / not a checkout
        commit = None
    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "commit": commit or None,
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc
        ).isoformat(timespec="seconds"),
    }


def smoke_mode() -> bool:
    """CI smoke sizing: tiny caps / dry-run-length streams. Enabled by the
    ``--smoke`` flag of the benchmark mains or ``BENCH_SMOKE=1`` (the env var
    reaches suites invoked through benchmarks.run)."""
    import os
    import sys

    return "--smoke" in sys.argv or os.environ.get("BENCH_SMOKE") == "1"


def write_bench_json(benchmark: str, rows, *, smoke: bool | None = None):
    """Write ``BENCH_<benchmark>.json`` at the repo root from ``emit``-style
    rows, so the perf trajectory is machine-readable PR-over-PR instead of
    living only in stdout. Returns the path."""
    import jax

    payload = {
        "benchmark": benchmark,
        "generated_unix": time.time(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "smoke": smoke_mode() if smoke is None else smoke,
        "provenance": run_provenance(),
        "rows": [
            {"name": name, "us_per_call": round(float(us), 2), **derived}
            for name, us, derived in rows
        ],
    }
    path = REPO_ROOT / f"BENCH_{benchmark}.json"
    path.write_text(json.dumps(payload, indent=2) + "\n")
    return path


def time_fn(fn, *args, warmup=2, iters=10):
    """Median wall-time per call in microseconds (jit-compiled callables)."""
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def emit(rows):
    """Print ``name,us_per_call,derived`` CSV lines."""
    for name, us, derived in rows:
        dstr = ";".join(f"{k}={v}" for k, v in derived.items())
        print(f"{name},{us:.1f},{dstr}", flush=True)
