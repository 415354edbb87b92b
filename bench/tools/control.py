"""Read the comparison's control, or a planted fault, on the chip at a cell's
own size, through the harness's own run and comparison.

    python3 bench/tools/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 50 --fault fp8

Each seed is one whole run of the cell (``bench.run.run``: set-up, window,
comparison), with the timed path replaced:

  * ``fp8``, the control: the plain Mamba2 reference put in the program's
    place, every matmul operand rounded to float8 e4m3 (the configuration
    computes in bfloat16), compared with the float32 reference as the
    program's readings are;
  * any fault of ``bench/tools/faults.py``, planted in the program.

Prints one JSON line per seed: ``{"seed", "fault", "correct", "checks"}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


class _Patch:
    """``setattr`` as pytest's monkeypatch takes it; a one-shot process
    needs no undo."""

    def setattr(self, target, name, value=None):
        import importlib

        if isinstance(target, str):
            mod, attr = target.rsplit(".", 1)
            target, name, value = importlib.import_module(mod), attr, name
        setattr(target, name, value)


def control_edit(precision: str):
    """An ``edit`` for :func:`bench.run.run` that puts the reference at
    ``precision`` in the program's place."""
    def edit(cfg, traffic):
        cfg["control"] = precision

    return edit


def main(argv=None) -> None:
    from bench import run as R
    from bench.tools.faults import FAULTS

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", required=True,
                    choices=["fp8", *sorted(FAULTS)])
    args = ap.parse_args(argv)
    edit = None
    if args.fault == "fp8":
        edit = control_edit("fp8")
    else:
        FAULTS[args.fault](_Patch())
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = R.run(args.workload, seed, args.seconds, False, edit=edit)
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)


if __name__ == "__main__":
    main()
