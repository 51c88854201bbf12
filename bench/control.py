#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... \
        [--control-seeds 3] [--seconds 5]

For each seed, in one process: the program's readings (set-up drives the
replayed windows, a short window serves traffic where the cell compares
what the window served) and, for the first ``--control-seeds`` seeds, the
control's: the plain reference computed in the precision below the
configuration's (float8 e4m3 for bfloat16) put in the program's place
and compared with the float32 reference.  One JSON line per seed.  The
benchmark's own runs do not run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run  # noqa: E402


def readings(spec: dict, seed: int, seconds: float,
             control: bool = True) -> dict:
    from bench.reference import ssm_lm
    mod = run.load_module(os.path.join(
        ROOT, "bench", "drivers", spec["traffic"]["kind"] + ".py"),
        "bench_driver_" + spec["traffic"]["kind"])
    drv = mod.Driver(spec, seed)
    drv.setup()
    if spec["traffic"]["kind"] == "decode":
        drv.window(seconds)
        drv.release()
        gc.collect()
        got = drv.readings(control=control)
        return {k: {"logit_gap": v} for k, v in got.items()}
    drv.release()
    gc.collect()
    want = list(drv.reference())
    out = {"program": drv.readings(drv.captured, want)}
    if control:
        out["control"] = drv.readings(list(drv.reference(ssm_lm.fp8)), want)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="run the control for the first N seeds (all)")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    spec = run.cell_spec(args.workload)
    run.configure_jax()
    n_control = len(args.seeds) if args.control_seeds is None \
        else args.control_seeds
    for i, seed in enumerate(args.seeds):
        out = {"workload": args.workload, "seed": seed,
               **readings(spec, seed, args.seconds, i < n_control)}
        print(json.dumps(out), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
