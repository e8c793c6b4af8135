#!/usr/bin/env python3
"""Readings the limits of ``limits/<cell>.json`` are set from, for many
seeds in one process (set-up is paid once for the compiled programs):

    python3 benchmarks/chip/calibrate.py --workload <name> --seeds 1,2,3 \\
        --seconds 8 [--control] [--fault frozen|half|answer] --out FILE

For each seed one short window runs at the cell's own load and the check's
numbers are read: of the program as it is (the lower readings), of the
program with a fault planted (``--fault``, ``faults.py``), and with
``--control`` of the reference computed in bfloat16 in the program's place
(the upper readings). One JSON line per seed goes to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
if __name__ == "__main__":
    sys.path[0:1] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]

from benchmarks.chip import faults, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=faults.FAULTS, default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="offer queries at this rate instead of the mix's "
                         "(the sweep that finds the sustained rate)")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    if args.rate is not None:
        cell["traffic"]["queries"]["rate_per_s"] = args.rate
    devs = run.tpu_devices(cell["chips"])
    run.compile_cache()
    undo = faults.plant(args.fault) if args.fault else (lambda: None)
    try:
        with open(args.out, "a") as f:
            for seed in (int(s) for s in args.seeds.split(",")):
                t = time.time()
                out = run.run_cell(cell, seed, args.seconds, False,
                                   devs=devs, t_start=t,
                                   control=args.control)
                line = dict(workload=args.workload, seed=seed,
                            fault=args.fault, rate=args.rate,
                            correct=out["correct"],
                            program={k: v["value"]
                                     for k, v in out["checks"].items()},
                            control=out.get("control"),
                            metrics={k: v["value"]
                                     for k, v in out["metrics"].items()},
                            seconds=time.time() - t)
                f.write(json.dumps(line) + "\n")
                f.flush()
                run.log(json.dumps(line))
    finally:
        undo()
    return 0


if __name__ == "__main__":
    sys.exit(main())
