#!/usr/bin/env python3
"""Run a cell with a fault or the control planted, over several seeds.

    python3 bench/control.py --workload misrn.ctr.bulk \
        --fault wrong_counter --seeds 11,12,13 --seconds 3

``--fault none`` runs the program as it is, which gives the readings a
limit is set from; ``bench/faults.py`` lists the faults.  All seeds run in
one process, one after another.  Each run prints one JSON line with the
seed, the fault, ``correct`` and every compared number; the benchmark's
own runs never plant anything.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import faults, harness
    cell = harness.resolve(args.workload)
    harness.use_bench_cache()
    try:
        harness.devices_for(cell)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from repro import compile_cache
    compile_cache.enable()
    planted = [] if args.fault == "none" else [faults.FAULTS[args.fault]]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                             trace=False, t_start=t0, faults=planted)
        r.pop("_check_lines")
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": r["correct"],
                          "attempted": r["attempted"],
                          "metrics": r["metrics"], "checks": r["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
