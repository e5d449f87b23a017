#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload misrn.ctr.bulk --seed 7 --seconds 10 \
        --trace 0

Prints, as the last line of standard output, one JSON object: whether the
timed path's output matched the plain reference (``correct``), the calls
attempted and found wrong, the cell's end-to-end metrics (``--trace 0``)
or per-layer metrics (``--trace 1``), and the device.  Each compared
number and its limit end standard error and the line's ``checks`` key.
Off the TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell = harness.resolve(args.workload)
    harness.use_bench_cache()

    def log(s: str) -> None:
        print(f"bench: {s}", file=sys.stderr, flush=True)

    import jax  # noqa: F401
    log(f"jax imported {time.perf_counter() - T_START:.3f}s")
    try:
        harness.devices_for(cell)
    except harness.NoChip as e:
        print(f"bench: {e}; this benchmark runs only on TPU chips",
              file=sys.stderr)
        return 2
    from repro import compile_cache
    compile_cache.enable()
    result = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, log=log)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
