# One function per paper table. Prints ``name,us_per_call,derived`` CSV
# and dumps the machine-readable perf trajectory to BENCH_throughput.json
# (GSample/s per backend/sampler/dtype/variant).
from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import apps, comparison, quality, roofline, throughput
    from repro import compile_cache

    compile_cache.enable()

    rows = []
    records = []

    def out(line: str):
        rows.append(line)
        print(line, flush=True)

    print("name,us_per_call,derived")
    suites = [
        ("quality", quality.run),          # Tables 2/3/4
        ("throughput",                     # Figs 5/6 + fused samplers
         lambda o: throughput.run(o, records=records)),
        ("pipelined",                      # block delivery: FIFO analogue
         lambda o: throughput.pipelined_smoke(o, records=records)),
        ("service",                        # randomness-as-a-service burst
         lambda o: throughput.service_smoke(o, records=records)),
        ("comparison", comparison.run),    # Tables 5/6
        ("apps", apps.run),                # Figs 8/9 + Table 7
        ("roofline",                       # GSample/s vs bandwidth bound
         lambda o: roofline.run(o, records=records)),
    ]
    t0 = time.time()
    failures = 0
    for name, fn in suites:
        try:
            fn(out)
        except Exception as e:  # pragma: no cover
            failures += 1
            out(f"{name}/ERROR,0.0,{type(e).__name__}: {e}")
    if records:
        throughput.write_bench_json(records)
        print(f"# wrote {throughput.BENCH_JSON} ({len(records)} rows)",
              flush=True)
    print(f"# {len(rows)} rows in {time.time() - t0:.1f}s", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
