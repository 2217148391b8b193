"""Benchmark harness driver — one module per paper table/figure plus the
beyond-paper studies and the roofline table.

    PYTHONPATH=src python -m benchmarks.run            # full
    PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized
    PYTHONPATH=src python -m benchmarks.run --only grid_cifar,prefetch

Prints one aligned table per bench, then a greppable CSV section
(``name,key=value,...``), and archives rows under artifacts/bench/.
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time

from benchmarks.common import csv_lines, fmt_table, save_rows

BENCHES = [
    # (name, module, paper table/figure)
    ("fastpath", "benchmarks.bench_fastpath", "perf gate"),
    ("locality", "benchmarks.bench_locality", "perf gate"),
    ("cache", "benchmarks.bench_cache", "perf gate"),
    ("straggler", "benchmarks.bench_straggler", "perf gate"),
    ("resilience", "benchmarks.bench_resilience", "perf gate"),
    ("grid_cifar", "benchmarks.bench_grid_cifar", "Fig 2a/2b/4"),
    ("prefetch", "benchmarks.bench_prefetch", "Fig 3"),
    ("coco_resolution", "benchmarks.bench_coco_resolution", "Table 1a-1d"),
    ("loader_wallclock", "benchmarks.bench_loader_wallclock", "real machinery"),
    ("multihost", "benchmarks.bench_multihost", "beyond-paper"),
    ("fleet", "benchmarks.bench_fleet", "beyond-paper"),
    ("elastic", "benchmarks.bench_elastic", "beyond-paper"),
    ("goodput", "benchmarks.bench_goodput", "beyond-paper"),
    ("search_cost", "benchmarks.bench_search_cost", "beyond-paper"),
    ("online_drift", "benchmarks.bench_online_drift", "beyond-paper"),
    ("roofline_table", "benchmarks.roofline_table", "§Roofline"),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    all_csv: list[str] = []
    failures = 0
    for name, modname, ref in BENCHES:
        if only and name not in only:
            continue
        mod = importlib.import_module(modname)
        t0 = time.perf_counter()
        try:
            rows = mod.run(quick=args.quick)
        except Exception as e:  # noqa: BLE001 — keep the harness running
            failures += 1
            print(f"\n== {name} ({ref}) FAILED: {type(e).__name__}: {e}",
                  flush=True)
            continue
        dt = time.perf_counter() - t0
        save_rows(name, rows)
        print(f"\n== {getattr(mod, 'TITLE', name)} ({ref}) "
              f"[{dt:.1f}s, {len(rows)} rows] ==", flush=True)
        print(fmt_table(rows))
        all_csv.extend(csv_lines(name, rows))

    print("\n== CSV ==")
    for line in all_csv:
        print(line)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
