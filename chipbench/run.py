#!/usr/bin/env python3
"""Chip benchmark: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the TPU chips the cell
asks for (``BENCHMARK.json``).  The run builds the cell's model, tuned
loader and trainer through the program's public constructors, runs set-up
(the startup DPT tune, compilation or the persistent-cache load, warm
steps), measures ``--seconds`` of steady training, checks what the timed
path produced against a plain reference, and prints one JSON object as the
last line of standard output:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer ones, read from a profiler trace of a few
steps after the window.  ``checks`` holds each compared number beside its
limit, also printed as the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, or without the
program's sources beside ``chipbench/``, the run exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seconds <= 0:
        print("chipbench: --seconds must be positive", file=sys.stderr)
        return 2
    src = os.path.join(os.path.dirname(HERE), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chipbench: the program's sources are not beside chipbench/",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    if os.environ.get("REPRO_KERNEL_IMPL"):
        print("chipbench: REPRO_KERNEL_IMPL must be unset: the benchmark "
              "runs the kernels the chip picks", file=sys.stderr)
        return 2
    import cell
    import spec
    log = cell.log

    try:
        bench = spec.benchmark()
        spec.cell(bench, args.workload)
        out = cell.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), t_start=T_START, bench=bench)
    except cell.NoChip as e:
        log(error=str(e))
        return 1
    except spec.SpecError as e:
        log(error=str(e))
        return 2
    log(cpus=os.cpu_count(), cpus_usable=len(os.sched_getaffinity(0)))
    for name, c in out["checks"].items():
        log(check=name, value=c["value"], limit=c["limit"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
