#!/usr/bin/env python3
"""Readings that the output check's limits are set from, on the chip, in
one process (so one compile serves every seed):

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 7,8,9 [--faults half_batch,...] [--out file.json]

* each ``--seeds`` seed: one run of the cell (a short window) and its
  compared numbers: the lower readings;
* each ``--control-seeds`` seed: the reference in the control's precision
  (float8 e4m3 matrix products) in the program's place, against the
  float32 reference: the control's readings;
* each fault of ``--faults`` (``faults.py``) on the control seeds: the
  program with that fault planted.

Prints one JSON line per reading and, last, the largest and smallest of
each number per group.  The benchmark's own runs do not run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    import check
    import cell
    import faults
    import plain
    import spec
    import traffic

    bench = spec.benchmark()
    w = spec.cell(bench, args.workload)
    cell.device_or_fail(w["chips"])
    model = spec.config(w["config"])
    mix = spec.traffic(w["traffic"])
    readings = []

    def emit(group, seed, nums):
        r = {"group": group, "seed": seed, **nums}
        readings.append(r)
        print(json.dumps(r), flush=True)

    for seed in args.seeds:
        out = cell.run_cell(args.workload, seed, args.seconds, False,
                            t_start=time.perf_counter(), bench=bench)
        emit("program", seed, {k: c["value"] for k, c in out["checks"].items()})
    ref_mod = spec.reference(model["reference"])
    for seed in args.control_seeds:
        items = traffic.make_items(mix, model["train"]["seq_len"],
                                   model["vocab_size"], seed)
        batches = check.reference_batches(model, mix, items)
        wseed = traffic.seeds(seed)["weights"]
        ref = plain.run_steps(ref_mod, model, wseed, batches)
        ctl = plain.run_steps(ref_mod, model, wseed, batches,
                              precision="fp8")
        emit("control", seed, check.numbers(ctl, ref))
    for name in [f for f in args.faults.split(",") if f]:
        for seed in args.control_seeds:
            out = cell.run_cell(args.workload, seed, args.seconds, False,
                                t_start=time.perf_counter(), bench=bench,
                                plant=faults.ALL[name])
            emit(name, seed, {k: c["value"] for k, c in out["checks"].items()})
    summary = {}
    for r in readings:
        s = summary.setdefault(r["group"], {})
        for k, v in r.items():
            if k in ("group", "seed"):
                continue
            lo, hi = s.get(k, (v, v))
            s[k] = (min(lo, v), max(hi, v))
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(readings, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
