#!/usr/bin/env python3
"""One traced run of one cell with the program's spans recorded, and what
``program_spans`` reads from them:

    python3 chipbench/span_run.py --workload <cell> --seed <n> [--seconds 30]

The run is ``run.py --trace 1``'s (set-up, timed window, traced tail,
output check), with the trainer's ``run()`` inside
``repro.utils.spans.recording()``; the trace is read for the program's
spans before the harness deletes it.  Prints ``run.py``'s result line
with two more keys:

* ``program_spans``: ``program_spans.reduce_dir``; the spans counted by
  name; set-up by span; each span's durations from the window's first
  step on; the median of each step span in the window (profiler off) and
  in the traced tail (profiler on); the window's longest steps; the
  first six dispatches; the bytes of the parameters the harness copies
  to the host at steps 0 and 3; tokens per second of the window
  (recorder on) and of the traced tail (profiler on too);
* ``span_cost_us``: the cost, on this host with no profiler running, of
  the spans the trainer opens in one step and the loader opens for one
  batch, without a recorder and with one.

The benchmark's own runs do not run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
STEP_SPANS = ("train.data_wait", "train.dispatch", "train.sync",
              "train.log", "train.hooks")
BATCH_SPANS = ("loader.stage", "loader.put", "loader.ready")


def span_cost_us(n: int = 20000):
    """Microseconds per step of the trainer's six spans, and per batch of
    the loader's five, with empty bodies."""
    from repro.utils import spans

    def step(i):
        with spans.step_span("train.step", i):
            for name in STEP_SPANS:
                with spans.span(name, step=i):
                    pass

    def batch(i):
        with spans.span("loader.collate", seq=i):
            pass
        with spans.span("loader.h2d", bytes=49152):
            for name in BATCH_SPANS:
                with spans.span(name):
                    pass

    def per_call(fn):
        t0 = time.perf_counter()
        for i in range(n):
            fn(i)
        return 1e6 * (time.perf_counter() - t0) / n

    out = {"step_off": per_call(step), "batch_off": per_call(batch)}
    with spans.recording():
        out["step_recorder"] = per_call(step)
        out["batch_recorder"] = per_call(batch)
    return out


def step_medians_ms(records, first: int, end: int):
    """Median milliseconds of each ``train.*`` span of steps
    ``first .. end-1``."""
    by = {}
    for r in records:
        if r.name.startswith("train.") and first <= r.attrs.get(
                "step", -1) < end:
            by.setdefault(r.name, []).append(r.seconds * 1e3)
    return {name: float(np.median(d)) for name, d in sorted(by.items())}


def longest_steps(records, first: int, end: int, n: int = 3):
    """The ``n`` longest ``train.step`` spans of steps ``first .. end-1``,
    each with its children's seconds and the spans of other threads that
    overlap it and last 50 ms or more."""
    kids = {}
    for r in records:
        if r.parent == "train.step":
            kids.setdefault(r.attrs["step"], {})[r.name] = r.seconds
    steps = sorted((r for r in records if r.name == "train.step"
                    and first <= r.attrs["step"] < end),
                   key=lambda r: -r.seconds)[:n]
    return [{"step": r.attrs["step"], "seconds": r.seconds,
             "children": kids.get(r.attrs["step"], {}),
             "others": sorted((o.seconds, o.name) for o in records
                              if o.thread != r.thread and o.seconds >= 0.05
                              and o.start_ns < r.end_ns
                              and o.end_ns > r.start_ns)}
            for r in steps]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    import cell
    import jax
    import program_spans
    from repro.utils import spans

    found = {}

    def plant(trainer, items):
        clock, run = trainer.step_fn, trainer.run

        def recorded_run():
            found["before_run_s"] = time.perf_counter() - T_START
            with spans.recording() as rec:
                out = run()
            records = rec.records
            found.update(program_spans.reduce_dir(clock.trace_dir, records))
            found["counts"] = rec.counts()
            found["setup_s"] = {
                name: rec.seconds(name) for name in
                ("train.init_state", "train.tune", "tune.measure",
                 "train.stream_start")}
            e = clock.entries
            found["setup_s"]["warm_steps"] = e[clock.open_i] - e[1]
            found["params_bytes"] = sum(
                x.nbytes for x in jax.tree_util.tree_leaves(
                    trainer.state.params))
            found["dispatch_s"] = [r.seconds for r in sorted(
                rec.named("train.dispatch"), key=lambda r: r.start_ns)[:6]]
            opened = next(r.start_ns for r in records
                          if r.name == "train.step"
                          and r.attrs["step"] == clock.open_i)
            found["steady_ms"] = {}
            for name in sorted({r.name for r in records}):
                d = [r.seconds * 1e3 for r in records
                     if r.name == name and r.start_ns >= opened]
                if d:
                    found["steady_ms"][name] = {
                        "n": len(d), "p50": float(np.percentile(d, 50)),
                        "p90": float(np.percentile(d, 90)),
                        "max": max(d)}
            found["phase_ms"] = {
                phase: step_medians_ms(records, a, b) for phase, (a, b) in
                (("window", (clock.open_i, clock.close_i)),
                 ("tail", (clock.trace_start_i, clock.trace_stop_i)))}
            found["longest_steps"] = longest_steps(
                records, clock.open_i, clock.close_i)
            tokens = clock.batches[0][0].size
            for key, (a, b) in (("window", (clock.open_i, clock.close_i)),
                                ("tail", (clock.trace_start_i,
                                          clock.trace_stop_i))):
                found[f"{key}_tokens_per_s"] = tokens * (b - a) / (e[b] - e[a])
            return out

        trainer.run = recorded_run

    cost = span_cost_us()
    out = cell.run_cell(args.workload, args.seed, args.seconds, True,
                        t_start=T_START, plant=plant)
    out["program_spans"] = found
    out["span_cost_us"] = cost
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
