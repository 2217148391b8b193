"""Reduction of a JAX profiler trace to device metrics.

From the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes:

* the traced window: from the first to the last of the benchmark's own
  host spans (``cell.STEP_SPAN``, ``cell.DATA_SPAN``) in the trace;
* busy time: the union of the intervals in which an operation ran on the
  device (the ``XLA Ops`` line of the first TPU plane, whose events are
  named by their HLO text, ``%<instruction> = ...``), clipped to the
  window; the idle share is one minus busy over the window;
* kernel time: the summed device durations of the events of each Pallas
  call, matched by the HLO instruction name ``hlo.pallas_calls`` gives;
* a breakdown: the ten device operations that took most time, loops and
  conditionals left out (their bodies' operations are events of their
  own), and the ten longest idle gaps, each named by the host span that
  overlaps it most: the wait for a batch, the step's dispatch, or
  ``chipbench.sync_and_hooks``, the time from a dispatch's return to the
  next wait for a batch (the trainer's ``block_until_ready`` and its
  per-step hooks); ``host`` where none does.
"""
from __future__ import annotations

import glob
import os
import shutil
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Tuple

import cell as bcell

OPS_LINE = "XLA Ops"
HOST_SPANS = (bcell.STEP_SPAN, bcell.DATA_SPAN)
SYNC_SPAN = "chipbench.sync_and_hooks"
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _is_container(name: str) -> bool:
    return name.split(".", 1)[0] in CONTAINERS


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _overlap(a: Tuple[int, int], b: Tuple[int, int]) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def reduce_events(device_ops: List[Tuple[str, int, int]],
                  host_spans: List[Tuple[str, int, int]],
                  calls: List[Dict[str, Any]]) -> Dict[str, Any]:
    """``device_ops`` and ``host_spans``: (name, start_ns, end_ns) on one
    clock; ``calls``: the step's Pallas calls."""
    ours = [(n, s, e) for n, s, e in host_spans if n in HOST_SPANS]
    if not ours or not device_ops:
        raise RuntimeError("the trace holds no benchmark span or no device "
                           "operation")
    lo = min(s for _, s, _ in ours)
    hi = max(e for _, _, e in ours)
    ours.sort(key=lambda sp: sp[1])
    ours += [(SYNC_SPAN, a[2], b[1]) for a, b in zip(ours, ours[1:])
             if a[0] == bcell.STEP_SPAN and b[0] == bcell.DATA_SPAN
             and b[1] > a[2]]
    busy = union(clip([(s, e) for _, s, e in device_ops], lo, hi))
    busy_ns = sum(e - s for s, e in busy)

    per_op: Dict[str, int] = defaultdict(int)
    per_op_calls: Dict[str, int] = defaultdict(int)
    for n, s, e in device_ops:
        if e > lo and s < hi:
            per_op[n] += e - s
            per_op_calls[n] += 1
    kernel_of = {c["name"]: c for c in calls}
    kernels: Dict[str, List[Tuple[Dict[str, Any], int, int]]] = defaultdict(list)
    for name, ns in per_op.items():
        if name in kernel_of:
            c = kernel_of[name]
            kernels[c["kernel"]].append((c, per_op_calls[name], ns))

    gaps = []
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            label = max(ours, key=lambda sp: _overlap((s, e), sp[1:]),
                        default=None)
            name = label[0] if label and _overlap((s, e), label[1:]) else "host"
            gaps.append((name, (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    top_ops = sorted(((n, ns) for n, ns in per_op.items()
                      if not _is_container(n)), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "kernels": {k: [{"call": c, "events": n, "seconds": ns / 1e9}
                        for c, n, ns in v] for k, v in kernels.items()},
        "breakdown": {"device_ops": [[n, ns / 1e9] for n, ns in top_ops],
                      "idle_gaps": [[n, s] for n, s in gaps[:10]]},
    }


def read_xplane(path: str):
    """(device_ops, host_spans) of one trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops, host = [], []
    tpu = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    if not tpu:
        raise RuntimeError("the trace has no TPU plane")
    dev = sorted(tpu, key=lambda p: p.name)[0]
    for line in dev.lines:
        if line.name == OPS_LINE:
            device_ops = [(op_name(ev.name), int(ev.start_ns),
                           int(ev.start_ns + ev.duration_ns))
                          for ev in line.events]
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns)))
    return device_ops, host


def reduce_dir(trace_dir: str, calls: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Reduce the one trace under ``trace_dir``, then delete the directory:
    traces are large and the result keeps what was read from them."""
    try:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {len(files)}")
        device_ops, host = read_xplane(files[0])
        return reduce_events(device_ops, host, calls)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
