"""The program's own spans, joined to the device's operations.

The program names its host work through ``repro.utils.spans``: the
trainer's set-up and steps (``train.*``), the loader's collate and device
edge (``loader.*``) and the tuner's trials (``tune.*``).  Each span is a
profiler annotation, and, while a recorder is installed, a record on the
realtime clock.

From the same ``.xplane.pb`` that ``tracing.read_xplane`` reads, this
module takes those spans with the trace line (the thread) each ran on,
and joins them to the device operations on the trace's clock, inside
the window that ``tracing`` uses for ``device_idle_share``:

* ``loop_idle_ms``: per ``train.step`` of the trace, the device's idle
  time that overlaps the step's ``train.dispatch``, ``train.sync``,
  ``train.log`` and ``train.hooks``; the median over steps;
* ``collate_ms.p90``, ``h2d_ms.p90``: the 90th percentile of the
  durations of ``loader.collate`` and ``loader.h2d`` in the trace;
* ``idle_by_span``: the device's idle seconds in the window by the
  innermost program span open on the trainer's thread at the time
  (``unspanned`` where none is);
* ``wrapper_us``: what the benchmark's own spans around the trainer's
  dispatch and batch wait (``cell.py``) add to the program's;
* ``longest_idle``: the window's longest idle gap, where it lies, and
  the program spans (of any thread) open in it.

From the recorder's records, which also cover set-up, before the trace:

* ``first_step_s``: the first ``train.step``: trace, lower, compile or
  cache load, and the first run;
* ``tune_overhead_s``: ``train.tune`` less its trials' timed windows
  (``tune.measure``): pool start-up and tear-down, and the tuner's own
  work; ``None`` when no trial ran;
* ``twin_offset_us``: how far each record lies from its own annotation
  in the trace (largest, 99th percentile, share within 100 us), after the
  trace's times are put on the realtime clock by the session's
  ``profile_start_time``.  A record can lag its annotation by as long as
  another thread holds the interpreter: a thread switch can fall between
  the annotation's edge and the clock read beside it.

A trace or a record list without program spans gives ``None`` for each.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import cell as bcell
import tracing

PREFIXES = ("train.", "loader.", "tune.")
STEP = "train.step"
LOOP = ("train.dispatch", "train.sync", "train.log", "train.hooks")
UNSPANNED = "unspanned"
# the program's span -> the benchmark's own span around the same call
WRAPPED = {"train.dispatch": bcell.STEP_SPAN,
           "train.data_wait": bcell.DATA_SPAN}
ENV_PLANE = "Task Environment"

# (name, start_ns, end_ns, line): line names the trace line (one host
# thread) the span ran on
Span = Tuple[str, int, int, Tuple[str, int]]


def is_program_span(name: str) -> bool:
    return name.startswith(PREFIXES)


def read_xplane(path: str) -> Tuple[List[Span], Optional[int]]:
    """The program's host spans of one trace file, and the realtime
    nanosecond at which the trace's clock starts (``None`` when the trace
    does not say)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    found: List[Span] = []
    base = None
    for plane in data.planes:
        if plane.name == ENV_PLANE:
            base = dict(plane.stats).get("profile_start_time")
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if is_program_span(ev.name):
                    found.append((ev.name, int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns),
                                  (plane.name, i)))
    return found, None if base is None else int(base)


def _idle(device_ops, lo: int, hi: int) -> List[Tuple[int, int]]:
    busy = tracing.union(tracing.clip([(s, e) for _, s, e in device_ops],
                                      lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def _idle_ns(idle: List[Tuple[int, int]], starts: List[int],
             a: int, b: int) -> int:
    """Idle nanoseconds inside [a, b]; ``starts`` are the idle intervals'
    starts, in order."""
    total = 0
    for s, e in idle[max(0, bisect.bisect_right(starts, a) - 1):]:
        if s >= b:
            break
        total += max(0, min(e, b) - max(s, a))
    return total


def _innermost(spans: Sequence[Span], a: int, b: int) -> str:
    """The name of the innermost span that covers [a, b] (spans of one
    thread nest), or ``UNSPANNED``."""
    best = None
    for sp in spans:
        if sp[1] <= a and b <= sp[2] and (
                best is None or (sp[1], -sp[2]) > (best[1], -best[2])):
            best = sp
    return UNSPANNED if best is None else best[0]


def idle_by_span(idle: List[Tuple[int, int]],
                 spans: Sequence[Span]) -> Dict[str, float]:
    """Idle seconds by the innermost span of ``spans`` (one thread)."""
    marks = sorted({x for sp in spans for x in sp[1:3]})
    segments = [(a, b, _innermost(spans, a, b))
                for a, b in zip(marks, marks[1:])]
    out: Dict[str, int] = defaultdict(int)
    for s, e in idle:
        covered = 0
        first = max(0, bisect.bisect_right(marks, s) - 1)
        for a, b, name in segments[first:]:
            if a >= e:
                break
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[name] += ov
                covered += ov
        if e - s > covered:
            out[UNSPANNED] += e - s - covered
    return {name: ns / 1e9 for name, ns in out.items()}


def _p90_ms(spans: Sequence[Span], name: str) -> Optional[float]:
    d = [(e - s) / 1e6 for n, s, e, _ in spans if n == name]
    return float(np.percentile(d, 90)) if d else None


def wrapper_us(host_spans: List[Tuple[str, int, int]],
               trainer: Sequence[Span]) -> Dict[str, Optional[float]]:
    """Per program span of ``WRAPPED``, the median over its occurrences of
    its duration less that of the one benchmark span inside it: what the
    harness's wrappers around the trainer add, in microseconds."""
    out = {}
    for name, inner in WRAPPED.items():
        d = []
        for n, s, e, _ in trainer:
            if n != name:
                continue
            ins = [he - hs for hn, hs, he in host_spans
                   if hn == inner and s <= hs and he <= e]
            if len(ins) == 1:
                d.append((e - s - ins[0]) / 1e3)
        out[name] = float(np.median(d)) if d else None
    return out


def reduce_events(device_ops: List[Tuple[str, int, int]],
                  host_spans: List[Tuple[str, int, int]],
                  program: List[Span]) -> Dict[str, Any]:
    """The trace's metrics.  ``device_ops`` and ``host_spans`` as
    ``tracing.read_xplane`` gives them; ``program`` as :func:`read_xplane`
    gives it; all on the trace's clock."""
    out: Dict[str, Any] = {"loop_idle_ms": None, "collate_ms.p90": None,
                           "h2d_ms.p90": None, "idle_by_span": None,
                           "wrapper_us": None, "longest_idle": None}
    ours = [(s, e) for n, s, e in host_spans if n in tracing.HOST_SPANS]
    if not program or not ours or not device_ops:
        return out
    out["collate_ms.p90"] = _p90_ms(program, "loader.collate")
    out["h2d_ms.p90"] = _p90_ms(program, "loader.h2d")
    lo, hi = min(s for s, _ in ours), max(e for _, e in ours)
    steps = [sp for sp in program if sp[0] == STEP]
    if not steps:
        return out
    line = steps[0][3]
    trainer = [sp for sp in program if sp[3] == line]
    idle = _idle(device_ops, lo, hi)
    starts = [s for s, _ in idle]
    per_step = []
    for _, s, e, _ in steps:
        loop = tracing.clip([(ss, se) for n, ss, se, _ in trainer
                             if n in LOOP and s <= ss and se <= e], lo, hi)
        per_step.append(sum(_idle_ns(idle, starts, a, b)
                            for a, b in loop) / 1e6)
    out["loop_idle_ms"] = float(np.median(per_step))
    out["idle_by_span"] = idle_by_span(idle, trainer)
    out["wrapper_us"] = wrapper_us(host_spans, trainer)
    if idle:
        s, e = max(idle, key=lambda iv: iv[1] - iv[0])
        out["longest_idle"] = {
            "ms": (e - s) / 1e6, "at_s": (s - lo) / 1e9,
            "by_span": idle_by_span([(s, e)], trainer),
            "open": sorted({n for n, ss, se, _ in program
                            if ss < e and se > s})}
    return out


def setup(records) -> Dict[str, Optional[float]]:
    """Set-up metrics from the recorder's records."""
    steps = sorted((r for r in records if r.name == STEP),
                   key=lambda r: r.start_ns)
    tunes = [r for r in records if r.name == "train.tune"]
    overhead = None
    if tunes:
        t = tunes[0]
        windows = [r.seconds for r in records if r.name == "tune.measure"
                   and t.start_ns <= r.start_ns and r.end_ns <= t.end_ns]
        if windows:
            overhead = t.seconds - sum(windows)
    return {"first_step_s": steps[0].seconds if steps else None,
            "tune_overhead_s": overhead}


def twin_offsets_us(program: List[Span], base_ns: Optional[int],
                    records) -> List[Tuple[str, float, float]]:
    """For each span in the trace, its twin: the record of the same name
    whose start and end lie nearest to the span's, as (name, record start
    less span start, record end less span end) in microseconds.  Empty
    when the trace gives no clock base."""
    if base_ns is None:
        return []
    by_name: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
    for r in records:
        by_name[r.name].append((r.start_ns, r.end_ns))
    out = []
    for name, s, e, _ in program:
        if by_name.get(name):
            s, e = s + base_ns, e + base_ns
            rs, r_end = min(by_name[name], key=lambda x: max(
                abs(x[0] - s), abs(x[1] - e)))
            out.append((name, (rs - s) / 1e3, (r_end - e) / 1e3))
    return out


def twin_summary(twins) -> Optional[Dict[str, Any]]:
    if not twins:
        return None
    off = [max(abs(ds), abs(de)) for _, ds, de in twins]
    worst: Dict[str, float] = defaultdict(float)
    for (name, _, _), o in zip(twins, off):
        worst[name] = max(worst[name], o)
    return {"max": max(off), "p99": float(np.percentile(off, 99)),
            "within_100us": sum(o <= 100 for o in off) / len(off),
            "spans": len(off),
            "start_p50": float(np.median([ds for _, ds, _ in twins])),
            "end_p50": float(np.median([de for _, _, de in twins])),
            "max_by_name": dict(worst)}


def reduce_dir(trace_dir: str, records) -> Dict[str, Any]:
    """Every metric of the one trace under ``trace_dir`` and of the
    recorder's ``records``.  The directory is left as it is."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {len(files)}")
    device_ops, host = tracing.read_xplane(files[0])
    program, base = read_xplane(files[0])
    out = reduce_events(device_ops, host, program)
    out.update(setup(records))
    out["twin_offset_us"] = twin_summary(
        twin_offsets_us(program, base, records))
    return out
