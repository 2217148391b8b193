"""The trace reduction on a small recorded trace kept in the repository:
the device's operations and the benchmark's host spans of one step of
``qwen2-0.5b.local-tokens`` on one TPU v5e, and the next step's start."""
import gzip
import json
import os

import pytest

import tinycell
import tracing

FIXTURE = os.path.join(tinycell.HERE, "data", "trace_qwen2_one_step.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rt") as f:
        d = json.load(f)
    ops = [tuple(x) for x in d["device_ops"]]
    spans = [tuple(x) for x in d["host_spans"]]
    return ops, spans


def _calls():
    t = lambda dt, shape: {"dtype": dt, "shape": shape}  # noqa: E731
    flash = {"name": "flash_attention.21", "kernel": "flash_attention",
             "operands": [t("bf16", (4, 14, 1024, 128)),
                          t("bf16", (4, 2, 1024, 128)),
                          t("bf16", (4, 2, 1024, 128))],
             "result": [t("bf16", (4, 14, 1024, 128))]}
    norm = {"name": "rmsnorm.42", "kernel": "rmsnorm",
            "operands": [t("bf16", (4096, 896)), t("f32", (896,))],
            "result": [t("bf16", (4096, 896))]}
    return [flash, norm]


def test_window_and_busy_time(recorded):
    ops, spans = recorded
    got = tracing.reduce_events(ops, spans, _calls())
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    assert got["window_s"] == pytest.approx((hi - lo) / 1e9, abs=1e-12)
    # a sweep over start (+1) and end (-1) marks: busy where any op runs
    marks = sorted([(max(s, lo), 1) for _, s, e in ops if e > lo and s < hi]
                   + [(min(e, hi), -1) for _, s, e in ops if e > lo and s < hi],
                   key=lambda m: (m[0], -m[1]))
    running, busy, since = 0, 0, None
    for t, d in marks:
        if running == 0 and d == 1:
            since = t
        running += d
        if running == 0:
            busy += t - since
    assert got["busy_s"] == pytest.approx(busy / 1e9, abs=1e-12)
    assert 0 < got["busy_s"] < got["window_s"]


def test_idle_gaps_are_named_by_the_host_span(recorded):
    ops, spans = recorded
    gaps = tracing.reduce_events(ops, spans, _calls())["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    # the step boundary: the device idles about 4 ms from the end of the
    # step to the next dispatch, most of it in the trainer's sync and hooks
    name, seconds = gaps[0]
    assert name == tracing.SYNC_SPAN
    assert 1e-3 < seconds < 1e-2


def test_kernel_events_and_device_ops(recorded):
    ops, spans = recorded
    got = tracing.reduce_events(ops, spans, _calls())
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    for kernel, name in (("flash_attention", "flash_attention.21"),
                         ("rmsnorm", "rmsnorm.42")):
        want = [(s, e) for n, s, e in ops if n == name and e > lo and s < hi]
        entries = got["kernels"].get(kernel, [])
        assert sum(x["events"] for x in entries) == len(want)
        assert sum(x["seconds"] for x in entries) == pytest.approx(
            sum(e - s for s, e in want) / 1e9)
    top = got["breakdown"]["device_ops"]
    assert 0 < len(top) <= 10
    assert not any(n.split(".")[0] in tracing.CONTAINERS for n, _ in top)


def test_op_names_from_hlo_text():
    assert tracing.op_name("%fusion.12 = bf16[4]{0} fusion(%p), kind=kLoop") \
        == "fusion.12"
    assert tracing.union([(0, 5), (3, 8), (10, 12)]) == [(0, 8), (10, 12)]
