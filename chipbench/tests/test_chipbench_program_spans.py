"""The reduction of the program's own spans (``program_spans``): on
synthetic device operations and spans with hand-computed answers, on the
recorded one-step trace (which holds no program span), and on a real
profiler trace of this host, against the recorder's records."""
import glob
import gzip
import json
import os
import threading
import time

import pytest

import tinycell  # noqa: F401  (puts chipbench/ on the path)
import program_spans as ps
import tracing

FIXTURE = os.path.join(tinycell.HERE, "data", "trace_qwen2_one_step.json.gz")
U = 10_000                       # ns per unit of the synthetic timeline
MAIN, WORKER = ("/host:CPU", 0), ("/host:CPU", 1)


def _synthetic():
    host = [("chipbench.data_wait", 100, 101),
            ("chipbench.step_dispatch", 103, 149),
            ("chipbench.data_wait", 200, 204),
            ("chipbench.step_dispatch", 206, 249),
            ("chipbench.data_wait", 290, 300)]
    ops = [("fusion.1", 20, 90), ("fusion.2", 120, 180),
           ("fusion.3", 215, 240), ("fusion.4", 230, 280)]
    main = [("train.step", 100, 200),
            ("train.data_wait", 100, 102), ("train.dispatch", 102, 150),
            ("train.sync", 150, 182), ("train.log", 182, 186),
            ("train.hooks", 186, 198),
            ("train.step", 200, 298),
            ("train.data_wait", 200, 205), ("train.dispatch", 205, 250),
            ("train.sync", 250, 285), ("train.log", 285, 290),
            ("train.hooks", 290, 296)]
    worker = [("loader.collate", 100, 130), ("loader.collate", 130, 200),
              ("loader.h2d", 140, 141), ("loader.h2d", 240, 242)]
    scale = lambda xs: [(n, s * U, e * U) for n, s, e in xs]  # noqa: E731
    program = [(n, s, e, MAIN) for n, s, e in scale(main)] + \
        [(n, s, e, WORKER) for n, s, e in scale(worker)]
    return scale(ops), scale(host), program


def test_idle_is_attributed_to_the_trainer_spans_by_hand():
    ops, host, program = _synthetic()
    got = ps.reduce_events(ops, host, program)
    # idle in the window [100, 300]: [100, 120], [180, 215], [280, 300].
    # step 1's dispatch, sync, log, hooks overlap 18 + 2 + 4 + 12 = 36 of
    # it, step 2's 10 + 5 + 5 + 6 = 26: the median is 31
    assert got["loop_idle_ms"] == pytest.approx(31 * U / 1e6)
    want = {"train.data_wait": 2 + 5, "train.dispatch": 18 + 10,
            "train.sync": 2 + 5, "train.log": 4 + 5,
            "train.hooks": 12 + 6, "train.step": 2 + 2,
            ps.UNSPANNED: 2}
    assert set(got["idle_by_span"]) == set(want)
    for name, units in want.items():
        assert got["idle_by_span"][name] == pytest.approx(units * U / 1e9)
    # the split covers the window's idle time, which tracing reads too
    t = tracing.reduce_events(ops, host, [])
    assert sum(got["idle_by_span"].values()) == pytest.approx(
        t["window_s"] - t["busy_s"])
    # the worker thread's collates: p90 of 30 and 70 units
    assert got["collate_ms.p90"] == pytest.approx(66 * U / 1e6)
    assert got["h2d_ms.p90"] == pytest.approx(1.9 * U / 1e6)
    longest = got["longest_idle"]
    assert longest["ms"] == pytest.approx(35 * U / 1e6)
    assert longest["at_s"] == pytest.approx(80 * U / 1e9)
    assert sum(longest["by_span"].values()) == pytest.approx(35 * U / 1e9)
    assert longest["by_span"]["train.dispatch"] == pytest.approx(10 * U / 1e9)
    assert longest["open"] == sorted(
        {"train.step", "train.sync", "train.log", "train.hooks",
         "train.data_wait", "train.dispatch", "loader.collate"})
    # the dispatches are 48 and 45 units around the benchmark's 46 and 43
    assert got["wrapper_us"] == {"train.dispatch": pytest.approx(2 * U / 1e3),
                                 "train.data_wait": pytest.approx(U / 1e3)}


def test_a_device_busy_through_the_window_idles_nowhere():
    _, host, program = _synthetic()
    got = ps.reduce_events([("fusion.9", 0, 400 * U)], host, program)
    assert got["loop_idle_ms"] == 0 and got["idle_by_span"] == {}
    assert got["longest_idle"] is None


def test_without_program_spans_every_new_number_is_none():
    ops, host, _ = _synthetic()
    got = ps.reduce_events(ops, host, [])
    assert got == {"loop_idle_ms": None, "collate_ms.p90": None,
                   "h2d_ms.p90": None, "idle_by_span": None,
                   "wrapper_us": None, "longest_idle": None}
    assert ps.setup([]) == {"first_step_s": None, "tune_overhead_s": None}
    assert ps.twin_offsets_us([], 0, []) == []
    assert ps.twin_summary([]) is None


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rt") as f:
        d = json.load(f)
    return ([tuple(x) for x in d["device_ops"]],
            [tuple(x) for x in d["host_spans"]])


def test_the_recorded_trace_reads_as_before(recorded):
    ops, spans = recorded
    assert not any(ps.is_program_span(n) for n, _, _ in spans)
    got = ps.reduce_events(ops, spans, [])
    assert all(v is None for v in got.values())
    # program spans beside the benchmark's own change nothing tracing reads
    before = tracing.reduce_events(ops, spans, [])
    lo = min(s for _, s, _ in spans)
    extra = [("train.step", lo, lo + 10 ** 6), ("train.sync", lo, lo + 10),
             ("loader.h2d", lo, lo + 5)]
    assert tracing.reduce_events(ops, spans + extra, []) == before


class _Rec:
    def __init__(self, name, start_ns, end_ns):
        self.name, self.start_ns, self.end_ns = name, start_ns, end_ns

    @property
    def seconds(self):
        return (self.end_ns - self.start_ns) / 1e9


def test_set_up_from_the_records():
    s = 10 ** 9
    records = [_Rec("train.init_state", 0, 1 * s),
               _Rec("tune.measure", 2 * s, 4 * s),
               _Rec("tune.measure", 5 * s, 6 * s),
               _Rec("train.tune", 1 * s, 8 * s),
               _Rec("tune.measure", 9 * s, 10 * s),       # a later retune
               _Rec("train.step", 12 * s, 13 * s),
               _Rec("train.step", 8 * s, 11 * s)]
    got = ps.setup(records)
    assert got["first_step_s"] == pytest.approx(3.0)
    assert got["tune_overhead_s"] == pytest.approx(7.0 - 3.0)
    no_trial = [r for r in records if r.name != "tune.measure"]
    assert ps.setup(no_trial)["tune_overhead_s"] is None


def test_a_real_trace_holds_each_record_as_its_twin(tmp_path):
    import jax
    from repro.utils import spans

    def worker():
        for i in range(3):
            with spans.span("loader.collate", seq=i):
                time.sleep(0.002)

    with spans.recording() as rec:
        jax.profiler.start_trace(str(tmp_path))
        try:
            t = threading.Thread(target=worker)
            t.start()
            for i in range(3):
                with spans.step_span("train.step", i):
                    with spans.span("train.dispatch", step=i):
                        time.sleep(0.003)
                    with spans.span("train.sync", step=i):
                        time.sleep(0.001)
            t.join(timeout=30)
            assert not t.is_alive()
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    program, base = ps.read_xplane(path)
    assert base is not None
    names = sorted(n for n, *_ in program)
    assert names == sorted(r.name for r in rec.records)
    lines = {n: ln for n, _, _, ln in program}
    assert lines["train.step"] == lines["train.sync"]
    assert lines["loader.collate"] != lines["train.step"]
    twins = ps.twin_offsets_us(program, base, rec.records)
    assert sorted(n for n, _, _ in twins) == names
    offsets = [max(abs(ds), abs(de)) for _, ds, de in twins]
    summary = ps.twin_summary(twins)
    assert summary["spans"] == len(program)
    assert summary["max"] == max(offsets)
    assert set(summary["max_by_name"]) == set(names)
    # the trace's clock, moved by its start, is the records' clock: the
    # typical twin lies within microseconds (a busy host can still delay
    # one clock read past its annotation's edge)
    assert sorted(offsets)[len(offsets) // 2] < 100
    # each record lies inside its annotation
    for n, s, e, _ in program:
        twin = min((r for r in rec.records if r.name == n),
                   key=lambda r: abs(r.start_ns - (s + base)))
        assert s + base <= twin.start_ns + 1000
        assert twin.end_ns <= e + base + 1000
