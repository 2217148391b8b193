"""Host spans (``repro.utils.spans``) and where the program emits them:
the trainer's step and set-up, the tuner's trials, the worker pool's
collate and the device edge."""
import sys
import threading
import time

import numpy as np
import pytest

from repro.data import DataLoader, LoaderParams, SlabArena, token_dataset
from repro.data.arena import ArenaBatch
from repro.data.prefetcher import DevicePrefetcher
from repro.data.worker_pool import ThreadWorkerPool
from repro.utils import spans

from conftest import make_index_dataset


def _by_name(records):
    out = {}
    for r in records:
        out.setdefault(r.name, []).append(r)
    return out


def _inside(child, parent):
    return parent.start_ns <= child.start_ns <= child.end_ns <= parent.end_ns


def test_parent_nesting_and_attrs():
    with spans.recording() as rec:
        with spans.span("a", k=1):
            with spans.span("b"):
                with spans.span("c", seq=7):
                    pass
            with spans.span("d"):
                pass
        with spans.step_span("s", 3):
            pass
    got = {r.name: r for r in rec.records}
    assert [r.name for r in rec.records] == ["c", "b", "d", "a", "s"]
    assert (got["a"].parent, got["b"].parent, got["c"].parent,
            got["d"].parent, got["s"].parent) == (None, "a", "b", "a", None)
    assert got["a"].attrs == {"k": 1} and got["c"].attrs == {"seq": 7}
    assert got["b"].attrs == {} and got["s"].attrs == {"step": 3}
    assert _inside(got["c"], got["b"]) and _inside(got["b"], got["a"])
    assert got["b"].end_ns <= got["d"].start_ns
    assert rec.counts() == {"a": 1, "b": 1, "c": 1, "d": 1, "s": 1}


def test_no_records_without_a_recorder():
    with spans.span("before"):
        pass
    with spans.recording() as rec:
        pass
    with spans.span("after"):
        pass
    assert rec.records == []


def test_a_span_that_raises_is_recorded_and_unwinds_its_parent():
    with spans.recording() as rec:
        with pytest.raises(ValueError):
            with spans.span("outer"):
                with spans.span("inner"):
                    raise ValueError("boom")
        with spans.span("next"):
            pass
    got = {r.name: r for r in rec.records}
    assert got["inner"].parent == "outer"
    assert got["next"].parent is None


def test_records_are_on_the_realtime_clock():
    with spans.recording() as rec:
        t0 = time.time_ns()
        with spans.span("timed"):
            time.sleep(0.02)
        t1 = time.time_ns()
    (r,) = rec.records
    assert t0 <= r.start_ns <= r.end_ns <= t1
    assert r.seconds >= 0.019


def test_appends_from_many_threads_lose_nothing():
    threads, per_thread = 16, 200
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with spans.recording() as rec:
            def work(t):
                for i in range(per_thread):
                    with spans.span("outer", t=t, i=i):
                        with spans.span("inner", t=t, i=i):
                            pass
            ts = [threading.Thread(target=work, args=(t,))
                  for t in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(switch)
    by = _by_name(rec.records)
    assert len(by["outer"]) == len(by["inner"]) == threads * per_thread
    # every inner span's parent is the outer span of its own thread
    assert all(r.parent == "outer" for r in by["inner"])
    assert all(r.parent is None for r in by["outer"])
    outer = {(r.attrs["t"], r.attrs["i"]): r for r in by["outer"]}
    assert len(outer) == threads * per_thread
    for r in by["inner"]:
        mine = outer[(r.attrs["t"], r.attrs["i"])]
        assert r.thread == mine.thread and _inside(r, mine)


@pytest.mark.parametrize("workers", [0, 3])
def test_worker_pool_collates_in_spans(workers):
    ds = make_index_dataset(48)
    batches = [np.arange(i, i + 4) for i in range(0, 48, 4)]
    with spans.recording() as rec:
        pool = ThreadWorkerPool(ds, iter(batches), num_workers=workers,
                                prefetch_factor=2)
        got = list(pool)
        pool.shutdown()
    assert len(got) == len(batches)
    collates = rec.named("loader.collate")
    assert len(collates) == len(batches)
    if workers:
        assert sorted(r.attrs["seq"] for r in collates) == \
            list(range(len(batches)))
        assert threading.get_ident() not in {r.thread for r in collates}
    else:
        assert all(r.attrs == {} for r in collates)


def _h2d_children(rec):
    by = _by_name(rec.records)
    h2d = by["loader.h2d"]
    kids = [r for r in rec.records if r.parent == "loader.h2d"]
    return h2d, kids, by


def test_prefetcher_stages_puts_and_waits_inside_h2d():
    arena = SlabArena(capacity=2)

    def producer():
        for i in range(5):
            slot = arena.acquire()
            if slot is None:
                slot = arena.adopt({"x": np.full((4, 8), float(i),
                                                 np.float32)})
            else:
                slot.arrays["x"][...] = i
            yield ArenaBatch(slot)

    with spans.recording() as rec:
        out = list(DevicePrefetcher(producer(), depth=2, staging_buffers=2))
    assert len(out) == 5
    h2d, kids, by = _h2d_children(rec)
    assert len(h2d) == 5
    assert all(r.attrs == {"bytes": 4 * 8 * 4} for r in h2d)
    assert {r.name for r in kids} == {"loader.stage", "loader.put",
                                      "loader.ready"}
    for name in ("loader.stage", "loader.put", "loader.ready"):
        assert len(by[name]) == 5
    for h in h2d:
        mine = [k for k in kids if k.thread == h.thread and _inside(k, h)]
        assert [k.name for k in mine] == ["loader.stage", "loader.put",
                                          "loader.ready"]


def test_prefetcher_puts_plain_batches_inside_h2d():
    batches = [{"x": np.full((4,), i, np.float32)} for i in range(3)]
    with spans.recording() as rec:
        out = list(DevicePrefetcher(iter(batches), depth=2))
    assert len(out) == 3
    h2d, kids, _ = _h2d_children(rec)
    assert [r.attrs["bytes"] for r in h2d] == [16, 16, 16]
    assert [k.name for k in kids] == ["loader.put"] * 3


STEP_CHILDREN = ["train.data_wait", "train.dispatch", "train.sync",
                 "train.log", "train.hooks"]


def test_trainer_run_spans_its_set_up_and_each_step(tmp_path):
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.train.optimizer import AdamWConfig
    from repro.train.train_step import TrainStepConfig
    from repro.train.trainer import Trainer, TrainerConfig

    steps = 3
    cfg = reduced(get_config("qwen2-0.5b"))
    ds = token_dataset(64, 16, cfg.vocab_size, seed=0)
    dl = DataLoader(ds, 8, params=LoaderParams(num_workers=1), seed=0)
    tc = TrainerConfig(total_steps=steps, autotune=True,
                       autotune_budget_batches=2, autotune_max_prefetch=1,
                       autotune_num_cpu_cores=2,
                       dpt_cache_path=str(tmp_path / "dpt.json"),
                       log_every=1,
                       step_config=TrainStepConfig(
                           remat_policy="none",
                           optimizer=AdamWConfig(total_steps=steps)))
    tr = Trainer(build_model(cfg), dl, tc)
    assert not hasattr(tr, "straggler")
    with spans.recording() as rec:
        tr.run()
    tr.loader._live_stream.close()
    records = rec.records
    by = _by_name(records)
    main = threading.get_ident()

    # set-up, in order, on the thread that runs the trainer
    (init,) = by["train.init_state"]
    (tune,) = by["train.tune"]
    (start,) = by["train.stream_start"]
    assert init.end_ns <= tune.start_ns and tune.end_ns <= start.start_ns
    assert {init.thread, tune.thread, start.thread} == {main}

    # every trial under the startup tune, each with its one timed window
    trials = by["tune.trial"]
    assert len(trials) == len(tr.tune_result.trials) > 0
    for t in trials:
        assert t.parent == "train.tune" and _inside(t, tune)
        assert {"workers", "prefetch", "axes"} == set(t.attrs)
        measures = [m for m in by["tune.measure"] if _inside(m, t)]
        assert len(measures) == 1 and measures[0].parent == "tune.trial"

    # each step: one StepTraceAnnotation around its five children, in order
    step_spans = by["train.step"]
    assert [s.attrs["step"] for s in step_spans] == list(range(steps))
    for s in step_spans:
        assert s.thread == main and s.parent is None
        kids = [r for r in records if r.parent == "train.step"
                and r.attrs.get("step") == s.attrs["step"]]
        assert [k.name for k in kids] == STEP_CHILDREN
        assert all(_inside(k, s) for k in kids)
        assert all(a.end_ns <= b.start_ns for a, b in zip(kids, kids[1:]))
    assert step_spans[0].end_ns <= step_spans[1].start_ns

    # the loader's spans come from its own threads while the trainer runs
    assert len(by["loader.h2d"]) >= steps
    assert by["loader.collate"]
    assert main not in {r.thread for r in by["loader.h2d"]}


@pytest.mark.parametrize("arch, kinds", [
    ("granite-4.0-h-micro", {"layers_mamba": 2, "layers_attention": 1}),
    ("qwen2-0.5b", {}),
])
def test_init_state_counts_each_layer_kind(arch, kinds):
    """``train.init_state`` carries a layer-pattern model's count of each
    layer kind; a model of one block carries none."""
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.train.optimizer import AdamWConfig
    from repro.train.train_step import TrainStepConfig
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = reduced(get_config(arch))
    ds = token_dataset(16, 16, cfg.vocab_size, seed=0)
    dl = DataLoader(ds, 2, params=LoaderParams(num_workers=1), seed=0)
    tc = TrainerConfig(total_steps=1, autotune=False,
                       step_config=TrainStepConfig(
                           remat_policy="none",
                           optimizer=AdamWConfig(total_steps=1)))
    tr = Trainer(build_model(cfg), dl, tc)
    with spans.recording() as rec:
        tr.run()
    tr.loader._live_stream.close()
    (init,) = rec.named("train.init_state")
    assert init.attrs == kinds
