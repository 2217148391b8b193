"""Unified tuning layer: registry/front door parity, hot-swap of a live
DataLoader, and the OnlineTuner drift loop."""
import math

import numpy as np
import pytest

from repro.core import (DPT, DPTConfig, LoaderSimulator, MachineProfile,
                        MemoryOverflow, SimulatorEvaluator)
from repro.core.cache import DPTCache
from repro.core.cluster import degraded_storage
from repro.data import DataLoader, Dataset, LoaderParams
from repro.data.loader import TransferStats
from repro.data.storage import ArrayStorage, cifar10_profile, coco_profile
from repro.tuning import (OnlineTuner, OnlineTunerConfig, available_strategies,
                          register_strategy, tune, worker_rungs)


# --------------------------------------------------------------------------
# registry + front door
# --------------------------------------------------------------------------
def test_registry_has_all_builtin_strategies():
    names = available_strategies()
    for expected in ("grid", "successive_halving", "hillclimb",
                     "warmstart_hillclimb", "goodput"):
        assert expected in names


def test_unknown_strategy_raises():
    with pytest.raises(ValueError, match="unknown tuning strategy"):
        tune(evaluator=lambda *a, **k: None, strategy="nope")


def test_custom_strategy_registration():
    @register_strategy("always_one_worker")
    class AlwaysOne:
        def tune(self, rec, **kw):
            t = rec.seconds(1, 1)
            return rec.result(1, 1, t)

    ev = _table(lambda i, j: float(i + j))
    res = tune(evaluator=ev, strategy="always_one_worker",
               config=DPTConfig(num_cpu_cores=4, num_devices=1))
    assert (res.nworker, res.nprefetch) == (1, 1)
    assert len(res.trials) == 1


def test_worker_rungs_clamped():
    assert worker_rungs(12, 4) == [4, 8, 12]
    assert worker_rungs(10, 4) == [4, 8, 10]
    assert worker_rungs(2, 4) == [2]


def _table(fn, overflow=None):
    overflow = overflow or (lambda i, j: False)

    def ev(i, j, *, num_batches=16, epoch=0):
        ev.calls += 1
        if overflow(i, j):
            raise MemoryOverflow(f"cell ({i},{j})")
        return TransferStats(fn(i, j), num_batches, 0)

    ev.calls = 0
    return ev


# --------------------------------------------------------------------------
# parity: the front door returns what Algorithm 1 and the pinned strategy
# picks give on the simulator profiles used across tests/test_dpt.py
# --------------------------------------------------------------------------
CFG = DPTConfig(num_cpu_cores=12, num_devices=1, max_prefetch=8,
                num_batches=64)


def _sim_ev():
    return SimulatorEvaluator(LoaderSimulator(cifar10_profile(),
                                              MachineProfile()),
                              batch_size=32)


def test_front_door_grid_matches_dpt_run():
    a = tune(evaluator=_sim_ev(), strategy="grid", config=CFG,
             measure_default=False)
    b = DPT(_sim_ev(), CFG).run(measure_default=False)
    assert (a.nworker, a.nprefetch, a.optimal_time) == \
        (b.nworker, b.nprefetch, b.optimal_time)
    assert len(a.trials) == len(b.trials)


@pytest.mark.parametrize("strategy,kwargs,pick", [
    ("successive_halving", {}, (10, 5, 0.8755469714746349)),
    ("warmstart_hillclimb",
     {"storage": cifar10_profile(), "machine": MachineProfile(),
      "batch_size": 32},
     (10, 5, 0.8755469714746349)),
    ("goodput", {"step_time_s": 1.0, "num_batches": 64},
     (1, 1, 4.778619949578188)),
])
def test_front_door_strategy_pick_is_pinned(strategy, kwargs, pick):
    """Each beyond-paper strategy's pick through ``tune(strategy=...)``
    on the simulator, pinned to the values the search gave before its
    per-strategy entry points were retired."""
    a = tune(evaluator=_sim_ev(), strategy=strategy, config=CFG, **kwargs)
    assert (a.nworker, a.nprefetch) == pick[:2]
    assert a.optimal_time == pytest.approx(pick[2], rel=1e-12)


def test_overflow_recorded_as_inf_trial():
    ev = _table(lambda i, j: 5.0 - i, overflow=lambda i, j: j >= 2)
    res = tune(evaluator=ev, strategy="grid", measure_default=False,
               config=DPTConfig(num_cpu_cores=2, num_devices=1,
                                max_prefetch=4, num_batches=2))
    assert any(t.overflowed and not math.isfinite(t.seconds)
               for t in res.trials)
    assert res.nprefetch == 1        # overflow broke the inner sweep


# --------------------------------------------------------------------------
# hot swap of a live stream
# --------------------------------------------------------------------------
def _index_dataset(n):
    """Items carry their own index so batches are accountable."""
    items = [np.full((4,), i, np.int32) for i in range(n)]
    return Dataset(ArrayStorage(items), transform=lambda a: {"x": a})


def _indices(batches):
    return sorted(np.concatenate([b["x"][:, 0] for b in batches]).tolist())


def test_hot_swap_zero_lost_zero_duplicated_batches():
    """Index accounting across two mid-epoch swaps (acceptance criterion).

    A drain boundary is a total flush: everything the outgoing pool pulled
    from the sampler has been delivered, and the incoming pool continues
    from exactly that position.  So at each completed swap the batches
    delivered so far must be EXACTLY the first k global batches — any lost
    batch leaves a hole, any duplicate shows up twice.  (Mid-stream, racing
    workers may deliver out of order, so only drain boundaries admit an
    exact check.)"""
    n, gb = 1024, 8
    dl = DataLoader(_index_dataset(n), gb, shuffle=False, seed=0,
                    params=LoaderParams(num_workers=2, prefetch_factor=2))
    stream = dl.stream(to_device=False)

    consumed = [next(stream) for _ in range(10)]
    dl.apply_params(LoaderParams(num_workers=4, prefetch_factor=3))
    while stream.swaps == 0:
        consumed.append(next(stream))
    b1 = len(consumed) - 1           # first post-swap batch just arrived
    assert _indices(consumed[:b1]) == list(range(b1 * gb))
    assert dl.params.num_workers == 4 and dl.params.prefetch_factor == 3

    consumed += [next(stream) for _ in range(10)]
    dl.apply_params(LoaderParams(num_workers=2, prefetch_factor=2))
    while stream.swaps == 1:
        consumed.append(next(stream))
    b2 = len(consumed) - 1
    assert b1 < b2 < n // gb         # still mid-epoch
    assert _indices(consumed[:b2]) == list(range(b2 * gb))
    assert stream.swaps == 2
    assert dl.params.num_workers == 2


def test_hot_swap_preserves_sampler_position_mid_epoch():
    """Single-worker pools are order-deterministic: the swapped stream must
    produce the exact same batch sequence as an untouched loader."""
    n, gb = 128, 8
    mk = lambda: DataLoader(_index_dataset(n), gb, shuffle=True, seed=7,
                            params=LoaderParams(num_workers=1,
                                                prefetch_factor=2))
    ref = [b["x"] for _, b in zip(range(2 * n // gb),
                                  mk().stream(to_device=False))]

    dl = DataLoader(_index_dataset(n), gb, shuffle=True, seed=7,
                    params=LoaderParams(num_workers=1, prefetch_factor=2))
    stream = dl.stream(to_device=False)
    got = [next(stream)["x"] for _ in range(5)]
    dl.apply_params(LoaderParams(num_workers=1, prefetch_factor=4))
    got += [next(stream)["x"] for _ in range(2 * n // gb - 5)]
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    assert stream.swaps == 1


def test_hot_swap_from_zero_workers():
    n, gb = 64, 8
    dl = DataLoader(_index_dataset(n), gb, shuffle=False, seed=0,
                    params=LoaderParams(num_workers=0))
    stream = dl.stream(to_device=False)
    consumed = [next(stream) for _ in range(3)]
    # swap to a single worker: delivery stays ordered, so consuming
    # exactly one epoch's worth of batches must cover the epoch exactly
    dl.apply_params(LoaderParams(num_workers=1, prefetch_factor=2))
    while len(consumed) < n // gb:
        consumed.append(next(stream))
    assert _indices(consumed) == list(range(n))
    assert stream.swaps == 1


def test_apply_params_without_stream_sets_params():
    dl = DataLoader(_index_dataset(32), 8)
    dl.apply_params(LoaderParams(num_workers=3, prefetch_factor=5))
    assert dl.params.num_workers == 3


# --------------------------------------------------------------------------
# OnlineTuner
# --------------------------------------------------------------------------
def _online_loader():
    return DataLoader(_index_dataset(64), 8, shuffle=False, seed=0,
                      params=LoaderParams(num_workers=1, prefetch_factor=1))


def _online_cfg(**kw):
    base = dict(stall_fraction=0.3, window=4, warmup_steps=2,
                cooldown_steps=6, retune_budget_batches=2, max_prefetch=3,
                num_cpu_cores=4, num_devices=1)
    base.update(kw)
    return OnlineTunerConfig(**base)


def test_online_tuner_retunes_on_goodput_drift(tmp_path):
    ev = _table(lambda i, j: 4.0 / i + 0.1 * j)     # optimum: many workers
    cache = DPTCache(str(tmp_path / "dpt.json"))
    dl = _online_loader()
    tuner = OnlineTuner(dl, evaluator=ev, cache=cache, config=_online_cfg(),
                        machine_fp="m", dataset_fp="d")
    # healthy phase: data fully hidden behind compute -> no retune
    for _ in range(8):
        assert tuner.observe(data_s=0.001, step_s=0.1) is None
    assert tuner.retunes == 0
    # drift: the step now stalls on data
    applied = None
    for _ in range(8):
        applied = applied or tuner.observe(data_s=0.09, step_s=0.1)
    assert applied is not None
    assert tuner.retunes == 1
    assert dl.params.num_workers == 4               # hillclimbed to the edge
    assert cache.get_params("m", "d", dl.global_batch) == (4, 1, {})


def test_online_tuner_respects_cooldown():
    ev = _table(lambda i, j: 1.0)
    tuner = OnlineTuner(_online_loader(), evaluator=ev,
                        config=_online_cfg(cooldown_steps=100),
                        machine_fp="m", dataset_fp="d")
    retunes = sum(
        tuner.observe(data_s=0.09, step_s=0.1) is not None
        for _ in range(40))
    assert retunes <= 1


def test_online_tuner_restores_params_when_search_overflows():
    ev = _table(lambda i, j: 1.0, overflow=lambda i, j: True)
    dl = _online_loader()
    orig = dl.params
    tuner = OnlineTuner(dl, evaluator=ev, config=_online_cfg(),
                        machine_fp="m", dataset_fp="d")
    assert tuner.force_retune() is None
    assert dl.params == orig
    assert tuner.retunes == 0


def test_online_tuner_restores_params_on_unexpected_error():
    """A non-MemoryOverflow evaluator crash mid-search must not leave a
    trial cell's params installed on the loader."""
    def ev(i, j, **kw):
        raise OSError("storage went away")

    dl = _online_loader()
    orig = dl.params
    tuner = OnlineTuner(dl, evaluator=ev, config=_online_cfg(),
                        machine_fp="m", dataset_fp="d")
    with pytest.raises(OSError):
        tuner.force_retune()
    assert dl.params == orig


def test_online_tuner_anti_churn_holds_off_lattice():
    """Current params not on the search lattice (e.g. grid's clamped rung
    with an incompatible G): the hillclimb's start trial is still the
    improvement reference, so a same-cost 'winner' is not applied."""
    ev = _table(lambda i, j: 1.0)                   # flat objective
    dl = DataLoader(_index_dataset(64), 8, shuffle=False, seed=0,
                    params=LoaderParams(num_workers=3, prefetch_factor=2))
    tuner = OnlineTuner(dl, evaluator=ev,
                        config=_online_cfg(num_cpu_cores=8, num_devices=2),
                        machine_fp="m", dataset_fp="d")
    assert tuner.force_retune() is None             # no >=5% win anywhere
    assert dl.params.num_workers == 3               # kept, not churned


def test_online_retune_recovers_within_10pct_of_scratch():
    """Simulated mid-run storage slowdown: a bounded hillclimb from the
    stale optimum must land within 10% of a from-scratch grid retune on
    the degraded profile (acceptance criterion; bench_online_drift.py
    reports the same numbers)."""
    machine = MachineProfile()
    healthy = coco_profile(160)
    degraded = degraded_storage(healthy, bw_scale=0.25, latency_scale=6.0)
    cfg = DPTConfig(num_cpu_cores=12, num_devices=1, max_prefetch=8,
                    num_batches=32)

    ev_h = SimulatorEvaluator(LoaderSimulator(healthy, machine),
                              batch_size=32)
    stale = tune(evaluator=ev_h, strategy="grid", config=cfg,
                 measure_default=False)

    mk = lambda: SimulatorEvaluator(LoaderSimulator(degraded, machine),
                                    batch_size=32)
    online_ev = mk()
    online = tune(evaluator=online_ev, strategy="hillclimb", config=cfg,
                  start=(stale.nworker, stale.nprefetch), max_steps=12)
    scratch = tune(evaluator=mk(), strategy="grid", config=cfg,
                   measure_default=False)
    assert online.optimal_time <= scratch.optimal_time * 1.10
    assert online_ev.calls < len(scratch.trials) / 2   # and it was cheaper


def test_apply_params_reaches_abandoned_stream_loader():
    """apply_params updates loader.params immediately even if the last
    stream was abandoned mid-iteration (future pools see new values)."""
    dl = DataLoader(_index_dataset(64), 8, shuffle=False, seed=0,
                    params=LoaderParams(num_workers=2, prefetch_factor=2))
    stream = dl.stream(to_device=False)
    next(stream)                      # consume one batch, then abandon
    dl.apply_params(LoaderParams(num_workers=5, prefetch_factor=3))
    assert dl.params.num_workers == 5
    assert dl.params.prefetch_factor == 3


def test_trainer_rejects_startup_incapable_strategy():
    from repro.train.trainer import Trainer, TrainerConfig
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.data import token_dataset
    cfg = reduced(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    ds = token_dataset(64, 16, cfg.vocab_size, seed=1)
    dl = DataLoader(ds, 8, params=LoaderParams(num_workers=0), seed=1)
    tr = Trainer(model, dl,
                 TrainerConfig(autotune=True, autotune_strategy="goodput"))
    with pytest.raises(ValueError, match="cannot run at startup"):
        tr.tune_loader()
