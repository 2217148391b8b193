"""Dual-lane slow-sample isolation (DESIGN.md §9).

Covers the whole chain: the per-item cost tracker (EM attribution,
exoneration, checkpointing), the dual-lane worker pools (ordered delivery
and exact coverage with stragglers planted), the heavy-tailed storage
mode's determinism, the simulator's lane pricing, the retune-time lane
sweep, the DPT cache's lane axis, the tail-ratio retune trigger, and the
serving frontend's slow group lane.
"""
import math
import pickle
import time

import numpy as np
import pytest

from conftest import flat_indices, make_cold_dataset, make_index_dataset

from repro.data import DataLoader, LoaderParams
from repro.data.costs import (KeyedCostTracker, SampleCostTracker,
                              percentile)

SLOW_EVERY = 16                   # planted straggler population: idx % 16


def _sleepy_transform(a):
    """Picklable index transform: every SLOW_EVERY-th item is a straggler
    (works in thread AND forked process workers)."""
    if int(a[0]) % SLOW_EVERY == 0:
        time.sleep(3e-3)
    return {"x": a}


# --------------------------------------------------------------------------
# SampleCostTracker: EM attribution over batch-aggregate timings
# --------------------------------------------------------------------------
def _feed_epochs(tracker, n, batch, *, epochs, slow_idx, base=1e-3,
                 extra=2e-2, seed=0):
    """Simulate recorded batches: every item costs ``base``; members of
    ``slow_idx`` add ``extra``.  Shuffled like a real epoch."""
    rng = np.random.default_rng(seed)
    for e in range(epochs):
        perm = rng.permutation(n)
        for b in range(n // batch):
            idx = perm[b * batch:(b + 1) * batch]
            total = base * batch + extra * np.isin(idx, slow_idx).sum()
            tracker.record(idx, float(total))


def test_tracker_learns_planted_straggler():
    n, batch = 64, 4
    t = SampleCostTracker(n)
    _feed_epochs(t, n, batch, epochs=4, slow_idx=[7])
    est = t.predict(np.arange(n))
    # the straggler's estimate separates cleanly from the fast population
    assert est[7] > 4.0 * np.median(est)
    assert t.is_slow([7, 1, 2, 3])
    assert not t.is_slow([1, 2, 3, 4])
    assert t.tail_ratio() > 4.0


def test_tracker_cold_never_routes():
    t = SampleCostTracker(64, min_records=8)
    for _ in range(7):                 # one short of min_records
        t.record([0, 1, 2, 3], 10.0)
    assert not t.is_slow([0, 1, 2, 3])


def test_tracker_exonerates_falsely_blamed_items():
    """An item that shared its batches with a straggler (shared blame
    while both were unseen) must be cleared by later fast sightings."""
    t = SampleCostTracker(64)
    # fast baseline: the median item cost settles at ~1ms
    for _ in range(3):
        for s in range(16, 64, 4):
            t.record([s, s + 1, s + 2, s + 3], 4e-3)
    # cold blame: 9 only ever rides in the straggler's batch, so the
    # outlier attribution has no evidence to separate them yet
    for _ in range(3):
        t.record([7, 9, 1, 2], 4e-3 + 2e-2)
    assert t.is_slow([9, 16, 17, 18])      # falsely suspected, for now
    # then 9 shows up in evidently-fast company while 7 stays slow
    for _ in range(4):
        t.record([9, 20, 21, 22], 4e-3)
        t.record([7, 24, 25, 26], 4e-3 + 2e-2)
    assert t.is_slow([7, 16, 17, 18])
    assert not t.is_slow([9, 20, 21, 22])


def test_tracker_state_roundtrip_and_pickle():
    n = 64
    a = SampleCostTracker(n)
    _feed_epochs(a, n, 4, epochs=3, slow_idx=[5, 21])
    b = SampleCostTracker(n)
    b.load_state_dict(a.state_dict())
    np.testing.assert_allclose(b.predict(np.arange(n)),
                               a.predict(np.arange(n)))
    assert b.records == a.records and b.is_slow([5, 1, 2, 3])
    # workers receive the tracker by reference in threads and by pickle in
    # forked pools' parents — it must survive the trip with its table
    c = pickle.loads(pickle.dumps(a))
    np.testing.assert_allclose(c.predict(np.arange(n)),
                               a.predict(np.arange(n)))
    assert c.is_slow([5, 1, 2, 3])


def test_tracker_bucket_fallback_bounds_table():
    t = SampleCostTracker(1 << 20, max_slots=1 << 10)
    assert t.bucket >= (1 << 10)
    assert t._ewma.size <= (1 << 10)
    # slots alias by design; recording and prediction still work
    _feed_epochs(t, 4096, 4, epochs=2, slow_idx=[])
    assert t.records > 0 and t.mean() > 0


def test_keyed_tracker_slow_key_and_roundtrip():
    t = KeyedCostTracker(min_records=4)
    for _ in range(4):
        t.record((16, 4), 0.002)
        t.record((512, 64), 0.050)
    assert t.is_slow((512, 64))
    assert not t.is_slow((16, 4))
    assert not t.is_slow((999, 9))     # unknown key is never slow
    b = KeyedCostTracker()
    b.load_state_dict(t.state_dict())
    assert b.is_slow((512, 64)) and b.predict((16, 4)) == t.predict((16, 4))


def test_percentile_helper():
    assert percentile([], 0.99) == 0.0
    assert percentile([1.0, 2.0, 3.0], 0.5) == 2.0


# --------------------------------------------------------------------------
# LoaderParams validation: misconfiguration fails loudly
# --------------------------------------------------------------------------
@pytest.mark.parametrize("kw", [
    {"slow_lane_workers": -1},
    {"slow_lane_lookahead": -1},
    {"slow_lane_threshold": 1.0},
    {"slow_lane_threshold": 0.5},
    {"use_processes": True, "ordered": False},
])
def test_loader_params_rejects_bad_lane_config(kw):
    with pytest.raises(ValueError):
        LoaderParams(**kw)


def test_arena_capacity_covers_lane_lookahead():
    base = LoaderParams(num_workers=2, zero_copy=True)
    lane = base.replace(slow_lane_workers=2, slow_lane_lookahead=8)
    # the slow lane's early-start span needs its own slots
    assert lane.arena_capacity() >= base.arena_capacity() + 2 + 8


# --------------------------------------------------------------------------
# dual-lane pools: ordered delivery + exact coverage with stragglers live
# --------------------------------------------------------------------------
def _lane_params(**kw):
    base = dict(num_workers=2, prefetch_factor=2, ordered=True,
                slow_lane_workers=2, slow_lane_lookahead=8,
                slow_lane_threshold=4.0)
    base.update(kw)
    return LoaderParams(**base)


def test_dual_lane_thread_pool_ordered_exact_coverage():
    """Three epochs through the real thread pool with the slow lane on:
    every epoch is delivered in exact sampler order (the lanes merge at
    the reorder buffer) and covers the dataset exactly once — and after
    the warm-up epoch the tracker routes batches to the slow lane."""
    n, gb = 96, 8
    ds = make_index_dataset(n, transform=_sleepy_transform)
    dl = DataLoader(ds, gb, params=_lane_params(), shuffle=True, seed=0)
    for epoch in range(3):
        batches = list(dl.host_batches(epoch=epoch, num_batches=n // gb))
        assert flat_indices(batches) == list(range(n))
        want = [dl.sampler.local_indices(epoch, b).tolist()
                for b in range(n // gb)]
        got = [np.asarray(b["x"])[:, 0].tolist() for b in batches]
        assert got == want, f"epoch {epoch} delivered out of order"
    assert dl.cost_tracker.records > 0
    assert dl.cost_tracker.slow_batches > 0, \
        "warm tracker never routed a straggler batch to the slow lane"
    io = dl.io_counters()
    assert io["sample_cost_tail_ratio"] > 1.0
    assert io["sample_cost_p99_s"] >= io["sample_cost_mean_s"]


def test_dual_lane_process_pool_ordered_exact_coverage():
    """The process pool's consumer-driven lane pump: same order and
    coverage guarantees (delivery is inherently ordered there)."""
    n, gb = 48, 8
    ds = make_index_dataset(n, transform=_sleepy_transform)
    dl = DataLoader(ds, gb,
                    params=_lane_params(use_processes=True, fast_path=False),
                    shuffle=True, seed=1)
    for epoch in range(2):
        batches = list(dl.host_batches(epoch=epoch, num_batches=n // gb))
        assert flat_indices(batches) == list(range(n))
        want = [dl.sampler.local_indices(epoch, b).tolist()
                for b in range(n // gb)]
        got = [np.asarray(b["x"])[:, 0].tolist() for b in batches]
        assert got == want
    assert dl.cost_tracker.records > 0


def test_lane_off_without_order_is_inert():
    """ordered=False (threads): the lane silently disables — there is no
    head-of-line pathology to fix — and delivery still covers exactly."""
    n, gb = 48, 8
    ds = make_index_dataset(n, transform=_sleepy_transform)
    dl = DataLoader(ds, gb, params=_lane_params(ordered=False),
                    shuffle=True, seed=0)
    batches = list(dl.host_batches(epoch=0, num_batches=n // gb))
    assert flat_indices(batches) == list(range(n))
    assert dl.cost_tracker.slow_batches == 0


def test_measure_transfer_time_lane_override_and_counters():
    """The slow-lane axis's measurement-only override: a trial at a
    candidate width must not touch the live params, and TransferStats
    carries the tail-cost counters."""
    n, gb = 48, 8
    ds = make_index_dataset(n, transform=_sleepy_transform)
    dl = DataLoader(ds, gb, params=LoaderParams(num_workers=2),
                    shuffle=True, seed=0)
    st = dl.measure_transfer_time(n // gb, epoch=0, to_device=False,
                                  slow_lane_workers=2)
    assert dl.params.slow_lane_workers == 0      # live params untouched
    assert st.sample_cost_mean_s > 0
    assert st.sample_cost_p99_s >= st.sample_cost_mean_s


def test_cost_tracker_rides_loader_checkpoint():
    n, gb = 64, 8
    ds = make_index_dataset(n, transform=_sleepy_transform)
    dl = DataLoader(ds, gb, params=_lane_params(), shuffle=True, seed=0)
    for e in range(2):
        list(dl.host_batches(epoch=e, num_batches=n // gb))
    saved = dl.state_dict()
    dl2 = DataLoader(make_index_dataset(n, transform=_sleepy_transform),
                     gb, params=_lane_params(), shuffle=True, seed=0)
    dl2.load_state_dict(saved)
    np.testing.assert_allclose(dl2.cost_tracker.predict(np.arange(n)),
                               dl.cost_tracker.predict(np.arange(n)))
    assert dl2.cost_tracker.records == dl.cost_tracker.records


# --------------------------------------------------------------------------
# heavy-tailed LatencyStorage: deterministic planted stragglers
# --------------------------------------------------------------------------
def test_latency_storage_tail_is_deterministic():
    from repro.data import ArrayStorage, LatencyStorage
    items = [np.zeros(4, np.float32) for _ in range(256)]

    def mk(seed):
        return LatencyStorage(ArrayStorage(items), latency_s=1e-5,
                              tail_fraction=0.05, tail_mult=20.0,
                              tail_seed=seed)

    a, b = mk(3), mk(3)
    mults = [a.tail_multiplier(i) for i in range(256)]
    assert mults == [b.tail_multiplier(i) for i in range(256)]
    assert mults == [a.tail_multiplier(i) for i in range(256)]  # stable
    tails = [i for i in range(256) if a.is_tail(i)]
    assert 1 <= len(tails) <= 40                  # ~5% of 256, wide margin
    assert all(a.tail_multiplier(i) == 20.0 for i in tails)
    # a different seed plants a different straggler set
    c = mk(4)
    assert tails != [i for i in range(256) if c.is_tail(i)]
    # the extra sleep charged is (mult - 1) base latencies per tail item
    assert a._tail_extra_s([tails[0]]) == pytest.approx(19.0 * 1e-5)
    assert a._tail_extra_s([(tails[0] + 1) % 256]) == 0.0


def test_latency_storage_lognormal_mode():
    from repro.data import ArrayStorage, LatencyStorage
    items = [np.zeros(4, np.float32) for _ in range(512)]
    st = LatencyStorage(ArrayStorage(items), latency_s=1e-5,
                        tail_fraction=1.0, tail_mult=20.0,
                        tail_mode="lognormal")
    mults = np.array([st.tail_multiplier(i) for i in range(512)])
    assert np.median(mults) == pytest.approx(1.0, rel=0.3)
    assert mults.max() > 4.0                     # a real tail exists
    with pytest.raises(ValueError):
        LatencyStorage(ArrayStorage(items), tail_mode="pareto")


def test_cold_dataset_tail_passthrough():
    ds = make_cold_dataset(32, latency_s=1e-5, tail_fraction=0.1,
                           tail_mult=10.0, tail_seed=2)
    st = ds.storage
    assert st.tail_fraction == 0.1 and st.tail_mult == 10.0
    assert any(st.is_tail(i) for i in range(32))


# --------------------------------------------------------------------------
# simulator: the fifth axis prices out of heavy-tailed profiles only
# --------------------------------------------------------------------------
def _decode_heavy_profile():
    import dataclasses
    from repro.data.storage import cifar10_profile
    return dataclasses.replace(cifar10_profile(), decode_cpu_s_fixed=1e-3,
                               vectorized_decode_fixed_s=None)


def _sim(profile):
    from repro.core.simulator import LoaderSimulator, MachineProfile
    return LoaderSimulator(profile, MachineProfile(
        physical_cores=8, logical_cores=8, reserved_cores=0, num_devices=2))


def test_simulator_neutral_profile_lane_free_is_identity():
    sim = _sim(_decode_heavy_profile())
    a = sim.simulate(batch_size=4, num_batches=64, nworker=2, nprefetch=1)
    b = sim.simulate(batch_size=4, num_batches=64, nworker=2, nprefetch=1,
                     slow_lane_workers=0)
    assert a.seconds == b.seconds and a.peak_bytes == b.peak_bytes


def test_simulator_prices_lane_on_heavy_tail():
    heavy = _decode_heavy_profile().with_heavy_tail(fraction=0.03,
                                                    mult=100.0)
    sim = _sim(heavy)
    t0 = sim.simulate(batch_size=4, num_batches=64, nworker=2,
                      nprefetch=1).seconds
    t1 = sim.simulate(batch_size=4, num_batches=64, nworker=2, nprefetch=1,
                      slow_lane_workers=1).seconds
    assert t1 < t0, "a slow lane must pay off on the straggler profile"
    # on a uniform profile the lane is pure overhead
    uni = _sim(_decode_heavy_profile())
    u0 = uni.simulate(batch_size=4, num_batches=64, nworker=2,
                      nprefetch=1).seconds
    u1 = uni.simulate(batch_size=4, num_batches=64, nworker=2, nprefetch=1,
                      slow_lane_workers=1).seconds
    assert u1 >= u0


def test_dpt_grid_resolves_lane_axis():
    """The full grid (workers x prefetch x lanes) picks a nonzero lane
    width on the heavy-tailed decode profile and zero on the uniform one
    — the knob only spends workers where stragglers exist."""
    from repro.core.dpt import DPTConfig
    from repro.core.evaluators import SimulatorEvaluator
    from repro.tuning import tune

    def pick(profile):
        ev = SimulatorEvaluator(_sim(profile), batch_size=4)
        cfg = DPTConfig(num_cpu_cores=8, num_devices=2, min_prefetch=1,
                        max_prefetch=2, num_batches=64,
                        axes={"slow_lane_workers": (0, 1, 2, 3)})
        return tune(evaluator=ev, strategy="grid", config=cfg,
                    measure_default=False)

    heavy = pick(_decode_heavy_profile().with_heavy_tail(fraction=0.03,
                                                         mult=100.0))
    assert heavy.axes["slow_lane_workers"] > 0
    assert any(t.axes["slow_lane_workers"] for t in heavy.trials)
    uniform = pick(_decode_heavy_profile())
    assert uniform.axes["slow_lane_workers"] == 0


def test_dpt_grid_without_lane_axis_never_passes_kwarg():
    """No lane axis in ``axes`` keeps the search lane-blind: evaluators
    that never heard of the axis must keep working."""
    from conftest import make_table_evaluator
    from repro.core.dpt import DPTConfig
    from repro.tuning import tune
    ev = make_table_evaluator(lambda i, j: 1.0 / i + 0.1 * j)
    r = tune(evaluator=ev, strategy="grid",
             config=DPTConfig(num_cpu_cores=4, num_devices=2,
                              max_prefetch=2),
             measure_default=False)
    assert r.axes == {}
    assert all(t.axes == {} for t in r.trials)


# --------------------------------------------------------------------------
# retune-time lane sweep + win test
# --------------------------------------------------------------------------
def _lane_table_evaluator(fn):
    from repro.data.loader import TransferStats

    def ev(i, j, *, num_batches=16, epoch=0, slow_lane_workers=None):
        ev.calls += 1
        return TransferStats(fn(i, j, slow_lane_workers or 0),
                             num_batches, 0)
    ev.calls = 0
    return ev


def test_sweep_slow_lanes_and_win():
    from repro.tuning import axis_win, sweep_axis
    ev = _lane_table_evaluator(lambda i, j, k: 1.0 / (1 + k))
    trials = sweep_axis(ev, "slow_lane_workers", (0, 2, 4), 0,
                        nworker=2, nprefetch=1, num_batches=8)
    assert set(trials) == {0, 2, 4}
    assert all(t.axes["slow_lane_workers"] == k for k, t in trials.items())
    assert axis_win(trials, 0) == 4


def test_slow_lane_win_defends_current():
    from repro.tuning import axis_win
    from repro.core.dpt import Trial
    # a 2% improvement does not clear the 5% threshold
    trials = {0: Trial(2, 1, 1.00, axes={"slow_lane_workers": 0}),
              2: Trial(2, 1, 0.98, axes={"slow_lane_workers": 2})}
    assert axis_win(trials, 0) is None
    # the current width being the argmin is never a "win"
    trials[2] = Trial(2, 1, 1.50, axes={"slow_lane_workers": 2})
    assert axis_win(trials, 0) is None
    # an overflowed candidate never wins
    trials = {0: Trial(2, 1, 1.0, axes={"slow_lane_workers": 0}),
              2: Trial(2, 1, math.inf, overflowed=True,
                       axes={"slow_lane_workers": 2})}
    assert axis_win(trials, 0) is None


def test_sweep_slow_lanes_handles_overflow():
    from repro.core.monitor import MemoryOverflow
    from repro.tuning import sweep_axis

    def ev(i, j, *, num_batches=16, epoch=0, slow_lane_workers=None):
        if (slow_lane_workers or 0) > 2:
            raise MemoryOverflow("lane widened past the budget")
        from repro.data.loader import TransferStats
        return TransferStats(1.0, num_batches, 0)

    trials = sweep_axis(ev, "slow_lane_workers", (0, 2, 4), 0,
                        nworker=2, nprefetch=1, num_batches=8)
    assert trials[4].overflowed and math.isinf(trials[4].seconds)
    assert not trials[2].overflowed


# --------------------------------------------------------------------------
# DPT cache: the lane axis persists with staleness semantics
# --------------------------------------------------------------------------
def _result(lane, *, searched):
    from repro.core.dpt import DPTResult, Trial
    trials = [Trial(2, 1, 1.0, axes={"slow_lane_workers": k})
              for k in ((0, lane) if searched else (0,))]
    return DPTResult(2, 1, 1.0, trials, axes={"slow_lane_workers": lane})


def test_dpt_cache_lane_axis_roundtrip(tmp_path):
    from repro.core.cache import DPTCache
    path = str(tmp_path / "dpt.json")
    cache = DPTCache(path)
    cache.put("m", "d", 32, _result(2, searched=True))
    got = cache.get_params("m", "d", 32, axes=("slow_lane_workers",))
    assert got is not None and got[2]["slow_lane_workers"] == 2
    # persists across a reload
    assert DPTCache(path).get_params(
        "m", "d", 32, axes=("slow_lane_workers",))[2] == {
            "slow_lane_workers": 2}


def test_dpt_cache_lane_blind_entry_is_stale():
    from repro.core.cache import DPTCache
    cache = DPTCache()
    cache.put("m", "d", 32, _result(0, searched=False))
    assert cache.get_params("m", "d", 32, axes=("slow_lane_workers",)) \
        is None
    assert cache.get_params("m", "d", 32) is not None   # fine axis-blind


def test_dpt_cache_lane_blind_refinement_never_clobbers():
    from repro.core.cache import DPTCache
    cache = DPTCache()
    cache.put("m", "d", 32, _result(2, searched=True))
    # an online 2-axis retune refines (workers, prefetch) lane-blind;
    # the searched lane width must survive
    cache.put("m", "d", 32, _result(0, searched=False))
    got = cache.get_params("m", "d", 32, axes=("slow_lane_workers",))
    assert got is not None and got[2]["slow_lane_workers"] == 2


# --------------------------------------------------------------------------
# online retune trigger: the cost tail is drift
# --------------------------------------------------------------------------
def test_tail_ratio_trigger_arms_only_with_lanes():
    from repro.tuning.online import (GoodputMonitor, OnlineTunerConfig,
                                     RetunePolicy)
    mon = GoodputMonitor()
    mon.note_tail(50.0)
    armed = RetunePolicy(OnlineTunerConfig(axes={"slow_lane_workers": (0, 2)},
                                           tail_ratio_trigger=10.0))
    assert armed.drifted(mon)
    below = GoodputMonitor()
    below.note_tail(5.0)
    assert not armed.drifted(below)
    # no lane axis -> the tail signal cannot trigger a search that could
    # never act on it
    disarmed = RetunePolicy(OnlineTunerConfig(tail_ratio_trigger=10.0))
    assert not disarmed.drifted(mon)
    off = RetunePolicy(OnlineTunerConfig(axes={"slow_lane_workers": (0, 2)}))
    assert not off.drifted(mon)


def test_online_tuner_observe_feeds_tail_signal():
    """The OnlineTuner pulls io_counters' tail ratio into its monitor once
    per window — the plumbing between the loader's tracker and the
    policy."""
    from repro.tuning.online import OnlineTuner, OnlineTunerConfig
    n, gb = 96, 8
    ds = make_index_dataset(n, transform=_sleepy_transform)
    dl = DataLoader(ds, gb, params=_lane_params(), shuffle=True, seed=0)
    for e in range(2):                 # warm the tracker
        list(dl.host_batches(epoch=e, num_batches=n // gb))
    cfg = OnlineTunerConfig(window=4, warmup_steps=10**6,  # never searches
                            axes={"slow_lane_workers": (0, 2)}, tail_ratio_trigger=1.5)
    tuner = OnlineTuner(dl, evaluator=None, config=cfg)
    for _ in range(cfg.window):
        tuner.observe(data_s=0.0, step_s=0.01)
    assert tuner.monitor.tail_ratio > 1.5


# --------------------------------------------------------------------------
# serving: expensive request groups take the slow lane
# --------------------------------------------------------------------------
class _FakeEngine:
    """Duck-typed stand-in for ServeEngine: expensive when max_new is
    large, instant otherwise."""
    max_batch = 4

    def generate(self, prompts, max_new):
        time.sleep(0.04 if max_new >= 64 else 0.001)

        class R:
            tokens = np.zeros((len(prompts), max_new), np.int32)
        return R()


def test_frontend_slow_lane_isolates_expensive_groups():
    from repro.serve.engine import BatchingFrontend
    fe = BatchingFrontend(_FakeEngine(), max_wait_s=0.002, slow_lane=True,
                          slow_threshold=4.0)
    try:
        rng = np.random.default_rng(0)

        def burst(k, max_new):
            return [fe.submit(rng.integers(0, 100, (16,)).astype(np.int32),
                              max_new) for _ in range(k)]

        # warm the keyed tracker with both shapes (the tracker records
        # once per served GROUP, so several rounds are needed)
        for _ in range(4):
            for r in burst(2, 4) + burst(2, 64):
                r.result.get(timeout=30)
        assert fe.cost_tracker.is_slow((16, 64))
        # now a mixed burst: the expensive group must route to the slow
        # thread and everything still completes
        reqs = burst(6, 64) + burst(6, 4)
        outs = [r.result.get(timeout=30) for r in reqs]
        assert len(outs) == 12
        assert fe.slow_groups > 0
        assert fe.assembly_wait_p99() >= 0.0
        assert fe.assembly_wait_p99(slow=True) > 0.0
    finally:
        fe.shutdown()
