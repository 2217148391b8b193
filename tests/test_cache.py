"""Cross-epoch cache tier (DESIGN.md §7): the tier itself, the read-path
adapter, loader integration (hot swap / reshard / trial isolation), the
cache-budget DPT axis through every tuner layer, and the simulator's
hit-ratio x latency-delta pricing of the knob.
"""
import dataclasses
import math

import numpy as np
import pytest

from conftest import (flat_indices, make_cold_dataset, make_index_dataset,
                      make_table_evaluator)

from repro.core.cache import DPTCache
from repro.core.dpt import DPTConfig, DPTResult, Trial
from repro.core.monitor import MemoryOverflow
from repro.core.simulator import LoaderSimulator, MachineProfile
from repro.data import DataLoader, LoaderParams
from repro.data.arena import SlabArena
from repro.data.cache import CachedStorage, CacheTier, plan_hot_chunks
from repro.data.storage import ArrayStorage, StorageProfile
from repro.tuning import axis_win, sweep_axis, tune


def _items(n, width=4):
    return [np.full((width,), i, np.int64) for i in range(n)]  # 32B each


# --------------------------------------------------------------------------
# the plan + the tier
# --------------------------------------------------------------------------
def test_plan_hot_chunks_deterministic_math():
    # 100 items in chunks of 8 -> 13 chunks of 80 bytes each
    assert plan_hot_chunks(0, 8, 100, 10.0) == 0
    assert plan_hot_chunks(79, 8, 100, 10.0) == 0      # < one chunk
    assert plan_hot_chunks(160, 8, 100, 10.0) == 2
    assert plan_hot_chunks(800, 8, 100, 10.0) == 10
    assert plan_hot_chunks(1 << 40, 8, 100, 10.0) == 13  # clamped
    assert plan_hot_chunks(1 << 40, 8, 0, 10.0) == 0
    assert plan_hot_chunks(1 << 40, 8, 100, 0.0) == 0


def test_tier_admits_hot_set_only_within_budget():
    items = _items(64)
    tier = CacheTier(8 * 32, chunk=4, num_items=64, item_nbytes=32.0)
    assert tier.hot_chunks == 2
    for i in range(64):
        assert tier.admit(i, items[i]) == (i < 8)
    assert len(tier) == 8
    assert tier.nbytes_in_use() == tier.budget_bytes
    hits, missing = tier.lookup([0, 7, 8, 63])
    assert sorted(hits) == [0, 7] and missing == [8, 63]
    c = tier.counters()
    assert c["cache_tier_hits"] == 2 and c["cache_tier_misses"] == 2
    assert c["cache_tier_items"] == 8 and c["cache_tier_bytes"] == 8 * 32


def test_tier_reconfigure_is_a_trim_never_a_flush():
    items = _items(64)
    tier = CacheTier(1 << 20, chunk=4, num_items=64, item_nbytes=32.0)
    for i in range(16):
        assert tier.admit(i, items[i])
    # shrink to one hot chunk: chunks 1..3 evicted highest-first, chunk 0
    # stays resident — warm entries survive the resize
    tier.resize(4 * 32)
    assert tier.hot_chunks == 1
    assert len(tier) == 4 and tier.evictions == 12
    hits, missing = tier.lookup([0, 3, 4])
    assert sorted(hits) == [0, 3] and missing == [4]
    assert tier.nbytes_in_use() == 4 * 32
    # re-spec the chunk size: hot set recomputed from the new geometry
    tier.reconfigure(budget_bytes=1 << 20, chunk=8)
    assert tier.hot_chunks == 8
    assert len(tier) == 4                # nothing flushed
    # growing the budget back never resurrects evicted entries by itself
    _, missing = tier.lookup([5])
    assert missing == [5]


def test_tier_budget_shared_with_arena():
    used = [0]
    tier = CacheTier(100, chunk=1, num_items=10, item_nbytes=10.0,
                     arena_bytes=lambda: used[0])
    ten = np.zeros(10, np.uint8)
    assert tier.admit(0, ten)
    used[0] = 95                        # arena pressure eats the budget
    assert not tier.admit(1, ten)
    used[0] = 0
    assert tier.admit(1, ten)
    assert tier.nbytes_in_use() == 20


def test_cached_storage_serves_hits_and_never_rereads():
    storage = ArrayStorage(_items(16))
    tier = CacheTier(1 << 20, chunk=4, num_items=16, item_nbytes=32.0)
    cs = CachedStorage(storage, tier, admit=True)
    assert len(cs) == 16
    first = cs.read_batch(range(16))
    assert [int(a[0]) for a in first] == list(range(16))
    assert (tier.hits, tier.misses) == (0, 16)
    second = cs.read_batch(range(16))
    assert [int(a[0]) for a in second] == list(range(16))
    assert tier.hits == 16
    assert int(cs.read(3)[0]) == 3 and tier.hits == 17
    # a read-only view (trial isolation) never admits
    tier.clear()
    ro = CachedStorage(storage, tier, admit=False)
    ro.read(3)
    ro.read_batch([4, 5])
    assert len(tier) == 0


def test_arena_nbytes_in_use_accounting():
    arena = SlabArena(2)
    assert arena.nbytes_in_use() == 0
    batch = {"x": np.zeros((4, 4), np.float32)}
    slot = arena.adopt(dict(batch))
    assert slot is not None
    nbytes = batch["x"].nbytes
    assert arena.nbytes_in_use() == arena.allocated * nbytes


# --------------------------------------------------------------------------
# loader integration: live stream, hot swap, reshard, trials, counters
# --------------------------------------------------------------------------
def _cached_loader(n=64, gb=16, *, budget=1 << 30, chunk=8, seed=0,
                   **kw):
    return DataLoader(make_index_dataset(n), gb,
                      params=LoaderParams(num_workers=1,
                                          locality_chunk=chunk,
                                          cache_budget_bytes=budget),
                      shuffle=True, seed=seed, **kw)


def test_stream_epoch_two_serves_from_the_tier():
    n, gb = 64, 16
    dl = _cached_loader(n, gb)
    tier = dl.cache_tier
    assert tier is not None and tier.hot_chunks == 8   # everything hot
    s = dl.stream(to_device=False)
    try:
        batches = [next(s) for _ in range(2 * (n // gb))]
    finally:
        s.close()
    # both epochs exact; the warm epoch was served from residency
    assert flat_indices(batches[:n // gb]) == list(range(n))
    assert flat_indices(batches[n // gb:]) == list(range(n))
    assert len(tier) == n
    io = dl.io_counters()
    assert io["cache_tier_hits"] >= n
    assert io["cache_tier_bytes"] == tier.nbytes_in_use()


def test_hot_swap_resizes_the_tier_in_place():
    n, gb = 64, 16
    dl = _cached_loader(n, gb)
    tier = dl.cache_tier
    s = dl.stream(to_device=False)
    try:
        for _ in range(n // gb):
            next(s)
        assert len(tier) > 0
        resident = len(tier)
        # a (workers, prefetch) swap keeps the tier and its contents
        dl.apply_params(dl.params.replace(num_workers=2))
        assert dl.cache_tier is tier
        assert len(tier) >= resident
        # a budget shrink resizes the SAME tier (trim, not flush); the
        # swap commits at the live stream's next drain boundary
        dl.apply_params(dl.params.replace(
            cache_budget_bytes=4 * 8 * 16))      # 4 chunks of 8 x 16B
        for _ in range(4 * (n // gb)):           # stream survives the swap
            next(s)
            if tier.hot_chunks == 4:
                break
        assert dl.cache_tier is tier
        assert tier.hot_chunks == 4
        assert 0 < len(tier) <= 32
    finally:
        s.close()


def test_reshard_respecs_the_tier_not_drops_it():
    n, gb = 96, 24
    dl = _cached_loader(n, gb, seed=1, host_index=0, host_count=2)
    tier = dl.cache_tier
    s = dl.stream(to_device=False)
    try:
        next(s)
        dl.reshard(1, 0)                  # take over the whole batch
        for _ in range(2):
            next(s)
        # the tier keys on ABSOLUTE indices, so a reshard re-specs it
        # (num_items unchanged here) instead of dropping warm entries
        assert dl.cache_tier is tier
    finally:
        s.close()


def test_measure_transfer_time_trial_isolation():
    n, gb = 64, 16
    dl = _cached_loader(n, gb)
    tier = dl.cache_tier
    # B > 0: throwaway tier (prewarmed at epoch >= 1); live tier untouched
    stats = dl.measure_transfer_time(2, epoch=1, to_device=False,
                                     cache_budget_bytes=1 << 20)
    assert stats.cache_hits == 2 * gb          # every trial read hit
    assert len(tier) == 0 and tier.hits == 0
    # 0: bypass — no tier in the trial's read path at all
    stats0 = dl.measure_transfer_time(2, epoch=1, to_device=False,
                                      cache_budget_bytes=0)
    assert stats0.cache_hits == 0
    assert len(tier) == 0
    # None: a read-only view over the LIVE tier — misses never admit
    dl.measure_transfer_time(2, epoch=0, to_device=False)
    assert len(tier) == 0


def test_transfer_stats_split_hits_and_misses_cold_storage():
    n, gb = 48, 12
    dl = DataLoader(make_cold_dataset(n, latency_s=1e-4), gb,
                    params=LoaderParams(num_workers=1, locality_chunk=8),
                    shuffle=True, seed=0)
    cold = dl.measure_transfer_time(4, epoch=0, to_device=False,
                                    cache_budget_bytes=1 << 30)
    assert cold.cache_hits == 0 and cold.cache_misses == n
    warm = dl.measure_transfer_time(4, epoch=1, to_device=False,
                                    cache_budget_bytes=1 << 30)
    assert warm.cache_hits == n and warm.cache_misses == 0


# --------------------------------------------------------------------------
# the cache-budget axis through the tuners
# --------------------------------------------------------------------------
SMALL, BIG = 1 << 16, 1 << 30


def test_sweep_cache_prices_warm_and_cache_win():
    ev = make_table_evaluator(
        lambda i, j, c, b, e: (1.0 - (0.4 if b and e >= 1 else 0.0)
                               + 0.2 * (b == BIG)), cache=True)
    trials = sweep_axis(ev, "cache_budget_bytes", (0, SMALL, BIG), 0,
                        nworker=4, nprefetch=2, num_batches=8)
    assert set(trials) == {0, SMALL, BIG}
    assert all(e == 1 for e in ev.epochs)     # priced at a WARM epoch
    assert all(t.axes["cache_budget_bytes"] == b for b, t in trials.items())
    assert axis_win(trials, 0) == SMALL
    assert axis_win(trials, SMALL) is None    # best == current: keep
    # an insignificant gap keeps the current budget
    flat = {0: Trial(4, 2, 1.0), SMALL: Trial(4, 2, 0.99)}
    assert axis_win(flat, 0, min_improvement=0.05) is None


def test_grid_search_four_axis_picks_nonzero_budget():
    ev = make_table_evaluator(
        lambda i, j, c, b, e: (4.0 / i + 0.1 * j
                               - (1.0 if b == SMALL and e >= 1 else 0.0)
                               + (0.5 if b == BIG else 0.0)), cache=True)
    cfg = DPTConfig(num_cpu_cores=4, num_devices=2, max_prefetch=2,
                    num_batches=4, epoch=1, axes={"cache_budget_bytes": (0, SMALL, BIG)})
    res = tune(evaluator=ev, strategy="grid", config=cfg,
               measure_default=False)
    assert (res.nworker, res.nprefetch) == (4, 1)
    assert res.axes["cache_budget_bytes"] == SMALL
    assert any(t.axes["cache_budget_bytes"] == SMALL for t in res.trials)
    # the axis unset: the evaluator must never see the kwarg (legacy
    # two-arg evaluators keep working) and the result carries budget 0
    legacy = make_table_evaluator(lambda i, j: 4.0 / i + 0.1 * j)
    res2 = tune(evaluator=legacy, strategy="grid",
                config=dataclasses.replace(cfg, axes={}),
                measure_default=False)
    assert res2.axes == {}


def test_dpt_cache_fourth_axis_backcompat_and_clobber_protection():
    cache = DPTCache()
    searched = DPTResult(4, 2, 0.5, [
        Trial(4, 2, 1.0, axes={"cache_budget_bytes": 0}),
        Trial(4, 2, 0.5, axes={"cache_budget_bytes": SMALL})],
        axes={"cache_budget_bytes": SMALL})
    cache.put("m", "d", 32, searched)
    # an axis-blind read still hits, with no axis values
    assert cache.get_params("m", "d", 32) == (4, 2, {})
    assert cache.get_params("m", "d", 32, axes=("cache_budget_bytes",)) \
        == (4, 2, {"cache_budget_bytes": SMALL})
    # a budget-blind refinement must not clobber the searched budget
    blind = DPTResult(6, 1, 0.4, [Trial(6, 1, 0.4)])
    cache.put("m", "d", 32, blind)
    assert cache.get_params("m", "d", 32, axes=("cache_budget_bytes",)) \
        == (6, 1, {"cache_budget_bytes": SMALL})
    # a fresh entry whose search never swept the axis misses the read
    cache.put("m2", "d", 32, blind)
    assert cache.get_params("m2", "d", 32,
                            axes=("cache_budget_bytes",)) is None


def test_online_retune_sweeps_and_applies_cache_budget():
    from repro.tuning import OnlineTuner, OnlineTunerConfig
    dl = DataLoader(make_index_dataset(64), 16,
                    params=LoaderParams(num_workers=2, prefetch_factor=1),
                    shuffle=True, seed=0)
    # flat in (workers, prefetch); only a warm cache budget helps
    ev = make_table_evaluator(
        lambda i, j, c, b, e: 1.0 - (0.5 if b == SMALL and e >= 1 else 0.0),
        cache=True)
    tuner = OnlineTuner(dl, evaluator=ev, config=OnlineTunerConfig(
        num_cpu_cores=4, num_devices=2, max_prefetch=2,
        retune_budget_batches=2, axes={"cache_budget_bytes": (0, SMALL)}))
    params = tuner.force_retune(reason="test")
    assert params is not None
    assert params.cache_budget_bytes == SMALL
    assert dl.params.cache_budget_bytes == SMALL
    assert dl.cache_tier is not None and dl.cache_tier.hot_chunks > 0
    assert tuner.history[-1]["outcome"] == "applied"
    assert tuner.history[-1]["cache_budget_bytes"] == SMALL


def test_fleet_consensus_pushes_uniform_cache_budget():
    from repro.tuning import FleetConfig, FleetCoordinator, HostAgent
    n, gb, hosts = 96, 12, 2
    coord = FleetCoordinator(config=FleetConfig(
        heartbeat_timeout_s=30.0, warmup_steps=1, cooldown_steps=1,
        num_cpu_cores=4, num_devices=2, max_prefetch=2,
        retune_budget_batches=2, axes={"cache_budget_bytes": (0, SMALL)}))
    agents = []
    for h in range(hosts):
        dl = DataLoader(make_index_dataset(n), gb, shuffle=True, seed=3,
                        params=LoaderParams(num_workers=2,
                                            prefetch_factor=1),
                        host_index=h, host_count=hosts)
        ev = make_table_evaluator(
            lambda i, j, c, b, e: (4.0 / i + 0.1 * j
                                   - (1.0 if b == SMALL and e >= 1
                                      else 0.0)), cache=True)
        agents.append(coord.register(HostAgent(f"host{h}", dl,
                                               evaluator=ev)))
    coord.request_consensus(reason="test")
    coord.poll()
    event = coord.events[-1]
    assert event["kind"] == "consensus" and event["applied"]
    assert event["cache_budget_bytes"] == SMALL
    for a in agents:
        assert a.loader.params.cache_budget_bytes == SMALL
        assert a.loader.cache_tier is not None
    # every host computes the same hot set — no coordination needed
    plans = {a.loader.cache_tier.hot_chunks for a in agents}
    assert len(plans) == 1


def test_fleet_join_copies_cache_plan_and_budget():
    from repro.tuning import FleetConfig, FleetCoordinator, HostAgent
    n, gb = 96, 12
    coord = FleetCoordinator(config=FleetConfig(heartbeat_timeout_s=30.0))

    def spawn(h, count, budget):
        dl = DataLoader(make_index_dataset(n), gb, shuffle=True, seed=5,
                        params=LoaderParams(num_workers=1,
                                            locality_chunk=8,
                                            cache_budget_bytes=budget),
                        host_index=h, host_count=count)
        return HostAgent(f"host{h}", dl,
                         evaluator=make_table_evaluator(lambda i, j: 1.0))

    incumbents = [coord.register(spawn(h, 2, SMALL)) for h in range(2)]
    joiner = spawn(2, 1, 0)
    coord.join(joiner)
    src = incumbents[0].loader
    assert joiner.loader.params.cache_budget_bytes == SMALL
    assert joiner.loader.sampler.cache_state() == src.sampler.cache_state()
    assert joiner.loader.cache_tier is not None
    assert joiner.loader.cache_tier.hot_chunks \
        == src.cache_tier.hot_chunks


# --------------------------------------------------------------------------
# the simulator's pricing of the axis
# --------------------------------------------------------------------------
_SP = StorageProfile(num_items=10_000, item_bytes=1e5,
                     decoded_item_bytes=4e5, io_latency_s=5e-3,
                     seek_congestion=0.2, storage_bw=80e6,
                     decode_cpu_s_fixed=100e-6, decode_cpu_s_per_byte=2e-9)
# RAM-constrained host whose page cache is unreliable under pressure —
# the regime where an explicitly pinned tier earns its footprint
_MP_TIGHT = MachineProfile(host_ram=8e9, page_cache_eff=0.2,
                           worker_overhead_bytes=0.2e9)


def test_simulator_neutral_default_is_bit_identical():
    sim = LoaderSimulator(_SP, MachineProfile())
    kw = dict(batch_size=32, num_batches=16, nworker=4, nprefetch=2,
              epoch=1)
    assert sim.simulate(**kw) == sim.simulate(**kw, cache_budget_bytes=0)


def test_simulator_prices_budget_as_hit_ratio_vs_footprint():
    sim = LoaderSimulator(_SP, _MP_TIGHT)
    kw = dict(batch_size=32, num_batches=16, nworker=4, nprefetch=2)
    # warm epoch: the pinned tier's certain hits beat the leaky page cache
    no_budget = sim.simulate(**kw, epoch=1)
    budget = sim.simulate(**kw, epoch=1, cache_budget_bytes=1e9)
    assert budget.warm_fraction > no_budget.warm_fraction
    assert budget.seconds < no_budget.seconds
    # cold epoch: the budget only costs footprint, never buys time
    cold0 = sim.simulate(**kw, epoch=0)
    cold1 = sim.simulate(**kw, epoch=0, cache_budget_bytes=1e9)
    assert cold1.seconds == cold0.seconds
    assert cold1.peak_bytes > cold0.peak_bytes
    # a budget past the RAM line overflows like any other footprint
    with pytest.raises(MemoryOverflow):
        sim.simulate(**kw, epoch=1, cache_budget_bytes=10e9)


def test_simulated_grid_picks_budget_warm_and_zero_cold():
    from repro.core.evaluators import SimulatorEvaluator
    ev = SimulatorEvaluator(LoaderSimulator(_SP, _MP_TIGHT), batch_size=32)
    cfg = DPTConfig(num_cpu_cores=4, num_devices=2, max_prefetch=2,
                    num_batches=8, epoch=1, axes={"cache_budget_bytes": (0, int(1e9))})
    warm = tune(evaluator=ev, strategy="grid", config=cfg,
                measure_default=False)
    assert warm.axes["cache_budget_bytes"] == int(1e9)
    cold = tune(evaluator=ev, strategy="grid",
                config=dataclasses.replace(cfg, epoch=0),
                measure_default=False)
    assert cold.axes["cache_budget_bytes"] == 0   # ties resolve to no cache


# --------------------------------------------------------------------------
# trainer plumbing
# --------------------------------------------------------------------------
def test_trainer_guards_cache_axis_like_locality():
    from repro.train.trainer import TrainerConfig
    cfg = TrainerConfig(autotune_axes={"cache_budget_bytes": (0, SMALL)})
    assert cfg.autotune_axes == {"cache_budget_bytes": (0, SMALL)}
    # the online tuner inherits the axis on a single host
    from repro.train.trainer import Trainer
    dl = DataLoader(make_index_dataset(32), 8, shuffle=True, seed=0,
                    params=LoaderParams(num_workers=1))
    t = Trainer.__new__(Trainer)
    t.loader, t.cfg = dl, cfg
    tuner = t._make_online_tuner()
    assert tuner.cfg.axes == {"cache_budget_bytes": (0, SMALL)}
    # sharded: the axis must stay off host-local retunes
    dl2 = DataLoader(make_index_dataset(32), 8, shuffle=True, seed=0,
                     params=LoaderParams(num_workers=1),
                     host_index=0, host_count=2)
    t.loader = dl2
    assert t._make_online_tuner().cfg.axes == {}
