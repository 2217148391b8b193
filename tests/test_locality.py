"""IO-locality fast path: chunked sampling quality, the DPT locality
axis, the ONLINE locality loop (retune sweep + adaptive controller,
DESIGN.md §6), the pinned staging-buffer pool, counter surfacing, and the
FileStorage fork hygiene fix (DESIGN.md §5).

Coverage/permutation invariants across randomized (chunk, shard count,
reshard, checkpoint) configurations live in test_properties.py — the
hand-enumerated case lists that used to sit here were replaced by that
property suite.
"""
import dataclasses
import multiprocessing as mp
import os

import numpy as np
import pytest

from conftest import make_cold_dataset as _cold_dataset

from repro.core.cache import DPTCache
from repro.core.dpt import DPTConfig, DPTResult, Trial
from repro.core.evaluators import LoaderEvaluator, SimulatorEvaluator
from repro.core.simulator import LoaderSimulator, MachineProfile
from repro.data import (DataLoader, FileStorage, LoaderParams,
                        ShardedSampler, coco_profile,
                        synthetic_image_dataset)
from repro.data.prefetcher import DevicePrefetcher, StagingPool
from repro.data.storage import coalesce_runs, storage_io_counters
from repro.tuning import tune


def test_chunked_batches_coalesce_into_runs():
    # chunk == global batch, one host: every batch is one contiguous run
    s = ShardedSampler(256, 32, seed=1, locality_chunk=32)
    for b in range(s.batches_per_epoch()):
        runs = coalesce_runs(s.local_indices(0, b))
        assert len(runs) == 1 and runs[0][1] == 32
    # random order: batches are essentially all singleton runs
    r = ShardedSampler(256, 32, seed=1)
    assert len(coalesce_runs(r.local_indices(0, 0))) > 24


# --------------------------------------------------------------------------
# shuffle quality: chunk order uniform, adjacency bounded
# --------------------------------------------------------------------------
def test_chunk_order_is_uniform():
    """Each chunk should land in each chunk-slot equally often across
    epochs (the chunk permutation is an unbiased rng.permutation)."""
    n, chunk = 64, 16                       # 4 chunks
    s = ShardedSampler(n, 16, seed=3, locality_chunk=chunk)
    epochs = 400
    counts = np.zeros((4, 4), int)          # chunk id x slot
    for e in range(epochs):
        perm = s._epoch_perm(e)
        for slot in range(4):
            counts[perm[slot * chunk] // chunk, slot] += 1
    expected = epochs / 4
    assert (np.abs(counts - expected) < 0.4 * expected).all(), counts


def test_adjacent_pair_rate_bounded_by_chunk_ceiling():
    from benchmarks.bench_locality import adjacent_pair_ceiling
    n = 4096
    for chunk in (16, 64, 256):
        perm = ShardedSampler(n, 64, seed=0,
                              locality_chunk=chunk)._epoch_perm(0)
        rate = float(np.mean(perm[1:] == perm[:-1] + 1))
        assert rate <= adjacent_pair_ceiling(chunk)
        assert rate < 0.2                   # nowhere near sequential (1.0)


# --------------------------------------------------------------------------
# chunked epoch == random epoch, as a sample multiset
# --------------------------------------------------------------------------
def test_chunked_epoch_is_byte_identical_multiset():
    ds = synthetic_image_dataset(128, 8, seed=0)

    def digests(chunk):
        dl = DataLoader(ds, 16, params=LoaderParams(locality_chunk=chunk),
                        shuffle=True, seed=0)
        out = []
        for batch in dl.host_batches(epoch=0, num_batches=8):
            out.extend(r.tobytes() for r in np.asarray(batch["image"]))
        return sorted(out)

    assert digests(0) == digests(32)


# --------------------------------------------------------------------------
# epoch-latched locality changes + live hot swap
# --------------------------------------------------------------------------
def test_set_locality_defers_to_next_epoch_midepoch():
    s = ShardedSampler(64, 8, seed=1)
    it = iter(s)
    first = [next(it) for _ in range(3)]            # mid-epoch now
    before = s._epoch_perm(0).copy()
    s.set_locality(8)
    assert s.chunk_for_epoch(0) == 0                # current epoch untouched
    assert s.chunk_for_epoch(1) == 8
    np.testing.assert_array_equal(s._epoch_perm(0), before)
    # at an epoch boundary the change is immediate
    s2 = ShardedSampler(64, 8, seed=1)
    s2.set_locality(8)
    assert s2.chunk_for_epoch(0) == 8
    del first


def test_hot_swap_locality_on_live_stream_zero_lost_dup():
    ds = synthetic_image_dataset(96, 8, seed=0)
    dl = DataLoader(ds, 16, params=LoaderParams(num_workers=2),
                    shuffle=True, seed=0)
    bpe = dl.sampler.batches_per_epoch()            # 6
    stream = dl.stream(to_device=False)
    seen = [next(stream) for _ in range(2)]         # mid-epoch 0
    dl.apply_params(dl.params.replace(locality_chunk=16, num_workers=1))
    # consume the rest of epoch 0 and all of epoch 1
    seen += [next(stream) for _ in range(2 * bpe - 2)]
    assert stream.swaps == 1
    assert stream.position == 2 * bpe
    assert dl.sampler.chunk_for_epoch(0) == 0       # epoch 0 kept its order
    assert dl.sampler.chunk_for_epoch(1) == 16
    # every epoch's delivered multiset is exact (no lost/dup batches)
    rows = [r.tobytes() for b in seen[:bpe] for r in np.asarray(b["image"])]
    rows2 = [r.tobytes() for b in seen[bpe:] for r in np.asarray(b["image"])]
    ref = sorted(ds.get_batch(np.arange(96), fast=False)["image"]
                 [i].tobytes() for i in range(96))
    assert sorted(rows) == ref and sorted(rows2) == ref
    stream.close()


def test_locality_schedule_survives_checkpoint_roundtrip():
    ds = synthetic_image_dataset(64, 8, seed=0)
    dl = DataLoader(ds, 8, params=LoaderParams(), shuffle=True, seed=0)
    it = iter(dl.sampler)
    for _ in range(3):
        next(it)
    dl.apply_params(dl.params.replace(locality_chunk=8))  # deferred
    state = dl.state_dict()

    dl2 = DataLoader(ds, 8, params=LoaderParams(), shuffle=True, seed=0)
    dl2.load_state_dict(state)
    assert dl2.params.locality_chunk == 8
    assert dl2.sampler.chunk_for_epoch(0) == 0      # deferral preserved
    assert dl2.sampler.chunk_for_epoch(1) == 8
    np.testing.assert_array_equal(dl2.sampler._epoch_perm(0),
                                  dl.sampler._epoch_perm(0))


# --------------------------------------------------------------------------
# the DPT third axis
# --------------------------------------------------------------------------
def test_grid_without_locality_axis_never_passes_kwarg():
    calls = []

    def ev(i, j, *, num_batches, epoch):            # no locality kwarg
        calls.append((i, j))
        from repro.data.loader import TransferStats
        return TransferStats(1.0 / (i + j), num_batches, 0)

    res = tune(evaluator=ev, strategy="grid",
               config=DPTConfig(num_cpu_cores=2, num_devices=1,
                                max_prefetch=2, num_batches=4),
               measure_default=False)
    assert calls and "locality_chunk" not in res.axes


def test_grid_selects_chunked_on_cold_cache_real_loader():
    ds = _cold_dataset(256)
    dl = DataLoader(ds, 32, params=LoaderParams(fast_path=True),
                    shuffle=True, seed=0)
    cfg = DPTConfig(num_cpu_cores=2, num_devices=2, min_prefetch=1,
                    max_prefetch=1, num_batches=6, epoch=0,
                    axes={"locality_chunk": (0, 32)})
    res = tune(evaluator=LoaderEvaluator(dl, to_device=False),
               strategy="grid", config=cfg, measure_default=False)
    assert res.axes["locality_chunk"] == 32
    assert {t.axes["locality_chunk"] for t in res.trials} == {0, 32}
    # measurement-only override: the live schedule never saw the sweep
    assert dl.sampler.chunk_for_epoch(0) == 0


def test_grid_selects_chunked_on_cold_cache_simulator():
    sim = LoaderSimulator(coco_profile(80), MachineProfile())
    ev = SimulatorEvaluator(sim, batch_size=64)
    cfg = DPTConfig(num_cpu_cores=4, num_devices=2, max_prefetch=2,
                    num_batches=8, epoch=0, axes={"locality_chunk": (0, 64)})
    res = tune(evaluator=ev, strategy="grid", config=cfg,
               measure_default=False)
    assert res.axes["locality_chunk"] == 64


def test_simulator_locality_neutral_default_and_cold_win():
    sim = LoaderSimulator(coco_profile(80), MachineProfile())
    kw = dict(batch_size=64, num_batches=8, nworker=4, nprefetch=2)
    base = sim.simulate(**kw)
    assert sim.simulate(**kw, locality_chunk=0).seconds == base.seconds
    assert sim.simulate(**kw, locality_chunk=1).seconds == base.seconds
    assert sim.simulate(**kw, locality_chunk=64).seconds < base.seconds


def test_dpt_cache_roundtrips_locality(tmp_path):
    path = str(tmp_path / "cache.json")
    cache = DPTCache(path)
    res = DPTResult(4, 2, 1.0,
                    [Trial(4, 2, 1.0, axes={"locality_chunk": 64})],
                    axes={"locality_chunk": 64})
    cache.put("m", "d", 32, res)
    assert cache.get_params("m", "d", 32) == (4, 2, {})
    assert DPTCache(path).get_params(
        "m", "d", 32, axes=("locality_chunk",)) == (4, 2,
                                                    {"locality_chunk": 64})
    # a file the previous on-disk format wrote (a pre-registry entry with
    # its geometry keys) reads back the same
    with open(path, "w") as f:
        f.write('{"m|d|b5|cold": {"nworker": 4, "nprefetch": 2, '
                '"optimal_time": 1.0, "locality_chunk": 64, '
                '"locality_searched": true, "global_batch": 0, '
                '"geometry_searched": false}}')
    assert DPTCache(path).get_params(
        "m", "d", 32, axes=("locality_chunk",)) == (4, 2,
                                                    {"locality_chunk": 64})
    # an entry from a two-axis sweep must not satisfy a three-axis run
    cache.put("m", "d2", 32, DPTResult(4, 2, 1.0, [Trial(4, 2, 1.0)]))
    assert cache.get_params("m", "d2", 32) == (4, 2, {})
    assert cache.get_params("m", "d2", 32, axes=("locality_chunk",)) is None


def test_trainer_locality_axis_ignored_on_sharded_fleet():
    """Per-host tuned chunks would give hosts different permutations —
    the startup tune must drop the axis when the sampler is sharded."""
    from repro.train.trainer import Trainer, TrainerConfig
    ds = synthetic_image_dataset(64, 8, seed=0)
    dl = DataLoader(ds, 8, params=LoaderParams(), shuffle=True, seed=0,
                    host_index=0, host_count=2)
    cfg = TrainerConfig(autotune=True,
                        autotune_axes={"locality_chunk": (0, 16)},
                        autotune_budget_batches=2, autotune_max_prefetch=1)
    tr = Trainer.__new__(Trainer)          # tune_loader only needs these
    tr.loader, tr.cfg = dl, cfg
    params = tr.tune_loader(force=True)
    assert params.locality_chunk == 0      # axis dropped, not searched


# --------------------------------------------------------------------------
# the online locality loop (DESIGN.md §6)
# --------------------------------------------------------------------------
def test_online_retune_converges_to_grid_optimal_chunk_real_loader():
    """Acceptance: an online retune started with a deliberately bad
    locality_chunk (0 = random on cold seek-bound storage) converges to
    the grid-optimal chunk WITHOUT restarting the live stream."""
    from repro.tuning import OnlineTuner, OnlineTunerConfig
    ds = _cold_dataset(256, latency_s=1e-3)
    dl = DataLoader(ds, 32, params=LoaderParams(num_workers=1,
                                                prefetch_factor=1),
                    shuffle=True, seed=0)
    bpe = dl.sampler.batches_per_epoch()            # 8
    stream = dl.stream(to_device=False)
    seen = [next(stream) for _ in range(2)]         # live, mid-epoch 0

    cfg = OnlineTunerConfig(num_cpu_cores=2, num_devices=2, max_prefetch=1,
                            retune_budget_batches=4,
                            axes={"locality_chunk": (0, 32)})
    tuner = OnlineTuner(dl, evaluator=LoaderEvaluator(dl, to_device=False),
                        config=cfg, machine_fp="m", dataset_fp="d")
    params = tuner.force_retune()
    assert params is not None and params.locality_chunk == 32
    assert tuner.retunes == 1
    assert tuner.history[-1]["locality_chunk"] == 32

    # the grid (same axis, same budget) agrees: the retune converged to
    # the grid-optimal chunk
    grid = tune(evaluator=LoaderEvaluator(dl, to_device=False),
                strategy="grid",
                config=DPTConfig(num_cpu_cores=2, num_devices=2,
                                 max_prefetch=1, num_batches=4,
                                 axes={"locality_chunk": (0, 32)}),
                measure_default=False)
    assert grid.axes["locality_chunk"] == params.locality_chunk

    # the stream was never rebuilt: the swap latches mid-flight, epoch 0
    # keeps its order and the chunk engages at the next epoch boundary
    seen += [next(stream) for _ in range(2 * bpe - 2)]
    assert stream.swaps == 1
    assert dl.sampler.chunk_for_epoch(0) == 0
    assert dl.sampler.locality_chunk == 32
    # epoch 0's delivered multiset is exact despite the mid-epoch swap
    rows = [r.tobytes() for b in seen[:bpe] for r in np.asarray(b["image"])]
    all_images = ds.get_batch(np.arange(256), fast=False)["image"]
    ref = sorted(all_images[i].tobytes() for i in range(256))
    assert sorted(rows) == ref
    stream.close()


def test_online_retune_converges_to_grid_optimal_chunk_simulator():
    """Same convergence through the virtual-time evaluator: the online
    sweep resolves the locality axis exactly where the grid does."""
    from repro.tuning import OnlineTuner, OnlineTunerConfig
    sim = LoaderSimulator(coco_profile(80), MachineProfile())
    ds = synthetic_image_dataset(64, 8, seed=0)
    dl = DataLoader(ds, 64, params=LoaderParams(num_workers=4,
                                                prefetch_factor=2),
                    shuffle=True, seed=0)
    cfg = OnlineTunerConfig(num_cpu_cores=4, num_devices=2, max_prefetch=2,
                            retune_budget_batches=8, strategy="grid",
                            axes={"locality_chunk": (0, 64)})
    tuner = OnlineTuner(dl, evaluator=SimulatorEvaluator(sim, batch_size=64),
                        config=cfg, machine_fp="m", dataset_fp="d")
    params = tuner.force_retune()
    assert params is not None and params.locality_chunk == 64

    grid = tune(evaluator=SimulatorEvaluator(sim, batch_size=64),
                strategy="grid",
                config=DPTConfig(num_cpu_cores=4, num_devices=2,
                                 max_prefetch=2, num_batches=8,
                                 axes={"locality_chunk": (0, 64)}),
                measure_default=False)
    assert grid.axes["locality_chunk"] == 64 == params.locality_chunk


def test_online_retune_keeps_good_chunk():
    """Anti-churn: when the current chunk is already optimal, the sweep
    must not thrash it (and a no-win retune backs off as before)."""
    from repro.tuning import OnlineTuner, OnlineTunerConfig
    ds = _cold_dataset(128, latency_s=5e-4)
    dl = DataLoader(ds, 32, params=LoaderParams(num_workers=1,
                                                prefetch_factor=1,
                                                locality_chunk=32),
                    shuffle=True, seed=0)
    cfg = OnlineTunerConfig(num_cpu_cores=2, num_devices=2, max_prefetch=1,
                            retune_budget_batches=4,
                            axes={"locality_chunk": (0, 32)})
    tuner = OnlineTuner(dl, evaluator=LoaderEvaluator(dl, to_device=False),
                        config=cfg, machine_fp="m", dataset_fp="d")
    assert tuner.force_retune() is None
    assert dl.params.locality_chunk == 32


def test_adaptive_controller_triggers_resize_on_run_len_collapse():
    """Acceptance: the adaptive controller proposes a resize when the
    live coalesced_run_len falls below half the active chunk — applied as
    an epoch-latched hot swap on the live stream."""
    from repro.tuning import (AdaptiveLocalityConfig,
                              AdaptiveLocalityController)
    ds = synthetic_image_dataset(96, 8, seed=0)
    dl = DataLoader(ds, 16, params=LoaderParams(num_workers=1,
                                                locality_chunk=16),
                    shuffle=True, seed=0)
    stream = dl.stream(to_device=False)
    next(stream)                                    # live, mid-epoch
    ctl = AdaptiveLocalityController(
        dl, AdaptiveLocalityConfig(patience=2, min_requests=4,
                                   cooldown_steps=0))
    # counters: healthy window first (run_len 16 = the chunk), then the
    # cache warms / topology changes and runs collapse to ~5 (< 8 = C/2)
    io = {"coalesced_requests": 10, "reads": 160, "cache_hits": 0}
    assert ctl.observe(dict(io)) is None            # baseline snapshot
    io = {"coalesced_requests": 20, "reads": 320, "cache_hits": 0}
    assert ctl.observe(dict(io)) is None            # healthy: run 16
    io = {"coalesced_requests": 30, "reads": 420, "cache_hits": 50}
    assert ctl.observe(dict(io)) is None            # low window 1 (run 5)
    io = {"coalesced_requests": 40, "reads": 520, "cache_hits": 100}
    proposal = ctl.observe(dict(io))                # low window 2 -> fire
    assert proposal == 4                            # 2^floor(log2(5))
    assert ctl.proposals == 1
    assert dl.params.locality_chunk == 4
    # epoch-latched on the live stream: current epoch keeps its order
    for _ in range(8):
        next(stream)
    assert stream.swaps == 1
    assert dl.sampler.chunk_for_epoch(0) == 16
    assert dl.sampler.locality_chunk == 4
    stream.close()


def test_adaptive_controller_healthy_run_never_fires():
    from repro.tuning import (AdaptiveLocalityConfig,
                              AdaptiveLocalityController)
    ds = synthetic_image_dataset(32, 8, seed=0)
    dl = DataLoader(ds, 8, params=LoaderParams(locality_chunk=8),
                    shuffle=True, seed=0)
    ctl = AdaptiveLocalityController(
        dl, AdaptiveLocalityConfig(patience=1, min_requests=4,
                                   cooldown_steps=0))
    ctl.observe({"coalesced_requests": 10, "reads": 80, "cache_hits": 0})
    for k in range(2, 6):       # run length stays ~8 = the chunk
        out = ctl.observe({"coalesced_requests": 10 * k,
                           "reads": 80 * k, "cache_hits": 0})
        assert out is None
    assert ctl.proposals == 0
    assert dl.params.locality_chunk == 8


def test_adaptive_controller_routes_to_fleet_not_local():
    """On a sharded fleet the controller must never change locality
    locally — the proposal routes to on_propose (the coordinator)."""
    from repro.tuning import (AdaptiveLocalityConfig,
                              AdaptiveLocalityController)
    ds = synthetic_image_dataset(64, 8, seed=0)
    dl = DataLoader(ds, 16, params=LoaderParams(locality_chunk=16),
                    shuffle=True, seed=0, host_index=0, host_count=2)
    routed = []
    ctl = AdaptiveLocalityController(
        dl, AdaptiveLocalityConfig(patience=1, min_requests=4,
                                   cooldown_steps=0),
        on_propose=routed.append)
    ctl.observe({"coalesced_requests": 10, "reads": 160, "cache_hits": 0})
    ctl.observe({"coalesced_requests": 20, "reads": 260, "cache_hits": 50})
    assert routed == [4]                            # run 50/10 -> snap 4
    assert dl.params.locality_chunk == 16           # untouched locally


def test_fleet_locality_reconsensus_uniform_push(fleet_factory):
    """The fleet path: re-consensus sweeps the locality axis uniformly,
    pushes the winner to every host, and pins ONE common latch epoch."""
    from repro.tuning import FleetConfig

    def fn(i, j, chunk):
        return (4.0 / i + 0.1 * j) * (0.4 if chunk == 8 else 1.0)

    fleet = fleet_factory(
        config=FleetConfig(heartbeat_timeout_s=5.0, warmup_steps=2,
                           cooldown_steps=4, num_cpu_cores=4, num_devices=1,
                           max_prefetch=2, retune_budget_batches=2,
                           axes={"locality_chunk": (0, 8)}))
    for a in fleet.agents:
        from conftest import make_table_evaluator
        a.evaluator = make_table_evaluator(fn, locality=True)
    fleet.coord.request_consensus(reason="forced")
    actions = fleet.coord.poll()
    consensus = next(a for a in actions if a["kind"] == "consensus")
    assert consensus["applied"] and consensus["locality_chunk"] == 8
    for a in fleet.agents:
        assert a.loader.params.locality_chunk == 8
    # the swap commits when each stream drains its pre-pulled batches;
    # afterwards every host's schedule pins the SAME latch epoch
    for s in fleet.streams:
        while s.swaps == 0:
            next(s)
    latches = {tuple(a.loader.sampler._locality_schedule[-1])
               for a in fleet.agents}
    assert len(latches) == 1                        # one common (epoch, 8)
    assert latches.pop()[1] == 8


def test_trainer_wires_adaptive_locality_by_mode():
    """TrainerConfig.adaptive_locality: single-host controllers apply
    locally; fleet-mode controllers route proposals to the agent's
    coordinator (notify_drift) and never touch params themselves."""
    from repro.train.trainer import Trainer, TrainerConfig
    ds = synthetic_image_dataset(32, 8, seed=0)
    dl = DataLoader(ds, 8, params=LoaderParams(locality_chunk=16),
                    shuffle=True, seed=0)
    tr = Trainer.__new__(Trainer)
    tr.loader, tr.cfg, tr.agent = dl, TrainerConfig(), None
    ctl = tr._make_locality_controller()
    assert ctl.on_propose is None and ctl.loader is dl

    class FakeAgent:
        def __init__(self):
            self.proposals = []

        def notify_locality(self, chunk):
            self.proposals.append(chunk)

    tr.agent = FakeAgent()
    ctl = tr._make_locality_controller()
    ctl.observe({"coalesced_requests": 10, "reads": 160, "cache_hits": 0})
    for _ in range(2):
        ctl.observe({"coalesced_requests": ctl._last[0] + 10,
                     "reads": 160, "cache_hits": 0})
    assert tr.agent.proposals == [0]
    assert dl.params.locality_chunk == 16       # untouched locally


def test_coordinator_drops_locality_request_without_axis(fleet_factory):
    """An adaptive proposal on a fleet with no locality axis must NOT
    force a re-consensus — the search could never touch the knob, so the
    repeated proposals would burn goodput forever."""
    fleet = fleet_factory()                     # no locality axis
    fleet.agents[0].notify_locality(4)
    assert fleet.coord.poll() == []             # nothing forced
    # with the axis configured the same signal IS honoured
    from repro.tuning import FleetConfig
    from conftest import make_table_evaluator
    fleet2 = fleet_factory(
        config=FleetConfig(heartbeat_timeout_s=5.0, warmup_steps=2,
                           cooldown_steps=4, num_cpu_cores=4, num_devices=1,
                           max_prefetch=2, retune_budget_batches=2,
                           axes={"locality_chunk": (0, 8)}))
    for a in fleet2.agents:
        a.evaluator = make_table_evaluator(lambda i, j, c: 1.0,
                                           locality=True)
    fleet2.agents[0].notify_locality(4)
    actions = fleet2.coord.poll()
    assert any(a["kind"] == "consensus"
               and a["reason"].startswith("locality-run-len-collapse")
               for a in actions)


def test_join_syncs_fleet_locality_to_newcomer(fleet_factory):
    """Locality is runtime-mutable, so a joiner built with a stale chunk
    must inherit the fleet's (epoch -> chunk) schedule at join — or it
    would slice different permutations than its peers."""
    from repro.data import DataLoader
    from repro.tuning import HostAgent
    from conftest import make_index_dataset, make_table_evaluator
    fleet = fleet_factory(480, 12)
    # fleet-wide chunk applied earlier (simulate: set schedule directly)
    for a in fleet.agents:
        a.loader.params = a.loader.params.replace(locality_chunk=8)
        a.loader.sampler.load_locality([[0, 0], [1, 8]])
    for _ in range(2):
        for s in fleet.streams:
            next(s)
    dl_new = DataLoader(make_index_dataset(480), 12, shuffle=True, seed=5)
    newcomer = HostAgent("host3", dl_new,
                         evaluator=make_table_evaluator(lambda i, j: 1.0))
    fleet.coord.join(newcomer)
    assert dl_new.params.locality_chunk == 8
    assert dl_new.sampler.locality_state() == \
        fleet.agents[0].loader.sampler.locality_state()


def test_adaptive_controller_never_applies_locally_on_sharded_loader():
    """Library-level guard: a sharded loader with no coordinator route
    must not resize locality locally (permutation divergence)."""
    from repro.tuning import (AdaptiveLocalityConfig,
                              AdaptiveLocalityController)
    ds = synthetic_image_dataset(64, 8, seed=0)
    dl = DataLoader(ds, 16, params=LoaderParams(locality_chunk=16),
                    shuffle=True, seed=0, host_index=0, host_count=2)
    ctl = AdaptiveLocalityController(
        dl, AdaptiveLocalityConfig(patience=1, min_requests=4,
                                   cooldown_steps=0))
    ctl.observe({"coalesced_requests": 10, "reads": 160, "cache_hits": 0})
    assert ctl.observe({"coalesced_requests": 20, "reads": 180,
                        "cache_hits": 0}) is None
    assert ctl.proposals == 0
    assert dl.params.locality_chunk == 16
    # and the trainer refuses to build one at all in that topology
    from repro.train.trainer import Trainer, TrainerConfig
    tr = Trainer.__new__(Trainer)
    tr.loader, tr.cfg, tr.agent = dl, TrainerConfig(), None
    assert tr._make_locality_controller() is None


def test_fleet_locality_keeps_chunk_when_flat(fleet_factory):
    from repro.tuning import FleetConfig
    from conftest import make_table_evaluator

    fleet = fleet_factory(
        config=FleetConfig(heartbeat_timeout_s=5.0, warmup_steps=2,
                           cooldown_steps=4, num_cpu_cores=4, num_devices=1,
                           max_prefetch=2, retune_budget_batches=2,
                           axes={"locality_chunk": (0, 8)}))
    for a in fleet.agents:
        a.evaluator = make_table_evaluator(lambda i, j, c: 1.0,
                                           locality=True)
    fleet.coord.request_consensus(reason="forced")
    actions = fleet.coord.poll()
    consensus = next(a for a in actions if a["kind"] == "consensus")
    assert consensus["locality_chunk"] is None
    assert not consensus["applied"]
    for a in fleet.agents:
        assert a.loader.params.locality_chunk == 0


# --------------------------------------------------------------------------
# counters: TransferStats + the monitor report
# --------------------------------------------------------------------------
def test_transfer_stats_surface_locality_counters():
    ds = _cold_dataset(128, latency_s=1e-5)
    dl = DataLoader(ds, 16, params=LoaderParams(num_workers=0),
                    shuffle=True, seed=0)
    random_stats = dl.measure_transfer_time(4, epoch=0, to_device=False,
                                            locality_chunk=0)
    chunked_stats = dl.measure_transfer_time(4, epoch=1, to_device=False,
                                             locality_chunk=16)
    assert random_stats.coalesced_requests > 0
    assert chunked_stats.coalesced_run_len > 4 * random_stats.coalesced_run_len
    assert chunked_stats.coalesced_requests < random_stats.coalesced_requests


def test_loader_io_counters_and_host_report():
    from repro.tuning.fleet import HostAgent
    ds = _cold_dataset(64, latency_s=1e-5)
    dl = DataLoader(ds, 8, params=LoaderParams(num_workers=0,
                                               locality_chunk=8),
                    shuffle=True, seed=0)
    dl.measure_transfer_time(4, epoch=0, to_device=False)
    agent = HostAgent("h0", dl)
    agent.observe(data_s=0.01, step_s=0.02)
    rep = agent.report()
    assert rep.io is not None
    assert rep.io["coalesced_requests"] > 0
    assert rep.io["coalesced_run_len"] > 1.0
    # a plain (uncounted) storage reports no io block
    ds2 = synthetic_image_dataset(32, 8, seed=0)
    dl2 = DataLoader(ds2, 8, params=LoaderParams(), shuffle=True, seed=0)
    assert HostAgent("h1", dl2).report().io is None


# --------------------------------------------------------------------------
# staging pool
# --------------------------------------------------------------------------
def test_staging_pool_acquire_release_retire_resize():
    pool = StagingPool(2)
    batch = {"x": np.zeros((4, 3), np.float32)}
    a = pool.acquire(batch)
    assert a["x"].shape == (4, 3) and pool.misses == 1
    pool.release(a)
    b = pool.acquire(batch)
    assert b is a and pool.hits == 1                # ring reuse
    pool.retire(b)
    assert pool.retired == 1
    c = pool.acquire(batch)
    assert c is not b
    # shape change drops the stale ring and re-establishes the spec
    pool.release(c)
    d = pool.acquire({"x": np.zeros((8, 3), np.float32)})
    assert d["x"].shape == (8, 3) and pool._free == type(pool._free)()
    pool.release(d)
    pool.resize(0)                                  # clamped to 1
    assert pool.capacity == 1


def test_staged_transfer_private_and_ordered():
    """With the staging pool on, zero-copy device batches must be immune
    to slab recycling (the guarantee _ensure_private used to provide)."""
    ds = synthetic_image_dataset(96, 8, seed=0)
    dl = DataLoader(ds, 16, params=LoaderParams(
        num_workers=2, zero_copy=True, staging_buffers=2),
        shuffle=False, seed=0)
    stream = dl.stream(to_device=True)
    got = [next(stream) for _ in range(6)]          # one epoch
    for b, dev in enumerate(got):                   # values still intact?
        ref = ds.get_batch(dl.sampler.local_indices(0, b), fast=False)
        np.testing.assert_array_equal(np.asarray(dev["image"]),
                                      ref["image"])
    assert stream._prefetcher.staging_hit_rate is not None
    stream.close()


def test_staging_disabled_falls_back_to_ensure_private():
    ds = synthetic_image_dataset(48, 8, seed=0)
    dl = DataLoader(ds, 16, params=LoaderParams(
        num_workers=1, zero_copy=True, staging_buffers=0),
        shuffle=False, seed=0)
    stream = dl.stream(to_device=True)
    got = [next(stream) for _ in range(3)]
    assert stream._prefetcher.staging_hit_rate is None
    for b, dev in enumerate(got):
        ref = ds.get_batch(dl.sampler.local_indices(0, b), fast=False)
        np.testing.assert_array_equal(np.asarray(dev["image"]),
                                      ref["image"])
    stream.close()


def test_set_staging_hot_swaps_with_depth():
    ds = synthetic_image_dataset(64, 8, seed=0)
    dl = DataLoader(ds, 8, params=LoaderParams(num_workers=1,
                                               zero_copy=True),
                    shuffle=False, seed=0)
    stream = dl.stream(to_device=True)
    next(stream)
    dl.apply_params(dl.params.replace(device_prefetch=3, staging_buffers=4))
    for _ in range(6):
        next(stream)
    assert stream.swaps == 1
    assert stream._prefetcher.depth == 3
    assert stream._prefetcher._staging.capacity == 4
    stream.close()


# --------------------------------------------------------------------------
# FileStorage fork hygiene
# --------------------------------------------------------------------------
@pytest.mark.skipif(not hasattr(os, "fork"), reason="fork-only")
def test_filestorage_fork_drops_inherited_mmaps(tmp_path):
    items = [np.arange(i, i + 6, dtype=np.int32) for i in range(4)]
    fs = FileStorage.create(str(tmp_path / "fs"), items)
    fs._mmap(0)
    fs._mmap(1)
    assert len(fs._mmaps) == 2
    r, w = mp.Pipe(duplex=False)
    pid = os.fork()
    if pid == 0:                                    # child
        ok = False
        try:
            inherited = len(fs._mmaps)              # should be reset to 0
            data = fs.read_batch([0, 1, 2])         # lazily reopens
            ok = (inherited == 0
                  and np.array_equal(data[2], items[2]))
        finally:
            w.send(ok)
            os._exit(0)
    assert r.poll(10)
    assert r.recv() is True
    os.waitpid(pid, 0)
    # parent cache untouched
    assert len(fs._mmaps) == 2
