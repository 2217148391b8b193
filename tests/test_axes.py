"""The tuner's axis registry (``core.dpt.AXES``): every beyond-paper axis
is one entry that the grid, the DPT cache and the trainer's guards read,
the grid's evaluator call order is pinned, and a cache file in the
on-disk format that predates the registry reads back the same."""
import dataclasses
import json

import pytest

from repro.core.cache import DPTCache
from repro.core.dpt import AXES, DPTConfig, DPTResult, Trial
from repro.core.evaluators import SimulatorEvaluator
from repro.core.simulator import LoaderSimulator, MachineProfile
from repro.data import DataLoader, LoaderParams
from repro.data.loader import TransferStats
from repro.data.storage import cifar10_profile
from repro.tuning import tune

from conftest import make_index_dataset

WINNERS = {"locality_chunk": 32, "cache_budget_bytes": 1 << 20,
           "slow_lane_workers": 2}


class Recording:
    """Wraps an evaluator and records every call it receives."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = []

    def __call__(self, i, j, *, num_batches=16, epoch=0, **axes):
        self.calls.append((i, j, num_batches, epoch, dict(axes)))
        return self.inner(i, j, num_batches=num_batches, epoch=epoch, **axes)


@pytest.mark.parametrize("axis", AXES, ids=[a.field for a in AXES])
def test_registry_axis_end_to_end(axis, tmp_path):
    win = WINNERS[axis.field]

    def table(i, j, *, num_batches=16, epoch=0, **axes):
        assert set(axes) == {axis.field}          # only the searched axis
        bonus = 0.5 if axes[axis.field] == win else 0.0
        return TransferStats(1.0 / i + 0.1 * j - bonus, num_batches, 0)

    # the grid searches the axis and the result carries the winner
    res = tune(evaluator=table, strategy="grid",
               config=DPTConfig(num_cpu_cores=2, num_devices=1,
                                max_prefetch=2, num_batches=4,
                                axes={axis.field: (0, win)}),
               measure_default=False)
    assert (res.nworker, res.nprefetch, res.axes) == (2, 1, {axis.field: win})
    assert {t.axes[axis.field] for t in res.trials} == {0, win}

    # the cache round-trips the value and the "<name>_searched" flag
    path = str(tmp_path / "dpt.json")
    DPTCache(path).put("m", "d", 32, res)
    with open(path) as f:
        entry = json.load(f)["m|d|b5|cold"]
    assert entry[axis.field] == win and entry[f"{axis.name}_searched"]
    assert DPTCache(path).get_params("m", "d", 32, axes=(axis.field,)) \
        == (2, 1, {axis.field: win})

    # an axis-blind refinement does not clobber the searched value
    cache = DPTCache(path)
    cache.put("m", "d", 32, DPTResult(3, 2, 0.4, [Trial(3, 2, 0.4)]))
    assert cache.get_params("m", "d", 32, axes=(axis.field,)) \
        == (3, 2, {axis.field: win})

    # a 2-host trainer drops the axis if and only if it is shared
    from repro.train.trainer import Trainer, TrainerConfig
    t = Trainer.__new__(Trainer)
    t.cfg = TrainerConfig(autotune_axes={axis.field: (0, win)})
    for host_count, kept in ((1, True), (2, not axis.shared)):
        t.loader = DataLoader(make_index_dataset(32), 8, shuffle=True,
                              seed=0, params=LoaderParams(num_workers=1),
                              host_index=0, host_count=host_count)
        want = {axis.field: (0, win)} if kept else {}
        assert t._tuner_axes() == want
        assert t._make_online_tuner().cfg.axes == want
    # ... and a fleet searches it only if it is shared
    from repro.tuning import FleetConfig
    if axis.shared:
        assert FleetConfig(axes={axis.field: (0, win)}).axes
    else:
        with pytest.raises(ValueError, match="must be shared"):
            FleetConfig(axes={axis.field: (0, win)})


# the grid's evaluator calls, recorded before the axes moved into the
# registry: (workers, prefetch, slow lane, cache budget, locality chunk)
_THREE_AXIS_CALLS = [
    (2, 1, 0, 0, 0), (2, 2, 0, 0, 0), (2, 3, 0, 0, 0),
    (4, 1, 0, 0, 0), (4, 2, 0, 0, 0), (4, 3, 0, 0, 0),
    (2, 1, 0, 0, 32), (2, 2, 0, 0, 32), (2, 3, 0, 0, 32),
    (4, 1, 0, 0, 32), (4, 2, 0, 0, 32), (4, 3, 0, 0, 32),
    (2, 1, 0, 1 << 24, 0), (2, 2, 0, 1 << 24, 0), (2, 3, 0, 1 << 24, 0),
    (4, 1, 0, 1 << 24, 0), (4, 2, 0, 1 << 24, 0), (4, 3, 0, 1 << 24, 0),
    (2, 1, 0, 1 << 24, 32), (2, 2, 0, 1 << 24, 32), (2, 3, 0, 1 << 24, 32),
    (4, 1, 0, 1 << 24, 32), (4, 2, 0, 1 << 24, 32), (4, 3, 0, 1 << 24, 32),
    (2, 1, 2, 0, 0), (2, 2, 2, 0, 0), (2, 3, 2, 0, 0), (4, 1, 2, 0, 0),
    (2, 1, 2, 0, 32), (2, 2, 2, 0, 32), (2, 3, 2, 0, 32), (4, 1, 2, 0, 32),
    (2, 1, 2, 1 << 24, 0), (2, 2, 2, 1 << 24, 0), (2, 3, 2, 1 << 24, 0),
    (4, 1, 2, 1 << 24, 0),
    (2, 1, 2, 1 << 24, 32), (2, 2, 2, 1 << 24, 32), (2, 3, 2, 1 << 24, 32),
    (4, 1, 2, 1 << 24, 32),
]


def _two_axis():
    """The chip cells' search: N=4, G=1, P=4, no beyond-paper axis."""
    def table(i, j, *, num_batches=16, epoch=0):
        return TransferStats(max(0.5, 2.0 / i) + (0.25 if j == 1 else 0.0),
                             num_batches, 0)
    cfg = DPTConfig(num_cpu_cores=4, num_devices=1, max_prefetch=4,
                    num_batches=12)
    calls = [(i, j, 12, 0, {}) for i in (1, 2, 3, 4) for j in (1, 2, 3, 4)]
    return table, cfg, calls, (4, 2, 0.5, {})


def _three_axis():
    """All three axes on a decode-heavy, RAM-tight simulator: the lane
    overflows the widest worker rung, so the break is pinned too."""
    prof = dataclasses.replace(
        cifar10_profile(), decode_cpu_s_fixed=1e-3,
        vectorized_decode_fixed_s=None).with_heavy_tail(fraction=0.03,
                                                       mult=100.0)
    sim = LoaderSimulator(prof, MachineProfile(host_ram=11e9,
                                               page_cache_eff=0.5))
    cfg = DPTConfig(num_cpu_cores=4, num_devices=2, max_prefetch=3,
                    num_batches=8, epoch=1,
                    axes={"locality_chunk": (0, 32),
                          "cache_budget_bytes": (0, 1 << 24),
                          "slow_lane_workers": (0, 2)})
    calls = [(i, j, 8, 1, {"locality_chunk": c, "cache_budget_bytes": b,
                           "slow_lane_workers": s})
             for i, j, s, b, c in _THREE_AXIS_CALLS]
    pick = (2, 3, 0.07780929731173783,
            {"locality_chunk": 32, "cache_budget_bytes": 1 << 24,
             "slow_lane_workers": 2})
    return SimulatorEvaluator(sim, batch_size=4), cfg, calls, pick


@pytest.mark.parametrize("case", [_two_axis, _three_axis],
                         ids=["two_axis", "three_axis"])
def test_grid_call_sequence_is_pinned(case):
    inner, cfg, calls, pick = case()
    ev = Recording(inner)
    res = tune(evaluator=ev, strategy="grid", config=cfg,
               measure_default=False)
    assert ev.calls == calls
    assert (res.nworker, res.nprefetch) == pick[:2]
    assert res.optimal_time == pytest.approx(pick[2], rel=1e-12)
    assert res.axes == pick[3]


# a cache file exactly as the previous on-disk format wrote it, geometry
# keys included
_PARENT_FORMAT = """{
 "m|d2|b5|warm": {
  "cache_budget_bytes": 0, "cache_searched": false,
  "geometry_searched": false, "global_batch": 0,
  "locality_chunk": 0, "locality_searched": false,
  "nprefetch": 1, "nworker": 3, "optimal_time": 0.25,
  "slow_lane_searched": false, "slow_lane_workers": 0
 },
 "m|d|b5|cold": {
  "cache_budget_bytes": 1048576, "cache_searched": true,
  "geometry_searched": true, "global_batch": 128,
  "locality_chunk": 16, "locality_searched": true,
  "nprefetch": 2, "nworker": 4, "optimal_time": 0.5,
  "slow_lane_searched": true, "slow_lane_workers": 2
 }
}"""


def test_parent_format_cache_file_reads_back(tmp_path):
    path = tmp_path / "dpt.json"
    path.write_text(_PARENT_FORMAT)
    cache = DPTCache(str(path))
    every = tuple(a.field for a in AXES)
    assert cache.get_params("m", "d", 32, axes=every) == (4, 2, {
        "locality_chunk": 16, "cache_budget_bytes": 1 << 20,
        "slow_lane_workers": 2})
    assert cache.get_params("m", "d2", 32, epoch=1) == (3, 1, {})
    assert cache.get_params("m", "d2", 32, epoch=1, axes=every) is None
    # a rewrite keeps the registry's keys and drops the geometry ones
    cache.put("m", "d", 32, DPTResult(5, 1, 0.4, [Trial(5, 1, 0.4)]))
    entry = json.loads(path.read_text())["m|d|b5|cold"]
    assert "global_batch" not in entry and "geometry_searched" not in entry
    assert (entry["locality_chunk"], entry["cache_budget_bytes"],
            entry["slow_lane_workers"]) == (16, 1 << 20, 2)
