"""Dataloader Parameter Tuner (DPT) — the paper's Algorithm 1, faithfully,
plus the multi-host fleet extension.

Faithful part (``DPT.run``):
    nWorker starts at G (accelerator count) and increases by G up to N
    (CPU cores, final rung clamped to N); for each, nPrefetch sweeps 1..P;
    each cell measures the dataloader transfer time; memory overflow breaks
    the inner loop and moves to the next worker count; the argmin is
    returned.

The tuner is decoupled from *how* a cell is measured: an ``Evaluator``
returns ``TransferStats`` (real wall-clock loader, or the virtual-time
simulator — see core/evaluators.py).  That is what lets the same algorithm
drive unit tests, paper-table benchmarks and the multi-host simulation.

The search loop itself now lives in the unified strategy layer
(``repro.tuning``): ``DPT.run`` delegates to the registered ``"grid"``
strategy, and this module keeps the shared dataclasses (DPTConfig,
Trial, DPTResult) plus the fleet tuner built on top.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

from repro.core.monitor import MemoryOverflow
from repro.data.loader import TransferStats

Evaluator = Callable[..., TransferStats]  # (nworker, nprefetch, **kw)


@dataclasses.dataclass(frozen=True)
class Axis:
    """One beyond-paper search axis (DESIGN.md §5).

    ``field`` is the ``LoaderParams`` field, the evaluator keyword and the
    key in ``Trial.axes``/``DPTResult.axes``; ``name`` prefixes the axis's
    keys in the DPT cache file.  ``shared`` axes shape the shared epoch
    permutation, so a sharded loader may change them only uniformly
    through the fleet coordinator.  ``warm`` axes only pay off once an
    epoch has run, so single-axis sweeps price them at ``max(1, epoch)``.
    """
    field: str
    name: str
    shared: bool
    warm: bool


# every consumer (grid, cache, online and fleet sweeps, trainer guards)
# iterates this table; a new axis is one entry here plus the loader's own
# mechanism behind the evaluator keyword
AXES: Tuple[Axis, ...] = (
    Axis("locality_chunk", "locality", shared=True, warm=False),    # §5-6
    Axis("cache_budget_bytes", "cache", shared=True, warm=True),    # §7
    Axis("slow_lane_workers", "slow_lane", shared=False, warm=False),  # §9
)
AXIS_BY_FIELD: Dict[str, Axis] = {a.field: a for a in AXES}

AxisCandidates = Mapping[str, Tuple[int, ...]]


def searched_axes(axes: AxisCandidates) -> List[Tuple[Axis, Tuple[int, ...]]]:
    """The registry entries ``axes`` gives candidates for, in registry
    order; an empty candidate tuple leaves the axis unsearched."""
    unknown = set(axes) - AXIS_BY_FIELD.keys()
    if unknown:
        raise ValueError(f"unknown tuner axes {sorted(unknown)}; "
                         f"known: {list(AXIS_BY_FIELD)}")
    return [(a, tuple(axes[a.field])) for a in AXES if axes.get(a.field)]


@dataclasses.dataclass(frozen=True)
class DPTConfig:
    num_cpu_cores: Optional[int] = None      # N  (default: os.cpu_count())
    num_devices: Optional[int] = None        # G  (default: local devices)
    max_prefetch: int = 8                    # P
    min_prefetch: int = 1
    num_batches: int = 32                    # measurement budget per cell
    epoch: int = 0                           # 0 = cold (1st), >=1 = warm
    # beyond-paper grid axes: registry field -> candidate values.  Empty
    # keeps the search on the paper's (nWorker, nPrefetch) plane, and an
    # unsearched axis never reaches the evaluator as a keyword.
    axes: AxisCandidates = dataclasses.field(default_factory=dict)

    def resolve(self) -> Tuple[int, int]:
        n = self.num_cpu_cores
        if n is None:
            n = os.cpu_count() or 1
        g = self.num_devices
        if g is None:
            try:
                import jax
                g = jax.local_device_count()
            except Exception:  # pragma: no cover
                g = 1
        return n, max(1, g)


@dataclasses.dataclass
class Trial:
    nworker: int
    nprefetch: int
    seconds: float
    overflowed: bool = False
    peak_bytes: float = 0.0
    # per-batch samples when the evaluator measured wall clock (None for
    # aggregate-only evaluators like the simulator)
    batch_seconds: Optional[List[float]] = None
    # the searched axes' values the cell was measured with (an unsearched
    # axis is absent)
    axes: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class DPTResult:
    nworker: int
    nprefetch: int
    optimal_time: float
    trials: List[Trial]
    default_time: Optional[float] = None
    # the winning value of each searched axis
    axes: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def speedup_vs_default(self) -> Optional[float]:
        if self.default_time is None or self.optimal_time == 0:
            return None
        return self.default_time / self.optimal_time

    @property
    def time_reduction_pct(self) -> Optional[float]:
        """Percent of the default-parameter time saved by the optimum
        (positive = improvement)."""
        if self.default_time is None or self.default_time == 0:
            return None
        return 100.0 * (self.default_time - self.optimal_time) / self.default_time


def default_params(num_cpu_cores: Optional[int] = None) -> Tuple[int, int]:
    """PyTorch's defaults the paper compares against: workers = cores/2,
    prefetch_factor = 2."""
    n = num_cpu_cores if num_cpu_cores is not None else (os.cpu_count() or 1)
    return max(1, n // 2), 2


class DPT:
    def __init__(self, evaluator: Evaluator,
                 config: DPTConfig = DPTConfig()):
        self.evaluator = evaluator
        self.config = config

    def _measure(self, i: int, j: int) -> TransferStats:
        return self.evaluator(i, j, num_batches=self.config.num_batches,
                              epoch=self.config.epoch)

    def run(self, *, measure_default: bool = True) -> DPTResult:
        """Algorithm 1 (served by the unified ``"grid"`` strategy; see
        ``repro.tuning.strategies.GridSearch`` for the line mapping)."""
        from repro.tuning import tune
        return tune(evaluator=self.evaluator, strategy="grid",
                    config=self.config, measure_default=measure_default)

    # ---- full grid (figures 2-4) --------------------------------------------
    def grid(self, workers: Sequence[int],
             prefetches: Sequence[int]) -> Dict[Tuple[int, int], float]:
        out: Dict[Tuple[int, int], float] = {}
        for i in workers:
            for j in prefetches:
                try:
                    out[(i, j)] = self._measure(i, j).seconds
                except MemoryOverflow:
                    out[(i, j)] = math.inf
        return out


# --------------------------------------------------------------------------
# multi-host fleet tuning (beyond paper; DESIGN.md §2 "Multi-pod semantics")
# --------------------------------------------------------------------------
@dataclasses.dataclass
class FleetResult:
    mode: str                             # "uniform" | "per_host"
    per_host: List[DPTResult]
    fleet_params: List[Tuple[int, int]]   # chosen (nworker, nprefetch)/host
    fleet_time: float                     # max over hosts (lockstep step time)
    uniform_params: Optional[Tuple[int, int]] = None


class MultiHostDPT:
    """Tunes a fleet where hosts may be heterogeneous (stragglers).

    The fleet steps in lockstep, so the effective transfer time is the MAX
    over hosts.  Two modes:

    * ``per_host``: each host tunes independently (optimal when per-host
      configs are allowed — independent minimization minimizes the max);
    * ``uniform``: one (nWorker, nPrefetch) for every host (common fleet
      constraint) chosen to minimize the max over hosts — a straggler-aware
      consensus the single-machine paper has no analogue of.
    """

    def __init__(self, evaluators: Sequence[Evaluator],
                 config: DPTConfig = DPTConfig()):
        self.evaluators = list(evaluators)
        self.config = config

    def run_per_host(self) -> FleetResult:
        results = [DPT(ev, self.config).run(measure_default=False)
                   for ev in self.evaluators]
        params = [(r.nworker, r.nprefetch) for r in results]
        fleet_time = max(r.optimal_time for r in results)
        return FleetResult("per_host", results, params, fleet_time)

    def run_uniform(self) -> FleetResult:
        """Per-host sweeps + straggler-aware consensus.  The consensus math
        lives in the fleet control plane (``repro.tuning.fleet``), which the
        FleetCoordinator also uses for online re-consensus."""
        from repro.tuning.fleet import uniform_consensus
        results = [DPT(ev, self.config).run(measure_default=False)
                   for ev in self.evaluators]
        best, fleet_time = uniform_consensus(results)
        return FleetResult("uniform", results, [best] * len(results),
                           fleet_time, uniform_params=best)
