"""Evaluators: the measurement side of DPT's hypothesis loop.

Both expose ``(nworker, nprefetch, *, num_batches, epoch, **axes) ->
TransferStats`` (``axes``: the searched ``core.dpt.AXES`` values) so
Algorithm 1, the beyond-paper search strategies and the fleet tuner are
indifferent to whether a cell is a real wall-clock run or a virtual-time
simulation.
"""
from __future__ import annotations

import math
from typing import Optional

from repro.core.dpt import AXES
from repro.core.monitor import MemoryOverflow
from repro.core.simulator import LoaderSimulator
from repro.data.loader import DataLoader, LoaderParams, TransferStats
from repro.utils.spans import span


class LoaderEvaluator:
    """Measures the real loader (threads, queues, device_put) in wall clock."""

    def __init__(self, loader: DataLoader, *, to_device: bool = True,
                 device_prefetch: int = 2):
        self.loader = loader
        self.to_device = to_device
        self.device_prefetch = device_prefetch
        self.calls = 0

    def __call__(self, nworker: int, nprefetch: int, *, num_batches: int = 16,
                 epoch: int = 0, **axes: int) -> TransferStats:
        self.calls += 1
        # replace() keeps the loader's delivery knobs (fast_path, zero_copy,
        # ordered, use_processes, ...) so trials measure the same machinery
        # the live stream runs.  The axes (core.dpt.AXES) are passed as
        # measurement-only overrides: a candidate must not touch the
        # shared sampler's live schedule, the live tier, or the live
        # pool's lane split.
        self.loader.with_params(self.loader.params.replace(
            num_workers=nworker, prefetch_factor=nprefetch,
            device_prefetch=self.device_prefetch))
        with span("tune.trial", workers=nworker, prefetch=nprefetch,
                  axes=",".join(f"{a.field}={axes[a.field]}"
                                for a in AXES if a.field in axes)):
            return self.loader.measure_transfer_time(
                num_batches, epoch=epoch, to_device=self.to_device, **axes)


class SimulatorEvaluator:
    """Queries the virtual-time model (paper-table benchmarks, fleet sim)."""

    def __init__(self, sim: LoaderSimulator, *, batch_size: int,
                 device_prefetch: int = 2, device_ram: Optional[float] = None,
                 num_batches_cap: Optional[int] = None):
        self.sim = sim
        self.batch_size = batch_size
        self.device_prefetch = device_prefetch
        self.device_ram = device_ram
        self.num_batches_cap = num_batches_cap
        self.calls = 0

    def __call__(self, nworker: int, nprefetch: int, *, num_batches: int = 16,
                 epoch: int = 0, **axes: int) -> TransferStats:
        self.calls += 1
        if self.num_batches_cap is not None:
            num_batches = min(num_batches, self.num_batches_cap)
        r = self.sim.simulate(
            batch_size=self.batch_size, num_batches=num_batches,
            nworker=nworker, nprefetch=nprefetch, epoch=epoch,
            device_prefetch=self.device_prefetch, device_ram=self.device_ram,
            **axes)
        return TransferStats(r.seconds, num_batches,
                             int(num_batches
                                 * self.sim.batch_bytes(self.batch_size)),
                             peak_loader_bytes=int(r.peak_bytes))

    def epoch_seconds(self, nworker: int, nprefetch: int, *,
                      epoch: int = 0) -> float:
        """Full-epoch transfer time (paper Table 1b reports whole epochs)."""
        n = self.sim.sp.num_items // self.batch_size
        try:
            return self(nworker, nprefetch, num_batches=n,
                        epoch=epoch).seconds
        except MemoryOverflow:
            return math.inf
