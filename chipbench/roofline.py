"""A kernel's share of its roofline, from the traced kernel time."""
from __future__ import annotations

from typing import Optional

import spec as bspec


def share(run, kernel: str) -> Optional[float]:
    """Least time the chip could take for the traced calls of ``kernel``
    (the larger of operations over peak FLOP/s and bytes over peak HBM
    bandwidth, per call) over their device time, in percent.  None where
    the trace holds no call of the kernel."""
    if run.peaks is None or run.trace is None or not run.trace["kernels"].get(kernel):
        return None
    cost = bspec.kernel_cost(kernel)
    least = seconds = 0.0
    for entry in run.trace["kernels"][kernel]:
        flops, nbytes = cost(entry["call"], run.model)
        least += entry["events"] * max(flops / run.peaks["bf16_flops"],
                                       nbytes / run.peaks["hbm_bytes_per_s"])
        seconds += entry["seconds"]
    return 100.0 * least / seconds if seconds > 0 else None
