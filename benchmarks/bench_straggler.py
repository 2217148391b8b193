"""A/B gate for dual-lane slow-sample isolation (DESIGN.md §9).

Ordered delivery has a head-of-line pathology no (workers, prefetch,
locality, cache) point fixes: one rare slow decode parks every finished
batch behind it in the reorder buffer.  This bench plants a deterministic
heavy tail (3% of items cost 100x the base latency — corrupt-JPEG-sized
stragglers) in a ``LatencyStorage`` dataset and runs the SAME warm-tracker
epoch through the thread pool with the slow lane off vs on, at equal
(num_workers, prefetch_factor).  Gate: the dual-lane config delivers
>= 2x host batches/sec, with correctness riders:

* the dual-lane epoch's sample multiset is byte-identical to the
  single-lane epoch's (the lane changes WHEN work starts, never what
  arrives or in which order);
* an equal-threads baseline (all lane workers folded into the fast pool)
  is recorded alongside — the win is isolation, not extra parallelism;
* a DPT grid over (workers, prefetch, slow_lane_workers) on the simulator's
  heavy-tailed decode profile picks a nonzero lane width, and zero on the
  uniform profile (the fifth axis resolves, and only where it should);
* the serving rider: a ``BatchingFrontend`` with ``slow_lane=True``
  routes predicted-expensive request groups to the slow thread.

Results land in ``artifacts/bench/straggler.json`` plus
``BENCH_straggler.json`` at the repo root (uploaded as a CI artifact),
mirroring the fastpath/locality/cache/fleet gates.
"""
from __future__ import annotations

import hashlib
import json
import os
import platform
import sys
import time

import numpy as np

from repro.data import DataLoader, LoaderParams
from repro.data.dataset import Dataset
from repro.data.storage import ArrayStorage, LatencyStorage

TITLE = "Dual-lane straggler isolation A/B (heavy-tail host batches/sec)"
PAPER_REF = "perf gate"
GATE_SPEEDUP = 2.0
ROOT_JSON = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_straggler.json")

# calibrated straggler regime: rare (3%) and huge (100x) — the shape where
# ordered delivery stalls hardest; tail cost scales with latency_s so the
# whole bench stays sub-second per epoch
N_ITEMS = 512
BATCH = 4
LATENCY_S = 2e-4
TAIL = dict(tail_fraction=0.03, tail_mult=100.0, tail_seed=3)
LANE_WORKERS = 3
LOOKAHEAD = 32


def _tail_dataset(n: int = N_ITEMS) -> Dataset:
    items = [np.full((4,), i, np.float32) for i in range(n)]
    storage = LatencyStorage(ArrayStorage(items), latency_s=LATENCY_S,
                             bandwidth=2e9, cache_bytes=0, **TAIL)
    return Dataset(storage, transform=lambda a: {"x": a})


def _params(lane: int, *, workers: int = 2) -> LoaderParams:
    return LoaderParams(num_workers=workers, prefetch_factor=1,
                        zero_copy=True, ordered=True,
                        slow_lane_workers=lane,
                        slow_lane_lookahead=LOOKAHEAD)


def _epoch_seconds(dl: DataLoader, *, epochs_warm: int = 2,
                   repeats: int = 3) -> float:
    """Min-of-N wall time for one warm-tracker epoch.  The warm epochs
    teach the cost tracker where the stragglers are — a cold tracker
    routes nothing, so measuring epoch 0 would understate the win."""
    bpe = N_ITEMS // BATCH
    for e in range(epochs_warm):
        for _ in dl.host_batches(epoch=e, num_batches=bpe):
            pass
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in dl.host_batches(epoch=epochs_warm, num_batches=bpe):
            pass
        best = min(best, time.perf_counter() - t0)
    return best


def _epoch_digests(dl: DataLoader, epoch: int) -> list:
    """Sorted per-sample digests of one delivered epoch (order-free)."""
    digests = []
    for batch in dl.host_batches(epoch=epoch, num_batches=N_ITEMS // BATCH):
        for row in np.asarray(batch["x"]):
            digests.append(hashlib.sha1(row.tobytes()).hexdigest())
    return sorted(digests)


# --------------------------------------------------------------------------
# DPT rider: the fifth axis resolves on the simulator's straggler profile
# --------------------------------------------------------------------------
def dpt_lane_pick(heavy: bool):
    import dataclasses

    from repro.core.dpt import DPTConfig
    from repro.core.evaluators import SimulatorEvaluator
    from repro.core.simulator import LoaderSimulator, MachineProfile
    from repro.data.storage import cifar10_profile
    from repro.tuning import tune

    sp = dataclasses.replace(cifar10_profile(), decode_cpu_s_fixed=1e-3,
                             vectorized_decode_fixed_s=None)
    if heavy:
        sp = sp.with_heavy_tail(fraction=0.03, mult=100.0)
    sim = LoaderSimulator(sp, MachineProfile(
        physical_cores=8, logical_cores=8, reserved_cores=0, num_devices=2))
    cfg = DPTConfig(num_cpu_cores=8, num_devices=2, min_prefetch=1,
                    max_prefetch=2, num_batches=64,
                    axes={"slow_lane_workers": (0, 1, 2, 3)})
    return tune(evaluator=SimulatorEvaluator(sim, batch_size=4),
                strategy="grid", config=cfg, measure_default=False)


# --------------------------------------------------------------------------
# serving rider: expensive request groups take the slow thread
# --------------------------------------------------------------------------
class _SkewedEngine:
    """Duck-typed ServeEngine: one request shape is 20x the other."""
    max_batch = 4

    def generate(self, prompts, max_new):
        time.sleep(0.02 if max_new >= 64 else 0.001)

        class R:
            tokens = np.zeros((len(prompts), max_new), np.int32)
        return R()


def serving_rider() -> dict:
    from repro.serve.engine import BatchingFrontend
    fe = BatchingFrontend(_SkewedEngine(), max_wait_s=0.002,
                          slow_lane=True, slow_threshold=4.0)
    try:
        rng = np.random.default_rng(0)

        def burst(k, max_new):
            return [fe.submit(
                rng.integers(0, 100, (16,)).astype(np.int32), max_new)
                for _ in range(k)]

        for _ in range(4):              # warm the keyed tracker
            for r in burst(2, 4) + burst(2, 64):
                r.result.get(timeout=60)
        for r in burst(8, 64) + burst(8, 4):
            r.result.get(timeout=60)
        return {"slow_groups": fe.slow_groups,
                "fast_p99_s": round(fe.assembly_wait_p99(), 5),
                "slow_p99_s": round(fe.assembly_wait_p99(slow=True), 5),
                "routed": fe.slow_groups > 0}
    finally:
        fe.shutdown()


def run(quick: bool = False):
    repeats = 2 if quick else 3

    # --- correctness rider: byte-identical multiset, lane on vs off -------
    single = DataLoader(_tail_dataset(), BATCH, params=_params(0),
                        shuffle=True, seed=0)
    dual = DataLoader(_tail_dataset(), BATCH,
                      params=_params(LANE_WORKERS), shuffle=True, seed=0)
    assert _epoch_digests(single, 0) == _epoch_digests(dual, 0), \
        "dual-lane epoch is not the single-lane epoch's sample multiset"

    # --- the A/B gate: equal (workers, prefetch), lane off vs on ----------
    t_single = _epoch_seconds(single, repeats=repeats)
    t_dual = _epoch_seconds(dual, repeats=repeats)
    assert dual.cost_tracker.slow_batches > 0, \
        "warm tracker never routed a batch to the slow lane"
    speedup = t_single / t_dual

    # honesty baseline: same TOTAL thread count, no isolation — shows the
    # win is the early start, not just extra workers
    equal_threads = DataLoader(_tail_dataset(), BATCH,
                               params=_params(0, workers=2 + LANE_WORKERS),
                               shuffle=True, seed=0)
    t_equal = _epoch_seconds(equal_threads, repeats=repeats)

    bpe = N_ITEMS // BATCH
    rows = [{"config": "single_lane", "workers": 2, "lanes": 0,
             "epoch_s": round(t_single, 3),
             "bps": round(bpe / t_single, 1)},
            {"config": "equal_threads", "workers": 2 + LANE_WORKERS,
             "lanes": 0, "epoch_s": round(t_equal, 3),
             "bps": round(bpe / t_equal, 1)},
            {"config": "dual_lane", "workers": 2, "lanes": LANE_WORKERS,
             "epoch_s": round(t_dual, 3), "bps": round(bpe / t_dual, 1),
             "speedup_x": round(speedup, 2)}]

    # --- the DPT fifth axis resolves (and only on the straggler profile) --
    heavy_pick = dpt_lane_pick(heavy=True)
    uniform_pick = dpt_lane_pick(heavy=False)
    heavy_lanes = heavy_pick.axes["slow_lane_workers"]
    uniform_lanes = uniform_pick.axes["slow_lane_workers"]
    assert heavy_lanes > 0, \
        "DPT grid never priced a slow lane on the heavy-tailed profile"
    assert uniform_lanes == 0, \
        f"DPT grid spent {uniform_lanes} lane workers on a uniform profile"

    # --- the serving rider ------------------------------------------------
    serve = serving_rider()
    assert serve["routed"], "frontend never routed an expensive group"

    payload = {
        "bench": "straggler",
        "gate": {"profile": "bimodal_3pct_100x", "batch": BATCH,
                 "required_speedup_x": GATE_SPEEDUP,
                 "measured_speedup_x": round(speedup, 2),
                 "passed": speedup >= GATE_SPEEDUP,
                 "byte_identical_multiset": True,
                 "slow_batches_routed": dual.cost_tracker.slow_batches,
                 "equal_threads_speedup_x": round(t_single / t_equal, 2),
                 "dpt_pick_heavy": {
                     "nworker": heavy_pick.nworker,
                     "nprefetch": heavy_pick.nprefetch,
                     "slow_lane_workers": heavy_lanes},
                 "dpt_pick_uniform": {"slow_lane_workers": uniform_lanes}},
        "serving": serve,
        "rows": rows,
        "host": {"platform": platform.platform(),
                 "python": sys.version.split()[0],
                 "numpy": np.__version__},
    }
    with open(ROOT_JSON, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")

    # honest 2x gate in the JSON; the hard failure floor is overridable so
    # noisy shared CI runners don't red-flag PRs on timing variance
    fail_below = float(os.environ.get("STRAGGLER_GATE_MIN", GATE_SPEEDUP))
    if speedup < fail_below:
        raise RuntimeError(
            f"straggler gate FAILED: {speedup:.2f}x < {fail_below}x "
            f"dual-vs-single lane on the heavy-tail profile "
            f"(see {ROOT_JSON})")
    return rows


if __name__ == "__main__":
    from benchmarks.common import fmt_table
    print(fmt_table(run(quick="--quick" in sys.argv)))
