"""A/B gate for the cross-epoch cache tier (DESIGN.md §7).

PR 4/5 made cold epochs cheap to *order* (locality chunking); what they
cannot remove is the storage latency itself — every epoch re-pays it.
The cache tier retains raw items across epochs under an explicit byte
budget, so epochs 2+ stream at memory speed.  This bench runs the SAME
cold-cache ``LatencyStorage`` dataset through the tier at equal
(num_workers, prefetch_factor) and gates on the warm epoch delivering
>= 3x the cold epoch's host batches/sec, with three correctness riders:

* the cached stream's per-epoch sample multiset is byte-identical to the
  cache-off stream's (the hot/cold interleave reorders, it never
  re-samples, and hits are the bytes that were admitted);
* the warm epoch's hit/miss split is exact: every read a hit, zero
  misses (and the cold epoch the reverse) — the ``TransferStats``
  counters the tuner prices the axis with;
* a 4-axis DPT grid (workers, prefetch, chunk axis off, budget) picks a
  non-zero budget on the cold profile at a warm epoch, and the simulator
  prices the same knob the same way on a RAM-tight machine profile.

Results land in ``artifacts/bench/cache.json`` plus ``BENCH_cache.json``
at the repo root (uploaded as a CI artifact), mirroring the
fastpath/locality/fleet gates.
"""
from __future__ import annotations

import hashlib
import json
import os
import platform
import sys

import numpy as np

from repro.core.dpt import DPTConfig
from repro.core.evaluators import LoaderEvaluator, SimulatorEvaluator
from repro.core.simulator import LoaderSimulator, MachineProfile
from repro.data import DataLoader, LoaderParams
from repro.data.dataset import Dataset, image_transform
from repro.data.storage import ArrayStorage, LatencyStorage, StorageProfile
from repro.tuning import tune

TITLE = "Cross-epoch cache tier A/B (cold vs warm host batches/sec)"
PAPER_REF = "perf gate"
GATE_SPEEDUP = 3.0
ROOT_JSON = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_cache.json")

BATCH = 64
BUDGET = 1 << 23     # 8 MiB — covers the whole bench dataset (~3 MiB)


def _cold_dataset(n: int, *, latency_s: float = 1.2e-3) -> Dataset:
    """Seek-bound cold storage with its own cache disabled: every epoch
    re-pays the full latency bill unless OUR tier absorbs it."""
    rng = np.random.default_rng(0)
    items = [rng.integers(0, 255, (32, 32, 3), dtype=np.uint8)
             for _ in range(n)]
    storage = LatencyStorage(ArrayStorage(items), latency_s=latency_s,
                             bandwidth=2e9, cache_bytes=0)
    return Dataset(storage, transform=image_transform)


def _cold_vs_warm(ds, *, num_batches, repeats):
    """Best-of-N delivery rate, cold (epoch 0, tier filling) vs warm
    (epoch >= 1, tier prewarmed) at EQUAL (num_workers, prefetch_factor).
    Both sides run through ``measure_transfer_time``'s measurement-only
    tier override, so the A/B never pollutes a live tier and repeats are
    independent."""
    params = LoaderParams(num_workers=2, prefetch_factor=2,
                          fast_path=True, zero_copy=True)
    dl = DataLoader(ds, BATCH, params=params, shuffle=True, seed=0)
    dl.measure_transfer_time(4, epoch=0, to_device=False)      # warmup
    best = {"cold": 0.0, "warm": 0.0}
    split = {"cold": (0, 0), "warm": (0, 0)}
    for rep in range(repeats):
        for name, epoch in (("cold", 0), ("warm", 1 + rep)):
            st = dl.measure_transfer_time(num_batches, epoch=epoch,
                                          to_device=False,
                                          cache_budget_bytes=BUDGET)
            bps = st.batches / st.seconds
            if bps > best[name]:
                best[name] = bps
                split[name] = (st.cache_hits, st.cache_misses)
    return best, split


def _stream_epoch_digests(ds, *, budget, num_batches, epochs=2):
    """Sorted per-sample digests of each LIVE-STREAM epoch (order-free).
    The stream is the path the tier actually serves, so this is the
    end-to-end identity check: admitted bytes == delivered bytes."""
    params = LoaderParams(num_workers=1, fast_path=True, locality_chunk=16,
                          cache_budget_bytes=budget)
    dl = DataLoader(ds, BATCH, params=params, shuffle=True, seed=0)
    s = dl.stream(to_device=False)
    per_epoch = []
    try:
        for _ in range(epochs):
            digests = []
            for _ in range(num_batches):
                batch = next(s)
                for row in np.asarray(batch["image"]):
                    digests.append(hashlib.sha1(row.tobytes()).hexdigest())
            per_epoch.append(sorted(digests))
    finally:
        s.close()
    return per_epoch, dl.io_counters()


def run(quick: bool = False):
    n = 1024 if quick else 2048
    num_batches = n // BATCH
    repeats = 2 if quick else 3
    ds = _cold_dataset(n)

    # --- correctness rider: byte-identical multiset, cache-on vs off ------
    cached, io = _stream_epoch_digests(ds, budget=BUDGET,
                                       num_batches=num_batches)
    uncached, _ = _stream_epoch_digests(ds, budget=0,
                                        num_batches=num_batches)
    for e in range(len(cached)):
        assert cached[e] == uncached[e], \
            f"cached epoch {e} is not the uncached epoch's sample multiset"
    assert io["cache_tier_hits"] > 0, "live stream never hit the tier"

    # --- the A/B gate ------------------------------------------------------
    best, split = _cold_vs_warm(ds, num_batches=num_batches,
                                repeats=repeats)
    speedup = best["warm"] / best["cold"]

    # --- rider: the hit/miss split is exact on both sides ------------------
    assert split["cold"] == (0, n), \
        f"cold epoch split {split['cold']} != (0, {n})"
    assert split["warm"] == (n, 0), \
        f"warm epoch split {split['warm']} != ({n}, 0)"

    # --- the DPT fourth axis resolves: real evaluator ----------------------
    dl = DataLoader(ds, BATCH, params=LoaderParams(fast_path=True),
                    shuffle=True, seed=0)
    cfg = DPTConfig(num_cpu_cores=2, num_devices=2, min_prefetch=1,
                    max_prefetch=2, num_batches=min(8, num_batches),
                    epoch=1, axes={"cache_budget_bytes": (0, BUDGET)})
    pick = tune(evaluator=LoaderEvaluator(dl, to_device=False),
                strategy="grid", config=cfg, measure_default=False)
    picked = pick.axes["cache_budget_bytes"]
    assert picked == BUDGET, f"DPT grid picked budget {picked}, not {BUDGET}"

    # --- ... and the simulator prices the knob the same way ---------------
    sp = StorageProfile(num_items=10_000, item_bytes=1e5,
                        decoded_item_bytes=4e5, io_latency_s=5e-3,
                        seek_congestion=0.2, storage_bw=80e6,
                        decode_cpu_s_fixed=100e-6,
                        decode_cpu_s_per_byte=2e-9)
    mp = MachineProfile(host_ram=8e9, page_cache_eff=0.2,
                        worker_overhead_bytes=0.2e9)
    sim_cfg = DPTConfig(num_cpu_cores=4, num_devices=2, max_prefetch=2,
                        num_batches=8, epoch=1,
                        axes={"cache_budget_bytes": (0, int(1e9))})
    sim_pick = tune(evaluator=SimulatorEvaluator(LoaderSimulator(sp, mp),
                                                 batch_size=32),
                    strategy="grid", config=sim_cfg,
                    measure_default=False)
    assert sim_pick.axes["cache_budget_bytes"] == int(1e9), \
        "simulator grid kept budget 0 on the RAM-tight warm profile"

    rows = [{"epoch": "cold", "workers": 2, "prefetch": 2,
             "bps": round(best["cold"], 1),
             "hits": split["cold"][0], "misses": split["cold"][1]},
            {"epoch": "warm", "workers": 2, "prefetch": 2,
             "bps": round(best["warm"], 1),
             "hits": split["warm"][0], "misses": split["warm"][1],
             "speedup_x": round(speedup, 2)}]

    payload = {
        "bench": "cache",
        "gate": {"profile": "cold_cache_latency",
                 "budget_bytes": BUDGET,
                 "required_speedup_x": GATE_SPEEDUP,
                 "measured_speedup_x": round(speedup, 2),
                 "passed": speedup >= GATE_SPEEDUP,
                 "byte_identical_multiset": True,
                 "warm_split_exact": True,
                 "dpt_pick": {"nworker": pick.nworker,
                              "nprefetch": pick.nprefetch,
                              "cache_budget_bytes": picked},
                 "sim_pick": {"nworker": sim_pick.nworker,
                              "nprefetch": sim_pick.nprefetch,
                              "cache_budget_bytes":
                              sim_pick.axes["cache_budget_bytes"]}},
        "rows": rows,
        "host": {"platform": platform.platform(),
                 "python": sys.version.split()[0],
                 "numpy": np.__version__},
    }
    with open(ROOT_JSON, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")

    # honest 3x gate in the JSON; the hard failure floor is overridable so
    # noisy shared CI runners don't red-flag PRs on timing variance
    fail_below = float(os.environ.get("CACHE_GATE_MIN", GATE_SPEEDUP))
    if speedup < fail_below:
        raise RuntimeError(
            f"cache gate FAILED: {speedup:.2f}x < {fail_below}x warm-vs-"
            f"cold on the cold-cache profile (see {ROOT_JSON})")
    return rows


if __name__ == "__main__":
    from benchmarks.common import fmt_table
    print(fmt_table(run(quick="--quick" in sys.argv)))
