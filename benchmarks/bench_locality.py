"""A/B gate for the IO-locality fast path (DESIGN.md §5).

PR 2's zero-copy path made collation cheap; on a cold cache the remaining
epoch cost is *where* the sampler sends reads — a fully random order
defeats ``read_batch`` coalescing (every item is its own storage request),
while ``locality_chunk`` shuffling turns a batch into a handful of
contiguous runs that each cost ONE request.  This bench runs the SAME
cold-cache ``LatencyStorage`` dataset through both orders at equal
(num_workers, prefetch_factor) and gates on the chunked order delivering
>= 2x host batches/sec, with three correctness riders:

* the chunked epoch's sample multiset is byte-identical to the random
  epoch's (chunking reorders, it never re-samples);
* shuffle quality holds: the adjacent-pair rate of the chunked permutation
  stays under the chunk-predicted ceiling (~2.5/C — far from sequential);
* a DPT grid over (workers, prefetch_factor, locality_chunk) picks a
  chunked config on the cold profile (the third axis resolves).

Results land in ``artifacts/bench/locality.json`` plus ``BENCH_locality
.json`` at the repo root (uploaded as a CI artifact), mirroring the
fastpath/fleet gates.
"""
from __future__ import annotations

import hashlib
import json
import os
import platform
import sys

import numpy as np

from repro.core.dpt import DPTConfig
from repro.core.evaluators import LoaderEvaluator
from repro.data import DataLoader, LoaderParams
from repro.data.dataset import Dataset, image_transform
from repro.data.sampler import ShardedSampler
from repro.data.storage import ArrayStorage, LatencyStorage
from repro.tuning import tune

TITLE = "IO-locality fast path A/B (cold-cache host batches/sec)"
PAPER_REF = "perf gate"
GATE_SPEEDUP = 2.0
ROOT_JSON = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_locality.json")

BATCH = 64
CHUNK = 64          # = BATCH: each global batch covers whole chunks


def _cold_dataset(n: int, *, latency_s: float = 1.2e-3) -> Dataset:
    """Seek-bound cold storage: every read pays a real (GIL-releasing)
    base latency, cache disabled so EVERY epoch is a cold epoch — the
    regime the paper's Table 1b cold column measures."""
    rng = np.random.default_rng(0)
    items = [rng.integers(0, 255, (32, 32, 3), dtype=np.uint8)
             for _ in range(n)]
    storage = LatencyStorage(ArrayStorage(items), latency_s=latency_s,
                             bandwidth=2e9, cache_bytes=0)
    return Dataset(storage, transform=image_transform)


def _ab_batches_per_s(ds, *, num_batches, repeats):
    """Best-of-N cold-epoch delivery rate, random vs chunked order, at
    EQUAL (num_workers, prefetch_factor).  Repeats interleaved so a load
    spike degrades both sides instead of skewing the ratio; the locality
    override measures both orders through one loader (same storage, same
    machinery)."""
    params = LoaderParams(num_workers=2, prefetch_factor=2,
                          fast_path=True, zero_copy=True)
    dl = DataLoader(ds, BATCH, params=params, shuffle=True, seed=0)
    dl.measure_transfer_time(4, epoch=0, to_device=False)      # warmup
    best = {"random": 0.0, "chunked": 0.0}
    run_len = {"random": 0.0, "chunked": 0.0}
    for rep in range(repeats):
        for name, chunk in (("random", 0), ("chunked", CHUNK)):
            st = dl.measure_transfer_time(num_batches, epoch=1 + rep,
                                          to_device=False,
                                          locality_chunk=chunk)
            best[name] = max(best[name], st.batches / st.seconds)
            run_len[name] = max(run_len[name], st.coalesced_run_len)
    return best, run_len


def _epoch_sample_digests(ds, *, locality_chunk, num_batches):
    """Sorted per-sample digests of one delivered epoch (order-free)."""
    params = LoaderParams(num_workers=0, fast_path=True,
                          locality_chunk=locality_chunk)
    dl = DataLoader(ds, BATCH, params=params, shuffle=True, seed=0)
    digests = []
    for batch in dl.host_batches(epoch=0, num_batches=num_batches):
        for row in np.asarray(batch["image"]):
            digests.append(hashlib.sha1(row.tobytes()).hexdigest())
    return sorted(digests)


def adjacent_pair_ceiling(chunk: int) -> float:
    """Chunk-predicted ceiling for the adjacent-pair rate: a uniform
    within-chunk shuffle leaves ~1 consecutive-value succession per chunk
    (expected rate 1/C); 2.5/C covers sampling noise with wide margin
    while still being ~40x below a sequential order's rate of 1.0."""
    return 2.5 / max(2, chunk)


# --------------------------------------------------------------------------
# multi-host layout gate (DESIGN.md §6): host-major keeps whole chunks on
# one host; the legacy strided layout dilutes per-host runs toward ~1
# --------------------------------------------------------------------------
MULTIHOST_CHUNK = 16       # <= local batch at every gated host count
MULTIHOST_GATE = 0.5       # host-major per-host run length >= 0.5 * C


def per_host_run_len(n: int, *, hosts: int, chunk: int,
                     layout: str) -> float:
    """Mean achieved per-host coalesced run length (items per storage
    request) over one epoch, straight from the sampler's index streams —
    the quantity LatencyStorage.achieved_run_len measures on real reads."""
    from repro.data.storage import coalesce_runs
    shards = [ShardedSampler(n, BATCH, seed=0, locality_chunk=chunk,
                             host_index=h, host_count=hosts, layout=layout)
              for h in range(hosts)]
    requests = sum(len(coalesce_runs(s.local_indices(0, b)))
                   for s in shards for b in range(n // BATCH))
    return n / requests


def multihost_rows(n: int):
    """Gate rows: at H in {2, 4}, host-major keeps per-host run length
    >= 0.5*C while the strided baseline collapses (< 0.5*C)."""
    rows = []
    for hosts in (2, 4):
        major = per_host_run_len(n, hosts=hosts, chunk=MULTIHOST_CHUNK,
                                 layout="host_major")
        strided = per_host_run_len(n, hosts=hosts, chunk=MULTIHOST_CHUNK,
                                   layout="strided")
        floor = MULTIHOST_GATE * MULTIHOST_CHUNK
        assert major >= floor, \
            (f"host-major per-host run length {major:.2f} < {floor} "
             f"at H={hosts} (C={MULTIHOST_CHUNK})")
        assert strided < floor, \
            (f"strided baseline unexpectedly kept locality at H={hosts}: "
             f"{strided:.2f} >= {floor}")
        rows.append({"hosts": hosts, "chunk": MULTIHOST_CHUNK,
                     "host_major_run_len": round(major, 2),
                     "strided_run_len": round(strided, 2),
                     "required_min": floor, "passed": major >= floor})
    return rows


def run(quick: bool = False):
    n = 1024 if quick else 2048
    num_batches = n // BATCH
    repeats = 2 if quick else 3
    ds = _cold_dataset(n)

    # --- correctness riders first: identity + shuffle quality -------------
    random_digests = _epoch_sample_digests(
        ds, locality_chunk=0, num_batches=num_batches)
    chunked_digests = _epoch_sample_digests(
        ds, locality_chunk=CHUNK, num_batches=num_batches)
    assert random_digests == chunked_digests, \
        "chunked epoch is not the random epoch's sample multiset"

    perm = ShardedSampler(n, BATCH, seed=0,
                          locality_chunk=CHUNK)._epoch_perm(0)
    adj_rate = float(np.mean(perm[1:] == perm[:-1] + 1))
    adj_ceiling = adjacent_pair_ceiling(CHUNK)
    assert adj_rate <= adj_ceiling, \
        f"shuffle-quality bound violated: {adj_rate:.4f} > {adj_ceiling:.4f}"

    # --- the A/B gate ------------------------------------------------------
    best, run_len = _ab_batches_per_s(ds, num_batches=num_batches,
                                      repeats=repeats)
    speedup = best["chunked"] / best["random"]

    # --- the DPT third axis resolves on the cold profile -------------------
    dl = DataLoader(ds, BATCH, params=LoaderParams(fast_path=True),
                    shuffle=True, seed=0)
    cfg = DPTConfig(num_cpu_cores=2, num_devices=2, min_prefetch=1,
                    max_prefetch=2, num_batches=min(8, num_batches),
                    epoch=0, axes={"locality_chunk": (0, CHUNK)})
    pick = tune(evaluator=LoaderEvaluator(dl, to_device=False),
                strategy="grid", config=cfg, measure_default=False)
    picked = pick.axes["locality_chunk"]
    assert picked == CHUNK, \
        f"DPT grid picked locality {picked}, expected {CHUNK}"

    # --- multi-host layout gate (host-major vs strided, DESIGN.md §6) ------
    mh_rows = multihost_rows(n)

    rows = [{"order": "random", "workers": 2, "prefetch": 2,
             "bps": round(best["random"], 1),
             "run_len": round(run_len["random"], 2)},
            {"order": "chunked", "workers": 2, "prefetch": 2,
             "bps": round(best["chunked"], 1),
             "run_len": round(run_len["chunked"], 2),
             "speedup_x": round(speedup, 2)}]

    payload = {
        "bench": "locality",
        "gate": {"profile": "cold_cache_latency", "chunk": CHUNK,
                 "required_speedup_x": GATE_SPEEDUP,
                 "measured_speedup_x": round(speedup, 2),
                 "passed": speedup >= GATE_SPEEDUP,
                 "byte_identical_multiset": True,
                 "adjacent_pair_rate": round(adj_rate, 5),
                 "adjacent_pair_ceiling": round(adj_ceiling, 5),
                 "dpt_pick": {"nworker": pick.nworker,
                              "nprefetch": pick.nprefetch,
                              "locality_chunk": picked}},
        "multihost": {"chunk": MULTIHOST_CHUNK,
                      "required_run_len_min": MULTIHOST_GATE
                      * MULTIHOST_CHUNK,
                      "rows": mh_rows},
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
    fail_below = float(os.environ.get("LOCALITY_GATE_MIN", GATE_SPEEDUP))
    if speedup < fail_below:
        raise RuntimeError(
            f"locality gate FAILED: {speedup:.2f}x < {fail_below}x "
            f"chunked-vs-random on the cold-cache profile (see {ROOT_JSON})")
    return rows


if __name__ == "__main__":
    from benchmarks.common import fmt_table
    print(fmt_table(run(quick="--quick" in sys.argv)))
