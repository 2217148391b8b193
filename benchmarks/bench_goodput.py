"""Beyond-paper: goodput-mode tuning — the loader only needs to outpace the
model step, so tuning to max throughput (the paper's objective) wastes host
cores whenever the accelerator is the bottleneck.

Two views:
 1. step-time sweep on the COCO-320 profile: tuned-for-max workers vs the
    smallest worker count that still hides the loader behind the step
    (cores freed on every node of a 1000-host fleet);
 2. per-arch coupling: the train_4k dry-run step-time estimate (roofline
    step_s from artifacts/dryrun) sets the target; the per-host input
    demand (global_batch/hosts x seq tokens) sets the dataset profile.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Dict, List

from repro.core import (DPT, DPTConfig, LoaderSimulator, MachineProfile,
                        SimulatorEvaluator)
from repro.data.storage import StorageProfile, coco_profile
from repro.tuning import tune

TITLE = "Goodput-mode tuning (loader >= model, minimal host resources)"
PAPER_REF = "beyond-paper (DESIGN.md §2 goodput mode)"

MACHINE = MachineProfile()
DRYRUN = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")


def token_profile(seq_len: int, *, vocab_bytes: int = 4) -> StorageProfile:
    """Pre-tokenized LM shards: sequential reads, negligible decode."""
    item = seq_len * vocab_bytes
    return StorageProfile(num_items=1_000_000, item_bytes=float(item),
                          decoded_item_bytes=float(2 * item),
                          io_latency_s=200e-6, seek_congestion=0.02,
                          storage_bw=1.2e9,
                          decode_cpu_s_fixed=30e-6,
                          decode_cpu_s_per_byte=0.5e-9)


def run(quick: bool = False) -> List[Dict]:
    rows: List[Dict] = []
    cfg = DPTConfig(num_cpu_cores=12, num_devices=1, max_prefetch=4,
                    num_batches=16 if quick else 32, epoch=1)

    # --- view 1: step-time sweep, image regime ------------------------------
    ev = SimulatorEvaluator(LoaderSimulator(coco_profile(320), MACHINE),
                            batch_size=64)
    max_res = DPT(ev, cfg).run(measure_default=False)
    for step_s in (0.05, 0.2, 1.0):
        g = tune(evaluator=ev, strategy="goodput", config=cfg,
                 step_time_s=step_s, num_batches=cfg.num_batches)
        rows.append({
            "view": "step-sweep", "profile": "coco320", "step_s": step_s,
            "max_workers": max_res.nworker, "goodput_workers": g.nworker,
            "cores_freed": max_res.nworker - g.nworker,
            "loader_s_per_batch": g.optimal_time / cfg.num_batches,
        })

    # --- view 2: per-arch coupling from the dry-run -------------------------
    hosts = 64                       # 256 chips, 4 local devices per host
    for arch in ("qwen2-0.5b", "yi-34b", "mixtral-8x22b"):
        path = os.path.join(DRYRUN, f"{arch}__train_4k__single.json")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            art = json.load(f)
        if not art.get("ok") or "roofline" not in art:
            continue
        step_s = art["roofline"]["step_s"]
        per_host_batch = max(1, 256 // hosts)
        prof = token_profile(4096)
        ev2 = SimulatorEvaluator(LoaderSimulator(prof, MACHINE),
                                 batch_size=per_host_batch)
        cfg2 = dataclasses.replace(cfg, num_devices=4)  # 4 local devices
        max2 = DPT(ev2, cfg2).run(measure_default=False)
        g2 = tune(evaluator=ev2, strategy="goodput", config=cfg2,
                  step_time_s=step_s, num_batches=cfg2.num_batches)
        rows.append({
            "view": "per-arch", "profile": arch, "step_s": round(step_s, 3),
            "max_workers": max2.nworker, "goodput_workers": g2.nworker,
            "cores_freed": max2.nworker - g2.nworker,
            "loader_s_per_batch": g2.optimal_time / cfg2.num_batches,
        })
        # input-bound check: can the loader keep up at all?
        per_batch = g2.optimal_time / cfg2.num_batches
        rows[-1]["input_bound"] = bool(per_batch > step_s)
    return rows


def main() -> None:
    from benchmarks.common import fmt_table, save_rows
    rows = run()
    print(f"== {TITLE} ({PAPER_REF}) ==")
    print(fmt_table(rows))
    print(save_rows("goodput", rows))


if __name__ == "__main__":
    main()
