"""Beyond-paper: search-strategy cost — the paper's exhaustive grid pays
O(N/G x P) full measurements per (machine, dataset) pair; on a 1000-node
fleet that cost recurs per machine class and per dataset.  Successive
halving and cost-model-warm-started hillclimb find the same optimum for a
fraction of the measurements.

Reported per (profile, strategy): measurements used, total measured seconds
(the tuning bill), found cell, regret vs the exhaustive-grid optimum.
"""
from __future__ import annotations

import math
from typing import Dict, List

from repro.core import (DPT, DPTConfig, LoaderSimulator, MachineProfile,
                        SimulatorEvaluator)
from repro.data.storage import cifar10_profile, coco_profile
from repro.tuning import tune

TITLE = "Tuning cost: grid vs successive-halving vs warmstart+hillclimb"
PAPER_REF = "beyond-paper (search.py)"

MACHINE = MachineProfile()

PROFILES = {
    "cifar10-warm": (cifar10_profile(), 32, 1),
    "coco80-cold": (coco_profile(80), 32, 0),
    "coco320-cold": (coco_profile(320), 64, 0),
    "coco640-warm": (coco_profile(640), 16, 1),
}


def run(quick: bool = False) -> List[Dict]:
    rows: List[Dict] = []
    names = list(PROFILES)[:2] if quick else list(PROFILES)
    for name in names:
        storage, batch, epoch = PROFILES[name]
        cfg = DPTConfig(num_cpu_cores=12, num_devices=1, max_prefetch=8,
                        num_batches=16 if quick else 32, epoch=epoch)

        def fresh_ev():
            return SimulatorEvaluator(LoaderSimulator(storage, MACHINE),
                                      batch_size=batch)

        # exhaustive grid (Algorithm 1)
        ev = fresh_ev()
        grid = DPT(ev, cfg).run(measure_default=False)
        grid_calls, grid_best = ev.calls, grid.optimal_time
        bill_grid = sum(t.seconds for t in grid.trials
                        if math.isfinite(t.seconds))
        rows.append({"profile": name, "strategy": "grid(Alg1)",
                     "measurements": grid_calls, "tuning_bill_s": bill_grid,
                     "found": f"({grid.nworker},{grid.nprefetch})",
                     "regret_pct": 0.0})

        # successive halving
        ev = fresh_ev()
        sh = tune(evaluator=ev, strategy="successive_halving", config=cfg)
        # re-measure SH's pick at the full budget for a fair regret
        t_sh = ev(sh.nworker, sh.nprefetch, num_batches=cfg.num_batches,
                  epoch=epoch).seconds
        rows.append({"profile": name, "strategy": "succ-halving",
                     "measurements": ev.calls - 1,
                     "tuning_bill_s": sum(t.seconds for t in sh.trials
                                          if math.isfinite(t.seconds)),
                     "found": f"({sh.nworker},{sh.nprefetch})",
                     "regret_pct": 100 * (t_sh / grid_best - 1)})

        # cost-model warmstart + coordinate hillclimb
        ev = fresh_ev()
        hc = tune(evaluator=ev, strategy="warmstart_hillclimb", config=cfg,
                  storage=storage, machine=MACHINE, batch_size=batch)
        t_hc = ev(hc.nworker, hc.nprefetch, num_batches=cfg.num_batches,
                  epoch=epoch).seconds
        rows.append({"profile": name, "strategy": "warmstart+hillclimb",
                     "measurements": ev.calls - 1,
                     "tuning_bill_s": sum(t.seconds for t in hc.trials
                                          if math.isfinite(t.seconds)),
                     "found": f"({hc.nworker},{hc.nprefetch})",
                     "regret_pct": 100 * (t_hc / grid_best - 1)})
    return rows


def main() -> None:
    from benchmarks.common import fmt_table, save_rows
    rows = run()
    print(f"== {TITLE} ({PAPER_REF}) ==")
    print(fmt_table(rows))
    print(save_rows("search_cost", rows))


if __name__ == "__main__":
    main()
