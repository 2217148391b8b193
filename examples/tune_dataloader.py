"""The paper's full workflow on the calibrated testbed model: reproduce the
CIFAR-10 grid (Fig 2) and a slice of the COCO resolution study (Table 1),
then show the beyond-paper tuners finding the same optimum for a fraction
of the measurements.

    PYTHONPATH=src python examples/tune_dataloader.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import (DPT, DPTConfig, LoaderSimulator, MachineProfile,
                        SimulatorEvaluator, default_params)
from repro.data.storage import cifar10_profile, coco_profile
from repro.tuning import tune

MACHINE = MachineProfile()    # the paper's i7-8700K / 64 GB / 1 GPU testbed


def paper_grid(profile, batch, epoch, label):
    ev = SimulatorEvaluator(LoaderSimulator(profile, MACHINE),
                            batch_size=batch)
    cfg = DPTConfig(num_cpu_cores=12, num_devices=1, max_prefetch=8,
                    num_batches=48, epoch=epoch)
    res = DPT(ev, cfg).run()
    print(f"{label:24s} optimal=({res.nworker:2d},{res.nprefetch})  "
          f"default={default_params(12)}  "
          f"speedup={res.speedup_vs_default:.2f}x  "
          f"cells={len(res.trials)}")
    return ev, cfg, res


def main() -> None:
    print("== CIFAR-10 (paper Fig 2: optimum ~10 workers, ~1.3x) ==")
    paper_grid(cifar10_profile(), 32, epoch=1, label="cifar10 b32 warm")

    print("\n== COCO resolutions (paper Table 1 regimes) ==")
    for res_px in (80, 160, 320, 640):
        paper_grid(coco_profile(res_px), 32, epoch=0,
                   label=f"coco {res_px}px b32 cold")
    paper_grid(coco_profile(80), 32, epoch=1, label="coco 80px b32 warm")

    print("\n== beyond-paper: same optimum, fewer measurements ==")
    storage = coco_profile(160)
    ev = SimulatorEvaluator(LoaderSimulator(storage, MACHINE), batch_size=32)
    cfg = DPTConfig(num_cpu_cores=12, num_devices=1, max_prefetch=8,
                    num_batches=48, epoch=0)
    grid = DPT(ev, cfg).run(measure_default=False)
    grid_cost = ev.calls

    ev2 = SimulatorEvaluator(LoaderSimulator(storage, MACHINE), batch_size=32)
    sh = tune(evaluator=ev2, strategy="successive_halving", config=cfg)
    ev3 = SimulatorEvaluator(LoaderSimulator(storage, MACHINE), batch_size=32)
    hc = tune(evaluator=ev3, strategy="warmstart_hillclimb", config=cfg,
              storage=storage, machine=MACHINE, batch_size=32)
    print(f"grid search     : ({grid.nworker},{grid.nprefetch}) "
          f"in {grid_cost} measurements")
    print(f"succ. halving   : ({sh.nworker},{sh.nprefetch}) "
          f"in {ev2.calls} cheaper measurements")
    print(f"warm+hillclimb  : ({hc.nworker},{hc.nprefetch}) "
          f"in {ev3.calls} measurements")


if __name__ == "__main__":
    main()
