"""The comparison that decides ``correct``.

Two layers make what a timed step consumes and produces, and both are
held against a plain reference that shares nothing with the program:

* the loader: every batch the trainer's step consumed, set-up and window
  alike, against a plain read of the seeded items in the sampler's order
  (``batches_wrong``, exact);
* the train step: the program's first three steps, run through the same
  ``Trainer.run()`` call and feed as the window, against the configuration's
  float32 reference (``plain.run_steps``) from the same seed:

  - ``loss_gap``: each step's loss, the largest relative gap;
  - ``grad_norm_gap``: each step's global gradient norm as the step reports
    it (before clipping), the largest relative gap;
  - ``change_gap``: each leaf's change over the three steps, as far as step
    4 keeps it: the gap between the two leaf norms over the larger of the
    reference's norm of that leaf and of the median leaf; the worst leaf.
    Leaves whose first reference gradient is under a thousandth of the
    median leaf's are left out: a key bias under softmax has no gradient,
    and Adam moves it by round-off alone.

The first gradient's leaf norms, as Adam's state holds them after one step,
are not compared: on the chip no control or planted fault separated from
the program's own readings (``PERF.md`` gives them).

Each number has a limit in the configuration file (``limits``), set from
the readings ``PERF.md`` gives.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np

import plain
import spec as bspec
import traffic as btraffic

CHECK_STEPS = 3


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
              keep=None) -> float:
    names = [k for k in ref if keep is None or keep(k)]
    median = float(np.median([ref[k] for k in names]))
    return float(np.max([abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
                         for k in names]))


def numbers(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The compared numbers of one run (or of the control against the
    reference)."""
    g_ref = ref["first_grad"]
    median = float(np.median(list(g_ref.values())))
    moved = lambda k: g_ref[k] >= 1e-3 * median
    rel = lambda a, b: float(np.max(np.abs(np.subtract(a, b))  # noqa: E731
                                    / np.abs(b)))
    return {
        "loss_gap": rel(prog["loss"], ref["loss"]),
        "grad_norm_gap": rel(prog["grad_norm"], ref["grad_norm"]),
        "change_gap": _leaf_gap(prog["change"], ref["change"], moved),
    }


def program_readings(run) -> Dict[str, Any]:
    hist = {r["step"]: r for r in run.history if "loss" in r}
    steps = range(1, CHECK_STEPS + 1)
    return {"loss": [hist[s]["loss"] for s in steps],
            "grad_norm": [hist[s]["grad_norm"] for s in steps],
            "change": plain.change_norms(run.clock.p0, run.clock.p3)}


def wrong_batches(run, items: np.ndarray, consumed: List[tuple]) -> int:
    t = run.model["train"]
    want = btraffic.plain_batches(items, run.mix, t["global_batch"], 0,
                                  len(consumed))
    wrong = 0
    for (tok, tgt, mask), w in zip(consumed, want):
        if not (np.array_equal(tok, w[:, :-1]) and np.array_equal(tgt, w[:, 1:])
                and np.all(mask == 1.0)):
            wrong += 1
    return wrong


def reference_batches(model: Dict[str, Any], mix: Dict[str, Any],
                      items: np.ndarray) -> List[Dict[str, np.ndarray]]:
    """The first steps' batches, read plainly from the seeded items."""
    plainb = btraffic.plain_batches(items, mix, model["train"]["global_batch"],
                                    0, CHECK_STEPS)
    return [{"tokens": b[:, :-1], "targets": b[:, 1:],
             "loss_mask": np.ones(b[:, 1:].shape, np.float32)}
            for b in plainb]


def reference(run, items: np.ndarray):
    ref_mod = bspec.reference(run.model["reference"])
    return plain.run_steps(ref_mod, run.model,
                           btraffic.seeds(run.seed)["weights"],
                           reference_batches(run.model, run.mix, items))


def check(run, items: np.ndarray, consumed: List[tuple]
          ) -> Dict[str, Dict[str, float]]:
    limits = run.model["limits"]
    got = numbers(program_readings(run), reference(run, items))
    out = {k: {"value": v, "limit": limits[k]} for k, v in got.items()}
    out["batches_wrong"] = {"value": wrong_batches(run, items, consumed),
                            "limit": 0}
    return out


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
