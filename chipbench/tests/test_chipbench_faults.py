"""The output check catches a broken timed path.  Each test plants one
fault (``faults.py``) underneath a tiny CPU run and sees ``correct`` come
out false: a step that returns its state unchanged; a step that leaves out
half of the batch and takes the mean over the rest; a token altered where
the loader produces it.  (The cells run on one chip, so there is no
exchange between chips to leave out.)"""
import numpy as np
import pytest

import tinycell
import faults


def _failing(checks):
    return sorted(k for k, c in checks.items()
                  if not (np.isfinite(c["value"]) and c["value"] <= c["limit"]))


@pytest.mark.parametrize("fault, caught_by", [
    ("frozen_state", "change_gap"),
    ("half_batch", "grad_norm_gap"),
    ("altered_token", "batches_wrong"),
])
def test_planted_fault_is_incorrect(monkeypatch, fault, caught_by):
    out = tinycell.run_tiny(monkeypatch, plant=faults.ALL[fault])
    assert out["correct"] is False
    assert caught_by in _failing(out["checks"])
