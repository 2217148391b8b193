"""DPT result cache (paper §5: "parameters deduced by DPT can be used for
datasets with similar characteristics" on the same machine).

Keyed by (machine fingerprint, dataset fingerprint, batch-size bucket,
epoch class).  Dataset fingerprints bucket item size / decode cost in
half-octave bins, so e.g. two ~100KB-JPEG folders share tuned parameters
while 80x80 and 640x640 resizes do not.
"""
from __future__ import annotations

import json
import math
import os
import threading
from typing import Dict, Optional, Sequence, Tuple

from repro.core.dpt import AXES, AXIS_BY_FIELD, DPTResult


def _batch_bucket(batch_size: int) -> int:
    return int(round(math.log2(max(batch_size, 1))))


class DPTCache:
    def __init__(self, path: Optional[str] = None):
        self.path = path
        self._lock = threading.Lock()
        self._store: dict = {}
        if path and os.path.exists(path):
            with open(path) as f:
                self._store = json.load(f)

    def _key(self, machine_fp: str, dataset_fp: str, batch_size: int,
             epoch: int) -> str:
        epoch_class = "cold" if epoch == 0 else "warm"
        return f"{machine_fp}|{dataset_fp}|b{_batch_bucket(batch_size)}|{epoch_class}"

    def get_params(self, machine_fp: str, dataset_fp: str, batch_size: int,
                   epoch: int = 0, *, axes: Sequence[str] = ()
                   ) -> Optional[Tuple[int, int, Dict[str, int]]]:
        """``(nworker, nprefetch, {field: value})`` for the requested axes
        (registry fields).  An entry whose search never swept a requested
        axis is a miss: a run that newly searches an axis must not be
        satisfied by a stale result that never priced it.  Entries written
        before an axis existed read back with that axis unsearched."""
        with self._lock:
            v = self._store.get(self._key(machine_fp, dataset_fp,
                                          batch_size, epoch))
        if not v:
            return None
        if not all(v.get(f"{AXIS_BY_FIELD[f].name}_searched", False)
                   for f in axes):
            return None
        return v["nworker"], v["nprefetch"], {f: int(v[f]) for f in axes}

    def put(self, machine_fp: str, dataset_fp: str, batch_size: int,
            result: DPTResult, epoch: int = 0) -> None:
        key = self._key(machine_fp, dataset_fp, batch_size, epoch)
        entry = {
            "nworker": result.nworker,
            "nprefetch": result.nprefetch,
            "optimal_time": result.optimal_time,
        }
        for a in AXES:
            entry[a.field] = result.axes.get(a.field, 0)
            # did the sweep actually price the axis?  any non-zero value
            # among the trials means candidates were measured (a searched
            # axis always includes one)
            entry[f"{a.name}_searched"] = any(
                t.axes.get(a.field, 0) for t in result.trials)
        with self._lock:
            prev = self._store.get(key)
            for a in AXES:
                if (not entry[f"{a.name}_searched"] and prev
                        and prev.get(f"{a.name}_searched")):
                    # an axis-blind refinement (e.g. an online 2-axis
                    # retune) was measured AT the live value: it refines
                    # (nworker, nprefetch) without invalidating the
                    # searched axis — keep it instead of clobbering to 0
                    entry[a.field] = prev.get(a.field, 0)
                    entry[f"{a.name}_searched"] = True
            self._store[key] = entry
            if self.path:
                tmp = self.path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(self._store, f, indent=1, sort_keys=True)
                os.replace(tmp, self.path)

    def __len__(self):
        return len(self._store)
