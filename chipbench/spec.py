"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

Every piece is found by name, so a later change adds a configuration, a
traffic mix, a metric or a kernel as a new file and a new entry, and
edits nothing that is already here:

* ``configs/<config>.json``: the model configuration as it is run;
* ``references/<reference>.py``: the plain reference a configuration names;
* ``traffic/<mix>.json``: the parameters of one traffic mix;
* ``metrics/<metric>.py``: ``read(ctx)`` for one metric;
* ``kernels/<kernel>.py``: ``cost(call, model)`` for one Pallas kernel;
* ``peaks.json``: the chip's published peaks by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class SpecError(ValueError):
    """A benchmark file is missing or malformed."""


def _checked_name(name: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise SpecError(f"not a benchmark name: {name!r}")
    return name


def load_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing benchmark file {os.path.relpath(path, ROOT)}")


def _module(kind: str, name: str):
    path = os.path.join(HERE, kind, _checked_name(name) + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no {kind[:-1]} file for {name!r} "
                        f"({os.path.relpath(path, ROOT)})")
    mod_name = f"chipbench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``read(ctx) -> float | None`` of one metric."""
    return _module("metrics", name).read


def kernel_cost(name: str):
    """``cost(call, model) -> (flops, bytes)`` of one call of a kernel."""
    return _module("kernels", name).cost


def reference(name: str):
    return _module("references", name)


def config(name: str) -> Dict[str, Any]:
    c = load_json(os.path.join(HERE, "configs", _checked_name(name) + ".json"))
    if c.get("name") != name:
        raise SpecError(f"configs/{name}.json names itself {c.get('name')!r}")
    return c


def traffic(name: str) -> Dict[str, Any]:
    return load_json(os.path.join(HERE, "traffic", _checked_name(name) + ".json"))


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"chipbench/peaks.json")
    return table[device_kind]


def benchmark(path: Optional[str] = None) -> Dict[str, Any]:
    return load_json(path or os.path.join(ROOT, "BENCHMARK.json"))


def cell(bench: Dict[str, Any], workload: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise SpecError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_for(bench: Dict[str, Any], workload: str, *,
                trace: bool) -> List[Dict[str, Any]]:
    """The metrics a run of ``workload`` reports: the end-to-end ones, or
    with ``trace`` the per-layer ones.  A metric without a ``workloads``
    key is reported wherever the metric it moves is."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    e2e_names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and (m.get("workloads") or m["moves"] in e2e_names)]
