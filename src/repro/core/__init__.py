"""DPT core package.  Imports are lazy to avoid data<->core import cycles
(data.loader uses core.monitor; core.dpt uses data.loader)."""
import importlib

_EXPORTS = {
    "DPT": "repro.core.dpt",
    "DPTConfig": "repro.core.dpt",
    "DPTResult": "repro.core.dpt",
    "FleetResult": "repro.core.dpt",
    "MultiHostDPT": "repro.core.dpt",
    "Trial": "repro.core.dpt",
    "default_params": "repro.core.dpt",
    "MemoryBudget": "repro.core.monitor",
    "MemoryMonitor": "repro.core.monitor",
    "MemoryOverflow": "repro.core.monitor",
    "LoaderSimulator": "repro.core.simulator",
    "MachineProfile": "repro.core.simulator",
    "SimResult": "repro.core.simulator",
    "LoaderEvaluator": "repro.core.evaluators",
    "SimulatorEvaluator": "repro.core.evaluators",
    "DPTCache": "repro.core.cache",
}


def __getattr__(name):
    if name in _EXPORTS:
        mod = importlib.import_module(_EXPORTS[name])
        return getattr(mod, name)
    raise AttributeError(f"module 'repro.core' has no attribute {name!r}")
