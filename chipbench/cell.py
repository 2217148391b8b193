"""One run of one cell: the program's trainer, fed by its tuned loader,
through set-up, a timed window and the check of what it produced.

The entry the window drives is ``Trainer.run()`` itself, with the worker
pool and the device prefetcher live.  ``run()`` has no notion of a
duration, so ``StepClock`` wraps the trainer's jitted step on the
instance: it timestamps every call, keeps a reference to every batch the
step consumed, copies what the output check needs out of the first steps,
and, once the window has lasted ``--seconds``, lowers the trainer's
``total_steps`` so that ``run()`` returns after the step in hand.  The
program is not edited.

Timeline of one ``run()`` (step ``i`` is the ``i``-th call of the step):

* set-up: process start, weights, the startup DPT tune, step 0 (which
  compiles, or loads from the persistent cache) and the warm steps;
* window: from the entry of step ``WARM_STEPS`` to the entry of the first
  step that starts ``--seconds`` or more later.  Step ``i``'s wall time is
  the time between the entries of steps ``i`` and ``i + 1``: its device
  time, the trainer's hooks and the wait for the next batch;
* with ``--trace 1``, a traced tail of steps after the window, so that the
  profiler slows no timed step.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import spec as bspec
import traffic as btraffic

# Steps 0-3 feed the output check (losses and gradient norms of 0-2, the
# change 0-2 made, read before 3); step 4 settles after the host copies.
WARM_STEPS = 5
TRACE_SECONDS = 3.0
DATA_SPAN = "chipbench.data_wait"
STEP_SPAN = "chipbench.step_dispatch"


def log(**fact) -> None:
    """One JSON line on standard error."""
    import json
    print(json.dumps(fact, default=str), file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def device_or_fail(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class CompileClock:
    """JAX's own backend-compile durations, with when each one ended."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.events: List[tuple] = []

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.events.append((time.perf_counter(), duration))

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)

    def total(self, start: float, end: float) -> float:
        return sum(d for t, d in self.events if start <= t < end)

    def count(self, start: float, end: float) -> int:
        return sum(1 for t, _ in self.events if start <= t < end)


def model_config(model: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file: the
    registered architecture with every field the file maps overridden by
    the file's value, so the program runs what the file states."""
    from repro.configs import get_config
    base = get_config(model["arch"])
    fields = {f: model[k] for f, k in model["program_fields"].items()}
    return dataclasses.replace(base, **fields)


class _SpannedStream:
    """The trainer's batch iterator, each wait for a batch in a profiler
    span (a no-op unless a trace is running)."""

    def __init__(self, it):
        self.it = it

    def __iter__(self):
        return self

    def __next__(self):
        import jax
        with jax.profiler.TraceAnnotation(DATA_SPAN):
            return next(self.it)


class StepClock:
    def __init__(self, trainer, *, seconds: float, trace_dir: Optional[str]):
        self.trainer = trainer
        self.jitted = trainer.step_fn
        self.inner = self.jitted
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.entries: List[float] = []
        self.batches: List[tuple] = []
        self.open_i: Optional[int] = None
        self.close_i: Optional[int] = None
        self.trace_start_i: Optional[int] = None
        self.trace_stop_i: Optional[int] = None
        self.p0 = self.p3 = None

    def _stop_after(self, i: int) -> None:
        self.trainer.cfg.total_steps = i + 1

    def __call__(self, state, batch):
        import jax
        i = len(self.entries)
        if i == 0:
            self.p0 = jax.device_get(state.params)
        elif i == 3:
            self.p3 = jax.device_get(state.params)
        now = time.perf_counter()
        if i == WARM_STEPS:
            self.open_i = i
        elif (self.open_i is not None and self.close_i is None
              and now - self.entries[self.open_i] >= self.seconds):
            self.close_i = i
            if self.trace_dir is None:
                self._stop_after(i)
            else:
                jax.profiler.start_trace(self.trace_dir)
                self.trace_start_i = i
                now = time.perf_counter()
        elif (self.trace_start_i is not None and self.trace_stop_i is None
              and now - self.entries[self.trace_start_i] >= TRACE_SECONDS):
            jax.profiler.stop_trace()
            self.trace_stop_i = i
            self._stop_after(i)
        self.entries.append(now)
        self.batches.append((batch["tokens"], batch["targets"],
                             batch["loss_mask"]))
        with jax.profiler.TraceAnnotation(STEP_SPAN):
            return self.inner(state, batch)


@dataclasses.dataclass
class Run:
    """What one run measured and produced, for the metric readers."""
    workload: Dict[str, Any]
    model: Dict[str, Any]
    mix: Dict[str, Any]
    seed: int
    t_start: float
    clock: StepClock
    history: List[Dict[str, Any]]
    tune_s: float
    tune_trials: int
    picked: Dict[str, int]
    compile_s_setup: float
    compiles_in_window: int
    device: Dict[str, Any]
    step_hlo: str = ""
    trace: Optional[Dict[str, Any]] = None
    peaks: Optional[Dict[str, float]] = None

    @property
    def tokens_per_step(self) -> int:
        t = self.model["train"]
        return t["global_batch"] * t["seq_len"]

    @property
    def window(self) -> tuple:
        return self.clock.open_i, self.clock.close_i

    @property
    def window_s(self) -> float:
        o, c = self.window
        return self.clock.entries[c] - self.clock.entries[o]

    @property
    def step_times(self) -> List[float]:
        o, c = self.window
        e = self.clock.entries
        return [e[i + 1] - e[i] for i in range(o, c)]

    @property
    def data_waits(self) -> List[float]:
        """The wait for each batch that arrived inside the window: steps
        ``open+1 .. close`` (history is 1-based)."""
        o, c = self.window
        by_step = {r["step"]: r["data_s"] for r in self.history
                   if "data_s" in r}
        return [by_step[i + 1] for i in range(o + 1, c + 1)]

    @property
    def setup_s(self) -> float:
        return self.clock.entries[self.clock.open_i] - self.t_start

    def kernel_calls(self) -> List[Dict[str, Any]]:
        import hlo
        return hlo.pallas_calls(self.step_hlo)


def _plant_spans(trainer, timing: Dict[str, float]) -> None:
    """Instance-level wrappers: the startup tune under the host clock, and
    the batch stream in profiler spans."""
    tune = trainer.tune_loader
    rebuild = trainer._rebuild_stream

    def timed_tune(*a, **kw):
        t0 = time.perf_counter()
        try:
            return tune(*a, **kw)
        finally:
            timing["tune_s"] = time.perf_counter() - t0

    trainer.tune_loader = timed_tune
    trainer._rebuild_stream = lambda step: _SpannedStream(rebuild(step))


def build(model: Dict[str, Any], mix: Dict[str, Any], seed: int):
    """The cell's model, dataset, ``DataLoader`` and ``Trainer``, through
    the program's public constructors, as ``launch.train.build_trainer``
    builds them."""
    from repro.data import DataLoader, LoaderParams
    from repro.models import build_model
    from repro.models import layers as program_layers
    from repro.train.optimizer import AdamWConfig
    from repro.train.train_step import TrainStepConfig
    from repro.train.trainer import Trainer, TrainerConfig

    train = model["train"]
    if str(program_layers.COMPUTE_DTYPE) != train["compute_dtype"]:
        raise bspec.SpecError(
            f"the program computes in {program_layers.COMPUTE_DTYPE}, the "
            f"configuration states {train['compute_dtype']}")
    cfg = model_config(model)
    items = btraffic.make_items(mix, train["seq_len"], cfg.vocab_size, seed)
    ds = btraffic.build_dataset(mix, items)
    tuner = mix["tuner"]
    loader = DataLoader(ds, train["global_batch"],
                        params=LoaderParams(num_workers=tuner["initial_workers"]),
                        seed=mix["order_seed"], host_index=0, host_count=1)
    tc = TrainerConfig(
        total_steps=10 ** 9, checkpoint_every=10 ** 9, log_every=1,
        autotune=tuner["autotune"],
        autotune_budget_batches=tuner["budget_batches"],
        autotune_num_cpu_cores=tuner["cores"],
        autotune_max_prefetch=tuner["max_prefetch"],
        seed=btraffic.seeds(seed)["weights"],
        step_config=TrainStepConfig(
            remat_policy=train["remat"],
            optimizer=AdamWConfig(**train["optimizer"])))
    return Trainer(build_model(cfg), loader, tc), items


def _free(trainer) -> None:
    stream = getattr(trainer.loader, "_live_stream", None)
    if stream is not None:
        stream.close()
    trainer.state = None
    gc.collect()


def _memory_peak(trainer, run: Run) -> int:
    """The larger of the runtime's peak and the compiler's account of the
    step (arguments + outputs - aliased + temporaries): the runtime's
    counter has been seen to leave the step's temporaries out."""
    import jax
    import jax.numpy as jnp
    t = run.model["train"]
    b, s = t["global_batch"], t["seq_len"]
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
             "targets": jax.ShapeDtypeStruct((b, s), jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct((b, s), jnp.float32)}
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), trainer.state)
    compiled = run.clock.jitted.lower(state, batch).compile()
    run.step_hlo = compiled.as_text()
    mem = compiled.memory_analysis()
    step_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    stats = jax.devices()[0].memory_stats() or {}
    return int(max(step_bytes, stats.get("peak_bytes_in_use", 0)))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench: Optional[Dict[str, Any]] = None,
             require_chip: bool = True,
             plant: Optional[Callable] = None) -> Dict[str, Any]:
    """One run of ``workload``; returns the result line's object.

    ``plant(trainer, items)`` runs after the trainer is built and before
    it runs; the harness's own tests break the timed path through it."""
    import jax

    bench = bench or bspec.benchmark()
    w = bspec.cell(bench, workload)
    if require_chip:
        devices = device_or_fail(w["chips"])
    else:
        devices = jax.devices()[:1]
    dev = devices[0]
    peaks = bspec.peaks(dev.device_kind) if require_chip else None
    model = bspec.config(w["config"])
    mix = bspec.traffic(w["traffic"])

    from repro.utils.compile_cache import enable_compile_cache
    if enable_compile_cache() is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    trace_dir = None
    if trace:
        import tempfile
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")

    with CompileClock() as compiles, jax.default_device(dev):
        trainer, items = build(model, mix, seed)
        timing: Dict[str, float] = {}
        _plant_spans(trainer, timing)
        clock = StepClock(trainer, seconds=seconds, trace_dir=trace_dir)
        trainer.step_fn = clock
        if plant is not None:
            plant(trainer, items)
        trainer.run()
        res = trainer.tune_result
        run = Run(
            workload=w, model=model, mix=mix, seed=seed,
            t_start=t_start, clock=clock, history=trainer.history,
            tune_s=timing.get("tune_s", 0.0),
            tune_trials=len(res.trials) if res is not None else 0,
            picked={"num_workers": trainer.loader.params.num_workers,
                    "prefetch_factor": trainer.loader.params.prefetch_factor},
            compile_s_setup=0.0, compiles_in_window=0,
            device={"platform": dev.platform, "kind": dev.device_kind,
                    "count": len(devices)},
            peaks=peaks)
        e = clock.entries
        run.compile_s_setup = compiles.total(0.0, e[clock.open_i])
        run.compiles_in_window = compiles.count(e[clock.open_i],
                                                e[clock.close_i])
        run.device["memory_peak_bytes"] = _memory_peak(trainer, run)
        consumed = [tuple(np.asarray(a) for a in jax.device_get(b))
                    for b in clock.batches]
        clock.batches = []
        _free(trainer)
        if trace_dir is not None:
            import tracing
            run.trace = tracing.reduce_dir(trace_dir, run.kernel_calls())
        log(tune_s=run.tune_s, tune_trials=run.tune_trials,
            picked=run.picked, setup_s=run.setup_s,
            compile_s=run.compile_s_setup,
            compiles_in_window=run.compiles_in_window,
            window_steps=clock.close_i - clock.open_i, window_s=run.window_s)
        import check
        t_ref = time.perf_counter()
        checks = check.check(run, items, consumed)
        log(reference_s=time.perf_counter() - t_ref)
        del trainer, items, consumed
        gc.collect()
    return report(bench, run, checks, trace)


def report(bench, run: Run, checks: Dict[str, Dict[str, float]],
           trace: bool) -> Dict[str, Any]:
    import check
    metrics = {}
    for m in bspec.metrics_for(bench, run.workload["name"], trace=trace):
        value = bspec.metric_reader(m["name"])(run)
        if value is None:
            continue
        if not (isinstance(value, (int, float)) and math.isfinite(value)):
            raise RuntimeError(f"metric {m['name']} read {value!r}")
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    steps = run.clock.close_i - run.clock.open_i
    losses = {r["step"]: r["loss"] for r in run.history if "loss" in r}
    failed = sum(1 for i in range(run.clock.open_i, run.clock.close_i)
                 if not math.isfinite(losses.get(i + 1, float("nan"))))
    checks["failed_steps"] = {"value": failed, "limit": 0}
    out = {"correct": check.passed(checks), "attempted": steps,
           "failed": failed, "metrics": metrics, "device": dict(run.device)}
    if trace and run.trace is not None:
        out["device"]["busy_s"] = run.trace["busy_s"]
        out["device"]["window_s"] = run.trace["window_s"]
        out["breakdown"] = run.trace["breakdown"]
    out["checks"] = checks
    return out
