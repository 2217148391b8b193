"""Trainer: the end-to-end loop that makes DPT a first-class framework
feature rather than an offline script.

Startup:  restore latest checkpoint (step + sampler offset + loader params)
          -> DPT-tune the loader (or reuse the cached result for this
          machine/dataset fingerprint) -> jit the train step.
Steady:   device-prefetched batches -> train step; every
          ``checkpoint_every`` steps an async checkpoint (params, opt
          state, sampler state, loader params).
Drift:    an OnlineTuner (repro.tuning.online) watches the per-step
          data-wait vs compute-time goodput signal; when the loader
          becomes the bottleneck it runs a bounded re-search and
          hot-swaps the winner into the live stream (no rebuild, no lost
          batches) — the online re-tuning the paper's conclusion gestures
          at for clouds.
Fleet:    on a coordinated fleet the Trainer is constructed with a
          HostAgent (repro.tuning.fleet) instead: the same goodput signal
          streams to the FleetCoordinator (doubling as the heartbeat),
          which owns the decide step — uniform re-consensus and elastic
          resharding arrive back through the agent's apply_params /
          reshard.  The local OnlineTuner is disabled in that mode so
          host-local and fleet-level retunes can never fight.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.cache import DPTCache
from repro.core.dpt import AxisCandidates, DPTConfig, searched_axes
from repro.core.evaluators import LoaderEvaluator
from repro.data.loader import DataLoader, LoaderParams
from repro.distributed.sharding_rules import current_ctx, params_shardings
from repro.train.train_step import (TrainState, TrainStepConfig,
                                    init_train_state, make_train_step)
from repro.tuning import (OnlineTuner, OnlineTunerConfig, adaptive_budget,
                          tune)
from repro.utils.fingerprint import machine_fingerprint
from repro.utils.spans import span, step_span


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    checkpoint_every: int = 50
    checkpoint_dir: Optional[str] = None
    log_every: int = 10
    seed: int = 0
    # DPT integration (startup tune + online retune, see repro.tuning)
    autotune: bool = True
    autotune_strategy: str = "grid"
    # None derives the per-cell budget adaptively (>= 3x the deepest
    # worker rung — see tuning.base.adaptive_budget)
    autotune_budget_batches: Optional[int] = None
    autotune_max_prefetch: int = 4
    # largest worker count the startup grid tries (None: os.cpu_count(),
    # which on a VM can count the physical host's cores, not the guest's)
    autotune_num_cpu_cores: Optional[int] = None
    # beyond-paper axes for the startup grid and the online retunes:
    # registry field -> candidates (core.dpt.AXES: locality_chunk,
    # DESIGN.md §5; cache_budget_bytes, §7; slow_lane_workers, §9).
    # Empty keeps the search on the paper's two axes; include 0 in each
    # tuple so "off" stays a candidate.  Only the grid strategy searches
    # them, and a sharded loader drops every ``shared`` axis (every host
    # must slice the SAME epoch permutation, so those change only
    # uniformly through the coordinator's ``FleetConfig.axes``).
    autotune_axes: AxisCandidates = dataclasses.field(default_factory=dict)
    # retune trigger on the per-item cost tail ratio (p99/median of the
    # loader's tracked per-item costs, ~1 uniform; see DESIGN.md §9).
    # 0 disables; only armed when autotune_axes searches slow_lane_workers.
    retune_tail_ratio_trigger: float = 0.0
    # retune trigger on the loader's windowed fault rate (DESIGN.md §10):
    # fires a re-search when the storage browns out and once more when
    # degraded mode heals.  0 disables.
    retune_fault_rate_trigger: float = 0.0
    # the online locality loop (DESIGN.md §6): when True, an
    # AdaptiveLocalityController watches the live coalesced-run-length
    # counters and shrinks locality_chunk when the storage stops
    # achieving it (cache warmed, topology changed) — no search, applied
    # as an epoch-latched hot swap.  On a fleet the proposal routes to
    # the coordinator instead (locality must change uniformly).  The
    # single-host OnlineTuner also sweeps autotune_axes' locality_chunk at
    # retune time, so the knob can climb back UP when storage slows.
    adaptive_locality: bool = False
    retune_stall_fraction: float = 0.5   # data-wait/compute drift trigger
    retune_window: int = 8
    retune_cooldown_steps: int = 16
    dpt_cache_path: Optional[str] = None
    # zero-copy slab-arena delivery (DESIGN.md §3).  Default ON: the train
    # loop consumes device batches through the prefetcher (which transfers
    # before the slab recycles) and never retains a host view, so the
    # batch-lifetime contract holds.  Silently inert for datasets without
    # the fast path or for process pools.
    zero_copy: bool = True
    # linear-scaling rule (DESIGN.md §11): when the elastic geometry latch
    # changes the loader's global batch mid-run (a fleet reshard scaled
    # the fleet), scale the LR schedule by new/old and re-jit the step.
    # plan_remesh promises exactly this hand-off ("the LR schedule is
    # re-scaled by the Trainer accordingly").
    lr_linear_scaling: bool = True
    step_config: TrainStepConfig = dataclasses.field(
        default_factory=TrainStepConfig)


class Trainer:
    def __init__(self, model, loader: DataLoader, cfg: TrainerConfig,
                 *, host_name: str = "host0", agent=None):
        self.model = model
        self.loader = loader
        self.cfg = cfg
        self.host_name = host_name
        # fleet mode: a repro.tuning.fleet.HostAgent — observations stream
        # to the coordinator and the local OnlineTuner stays off
        self.agent = agent
        self.checkpointer = Checkpointer(cfg.checkpoint_dir) \
            if cfg.checkpoint_dir else None
        self.step_fn = self._jit_step()
        self.state: Optional[TrainState] = None
        self.start_step = 0
        # reference batch for the linear-scaling LR hook: the geometry the
        # current step_fn's schedule was built for
        self._lr_batch = loader.global_batch
        self.online_tuner: Optional[OnlineTuner] = None
        self.locality_controller = None
        self.history: List[Dict[str, Any]] = []
        # the startup search's result (None when a cached pick was reused
        # or autotune is off)
        self.tune_result = None

    def _jit_step(self):
        """The jitted train step.  The state is donated: the step's output
        state reuses its buffers, so params and optimizer moments are held
        once, not twice."""
        return jax.jit(make_train_step(self.model, self.cfg.step_config),
                       donate_argnums=(0,))

    def connect_fleet(self, transport, *, join: bool = False,
                      coord: str = "coord", link_config=None,
                      clock=time.monotonic):
        """Attach this trainer to a fleet over a message transport.

        Builds a transport-attached HostAgent around ``self.loader`` and
        registers (or ``join=True`` mid-run admits) it with the
        coordinator endpoint.  After this, ``run()`` streams observations
        over the wire and the coordinator's pushes (params, reshards,
        schedules) arrive as fenced commands — and a coordinator outage
        never blocks the step loop: the host trains on its last
        latched params and re-syncs on reconnect."""
        from repro.tuning.fleet import connect_host
        self.agent = connect_host(
            transport, self.host_name, self.loader, coord=coord,
            link_config=link_config, clock=clock, join=join)
        return self.agent

    # ---- DPT integration ----------------------------------------------------
    def tune_loader(self, *, force: bool = False) -> LoaderParams:
        """Startup tune through the unified ``tune(...)`` front door (or
        reuse the cached result for this machine/dataset fingerprint)."""
        cache = DPTCache(self.cfg.dpt_cache_path)
        mfp = machine_fingerprint()
        dfp = self.loader.dataset.fingerprint()
        strategy = self.cfg.autotune_strategy
        axes = self._tuner_axes() if strategy == "grid" else {}
        cached = None if force else cache.get_params(
            mfp, dfp, self.loader.global_batch, axes=tuple(axes))
        if cached is not None:
            # only the searched axes are adopted from the cache: a 2-axis
            # run must not silently reset a user-set value to a stale one
            nworker, nprefetch, values = cached
            params = self.loader.params.replace(
                num_workers=nworker, prefetch_factor=nprefetch, **values)
            self.loader.with_params(params)
            return params
        ev = LoaderEvaluator(self.loader, to_device=True)
        search_cfg = DPTConfig(max_prefetch=self.cfg.autotune_max_prefetch,
                               num_cpu_cores=self.cfg.autotune_num_cpu_cores,
                               axes=axes)
        search_cfg = dataclasses.replace(search_cfg, num_batches=(
            adaptive_budget(search_cfg, self.cfg.autotune_budget_batches)))
        if strategy == "grid":
            kwargs = {"measure_default": False}
        elif strategy == "successive_halving":
            kwargs = {}
        elif strategy == "hillclimb":
            _, G = search_cfg.resolve()
            kwargs = {"start": (max(G, self.loader.params.num_workers),
                                self.loader.params.prefetch_factor)}
        else:
            # goodput needs a measured step time, warmstart needs profiles —
            # neither exists before the first step
            raise ValueError(
                f"autotune_strategy {strategy!r} cannot run at startup; "
                "use 'grid', 'successive_halving' or 'hillclimb'")
        result = tune(evaluator=ev, strategy=strategy,
                      config=search_cfg, **kwargs)
        cache.put(mfp, dfp, self.loader.global_batch, result)
        self.tune_result = result
        params = self.loader.params.replace(
            num_workers=result.nworker, prefetch_factor=result.nprefetch,
            **result.axes)
        self.loader.with_params(params)
        return params

    def _tuner_axes(self) -> Dict[str, tuple]:
        """``autotune_axes`` as this host may search them: a sharded
        loader drops every ``shared`` axis, since per-host values would
        give each host a DIFFERENT epoch permutation and break the
        cross-host coverage invariant (those axes change uniformly
        through the coordinator instead)."""
        sharded = self.loader.sampler.host_count > 1
        return {a.field: cands
                for a, cands in searched_axes(self.cfg.autotune_axes)
                if not (a.shared and sharded)}

    def _make_online_tuner(self) -> OnlineTuner:
        return OnlineTuner(
            self.loader,
            evaluator=LoaderEvaluator(self.loader, to_device=True),
            cache=DPTCache(self.cfg.dpt_cache_path),
            config=OnlineTunerConfig(
                stall_fraction=self.cfg.retune_stall_fraction,
                window=self.cfg.retune_window,
                cooldown_steps=self.cfg.retune_cooldown_steps,
                retune_budget_batches=self.cfg.autotune_budget_batches,
                max_prefetch=self.cfg.autotune_max_prefetch,
                num_cpu_cores=self.cfg.autotune_num_cpu_cores,
                axes=self._tuner_axes(),
                tail_ratio_trigger=self.cfg.retune_tail_ratio_trigger,
                fault_rate_trigger=self.cfg.retune_fault_rate_trigger))

    def _make_locality_controller(self):
        """The counter-driven side of the online locality loop: applies
        locally on a single host; on a fleet, a proposal only *signals*
        the coordinator (locality must change uniformly there).  A
        sharded loader WITHOUT an agent gets no controller at all — a
        local resize would hand this host a different epoch permutation
        than its peers (same guard as the startup tune's locality axis).
        """
        from repro.tuning import AdaptiveLocalityController
        if self.agent is None and self.loader.sampler.host_count > 1:
            return None
        on_propose = None
        if self.agent is not None:
            # the coordinator drops the request when the fleet searches
            # no locality axis (a search that can't touch the knob would
            # burn goodput on every repeated proposal)
            on_propose = self.agent.notify_locality
        return AdaptiveLocalityController(self.loader,
                                          on_propose=on_propose)

    # ---- checkpoint/restart ---------------------------------------------------
    def _place_state(self, state: TrainState) -> TrainState:
        """Under a mesh (the Trainer built and run inside
        ``sharding_rules.use_rules``), lay params and moments out by the
        sharding rules; off-mesh the state stays where it was made."""
        ctx = current_ctx()
        if ctx is None:
            return state
        p_sh = params_shardings(self.model, ctx)

        def put(tree):
            return None if tree is None else jax.device_put(tree, p_sh)

        opt = state.opt._replace(
            step=jax.device_put(state.opt.step, ctx.named_sharding(())),
            mu=put(state.opt.mu), nu=put(state.opt.nu))
        return TrainState(put(state.params), opt, put(state.err))

    def _maybe_restore(self) -> None:
        if self.checkpointer is None or self.checkpointer.latest_step() is None:
            self.state = self._place_state(init_train_state(
                self.model, jax.random.PRNGKey(self.cfg.seed),
                self.cfg.step_config))
            return
        template = init_train_state(
            self.model, jax.random.PRNGKey(self.cfg.seed),
            self.cfg.step_config)
        state, aux = self.checkpointer.restore(template)
        self.state = self._place_state(state)
        self.start_step = int(aux["step"])
        if "loader" in aux:
            self.loader.load_state_dict(aux["loader"])

    def _consumed_state(self, step: int):
        """Sampler state reflecting batches the TRAINER consumed (one per
        step) — the producer runs ahead by worker queues + device prefetch,
        so loader.sampler.state would skip batches on restart.  Walks the
        geometry schedule (batches-per-epoch can differ per epoch after an
        elastic latch), not a fixed bpe."""
        s = self.loader.sampler
        base = s.epoch_start(self._stream_base.epoch) \
            + self._stream_base.batch_offset
        return s.state_at(base + (step - self._stream_base_step))

    def _rebuild_stream(self, step: int):
        """(Re)create the batch iterator from the consumed position."""
        with span("train.stream_start", step=step):
            self.loader.sampler.state = self._consumed_state(step) \
                if hasattr(self, "_stream_base") \
                else self.loader.sampler.state
            import copy
            self._stream_base = copy.deepcopy(self.loader.sampler.state)
            self._stream_base_step = step
            return iter(self.loader)

    def _save(self, step: int, block: bool = False) -> None:
        if self.checkpointer is None:
            return
        sd = self.loader.state_dict()
        sd["sampler"] = self._consumed_state(step).to_dict()
        self.checkpointer.save(step, self.state, aux={"loader": sd},
                               block=block)

    def _maybe_rescale_lr(self) -> None:
        """Linear-scaling rule: when the global batch moved (an elastic
        geometry latch crossed an epoch boundary), scale peak_lr by
        new/old and re-jit.  Geometry changes are epoch-rare, so the
        re-jit cost is negligible against an epoch of steps."""
        gb = self.loader.global_batch
        if not self.cfg.lr_linear_scaling or gb == self._lr_batch:
            return
        scale = gb / self._lr_batch
        opt = self.cfg.step_config.optimizer
        self.cfg.step_config = dataclasses.replace(
            self.cfg.step_config,
            optimizer=dataclasses.replace(opt, peak_lr=opt.peak_lr * scale))
        self.step_fn = self._jit_step()
        self.history.append({"event": "lr_rescale", "scale": scale,
                             "global_batch": gb,
                             "peak_lr": self.cfg.step_config.optimizer.peak_lr})
        self._lr_batch = gb

    def _apply_delivery_defaults(self) -> None:
        """Flip zero-copy delivery on when the pipeline supports it — the
        trainer's consumption pattern (device batches via the prefetcher,
        nothing retained host-side) satisfies the batch-lifetime contract
        unconditionally."""
        p = self.loader.params
        if (self.cfg.zero_copy and not p.zero_copy and p.fast_path
                and not p.use_processes
                and self.loader.dataset.supports_fast_path):
            self.loader.with_params(p.replace(zero_copy=True))

    # ---- main loop -----------------------------------------------------------
    def run(self) -> Dict[str, Any]:
        cfg = self.cfg
        kinds = self.model.cfg.layer_kinds
        with span("train.init_state",
                  **{f"layers_{k}": kinds.count(k) for k in set(kinds)}):
            self._maybe_restore()
        self._apply_delivery_defaults()
        if cfg.autotune:
            with span("train.tune"):
                self.tune_loader()
            if self.agent is None:
                self.online_tuner = self._make_online_tuner()
        if cfg.adaptive_locality:
            self.locality_controller = self._make_locality_controller()

        step = self.start_step
        batches = self._rebuild_stream(step)
        # a restore may have moved the geometry; each step's hooks re-check
        self._maybe_rescale_lr()
        t_wall = time.perf_counter()
        last_metrics: Dict[str, Any] = {}
        while step < cfg.total_steps:
            i = step
            with step_span("train.step", i):
                with span("train.data_wait", step=i):
                    t0 = time.perf_counter()
                    try:
                        batch = next(batches)
                    except StopIteration:
                        batches = self._rebuild_stream(step)
                        batch = next(batches)
                    t_data = time.perf_counter() - t0
                with span("train.dispatch", step=i):
                    self.state, metrics = self.step_fn(self.state, batch)
                with span("train.sync", step=i):
                    jax.block_until_ready(metrics["loss"])
                dt = time.perf_counter() - t0
                step += 1

                with span("train.log", step=i):
                    if step % cfg.log_every == 0 or step == cfg.total_steps:
                        rec = {"step": step,
                               "loss": float(metrics["loss"]),
                               "grad_norm": float(metrics["grad_norm"]),
                               "lr": float(metrics["lr"]),
                               "step_s": dt, "data_s": t_data}
                        self.history.append(rec)
                        last_metrics = rec
                with span("train.hooks", step=i):
                    # loader-drift retune (paper §5: cloud environments
                    # drift).  A triggered retune hot-swaps the live
                    # stream in place — no rebuild, no lost batches,
                    # sampler position preserved.  In fleet mode the same
                    # signal streams to the coordinator instead (which may
                    # push a uniform retune or a reshard back).
                    if self.agent is not None:
                        self.agent.observe(data_s=t_data, step_s=dt)
                    elif self.online_tuner is not None:
                        self.online_tuner.observe(data_s=t_data, step_s=dt)
                    if self.locality_controller is not None:
                        self.locality_controller.step()
                    if self.checkpointer and step % cfg.checkpoint_every == 0:
                        self._save(step)
                    self._maybe_rescale_lr()
        self._save(cfg.total_steps, block=True)
        wall = time.perf_counter() - t_wall
        return {"final_step": step, "wall_s": wall, **last_metrics}
