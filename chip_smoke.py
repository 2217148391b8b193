#!/usr/bin/env python3
"""Chip smoke: the system's main path, once, on a TPU.

    python chip_smoke.py             # one chip: tuned loader -> training, then serving
    python chip_smoke.py --chips 4   # four chips: the data-parallel train step only

One chip (the default):

1. Train.  A seeded token dataset feeds a ``DataLoader``; ``Trainer.run()``
   first lets DPT pick the loader's worker count and prefetch depth by
   timing real deliveries to the chip, then takes 8 steps of qwen2-0.5b at
   full published width (24 layers, d_model 896, 14/2 heads of dim 64,
   vocab 151936; random weights from a seed) on 4 x 1024 tokens each.
   The loader and trainer are built by ``repro.launch.train.build_trainer``,
   as the launcher builds them.  Checks: every loss finite, the last below the first, and the
   compiled step calls the Pallas flash-attention and rmsnorm kernels.
2. Serve.  ``ServeEngine`` + ``BatchingFrontend`` (``repro.launch.serve``)
   answer 4 requests on the same model; each answer's length is checked.

``--chips 4``: one train step on a (data, model) = (4, 1) mesh, the
explicit data-parallel step with each global batch sharded over the data
axis, against the same step on one of those chips.

Each fact goes to stdout as one JSON line.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
and appears only when every phase passed.  Without a TPU, or without the
repository's sources beside this file, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen2-0.5b"
# The largest batch x length (>= 1024 tokens per sequence) whose train step
# compiles for one v5e chip with >= 1 GB of HBM to spare: 8 x 1024 runs out
# of HBM, 4 x 1024 peaks at 15.66 GB of 16.9 (see CHANGES.md).
GLOBAL_BATCH, SEQ_LEN = 4, 1024
HBM_HEADROOM = 10**9
TRAIN_STEPS = 8
# Four batches per epoch: steps 5-8 revisit the sequences of steps 1-4, so
# "last loss below first" checks that the model learned what it saw (on
# unseen uniform tokens the loss only drifts within batch noise).  DPT's
# per-trial budget is that one epoch.
NUM_ITEMS, DPT_BUDGET, DPT_CORES = 4 * GLOBAL_BATCH, 4, 4
SERVE_REQUESTS, PROMPT_LEN, MAX_NEW = 4, 32, 16
# test_dp_manual's tolerances: worst param diff, relative loss diff,
# absolute grad-norm diff after one step
PARAM_TOL, LOSS_RTOL, GNORM_TOL = 5e-3, 0.02, 5e-3


def emit(**fact) -> None:
    print(json.dumps(fact, default=float), flush=True)


def check(ok: bool, detail) -> None:
    """A failed check fails the run (unlike ``assert``, also under -O)."""
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {detail}")


class CompileClock:
    """Sums JAX's own trace, lowering and backend-compile durations."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
              "/jax/core/compile/backend_compile_duration": "compile_s"}

    def __init__(self):
        self.totals = dict.fromkeys(self.EVENTS.values(), 0.0)
        self.largest_compile_s = 0.0

    def __call__(self, event, duration, **_):
        key = self.EVENTS.get(event)
        if key is not None:
            self.totals[key] += duration
            if key == "compile_s":
                self.largest_compile_s = max(self.largest_compile_s, duration)

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


def kernel_calls(hlo_text: str) -> dict:
    """Pallas (Mosaic) custom calls in compiled HLO, by kernel."""
    counts = {"flash_attention": 0, "rmsnorm": 0}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        for name in counts:
            if f"jit({name})" in line:
                counts[name] += 1
    return counts


def train_argv(*extra: str) -> list:
    return ["--arch", ARCH, "--global-batch", str(GLOBAL_BATCH),
            "--seq-len", str(SEQ_LEN), "--remat", "dots", "--log-every", "1",
            *extra]


def train_phase(argv) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch import train

    args = train.make_parser().parse_args(argv)
    trainer = train.build_trainer(args)
    with CompileClock() as clock:
        trainer.run()

    res = trainer.tune_result
    check(res is not None, "DPT ran no search")
    emit(phase="train", fact="dpt", num_workers=trainer.loader.params.num_workers,
         prefetch_factor=trainer.loader.params.prefetch_factor,
         trials=len(res.trials), optimal_s=res.optimal_time,
         budget_batches=args.autotune_budget)
    emit(phase="train", fact="compile", largest_compile_s=clock.largest_compile_s,
         **clock.totals)
    steps = [r for r in trainer.history if "loss" in r]
    for r in steps:
        emit(phase="train", fact="step", step=r["step"], loss=r["loss"],
             grad_norm=r["grad_norm"], step_s=r["step_s"], data_s=r["data_s"])
    stats = jax.devices()[0].memory_stats() or {}
    emit(phase="train", fact="memory",
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"))

    losses = [r["loss"] for r in steps]
    check(len(losses) == args.steps, (len(losses), args.steps))
    check(all(math.isfinite(v) for v in losses), losses)
    check(losses[-1] < losses[0], losses)

    b, s = args.global_batch, args.seq_len
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
             "targets": jax.ShapeDtypeStruct((b, s), jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct((b, s), jnp.float32)}
    compiled = trainer.step_fn.lower(trainer.state, batch).compile()
    calls = kernel_calls(compiled.as_text())
    emit(phase="train", fact="kernels", **calls)
    check(all(calls.values()), f"Pallas kernels missing from the step: {calls}")

    # the compiler's own account of the step's HBM: the runtime's
    # peak_bytes_in_use above need not count the step's temporaries
    mem = compiled.memory_analysis()
    step_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    limit = stats.get("bytes_limit")
    emit(phase="train", fact="step_hbm", argument_bytes=mem.argument_size_in_bytes,
         output_bytes=mem.output_size_in_bytes,
         alias_bytes=mem.alias_size_in_bytes, temp_bytes=mem.temp_size_in_bytes,
         step_bytes=step_bytes, bytes_limit=limit)
    check(limit is not None and limit - step_bytes >= HBM_HEADROOM,
          f"the step leaves under {HBM_HEADROOM} bytes of HBM: "
          f"{step_bytes} of {limit}")


def serve_phase(argv) -> None:
    from repro.launch import serve

    args = serve.make_parser().parse_args(argv)
    cfg, frontend = serve.build_frontend(args)
    try:
        outs = serve.serve_requests(args, cfg, frontend)
    finally:
        frontend.shutdown()
    lengths = [len(o) for o in outs]
    emit(phase="serve", requests=len(outs), tokens=lengths,
         batches_served=frontend.batches_served)
    check(len(outs) == args.requests, (len(outs), args.requests))
    check(lengths == [args.max_new] * args.requests, lengths)
    check(all(0 <= int(t) < cfg.vocab_size for o in outs for t in o),
          "token id out of the vocabulary")


def mesh_phase(argv, devices) -> None:
    """One explicit-DP step on a (4, 1) mesh vs the same step on one of
    those chips."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.sharding_rules import rules_for, use_rules
    from repro.launch import train
    from repro.launch.mesh import make_local_mesh

    args = train.make_parser().parse_args(argv)

    def one_step(trainer):
        trainer.run()
        rec = trainer.history[-1]
        return jax.device_get(trainer.state.params), rec

    with jax.default_device(devices[0]):
        ref_params, ref = one_step(train.build_trainer(args))
    gc.collect()              # the reference's device state goes first
    mesh = make_local_mesh(1, devices=devices)
    with use_rules(mesh, rules_for("train")):
        trainer = train.build_trainer(
            args, sharding=NamedSharding(mesh, P("data")))
        got_params, got = one_step(trainer)

    worst = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                    - np.asarray(b, np.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(ref_params),
                                jax.tree_util.tree_leaves(got_params)))
    dloss = abs(ref["loss"] - got["loss"])
    dgnorm = abs(ref["grad_norm"] - got["grad_norm"])
    emit(phase="mesh", mesh=dict(mesh.shape), loss_one_chip=ref["loss"],
         loss_mesh=got["loss"], grad_norm_one_chip=ref["grad_norm"],
         grad_norm_mesh=got["grad_norm"], worst_param_diff=worst,
         step_s_one_chip=ref["step_s"], step_s_mesh=got["step_s"])
    check(worst < PARAM_TOL, worst)
    check(dloss < LOSS_RTOL * ref["loss"], dloss)
    check(dgnorm < GNORM_TOL, dgnorm)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    opts = ap.parse_args()

    if os.environ.get("REPRO_KERNEL_IMPL"):
        print("chip_smoke: REPRO_KERNEL_IMPL must be unset: the smoke runs "
              "the kernels the chip picks", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} needs {opts.chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's sources are not beside this "
              f"file ({e})", file=sys.stderr)
        return 2
    emit(fact="setup", compile_cache=enable_compile_cache(),
         cpu_count=os.cpu_count(), cpus_usable=len(os.sched_getaffinity(0)))

    if opts.chips == 4:
        mesh_phase(train_argv("--steps", "1", "--no-autotune",
                              "--dp-manual"), devices[:4])
    else:
        train_phase(train_argv(
            "--steps", str(TRAIN_STEPS), "--num-items", str(NUM_ITEMS),
            "--autotune-budget", str(DPT_BUDGET),
            "--autotune-cores", str(DPT_CORES)))
        gc.collect()          # the trainer's device state goes first
        serve_phase(["--arch", ARCH, "--requests", str(SERVE_REQUESTS),
                     "--prompt-len", str(PROMPT_LEN),
                     "--max-new", str(MAX_NEW),
                     "--max-batch", str(SERVE_REQUESTS)])
    dev = jax.devices()[0]
    emit(ok=True, device={"platform": dev.platform, "kind": dev.device_kind,
                          "count": len(jax.devices())})
    return 0


if __name__ == "__main__":
    sys.exit(main())
