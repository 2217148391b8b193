"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Run as a script, it sets XLA_FLAGS (512 host devices) and JAX_PLATFORMS=cpu
before jax is imported (jax locks the device count on first init).
Importing it sets neither:

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell, resumable

Each cell writes artifacts/dryrun/<arch>__<shape>__<mesh>.json with
memory_analysis, cost_analysis, collective bytes and roofline terms.
"""
import os
if __name__ == "__main__":   # a script run only: importing sets nothing
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import (SHAPES, ModelConfig, ShapeConfig,  # noqa: E402
                                applicable_shapes, get_config, list_configs)
from repro.distributed.sharding_rules import (  # noqa: E402
    ShardingCtx, batch_shardings, params_shardings, rules_for, use_rules)
from repro.launch.mesh import make_production_mesh, mesh_chips  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.module import logical_axes  # noqa: E402
from repro.roofline.analysis import build_report  # noqa: E402
from repro.train.optimizer import abstract_adamw  # noqa: E402
from repro.train.train_step import (TrainState, TrainStepConfig,  # noqa: E402
                                    make_train_step)

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "artifacts", "dryrun")


# ---------------------------------------------------------------------------
# per-arch training knobs sized so every cell fits 16 GB/chip (see DESIGN.md)
# ---------------------------------------------------------------------------
def train_step_config(cfg: ModelConfig) -> TrainStepConfig:
    # microbatch floor of 8 (global 256 -> 32/microbatch): per-layer
    # activation checkpoints and large-vocab logit transients both scale
    # with the microbatch size; the v0 baseline at mb=1 blew the 16 GB HBM
    # budget on every mid-size arch (see EXPERIMENTS.md §Perf iteration 1).
    # dp_manual=True is §Perf iteration 2: explicit-DP shard_map step (bf16
    # FSDP gathers, once-per-step grad psum, EP MoE, sharded fused xent).
    n = cfg.param_count()
    if n > 50e9:
        mb, remat = 16, "nothing"
    elif n > 20e9:
        mb, remat = 8, "nothing"
    else:
        mb, remat = 8, "dots"
    return TrainStepConfig(remat_policy=remat, microbatches=mb,
                           dp_manual=True)


def use_seq_parallel(cfg: ModelConfig, shape: ShapeConfig) -> bool:
    # iteration 2: OFF — under the pjit path Megatron-style seq-parallelism
    # made GSPMD re-shard (all-gather) the f32 WEIGHTS per layer instead of
    # the activations (EXPERIMENTS.md §Perf yi-34b iteration); the manual-DP
    # step keeps activations replicated over 'model' and TP handles the
    # heavy matmuls.
    return False


def serve_params_dtype(t):
    return jax.ShapeDtypeStruct(t.shape, jnp.bfloat16) \
        if t.dtype == jnp.float32 else t


def choose_kv_dtype(model, cfg: ModelConfig, shape: ShapeConfig, chips: int):
    """fp8 KV-cache quantization when the bf16 cache would exceed ~7 GB per
    device (MHA archs at 32k x 128: phi-3-vision, whisper, mistral, yi)."""
    from repro.utils.tree import tree_bytes
    cache = model.init_cache(shape.global_batch, shape.seq_len, abstract=True)
    per_dev = tree_bytes(cache) / chips
    if per_dev > 7e9:
        return jnp.float8_e4m3fn
    return jnp.bfloat16


# ---------------------------------------------------------------------------
# sharding trees
# ---------------------------------------------------------------------------
CACHE_AXES = {
    "k": ("layers", "batch", "kv_seq", None, None),
    "v": ("layers", "batch", "kv_seq", None, None),
    "cross_k": ("layers", "batch", "kv_seq", None, None),
    "cross_v": ("layers", "batch", "kv_seq", None, None),
    "ssm_conv": ("layers", "batch", None, "ssm_inner"),
    "ssm_state": ("layers", "batch", "ssm_heads", None, None),
}


def cache_shardings(cache_abstract, ctx: ShardingCtx):
    return {k: ctx.named_sharding(CACHE_AXES[k], v.shape)
            for k, v in cache_abstract.items()}


def opt_state_shardings(model, ctx: ShardingCtx):
    p = params_shardings(model, ctx)
    scalar = ctx.named_sharding((), ())
    from repro.train.optimizer import AdamWState
    return AdamWState(step=scalar, mu=p, nu=p)


# ---------------------------------------------------------------------------
# cell lowering
# ---------------------------------------------------------------------------
def _serve_wrap(model, cfg, ctx, fn, *, global_batch: int = 0,
                out_is_cache_second=True):
    """Wrap a serve fn (prefill/decode) in shard_map over the batch axes so
    the manual paths (per-layer bf16 FSDP gathers for >50B archs, local EP
    MoE dispatch) activate — the pjit MoE dispatch was 121-126 GiB/dev on
    the 32k prefill cells (EXPERIMENTS.md §Perf iteration 6)."""
    import jax.tree_util as jtu
    from jax.sharding import PartitionSpec as P

    from repro.distributed import dp_shard
    mesh = ctx.mesh
    manual = dp_shard.manual_axes(mesh)
    if not manual or not dp_shard.validate_manual_divisibility(
            ctx, model.logical_axes(), model.abstract_params(), manual):
        return None
    if global_batch and global_batch % dp_shard.manual_size(mesh):
        return None   # long_500k: batch 1 can't shard over the DP axes
    axes_tree = model.logical_axes()
    p_specs = dp_shard.param_manual_specs(ctx, axes_tree,
                                          model.abstract_params(), manual)
    bspec = P(manual if len(manual) > 1 else manual[0])

    def cache_mspec(axes):
        ents = [tuple(a for a in (TRAIN_MANUAL_BATCH if n == "batch" else ())
                      if a in manual) or None for n in axes]
        ents = [e[0] if isinstance(e, tuple) and len(e) == 1 else e
                for e in ents]
        while ents and ents[-1] is None:
            ents.pop()
        return P(*ents)

    def wrapped(params, batch, cache):
        def body(params, batch, cache):
            with ctx.manual_region(set(manual)):
                params_g = dp_shard.gather_params(params, axes_tree)
                return fn(params_g, batch, cache)
        c_specs = {k: cache_mspec(CACHE_AXES[k]) for k in cache}
        b_specs = jtu.tree_map(lambda _: bspec, batch)
        out_specs = (bspec, c_specs)
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(p_specs, b_specs, c_specs),
                             out_specs=out_specs,
                             axis_names=set(manual), check_vma=False)(
            params, batch, cache)

    return wrapped


TRAIN_MANUAL_BATCH = ("pod", "data")


def lower_cell(arch: str, shape: ShapeConfig, mesh, mesh_name: str,
               *, do_compile: bool = True) -> dict:
    cfg = get_config(arch)
    model = build_model(cfg)
    rules = rules_for(shape.kind,
                      seq_parallel=use_seq_parallel(cfg, shape),
                      big_params=cfg.param_count() > 20e9)
    t0 = time.perf_counter()

    with use_rules(mesh, rules) as ctx:
        if shape.kind == "train":
            scfg = train_step_config(cfg)
            step = make_train_step(model, scfg)
            p_sh = params_shardings(model, ctx)
            state_sh = TrainState(p_sh, opt_state_shardings(model, ctx), None)
            state_abs = TrainState(model.abstract_params(),
                                   abstract_adamw(model.abstract_params()),
                                   None)
            in_specs = model.input_specs(shape)
            b_sh = batch_shardings(in_specs, ctx)
            jf = jax.jit(step, in_shardings=(state_sh, b_sh),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
            lowered = jf.lower(state_abs, in_specs)
        elif shape.kind == "prefill":
            params_abs = jax.tree_util.tree_map(serve_params_dtype,
                                                model.abstract_params())
            p_sh = params_shardings(model, ctx)
            kv_dtype = choose_kv_dtype(model, cfg, shape, mesh_chips(mesh))
            cache_abs = model.init_cache(shape.global_batch, shape.seq_len,
                                         abstract=True, kv_dtype=kv_dtype)
            c_sh = cache_shardings(cache_abs, ctx)
            in_specs = model.input_specs(shape)
            b_sh = batch_shardings(in_specs, ctx)
            logits_sh = ctx.named_sharding(
                ("batch", None, "vocab_act"),
                (shape.global_batch, 1, cfg.vocab_size))

            def prefill(params, batch, cache):
                return model.prefill(params, batch, cache)

            wrapped = _serve_wrap(model, cfg, ctx, model.prefill,
                                  global_batch=shape.global_batch)
            if wrapped is not None:
                prefill = wrapped
            jf = jax.jit(prefill, in_shardings=(p_sh, b_sh, c_sh),
                         out_shardings=(logits_sh, c_sh),
                         donate_argnums=(2,))
            lowered = jf.lower(params_abs, in_specs, cache_abs)
        else:  # decode
            params_abs = jax.tree_util.tree_map(serve_params_dtype,
                                                model.abstract_params())
            p_sh = params_shardings(model, ctx)
            kv_dtype = choose_kv_dtype(model, cfg, shape, mesh_chips(mesh))
            cache_abs = model.init_cache(shape.global_batch, shape.seq_len,
                                         abstract=True, kv_dtype=kv_dtype)
            c_sh = cache_shardings(cache_abs, ctx)
            in_specs = model.input_specs(shape)
            b_sh = batch_shardings(in_specs, ctx)
            logits_sh = ctx.named_sharding(
                ("batch", None, "vocab_act"),
                (shape.global_batch, 1, cfg.vocab_size))

            # decode stays on the pjit path: its MoE touches only B tokens
            # (no dispatch blow-up) and the manual wrapper's threaded cache
            # picks up replicated f32 loop-state twins on the CPU backend
            # (granite decode 3.7 -> 21 GiB; see EXPERIMENTS.md §Perf it. 6).
            def decode(params, cache, tokens, positions):
                return model.decode_step(params, cache, tokens, positions)

            jf = jax.jit(decode,
                         in_shardings=(p_sh, c_sh, b_sh["tokens"],
                                       b_sh["positions"]),
                         out_shardings=(logits_sh, c_sh),
                         donate_argnums=(1,))
            lowered = jf.lower(params_abs, cache_abs, in_specs["tokens"],
                               in_specs["positions"])

    t_lower = time.perf_counter() - t0
    out = {
        "arch": arch, "shape": shape.name, "mesh": mesh_name,
        "chips": mesh_chips(mesh),
        "lower_s": round(t_lower, 2),
        "dropped_shardings": [list(map(str, d)) for d in ctx.dropped[:20]],
        "ok": True,
    }
    if not do_compile:
        return out

    t1 = time.perf_counter()
    compiled = lowered.compile()
    out["compile_s"] = round(time.perf_counter() - t1, 2)

    mem = compiled.memory_analysis()
    out["memory"] = {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "peak_per_device": int(mem.argument_size_in_bytes
                               + mem.temp_size_in_bytes
                               + mem.output_size_in_bytes
                               - mem.alias_size_in_bytes),
    }
    cost = compiled.cost_analysis()
    out["cost"] = {"flops": float(cost.get("flops", 0.0)),
                   "bytes_accessed": float(cost.get("bytes accessed", 0.0))}
    hlo = compiled.as_text()
    report = build_report(arch=arch, shape=shape, mesh_name=mesh_name,
                          chips=mesh_chips(mesh), cost=cost, mem=mem,
                          hlo_text=hlo, cfg=cfg)
    out["roofline"] = report.to_dict()
    out["fits_hbm_16g"] = out["memory"]["peak_per_device"] < 16e9
    return out


def cell_path(arch: str, shape_name: str, mesh_name: str) -> str:
    os.makedirs(ARTIFACTS, exist_ok=True)
    return os.path.join(ARTIFACTS, f"{arch}__{shape_name}__{mesh_name}.json")


def run_cell(arch: str, shape_name: str, mesh_name: str,
             *, force: bool = False) -> dict:
    path = cell_path(arch, shape_name, mesh_name)
    if not force and os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    shape = SHAPES[shape_name]
    try:
        out = lower_cell(arch, shape, mesh, mesh_name)
    except Exception as e:  # noqa: BLE001 — a failed cell is a recorded bug
        out = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "ok": False, "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    return out


def all_cells():
    for arch in list_configs():
        cfg = get_config(arch)
        for shape in applicable_shapes(cfg):
            for mesh_name in ("single", "multi"):
                yield arch, shape.name, mesh_name


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        for c in all_cells():
            print("/".join(c))
        return 0

    if args.all:
        failures = 0
        for arch, shape_name, mesh_name in all_cells():
            out = run_cell(arch, shape_name, mesh_name, force=args.force)
            status = "OK " if out.get("ok") else "FAIL"
            extra = ""
            if out.get("ok") and "memory" in out:
                extra = (f" peak/dev={out['memory']['peak_per_device']/2**30:.2f}GiB"
                         f" dominant={out['roofline']['dominant']}")
            print(f"[{status}] {arch} x {shape_name} x {mesh_name}{extra}",
                  flush=True)
            failures += 0 if out.get("ok") else 1
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    out = run_cell(args.arch, args.shape, args.mesh, force=args.force)
    print(json.dumps({k: v for k, v in out.items() if k != "traceback"},
                     indent=1))
    return 0 if out.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
