"""Training launcher: ``python -m repro.launch.train --arch qwen2-0.5b ...``

Wires together everything the framework provides: config registry (--arch
selects any of the 10 assigned architectures, reduced or full), the
DPT-autotuned data pipeline, the jit'd train step, checkpoint/restart and
the straggler/retune hooks.  On a real fleet each host runs this entry
point under the cluster launcher (GKE/xmanager); jax.distributed handles
cross-host init — on this container it runs single-process.

``build_trainer`` is the whole construction (model, dataset, loader,
trainer); ``chip_smoke.py`` drives the chip through it too.
"""
from __future__ import annotations

import argparse
import json
from typing import Optional, Sequence


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--no-autotune", action="store_true")
    ap.add_argument("--autotune-budget", type=int, default=None,
                    help="batches per DPT trial (default: adaptive)")
    ap.add_argument("--autotune-cores", type=int, default=None,
                    help="largest worker count DPT tries "
                         "(default: os.cpu_count())")
    ap.add_argument("--dpt-cache", default=None)
    ap.add_argument("--num-items", type=int, default=2048)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--dp-manual", action="store_true",
                    help="explicit data-parallel step when built under a "
                         "mesh (distributed/dp_shard.py)")
    ap.add_argument("--remat", default="none",
                    choices=["none", "dots", "nothing", "full"])
    ap.add_argument("--seed", type=int, default=0)
    return ap


def build_trainer(args, *, sharding=None):
    """Model, seeded dataset, ``DataLoader`` and ``Trainer`` from parsed
    launcher arguments.  ``sharding`` places each global batch (a
    ``NamedSharding`` over the data axis for a mesh run); a Trainer built
    inside ``sharding_rules.use_rules`` jits its step for that mesh."""
    import jax

    from repro.configs import get_config, reduced
    from repro.data import DataLoader, LoaderParams, token_dataset
    from repro.models import build_model
    from repro.train.optimizer import AdamWConfig
    from repro.train.train_step import TrainStepConfig
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)

    if cfg.family in ("vlm", "encdec"):
        # modality stubs: wrap the token dataset with stub frontends
        from repro.data.dataset import Dataset, ArrayStorage
        import numpy as np
        rng = np.random.default_rng(args.seed)
        items = [rng.integers(0, cfg.vocab_size,
                              (args.seq_len + 1,)).astype(np.int32)
                 for _ in range(args.num_items)]

        def transform(arr):
            out = {"tokens": arr[:-1], "targets": arr[1:],
                   "loss_mask": np.ones(args.seq_len, np.float32)}
            if cfg.num_patches:
                out["patch_embeds"] = rng.normal(
                    0, 1, (cfg.num_patches, cfg.patch_embed_dim)
                ).astype(np.float32)
            if cfg.encoder_layers:
                out["frames"] = rng.normal(
                    0, 1, (cfg.max_source_positions, cfg.d_model)
                ).astype(np.float32)
            return out

        ds = Dataset(ArrayStorage(items), transform=transform)
    else:
        ds = token_dataset(args.num_items, args.seq_len, cfg.vocab_size,
                           seed=args.seed)

    loader = DataLoader(ds, args.global_batch,
                        params=LoaderParams(num_workers=2),
                        seed=args.seed,
                        host_index=jax.process_index(),
                        host_count=jax.process_count(),
                        sharding=sharding)

    tc = TrainerConfig(
        total_steps=args.steps,
        checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir,
        log_every=args.log_every,
        autotune=not args.no_autotune,
        autotune_budget_batches=args.autotune_budget,
        autotune_num_cpu_cores=args.autotune_cores,
        dpt_cache_path=args.dpt_cache,
        seed=args.seed,
        step_config=TrainStepConfig(
            remat_policy=args.remat,
            microbatches=args.microbatches,
            compress_grads=args.compress_grads,
            dp_manual=args.dp_manual,
            optimizer=AdamWConfig(peak_lr=args.lr,
                                  total_steps=args.steps,
                                  warmup_steps=max(2, args.steps // 20))),
    )
    return Trainer(model, loader, tc)


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = make_parser().parse_args(argv)

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    trainer = build_trainer(args)
    result = trainer.run()
    print(json.dumps(result, default=float))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
