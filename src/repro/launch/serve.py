"""Serving launcher: ``python -m repro.launch.serve --arch qwen2-0.5b --reduced``

Builds the model, spins up the batching frontend and runs a synthetic
request workload through prefill + jit'd decode (greedy or sampled),
reporting tokens/s and batch formation stats.
"""
from __future__ import annotations

import argparse
import json
from typing import Optional, Sequence

import numpy as np


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def build_frontend(args):
    """Model with seeded random weights, ``ServeEngine`` and
    ``BatchingFrontend`` from parsed launcher arguments.  Returns
    (config, frontend)."""
    import jax

    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.serve.engine import BatchingFrontend, ServeEngine

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if not cfg.has_decoder:
        raise SystemExit(f"{cfg.name}: serving a layer_pattern model is not "
                         f"implemented (pattern {cfg.layer_pattern}); it "
                         f"trains only")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    engine = ServeEngine(model, params, max_batch=args.max_batch,
                         max_len=args.prompt_len + args.max_new + 8,
                         temperature=args.temperature)
    return cfg, BatchingFrontend(engine)


def serve_requests(args, cfg, frontend):
    """Submit ``args.requests`` seeded prompts; returns each answer's
    generated token ids, in submission order."""
    rng = np.random.default_rng(args.seed)
    reqs = []
    for _ in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, (args.prompt_len,))
        reqs.append(frontend.submit(prompt.astype(np.int32), args.max_new))
    return [r.result.get(timeout=600) for r in reqs]


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = make_parser().parse_args(argv)

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg, frontend = build_frontend(args)
    try:
        outs = serve_requests(args, cfg, frontend)
    finally:
        frontend.shutdown()
    print(json.dumps({
        "requests": len(outs),
        "batches_served": frontend.batches_served,
        "tokens_generated": int(sum(len(o) for o in outs)),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
