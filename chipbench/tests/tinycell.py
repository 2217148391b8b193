"""A cell at a size a CPU test can run: the benchmark's own files, with
the configuration cut to a few tiny layers and a small in-memory mix."""
import copy
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import spec  # noqa: E402

TINY = {
    "qwen2-0.5b": ({"hidden_size": 64, "intermediate_size": 128,
                    "num_attention_heads": 4, "num_key_value_heads": 2,
                    "head_dim": 16, "num_hidden_layers": 2,
                    "vocab_size": 512},
                   {"global_batch": 2, "seq_len": 32}),
    "mamba2-780m": ({"d_model": 64, "n_layer": 2, "vocab_size": 512,
                     "d_state": 16, "headdim": 16, "chunk_size": 16},
                    {"global_batch": 2, "seq_len": 64}),
}
MIX = {"items": 2048, "order": "random", "order_seed": 0,
       "storage": {"kind": "memory"},
       "tuner": {"autotune": True, "cores": 2, "max_prefetch": 2,
                 "budget_batches": 4, "initial_workers": 2}}


def tiny_model(config: str):
    m = copy.deepcopy(spec.config(config))
    sizes, train = TINY[config]
    m.update(sizes)
    m["train"].update(train)
    return m


def program_computes_as_configured(monkeypatch, model):
    """The program's compute dtype as the configuration states it (the
    repository's other tests set float32 for the whole process)."""
    import jax.numpy as jnp
    from repro.models import layers
    monkeypatch.setattr(layers, "COMPUTE_DTYPE",
                        jnp.dtype(model["train"]["compute_dtype"]))


def run_tiny(monkeypatch, config="qwen2-0.5b", *, seed=2**31 + 5,
             seconds=0.5, plant=None):
    """One CPU run of a tiny cell through ``cell.run_cell``, with the
    harness's look for a chip skipped."""
    import jax
    import cell
    jax.config.update("jax_enable_compilation_cache", False)
    model = tiny_model(config)
    program_computes_as_configured(monkeypatch, model)
    bench = copy.deepcopy(spec.benchmark())
    bench["workloads"].append({"name": "tiny", "config": config,
                               "traffic": "tiny-mix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny")
    monkeypatch.setattr(spec, "config", lambda name: model)
    monkeypatch.setattr(spec, "traffic", lambda name: dict(MIX))
    return cell.run_cell("tiny", seed, seconds, False,
                         t_start=time.perf_counter(), bench=bench,
                         require_chip=False, plant=plant)
