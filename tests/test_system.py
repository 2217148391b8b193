"""End-to-end behaviour tests: DPT-tuned training on a latency-injected
storage, restart-after-crash, and the full serve path — the system acting
as the paper + framework promises."""
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.monitor import MemoryBudget
from repro.data import (DataLoader, Dataset, LatencyStorage, LoaderParams,
                        token_dataset)
from repro.models import build_model
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import TrainStepConfig
from repro.train.trainer import Trainer, TrainerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_end_to_end_dpt_tuned_training(tmp_path):
    """The headline integration: loader tuned by DPT (real wall-clock
    measurements on latency-injected storage) feeding a real train loop,
    with checkpointing; loss decreases and tuned params beat 0 workers."""
    cfg = reduced(get_config("qwen2-0.5b"))
    model = build_model(cfg)

    base = token_dataset(96, 16, cfg.vocab_size, seed=0)
    lat = LatencyStorage(base.storage, latency_s=1e-3, bandwidth=1e9)
    ds = Dataset(lat, transform=base.transform)
    dl = DataLoader(ds, 8, seed=0)

    tc = TrainerConfig(
        total_steps=36, checkpoint_every=18, log_every=6,
        checkpoint_dir=str(tmp_path / "ckpt"),
        autotune=True, autotune_budget_batches=4, autotune_max_prefetch=2,
        dpt_cache_path=str(tmp_path / "dpt.json"),
        step_config=TrainStepConfig(
            remat_policy="none",
            optimizer=AdamWConfig(peak_lr=3e-3, warmup_steps=2,
                                  total_steps=36)))
    tr = Trainer(model, dl, tc)
    out = tr.run()
    assert out["final_step"] == 36
    assert out["loss"] < 5.4   # memorizing the 96-item set (ln(256)=5.545 at init)
    assert dl.params.num_workers >= 1  # DPT chose parallel loading

    # crash-restart: a new trainer resumes from the checkpoint
    dl2 = DataLoader(ds, 8, seed=0)
    tr2 = Trainer(model, dl2, tc)
    tr2._maybe_restore()
    assert tr2.start_step == 36


def test_serve_end_to_end():
    cfg = reduced(get_config("qwen3-1.7b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    from repro.serve.engine import ServeEngine
    eng = ServeEngine(model, params, max_batch=2, max_len=48)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    res = eng.generate(prompts, 8)
    assert res.tokens.shape == (2, 8)
    assert res.tokens_per_second > 0


def test_launchers_run(tmp_path):
    """The CLI entry points work end to end (reduced configs)."""
    import subprocess, sys, json
    # the launchers turn JAX's persistent compile cache on; the suite
    # keeps it off
    env = dict(os.environ, PYTHONPATH="src", REPRO_COMPUTE_DTYPE="float32",
               JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "mamba2-780m",
         "--reduced", "--steps", "6", "--global-batch", "4",
         "--seq-len", "32", "--no-autotune",
         "--checkpoint-dir", str(tmp_path / "ck")],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["final_step"] == 6

    r2 = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "qwen2-0.5b",
         "--reduced", "--requests", "4", "--prompt-len", "8",
         "--max-new", "4", "--max-batch", "2"],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env=env)
    assert r2.returncode == 0, r2.stderr[-2000:]
    out2 = json.loads(r2.stdout.strip().splitlines()[-1])
    assert out2["requests"] == 4


_CACHE_PROBE = """
import json, os, jax, jax.numpy as jnp
from repro.utils.compile_cache import REPO_CACHE_DIR, enable_compile_cache
where = enable_compile_cache()
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
print(json.dumps({"where": where, "repo": REPO_CACHE_DIR,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "repo"])
def test_compile_cache_location(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, where set, is the only cache; unset, the
    cache is the checkout's fixed .jax_cache."""
    import json, subprocess, sys
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("JAX_COMPILATION_CACHE")}
    env.update(PYTHONPATH="src", JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    cache = tmp_path / "cache"
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    repo_cache = os.path.join(REPO, ".jax_cache")
    before = sorted(os.listdir(repo_cache)) if os.path.isdir(repo_cache) \
        else None
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                       capture_output=True, text=True, timeout=300, cwd=REPO,
                       env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["repo"] == repo_cache
    if env_dir:
        assert out["where"] == out["config"] == str(cache)
        assert os.listdir(cache), "nothing was cached"
        after = sorted(os.listdir(repo_cache)) \
            if os.path.isdir(repo_cache) else None
        assert after == before
    else:
        assert out["where"] == out["config"] == repo_cache
