"""Manual-DP runtime (distributed/dp_shard.py): numerical equivalence of the
shard_map train/serve paths against the single-device reference, plus the
regression repro for the XLA partitioner crash the gathers work around.

Subprocess tests: the 8-device mesh needs XLA_FLAGS set before jax init.
"""
import subprocess
import sys
import textwrap

import pytest

PRE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.configs.base import get_config, reduced
from repro.distributed.sharding_rules import (rules_for, use_rules,
                                              params_shardings, batch_shardings)
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.train.train_step import TrainState, TrainStepConfig, make_train_step
from repro.train.optimizer import init_adamw

def make_batch(cfg, B, S, seed=0):
    r = np.random.default_rng(seed)
    b = {"tokens": jnp.asarray(r.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
         "targets": jnp.asarray(r.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
         "loss_mask": jnp.ones((B, S), jnp.float32)}
    return b
"""


def run_py(code: str, timeout=560):
    r = subprocess.run([sys.executable, "-c", PRE + textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       cwd=__file__.rsplit("/", 2)[0])
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    return r.stdout


@pytest.mark.parametrize("arch,impl", [
    pytest.param("qwen2-0.5b", "", id="qwen2-0.5b"),
    pytest.param("granite-moe-3b-a800m", "", id="granite-moe-3b-a800m"),
    # the Pallas kernels (interpreted) under the mesh: each runs per shard
    # of the still-automatic 'model' axis (kernels/ops.py)
    pytest.param("qwen2-0.5b", "pallas_interpret", id="qwen2-0.5b-pallas"),
])
def test_manual_train_step_matches_single_device(arch, impl):
    """One manual-DP train step on a (2,2,2) mesh == one single-device step
    (max param diff < 5e-3, driven by bf16 layout differences)."""
    out = run_py(f"""
    if {impl!r}:
        os.environ["REPRO_KERNEL_IMPL"] = {impl!r}
    cfg = reduced(get_config({arch!r}))
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    B, S = 16, 32
    batch = make_batch(cfg, B, S)
    scfg = TrainStepConfig(remat_policy="dots", microbatches=2)

    params = model.init(rng)
    state = TrainState(params, init_adamw(params), None)
    ref_state, ref_metrics = jax.jit(make_train_step(model, scfg))(state, batch)
    ref = jax.device_get(ref_state.params)

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    import dataclasses
    scfg = dataclasses.replace(scfg, dp_manual=True)
    with use_rules(mesh, rules_for("train")) as ctx:
        params = model.init(rng)
        params = jax.device_put(params, params_shardings(model, ctx))
        state = TrainState(params, init_adamw(params), None)
        batch_d = jax.device_put(batch, batch_shardings(batch, ctx))
        new_state, metrics = jax.jit(make_train_step(model, scfg))(state, batch_d)
    got = jax.device_get(new_state.params)

    worst = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                    - np.asarray(b, np.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(ref),
                                jax.tree_util.tree_leaves(got)))
    rel_loss = abs(float(ref_metrics["loss"]) - float(metrics["loss"]))
    print("worst", worst, "dloss", rel_loss)
    assert worst < 5e-3, worst
    assert rel_loss < 0.02 * float(ref_metrics["loss"])
    assert abs(float(ref_metrics["grad_norm"]) - float(metrics["grad_norm"])) < 5e-3
    """)
    assert "worst" in out


def test_serve_prefill_decode_match_single_device():
    """Manual-wrapped prefill+decode logits == single-device logits."""
    run_py("""
    from repro.launch.dryrun import _serve_wrap
    cfg = reduced(get_config("granite-moe-3b-a800m"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 8, 16
    r = np.random.default_rng(0)
    tokens = jnp.asarray(r.integers(0, cfg.vocab_size, (B, S)), jnp.int32)

    cache = model.init_cache(B, S + 4)
    ref_logits, ref_cache = jax.jit(model.prefill)(
        params, {"tokens": tokens}, cache)
    ref_dec, _ = jax.jit(model.decode_step)(
        params, ref_cache, tokens[:, :1], jnp.full((B,), S, jnp.int32))

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    with use_rules(mesh, rules_for("prefill")) as ctx:
        wrapped = _serve_wrap(model, cfg, ctx, model.prefill)
        assert wrapped is not None
        logits, cache2 = jax.jit(wrapped)(
            params, {"tokens": tokens}, model.init_cache(B, S + 4))
        dec_w = _serve_wrap(model, cfg, ctx,
                            lambda p, b, c: model.decode_step(
                                p, c, b["tokens"], b["positions"]))
        dec, _ = jax.jit(dec_w)(
            params, {"tokens": tokens[:, :1],
                     "positions": jnp.full((B,), S, jnp.int32)}, cache2)
    d1 = float(jnp.max(jnp.abs(ref_logits.astype(jnp.float32)
                               - logits.astype(jnp.float32))))
    d2 = float(jnp.max(jnp.abs(ref_dec.astype(jnp.float32)
                               - dec.astype(jnp.float32))))
    print("prefill diff", d1, "decode diff", d2)
    assert d1 < 0.05 and d2 < 0.05, (d1, d2)
    """)


def test_cast_gather_partitioner_crash_workaround():
    """Regression: differentiating convert->all_gather under a partial-manual
    mesh aborts XLA ("Invalid binary instruction opcode copy"); the
    fully-manual inner-wrap used by dp_shard.gather_leaf must not."""
    run_py("""
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.distributed.dp_shard import gather_leaf
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    D, F, B = 8, 8, 8
    w = jax.device_put(jnp.arange(float(D * F)).reshape(D, F) / 10,
                       NamedSharding(mesh, P("data", None)))
    x = jax.device_put(jnp.ones((B, D)),
                       NamedSharding(mesh, P(("pod", "data"), None)))

    def dp_body(w_loc, xb):
        def loss_fn(wl, mb):
            g = gather_leaf(wl, {0: ("data",)}, dtype=jnp.bfloat16,
                            wrap_axes=("model",))
            y = mb.astype(jnp.bfloat16) @ g
            return jnp.sum(y.astype(jnp.float32) ** 2)
        def body(acc, mb):
            return jax.tree.map(jnp.add, acc,
                                jax.grad(loss_fn)(w_loc, mb)), None
        acc, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros_like(w_loc),
                              xb.reshape(2, -1, D))
        return jax.lax.psum(acc, ("pod",))

    out = jax.jit(jax.shard_map(
        dp_body, mesh=mesh,
        in_specs=(P("data", None), P(("pod", "data"), None)),
        out_specs=P("data", None), axis_names={"pod", "data"},
        check_vma=False))(w, x)
    assert out.shape == (D, F)
    print("gather-under-grad OK")
    """)
