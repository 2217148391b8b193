"""Distributed machinery: sharding rules, fault tolerance, elastic planning,
collective matmul + multi-device equivalence (subprocess with 8 CPU devs)."""
import os
import subprocess
import sys
import textwrap

import pytest
from _hypothesis_shim import given, settings, st

from repro.distributed.fault_tolerance import (FailureInjector,
                                               HeartbeatRegistry,
                                               StragglerDetector, plan_remesh)


# --------------------------------------------------------------------------
# sharding rules (no devices needed: pure PartitionSpec logic)
# --------------------------------------------------------------------------
def _ctx(shape=(2, 16, 16), axes=("pod", "data", "model")):
    from repro.distributed.sharding_rules import ShardingCtx, TRAIN_RULES

    class FakeMesh:
        def __init__(self):
            self.shape = dict(zip(axes, shape))
    return ShardingCtx(FakeMesh(), TRAIN_RULES)


def test_partition_spec_basic():
    ctx = _ctx()
    p = ctx.partition_spec(("batch", None), (256, 4096))
    assert p == __import__("jax").sharding.PartitionSpec(("pod", "data"))


def test_partition_spec_divisibility_guard():
    ctx = _ctx()
    # vocab 49155 (granite) is not divisible by 16 -> axis dropped
    p = ctx.partition_spec(("vocab", "embed"), (49155, 1536))
    assert p[0] is None
    assert ("vocab", "model", 49155) in [tuple(d) for d in ctx.dropped]


def test_partition_spec_no_axis_reuse():
    ctx = _ctx()
    # both logical axes map to "model": second one must not reuse it
    p = ctx.partition_spec(("mlp", "vocab"), (1024, 1024))
    used = [e for e in p if e is not None]
    flat = []
    for e in used:
        flat.extend(e if isinstance(e, tuple) else [e])
    assert len(flat) == len(set(flat))


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 8).map(lambda k: 2 ** k),
       st.sampled_from(["vocab", "mlp", "heads", "embed", "batch"]),
       st.integers(1, 3))
def test_partition_spec_always_divides_property(dim_scale, axis, rank):
    """Property: every sharded dim is divisible by its shard count."""
    import numpy as np
    ctx = _ctx()
    dims = tuple(dim_scale * (i + 1) for i in range(rank))
    axes = (axis,) + (None,) * (rank - 1)
    p = ctx.partition_spec(axes, dims)
    entry = p[0] if len(p) > 0 else None
    if entry is not None:
        names = entry if isinstance(entry, tuple) else (entry,)
        shards = int(np.prod([ctx.mesh.shape[n] for n in names]))
        assert dims[0] % shards == 0


# --------------------------------------------------------------------------
# fault tolerance
# --------------------------------------------------------------------------
def test_heartbeat_detects_dead_host():
    t = [0.0]
    reg = HeartbeatRegistry(timeout_s=10, clock=lambda: t[0])
    reg.beat("a")
    reg.beat("b")
    t[0] = 5.0
    reg.beat("a")
    t[0] = 12.0
    assert reg.dead_hosts() == ["b"]
    assert reg.alive_hosts() == ["a"]


def test_straggler_detector_flags_slow_host():
    det = StragglerDetector(window=8, threshold=1.5)
    for _ in range(8):
        for h in ("a", "b", "c", "d"):
            det.record(h, 1.0 if h != "c" else 2.0)
    assert det.stragglers() == ["c"]


def test_straggler_detector_needs_data():
    det = StragglerDetector()
    det.record("a", 1.0)
    assert det.stragglers() == []


def test_elastic_plan_keeps_model_axis():
    plan = plan_remesh(alive_hosts=30, devices_per_host=8, model_axis=16,
                       old_hosts=32, old_global_batch=256, restore_step=100)
    assert plan.feasible
    assert plan.new_data_axis == 15
    assert plan.new_global_batch == 240      # per-replica batch preserved
    bad = plan_remesh(alive_hosts=3, devices_per_host=8, model_axis=16,
                      old_hosts=32, old_global_batch=256, restore_step=100)
    assert not bad.feasible


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 64), st.integers(1, 64), st.integers(0, 8))
def test_elastic_plan_batch_scaling_property(old_hosts, alive, dphost_pow):
    """Property: per-data-replica batch is invariant under feasible plans."""
    devices_per_host = 2 ** (dphost_pow % 4)
    model_axis = 4
    gb = max(4, old_hosts * devices_per_host // model_axis * 4)
    plan = plan_remesh(alive_hosts=alive, devices_per_host=devices_per_host,
                       model_axis=model_axis, old_hosts=old_hosts,
                       old_global_batch=gb, restore_step=None)
    if plan.feasible:
        old_data = max(1, old_hosts * devices_per_host // model_axis)
        assert abs(plan.new_global_batch / plan.new_data_axis
                   - gb / old_data) < 1.0


def test_failure_injector_schedule():
    inj = FailureInjector({3: ["h1"], 7: ["h2", "h3"]})
    assert inj.advance(1) == []
    assert inj.advance(3) == ["h1"]
    assert inj.advance(7) == ["h2", "h3"]
    assert inj.dead == {"h1", "h2", "h3"}


# --------------------------------------------------------------------------
# multi-device equivalence (subprocess: 8 CPU devices)
# --------------------------------------------------------------------------
def _run_subprocess(code: str):
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
           "REPRO_COMPUTE_DTYPE": "float32",
           "JAX_PLATFORMS": "cpu", "HOME": os.environ.get("HOME", "/")}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


@pytest.mark.slow
def test_ring_weight_matmul_equals_dot():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.distributed.collective_matmul import ring_weight_matmul
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ('model',))
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
        w = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
        with mesh:
            out = ring_weight_matmul(x, w, mesh)
        err = float(jnp.abs(out - jnp.dot(x, w)).max())
        assert err < 1e-4, err
        print('OK', err)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_loss_equals_unsharded():
    """The same model code under mesh+rules (with GQA head padding) must
    produce the identical loss as the single-device run."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.models import build_model
        from repro.distributed.sharding_rules import use_rules, rules_for
        from repro.launch.mesh import make_mesh
        for arch in ['qwen2-0.5b', 'granite-moe-3b-a800m', 'mamba2-780m',
                     'hymba-1.5b']:
            cfg = reduced(get_config(arch))
            m = build_model(cfg)
            params = m.init(jax.random.PRNGKey(0))
            rng = np.random.default_rng(0)
            toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32))
                               .astype(np.int32))
            batch = {'tokens': toks, 'targets': toks,
                     'loss_mask': jnp.ones((8, 32), jnp.float32)}
            ref, _ = m.loss(params, batch, remat_policy='none')
            mesh = make_mesh((2, 4), ('data', 'model'))
            with use_rules(mesh, rules_for('train')):
                sh, _ = jax.jit(lambda p, b: m.loss(
                    p, b, remat_policy='none'))(params, batch)
            d = abs(float(ref) - float(sh))
            assert d < 2e-3, (arch, d)
            print('OK', arch, d)
    """)
    assert out.count("OK") == 4


@pytest.mark.slow
def test_compressed_psum_in_shard_map():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distributed.grad_compress import compressed_psum
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ('data',))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        err0 = jnp.zeros((8, 64))

        def body(gl, el):
            mean, new_err = compressed_psum(gl[0], el[0], 'data')
            return mean[None], new_err[None]

        with mesh:
            mean, err = jax.shard_map(body, mesh=mesh,
                                  in_specs=(P('data'), P('data')),
                                  out_specs=(P('data'), P('data')))(g, err0)
        true_mean = g.mean(0)
        got = mean[0]
        err_ = float(jnp.abs(got - true_mean).max())
        # int8 channel: error bounded by one quantization bin
        bin_ = float(jnp.abs(g).max()) / 127
        assert err_ <= bin_ + 1e-6, (err_, bin_)
        print('OK', err_)
    """)
    assert "OK" in out
