"""The main path's Pallas kernels compile for a TPU v5e chip, forward and
backward, at the widths the chip smoke runs (qwen2-0.5b attention and
norms; mamba2-780m's SSD scan).

Nothing runs: the TPU compiler builds each program for a described (not
attached) v5e:2x2 topology, and each test asserts that the compiled HLO
calls the Pallas kernel (``tpu_custom_call``).  The topology is described
inside a fixture, never while a module is imported, and the persistent
compilation cache is off around the compiles (a chip-less process cannot
read back what it would write).
"""
import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm, rmsnorm_residual
from repro.kernels.ssd_scan import ssd_scan


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _grad_of_sum(fn, argnums):
    """Value and gradient of sum(fn): the value keeps the forward kernel
    live (the gradient alone needs only the backward)."""
    def loss(*args):
        out = fn(*args)
        outs = out if isinstance(out, tuple) else (out,)
        return sum(jnp.sum(o.astype(jnp.float32)) for o in outs)
    return jax.value_and_grad(loss, argnums=argnums)


# qwen2-0.5b: 14 query heads, 2 kv heads of dim 64, d_model 896
_QWEN_ATTN = [((4, 1024, 14, 64), jnp.bfloat16),
              ((4, 1024, 2, 64), jnp.bfloat16),
              ((4, 1024, 2, 64), jnp.bfloat16)]
_QWEN_NORM = [((4, 1024, 896), jnp.bfloat16), ((896,), jnp.float32)]
_QWEN_NORM_RES = [((4, 1024, 896), jnp.bfloat16),
                  ((4, 1024, 896), jnp.bfloat16), ((896,), jnp.float32)]
# mamba2-780m: 48 heads of dim 64, state 128, chunk 256
_MAMBA_SSD = [((1, 1024, 48, 64), jnp.bfloat16), ((1, 1024, 48), jnp.float32),
              ((48,), jnp.float32), ((1, 1024, 1, 128), jnp.bfloat16),
              ((1, 1024, 1, 128), jnp.bfloat16)]
# granite-4.0-h-micro's cell: 64 heads of dim 64, state 128, one group,
# 4096 tokens in chunks of 256
_GRANITE_SSD = [((1, 4096, 64, 64), jnp.bfloat16),
                ((1, 4096, 64), jnp.float32), ((64,), jnp.float32),
                ((1, 4096, 1, 128), jnp.bfloat16),
                ((1, 4096, 1, 128), jnp.bfloat16)]


_CHIPBENCH = Path(__file__).resolve().parents[1] / "chipbench"


def _bench_module(name, rel):
    spec = importlib.util.spec_from_file_location(name, _CHIPBENCH / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_hlo():
    """The benchmark's reader of Pallas calls in compiled HLO."""
    return _bench_module("chipbench_hlo", "hlo.py")


def _flash_calls(text):
    return [c for c in _bench_hlo().pallas_calls(text)
            if c["kernel"] == "flash_attention"]


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_attention_compiles_for_v5e(one_chip, grad):
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True)  # noqa: E731
    if grad:
        fn = _grad_of_sum(fn, (0, 1, 2))
    text = _compile_text(fn, one_chip, *_QWEN_ATTN)
    assert "tpu_custom_call" in text
    if not grad:
        # one forward call, read by the benchmark's roofline at the
        # published head dim: q (B, H, S, D) and k (B, K, T, D), unpadded
        (call,) = _flash_calls(text)
        assert call["operands"][0] == {"dtype": "bf16",
                                       "shape": (4, 14, 1024, 64)}
        assert call["operands"][1] == {"dtype": "bf16",
                                       "shape": (4, 2, 1024, 64)}


# head dims 96 (phi-3-vision) and 128 with a sliding window (mixtral):
# full-dim blocks at every registered width
@pytest.mark.parametrize("shapes,window", [
    ([((1, 2048, 32, 96), jnp.bfloat16)] * 3, 0),
    ([((1, 4096, 48, 128), jnp.bfloat16),
      ((1, 4096, 8, 128), jnp.bfloat16),
      ((1, 4096, 8, 128), jnp.bfloat16)], 1024),
], ids=["d96", "d128_window"])
def test_flash_attention_head_dims_compile_for_v5e(one_chip, shapes, window):
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True,  # noqa: E731
                                         window=window)
    (call,) = _flash_calls(_compile_text(fn, one_chip, *shapes))
    assert call["operands"][0]["shape"][-1] == shapes[0][0][-1]


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_rmsnorm_compiles_for_v5e(one_chip, grad):
    fn = lambda x, s: rmsnorm(x, s, eps=1e-6)  # noqa: E731
    if grad:
        fn = _grad_of_sum(fn, (0, 1))
    assert "tpu_custom_call" in _compile_text(fn, one_chip, *_QWEN_NORM)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_rmsnorm_residual_compiles_for_v5e(one_chip, grad):
    fn = lambda x, r, s: rmsnorm_residual(x, r, s, eps=1e-6)  # noqa: E731
    if grad:
        fn = _grad_of_sum(fn, (0, 1, 2))
    assert "tpu_custom_call" in _compile_text(fn, one_chip, *_QWEN_NORM_RES)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_ssd_scan_compiles_for_v5e(one_chip, grad):
    fn = lambda x, dt, a, b, c: ssd_scan(x, dt, a, b, c,  # noqa: E731
                                         chunk=256)
    if grad:
        fn = _grad_of_sum(fn, (0, 1, 2, 3, 4))
    assert "tpu_custom_call" in _compile_text(fn, one_chip, *_MAMBA_SSD)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_ssd_scan_granite_compiles_for_v5e(one_chip, monkeypatch, grad):
    """At granite-4.0-h-micro's shapes the one ``ssd_scan`` call keeps the
    operands the benchmark's count reads (``x`` and ``B`` by position,
    every operand's bytes), so its operations and bytes are the parent's;
    the forward needs no ``dt`` padded to a 128-lane tile per element (the
    parent's temporaries: 201,455,616 bytes)."""
    def fn(x, dt, a, b, c):
        # under the model's scope, as the step calls it: the scope keeps
        # ``jit(ssd_scan)/pallas_call`` in the op name under ``jvp``
        with jax.named_scope("mixer.mamba"):
            return ssd_scan(x, dt, a, b, c, chunk=256)

    if grad:
        fn = _grad_of_sum(fn, (0, 1, 2, 3, 4))
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in _GRANITE_SSD]
    compiled = jax.jit(fn).lower(*args).compile()
    (call,) = [c for c in _bench_hlo().pallas_calls(compiled.as_text())
               if c["kernel"] == "ssd_scan"]
    x, _dt, _a, bm, cm = call["operands"]
    assert x == {"dtype": "bf16", "shape": (1, 64, 4096, 64)}
    assert bm == cm == {"dtype": "bf16", "shape": (1, 1, 4096, 128)}
    assert call["result"] == [{"dtype": "bf16", "shape": (1, 64, 4096, 64)}]
    monkeypatch.syspath_prepend(str(_CHIPBENCH))
    count = _bench_module("chipbench_ssd_scan", "kernels/ssd_scan.py")
    assert count.cost(call, {"chunk_size": 256}) == (13_036_421_120,
                                                     70_254_848)
    if not grad:
        assert compiled.memory_analysis().temp_size_in_bytes < 201_455_616
