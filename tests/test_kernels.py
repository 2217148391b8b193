"""Pallas kernel validation: interpret-mode kernels vs pure-jnp oracles,
swept over shapes/dtypes, plus hypothesis property tests on the oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm as rmsnorm_kernel
from repro.kernels.rmsnorm import rmsnorm_residual
from repro.kernels.ssd_scan import ssd_scan


def _rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32) \
        .astype(dtype)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,T,H,K,D,causal,window",
    [
        (1, 32, 32, 4, 4, 16, True, 0),      # MHA causal
        (2, 64, 64, 4, 2, 32, True, 0),      # GQA causal
        (2, 48, 48, 6, 2, 16, False, 0),     # non-causal (encoder)
        (1, 64, 64, 4, 1, 16, True, 20),     # sliding window, MQA
        (2, 40, 40, 4, 4, 24, True, 0),      # non-pow2 seq and head_dim
        (1, 128, 128, 8, 8, 64, True, 48),   # bigger window
        (1, 1024, 1024, 14, 2, 64, True, 0),  # qwen2-0.5b heads, full+edge
        (2, 64, 64, 4, 2, 96, True, 0),      # head_dim 96 (phi-3-vision)
        (1, 128, 128, 4, 2, 128, True, 40),  # head_dim 128 with a window
        (2, 40, 40, 4, 2, 16, False, 0),     # non-causal, padded keys
        (1, 24, 56, 4, 2, 16, True, 0),      # q_offset 32 (serve prefill)
        (1, 24, 56, 4, 2, 16, True, 20),     # q_offset 32 with a window
    ])
def test_flash_matches_oracle(B, S, T, H, K, D, causal, window, dtype):
    q = _rand(0, (B, S, H, D), dtype)
    k = _rand(1, (B, T, K, D), dtype)
    v = _rand(2, (B, T, K, D), dtype)
    # long sequences run at 256 blocks, a 4 x 4 grid of causally skipped,
    # interior and diagonal blocks; fewer queries than keys are the last
    # S positions, at q_offset T - S
    block = 256 if S >= 1024 else 16
    q_offset = T - S
    out = flash_attention(q, k, v, causal=causal, window=window,
                          q_offset=q_offset, block_q=block, block_k=block,
                          interpret=True)
    q_pos = jnp.broadcast_to(q_offset + jnp.arange(S)[None, :], (B, S))
    expect = ref.mha(q, k, v, causal=causal, window=window, q_pos=q_pos)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [0, 100])
def test_flash_bf16_within_one_ulp(window):
    """bf16 operands go to the MXU as they are, with ``p`` split into a
    bf16 high and low part for ``PV``: the output is the exact attention
    of the bf16 inputs, rounded once to bf16."""
    q = _rand(0, (1, 1024, 4, 64), jnp.bfloat16)
    k = _rand(1, (1, 1024, 2, 64), jnp.bfloat16)
    v = _rand(2, (1, 1024, 2, 64), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True)
    expect = ref.mha(q.astype(jnp.float32), k.astype(jnp.float32),
                     v.astype(jnp.float32), causal=True, window=window)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect.astype(jnp.bfloat16),
                                          np.float32),
                               rtol=2 ** -7, atol=1e-3)


def test_flash_block_shape_invariance():
    q = _rand(0, (2, 64, 4, 32), jnp.float32)
    k = _rand(1, (2, 64, 2, 32), jnp.float32)
    v = _rand(2, (2, 64, 2, 32), jnp.float32)
    outs = [flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                            interpret=True)
            for bq, bk in [(8, 8), (16, 32), (64, 64), (32, 8)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5, rtol=1e-5)


def test_chunked_oracle_matches_full():
    q = _rand(0, (2, 100, 4, 16), jnp.float32)
    k = _rand(1, (2, 100, 2, 16), jnp.float32)
    v = _rand(2, (2, 100, 2, 16), jnp.float32)
    for window, sink in [(0, 0), (24, 0), (24, 4)]:
        full = ref.mha(q, k, v, causal=True, window=window, num_sink=sink)
        chunk = ref.mha_chunked(q, k, v, causal=True, window=window,
                                num_sink=sink, block_q=32)
        np.testing.assert_allclose(np.asarray(full), np.asarray(chunk),
                                   atol=1e-5, rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 3), st.integers(1, 24), st.integers(1, 4),
       st.integers(0, 1), st.booleans())
def test_attention_causality_property(b, s, k, g_extra, causal):
    """Property: output at position i never depends on tokens > i (causal)."""
    h = k * (1 + g_extra)
    q = _rand(3, (b, s, h, 8), jnp.float32)
    kk = _rand(4, (b, s, k, 8), jnp.float32)
    v = _rand(5, (b, s, k, 8), jnp.float32)
    out = ref.mha(q, kk, v, causal=causal)
    if causal and s > 1:
        # perturb the last token; all earlier outputs must be unchanged
        kk2 = kk.at[:, -1].add(10.0)
        v2 = v.at[:, -1].add(10.0)
        out2 = ref.mha(q, kk2, v2, causal=True)
        np.testing.assert_allclose(np.asarray(out[:, :-1]),
                                   np.asarray(out2[:, :-1]),
                                   atol=1e-5, rtol=1e-5)
    # rows are convex combos of V: bounded by V extrema
    assert float(jnp.max(jnp.abs(out))) <= float(jnp.max(jnp.abs(v))) + 1e-4


# --------------------------------------------------------------------------
# rmsnorm
# --------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(4, 128), (3, 7, 100), (1, 1, 1, 256),
                                   (5, 333)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_oracle(shape, dtype):
    x = _rand(0, shape, dtype)
    scale = _rand(1, shape[-1:], jnp.float32)
    out = rmsnorm_kernel(x, scale, interpret=True)
    expect = ref.rmsnorm(x, scale)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_rmsnorm_residual_fusion():
    x = _rand(0, (4, 37, 96), jnp.float32)
    res = _rand(1, (4, 37, 96), jnp.float32)
    scale = _rand(2, (96,), jnp.float32)
    normed, new_res = rmsnorm_residual(x, res, scale, interpret=True)
    np.testing.assert_allclose(np.asarray(new_res), np.asarray(x + res),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(normed),
                               np.asarray(ref.rmsnorm(x + res, scale)),
                               atol=1e-5, rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 8), st.integers(1, 300))
def test_rmsnorm_scale_property(rows, d):
    """rmsnorm(a*x) == rmsnorm(x) for positive scalar a (scale-invariant —
    up to the eps regularizer, so keep |x| well above sqrt(eps))."""
    x = jnp.abs(_rand(0, (rows, d), jnp.float32)) + 0.5
    s = jnp.ones((d,))
    a = 3.7
    np.testing.assert_allclose(np.asarray(ref.rmsnorm(a * x, s)),
                               np.asarray(ref.rmsnorm(x, s)),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# SSD scan
# --------------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,s,h,p,g,n,chunk",
    [
        (1, 32, 2, 8, 1, 4, 8),
        (2, 64, 4, 16, 2, 8, 16),
        (2, 64, 4, 16, 4, 8, 32),     # groups == heads
        (1, 96, 6, 8, 2, 16, 24),     # non-pow2 chunk
        (1, 64, 8, 8, 1, 4, 16),      # a block of 8 heads over 4 chunks
        (1, 48, 10, 8, 1, 8, 16),     # 10 heads in one group
        (2, 48, 12, 8, 2, 8, 16),     # 6 heads a group, two groups
        (1, 512, 6, 8, 1, 8, 256),    # chunk 256: its masked quarter skipped
    ])
def test_ssd_kernel_matches_naive(b, s, h, p, g, n, chunk):
    x = _rand(0, (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(_rand(1, (b, s, h), jnp.float32))
    A = -jnp.exp(_rand(2, (h,), jnp.float32) * 0.5)
    B = _rand(3, (b, s, g, n), jnp.float32)
    C = _rand(4, (b, s, g, n), jnp.float32)
    expect, _ = ref.ssd_naive(x, dt, A, B, C)
    kern = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    chunked, _ = ref.ssd_chunked(x, dt, A, B, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(expect),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(expect),
                               atol=1e-4, rtol=1e-3)


def _ssd_inputs(b, s, h, p, g, n, dtype=jnp.float32):
    return (_rand(0, (b, s, h, p), dtype),
            jax.nn.softplus(_rand(1, (b, s, h), jnp.float32)),
            -jnp.exp(_rand(2, (h,), jnp.float32) * 0.5),
            _rand(3, (b, s, g, n), dtype),
            _rand(4, (b, s, g, n), dtype))


def test_ssd_bf16_within_one_ulp():
    """bf16 x, B and C with f32 dt, as the model passes them: the output is
    the recurrence of the bf16 inputs, rounded once to bf16."""
    x, dt, A, B, C = _ssd_inputs(1, 128, 8, 16, 1, 32, jnp.bfloat16)
    out = ssd_scan(x, dt, A, B, C, chunk=32, interpret=True)
    expect, _ = ref.ssd_naive(x.astype(jnp.float32), dt, A,
                              B.astype(jnp.float32), C.astype(jnp.float32))
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect.astype(jnp.bfloat16),
                                          np.float32),
                               rtol=2 ** -7, atol=1e-3)


def test_ssd_kernel_head_blocks_within_group(monkeypatch):
    """A budget that fits 2 heads splits each group of 6 into three head
    blocks, which share their group's B and C."""
    from repro.kernels import ssd_scan as mod
    budget = mod._vmem_bytes(2, 8, 8, 16, 4)
    monkeypatch.setattr(mod, "_VMEM_BUDGET", budget)
    assert mod._head_block(12, 2, 8, 8, 16, 4) == 2
    x, dt, A, B, C = _ssd_inputs(1, 48, 12, 8, 2, 8)
    kern = mod.ssd_scan(x, dt, A, B, C, chunk=16, interpret=True)
    expect, _ = ref.ssd_naive(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(expect),
                               atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("h,n", [(64, 128), (48, 128), (50, 16)],
                         ids=["granite-4.0-h-micro", "mamba2-780m",
                              "hymba-1.5b"])
def test_ssd_head_block_fits_budget(h, n):
    """At each configuration's heads and state (one group, head dim 64,
    chunk 256, bf16), the head block divides the heads and is the largest
    divisor within the VMEM budget."""
    from repro.kernels import ssd_scan as mod
    hb = mod._head_block(h, 1, 64, n, 256, 2)
    assert h % hb == 0
    assert mod._vmem_bytes(hb, 64, n, 256, 2) <= mod._VMEM_BUDGET
    larger = [d for d in range(hb + 1, h + 1) if h % d == 0]
    assert all(mod._vmem_bytes(d, 64, n, 256, 2) > mod._VMEM_BUDGET
               for d in larger)
    assert hb >= 8


def test_ssd_decode_step_matches_scan():
    b, s, h, p, g, n = 2, 16, 2, 8, 1, 4
    x = _rand(0, (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(_rand(1, (b, s, h), jnp.float32))
    A = -jnp.exp(_rand(2, (h,), jnp.float32) * 0.5)
    B = _rand(3, (b, s, g, n), jnp.float32)
    C = _rand(4, (b, s, g, n), jnp.float32)
    y_full, final_state = ref.ssd_naive(x, dt, A, B, C)
    state = jnp.zeros((b, h, p, n), jnp.float32)
    ys = []
    for t in range(s):
        y, state = ref.ssd_step(state, x[:, t], dt[:, t], A, B[:, t], C[:, t])
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_full), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(state), np.asarray(final_state),
                               atol=1e-4, rtol=1e-3)


def test_ssd_chunked_vjp_finite_at_chunk_256():
    """At the published chunk of 256 a chunk's dt*A sums far past 88, where
    exp overflows in float32: the chunked form's VJP stays finite and is
    the sequential recurrence's."""
    b, s, h, p, g, n = 1, 512, 2, 8, 1, 4
    x = _rand(0, (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(_rand(1, (b, s, h), jnp.float32))
    A = -jnp.exp(_rand(2, (h,), jnp.float32) * 0.5)
    B = _rand(3, (b, s, g, n), jnp.float32)
    C = _rand(4, (b, s, g, n), jnp.float32)
    per_chunk = -(dt * A).reshape(b, s // 256, 256, h).sum(2)
    assert float(per_chunk.min()) > 88.0

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    argnums = tuple(range(5))
    got = jax.grad(loss(lambda *a: ref.ssd_chunked(*a, chunk=256)[0]),
                   argnums=argnums)(x, dt, A, B, C)
    expect = jax.grad(loss(lambda *a: ref.ssd_naive(*a)[0]),
                      argnums=argnums)(x, dt, A, B, C)
    for g_, e in zip(got, expect):
        assert bool(jnp.all(jnp.isfinite(g_)))
        np.testing.assert_allclose(np.asarray(g_), np.asarray(e),
                                   atol=1e-4, rtol=1e-3)


@settings(max_examples=15, deadline=None)
@given(st.integers(8, 48), st.integers(1, 3))
def test_ssd_chunk_invariance_property(s, b):
    """Property: chunked SSD is chunk-size invariant (same math)."""
    h, p, g, n = 2, 4, 1, 4
    s = (s // 8) * 8
    x = _rand(0, (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(_rand(1, (b, s, h), jnp.float32))
    A = -jnp.exp(_rand(2, (h,), jnp.float32) * 0.5)
    B = _rand(3, (b, s, g, n), jnp.float32)
    C = _rand(4, (b, s, g, n), jnp.float32)
    y8, st8 = ref.ssd_chunked(x, dt, A, B, C, chunk=8)
    y4, st4 = ref.ssd_chunked(x, dt, A, B, C, chunk=4)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y4),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st8), np.asarray(st4),
                               atol=1e-4, rtol=1e-3)


# --------------------------------------------------------------------------
# gradients: kernel forward + oracle backward (ref.oracle_vjp)
# --------------------------------------------------------------------------
def _grad_case(name):
    if name == "flash":
        args = (_rand(0, (2, 32, 4, 16), jnp.float32),
                _rand(1, (2, 32, 2, 16), jnp.float32),
                _rand(2, (2, 32, 2, 16), jnp.float32))
        return (args,
                lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                block_q=16, block_k=16,
                                                interpret=True),
                lambda q, k, v: ref.mha(q, k, v, causal=True))
    if name == "rmsnorm":
        args = (_rand(0, (3, 5, 40), jnp.float32),
                _rand(1, (40,), jnp.float32))
        return (args, lambda x, s: rmsnorm_kernel(x, s, interpret=True),
                lambda x, s: ref.rmsnorm(x, s))
    if name == "rmsnorm_residual":
        args = (_rand(0, (3, 5, 40), jnp.float32),
                _rand(1, (3, 5, 40), jnp.float32),
                _rand(2, (40,), jnp.float32))
        return (args,
                lambda x, r, s: rmsnorm_residual(x, r, s, interpret=True),
                lambda x, r, s: (ref.rmsnorm(x + r, s), x + r))
    b, s, h, p, g, n = 1, 32, 2, 8, 1, 4
    args = (_rand(0, (b, s, h, p), jnp.float32),
            jax.nn.softplus(_rand(1, (b, s, h), jnp.float32)),
            -jnp.exp(_rand(2, (h,), jnp.float32) * 0.5),
            _rand(3, (b, s, g, n), jnp.float32),
            _rand(4, (b, s, g, n), jnp.float32))
    return (args,
            lambda *a: ssd_scan(*a, chunk=8, interpret=True),
            lambda *a: ref.ssd_naive(*a)[0])


@pytest.mark.parametrize("name", ["flash", "rmsnorm", "rmsnorm_residual",
                                  "ssd"])
def test_kernel_grad_matches_oracle(name):
    """Every kernel is differentiable, and its gradient is the oracle's."""
    args, kernel, oracle = _grad_case(name)

    def loss(fn):
        def f(*a):
            out = fn(*a)
            outs = out if isinstance(out, tuple) else (out,)
            return sum(jnp.sum(jnp.sin(o)) for o in outs)
        return f

    argnums = tuple(range(len(args)))
    got = jax.grad(loss(kernel), argnums=argnums)(*args)
    expect = jax.grad(loss(oracle), argnums=argnums)(*args)
    for g, e in zip(got, expect):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   atol=1e-4, rtol=1e-3)
