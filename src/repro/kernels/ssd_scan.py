"""Mamba2 SSD (state-space duality) chunked-scan Pallas TPU kernel.

TPU adaptation of the SSD algorithm (paper: arXiv 2405.21060, GPU Triton
kernels): the sequence is split into chunks; within a chunk the dual
(quadratic, MXU-friendly) form computes the causal-decay-masked C B^T x
contribution as two small matmuls, and the recurrent inter-chunk state is
carried in VMEM scratch across the innermost grid dimension (TPU grids are
sequential, so the (P x N) state simply persists between chunk steps — the
TPU analogue of the GPU kernel's cross-CTA state passing).

Grid: (batch, heads, num_chunks); the state scratch is reset at chunk 0.
Layout for Mosaic's (8, 128) tiling: ``dt`` carries a trailing unit dim so
its block is (chunk, 1); ``A`` sits whole in SMEM and is read by head id;
the within-chunk cumulative sum is a lower-triangular-ones matmul (Pallas
TPU has no cumsum lowering).
Oracles: ``ref.ssd_chunked`` (same chunked math) and ``ref.ssd_naive``
(sequential recurrence ground truth).

The backward pass is XLA's: the VJP of ``ref.ssd_chunked``, recomputed
(``ref.oracle_vjp``), until a Pallas backward lands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, state_scr):
    ih = pl.program_id(1)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)        # (c, p)
    dt = dt_ref[0, 0].astype(jnp.float32)      # (c, 1)
    a = a_ref[ih]                              # scalar (SMEM)
    bm = b_ref[0, 0].astype(jnp.float32)       # (c, n)
    cm = c_ref[0, 0].astype(jnp.float32)       # (c, n)
    c_len, n = bm.shape

    ii = jax.lax.broadcasted_iota(jnp.int32, (c_len, c_len), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c_len, c_len), 1)
    causal = jj <= ii
    # inclusive cumsum of dt*A as tril(ones) @ da, with da broadcast over
    # the state lanes: every column of cum_n is the cumsum, its last row
    # the chunk total, and its transpose lays the cumsum along a row.
    da = jnp.broadcast_to(dt * a, (c_len, n))
    cum_n = jax.lax.dot_general(causal.astype(jnp.float32), da,
                                (((1,), (0,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
    cum = cum_n[:, 0:1]                                     # (c, 1)
    cum_row = cum_n.T[0:1, :]                               # (1, c)
    total = cum_n[c_len - 1:c_len, :]                       # (1, n)

    L = jnp.where(causal, jnp.exp(cum - cum_row), 0.0)      # (c, c)

    xdt = x * dt                                            # (c, p)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * L
    y_intra = jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    state = state_scr[...]                                  # (p, n)
    c_exp = cm * jnp.exp(cum_n)                             # (c, n)
    y_inter = jax.lax.dot_general(c_exp, state, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    tail = jnp.exp(total - cum_n)                           # (c, n)
    new_state = jax.lax.dot_general(xdt, bm * tail,
                                    (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    state_scr[...] = state * jnp.exp(total) + new_state

    o_ref[0, 0] = (y_intra + y_inter).astype(o_ref.dtype)


def _ssd_fwd(x, dt, A, B, C, *, chunk: int, interpret: bool):
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    nc = s // chunk
    group = h // g

    xt = jnp.moveaxis(x, 2, 1)                 # (b, h, s, p)
    dtt = jnp.moveaxis(dt, 2, 1)[..., None]    # (b, h, s, 1)
    bt = jnp.moveaxis(B, 2, 1)                 # (b, g, s, n)
    ct = jnp.moveaxis(C, 2, 1)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    out = pl.pallas_call(
        _kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda ib, ih, ic: (ib, ih // group, ic, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda ib, ih, ic: (ib, ih // group, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda ib, ih, ic: (ib, ih, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        **params,
    )(xt, dtt, A.astype(jnp.float32), bt, ct)
    return jnp.moveaxis(out, 1, 2)


def _ssd_oracle(x, dt, A, B, C, *, chunk: int):
    return ref.ssd_chunked(x, dt, A, B, C, chunk=chunk)[0]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 256, interpret: bool = False):
    """Chunked SSD scan.

    x: (b, s, h, p); dt: (b, s, h); A: (h,); B, C: (b, s, g, n), h % g == 0.
    Returns y: (b, s, h, p).  Sequence length must be a multiple of ``chunk``
    (the wrapper in ops.py pads).  Differentiable; the backward is XLA's
    (VJP of ``ref.ssd_chunked``) until a Pallas backward lands.
    """
    assert x.shape[1] % chunk == 0, (x.shape[1], chunk)
    kernel = functools.partial(_ssd_fwd, chunk=chunk, interpret=interpret)
    oracle = functools.partial(_ssd_oracle, chunk=chunk)
    return ref.oracle_vjp(kernel, oracle)(x, dt, A, B, C)
