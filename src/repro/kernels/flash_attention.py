"""Blockwise (flash) attention Pallas TPU kernel.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
* The grid's innermost dimension is executed sequentially on a TPU core, so
  the online-softmax running state (m, l, acc) lives in VMEM scratch that
  persists across KV-block grid steps — no shared-memory/warp machinery.
  The row statistics m and l are kept as (block_q, 1) columns, the layout
  the row reductions produce and the (block_q, head_dim) accumulator
  broadcasts from; a (block_q,) vector would be relaid from lanes to
  sublanes on every step.
* Block shapes are block_q x head_dim and block_k x head_dim at the
  published head dim: a block's last dim equals the array's, so 64, 96 and
  128 need no padding.  Only the sequence dims are padded to block
  multiples.
* The MXU gets the inputs' own dtype.  ``QK^T`` of bf16 q and k is exact in
  the f32 accumulator.  For ``PV`` with a v narrower than f32, the f32
  probabilities p go in as two parts in v's dtype, ``p_hi = bf16(p)`` and
  ``p_lo = bf16(p - p_hi)``: one bf16 part alone keeps p to 2^-8 relative,
  the pair to about 2^-16, for a second MXU pass.  f32 inputs go in as
  they are, at the default matmul precision, which on a v5e is one bf16
  pass: an f32 configuration's p (and its q, k, v) keep about 2^-8, less
  than the bf16 path's hi/lo p.
* GQA is native: the kv-head index map folds the query-head -> kv-head
  mapping, so grouped heads never materialize repeated K/V.
* Causal + sliding-window masking is positional.  Fully-masked KV blocks
  are skipped via ``pl.when`` (halves work for causal, much more for SWA).
  Every computed block builds the mask that its call needs (causal,
  window, padded keys); a non-causal call without a window or padding
  builds none.

Validated in interpret mode against ``ref.mha`` (see tests/test_kernels.py).

The backward pass is XLA's: the VJP of ``ref.mha_chunked`` (the same
blockwise math), recomputed (``ref.oracle_vjp``), until a Pallas backward
lands.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

NEG_INF = -1e30
_QK = (((1,), (1,)), ((), ()))
_PV = (((1,), (0,)), ((), ()))


def _pv(p, v):
    """``p @ v`` in f32; for a v narrower than f32, p as a hi/lo pair."""
    if v.dtype == jnp.float32:
        return jax.lax.dot_general(p, v, _PV,
                                   preferred_element_type=jnp.float32)
    p_hi = p.astype(v.dtype)
    p_lo = (p - p_hi.astype(jnp.float32)).astype(v.dtype)
    return (jax.lax.dot_general(p_hi, v, _PV,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(p_lo, v, _PV,
                                  preferred_element_type=jnp.float32))


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, block_q: int,
            block_k: int, num_kv_blocks: int, kv_len: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q + q_offset          # absolute position of q row 0
    kv_start = ik * block_k

    run = jnp.bool_(True)
    if causal:
        run &= kv_start <= q_start + block_q - 1
    if window > 0:
        run &= kv_start + block_k - 1 > q_start - window

    @pl.when(run)
    def _compute():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(q, k, _QK,
                                preferred_element_type=jnp.float32) * scale
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # kv_pos - q_pos = rel - off
        rel, off = col - row, q_start - kv_start
        keep = []
        if causal:
            keep.append(rel <= off)
        if window > 0:
            keep.append(rel > off - window)
        if kv_len % block_k:
            keep.append(col < kv_len - kv_start)                # pad keys
        if keep:
            # -inf, not NEG_INF: exp(-inf - m) is 0 for the finite m the
            # running max starts from, so p needs no second mask
            s = jnp.where(functools.reduce(jnp.logical_and, keep), s,
                          -jnp.inf)

        m_prev = m_scr[...]                                     # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + _pv(p, v)
        m_scr[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _pad_to(x, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _flash_fwd(q, k, v, *, causal: bool, window: int, q_offset: int,
               scale: float, block_q: int, block_k: int, interpret: bool):
    B, S, H, D = q.shape
    _, T, K, _ = k.shape
    block_q = min(block_q, max(S, 8))
    block_k = min(block_k, max(T, 8))

    # (B,H,S,D) layout; pad seq dims to block multiples.
    qt = _pad_to(jnp.moveaxis(q, 2, 1), 2, block_q)
    kt = _pad_to(jnp.moveaxis(k, 2, 1), 2, block_k)
    vt = _pad_to(jnp.moveaxis(v, 2, 1), 2, block_k)
    Sp, Tp = qt.shape[2], kt.shape[2]
    nq, nk = Sp // block_q, Tp // block_k
    group = H // K

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, block_q=block_q,
        block_k=block_k, num_kv_blocks=nk, kv_len=T, q_offset=q_offset)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        **params,
    )(qt, kt, vt)
    return jnp.moveaxis(out[:, :, :S], 1, 2)


def _flash_oracle(q, k, v, *, causal: bool, window: int, q_offset: int,
                  scale: float):
    if q_offset == 0:
        return ref.mha_chunked(q, k, v, causal=causal, window=window,
                               scale=scale)
    B, S = q.shape[:2]
    q_pos = jnp.broadcast_to(q_offset + jnp.arange(S)[None, :], (B, S))
    return ref.mha(q, k, v, causal=causal, window=window, q_pos=q_pos,
                   scale=scale)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "q_offset",
                     "interpret", "scale"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False):
    """q: (B,S,H,D); k, v: (B,T,K,D), H % K == 0.  Returns (B,S,H,D).

    Differentiable; the backward is XLA's (VJP of ``ref.mha_chunked``)
    until a Pallas backward lands."""
    H, K, D = q.shape[2], k.shape[2], q.shape[3]
    assert H % K == 0, (H, K)
    scale = float(scale if scale is not None else D ** -0.5)
    kernel = functools.partial(
        _flash_fwd, causal=causal, window=window, q_offset=q_offset,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret)
    oracle = functools.partial(_flash_oracle, causal=causal, window=window,
                               q_offset=q_offset, scale=scale)
    return ref.oracle_vjp(kernel, oracle)(q, k, v)
