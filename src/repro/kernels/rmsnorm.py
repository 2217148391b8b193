"""Fused RMSNorm Pallas TPU kernel (optionally fused residual add).

One VMEM round-trip instead of three (square/mean, rsqrt-scale, residual):
rows are tiled (block_rows x d) so the working set stays in VMEM; the
reduction and scale run in fp32 on the VPU and the result is written back in
the input dtype.  Oracle: ``ref.rmsnorm``.

The backward pass is XLA's: the VJP of ``ref.rmsnorm``, recomputed
(``ref.oracle_vjp``), until a Pallas backward lands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref


def _kernel(x_ref, scale_ref, o_ref, *, eps: float, d: int):
    x = x_ref[...].astype(jnp.float32)
    # padded tail columns contribute zeros; divide by true d
    var = jnp.sum(x * x, axis=-1, keepdims=True) / d
    y = x * jax.lax.rsqrt(var + eps) * scale_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _kernel_residual(x_ref, res_ref, scale_ref, o_ref, newres_ref, *,
                     eps: float, d: int):
    x = x_ref[...].astype(jnp.float32) + res_ref[...].astype(jnp.float32)
    newres_ref[...] = x.astype(newres_ref.dtype)
    var = jnp.sum(x * x, axis=-1, keepdims=True) / d
    y = x * jax.lax.rsqrt(var + eps) * scale_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _rows(x, block_rows: int):
    """(..., d) -> padded (R, Dp) rows, true row count, block rows."""
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    block_rows = min(block_rows, max(rows, 1))
    x2 = x.reshape(rows, d)
    pad_rows = (-rows) % block_rows
    pad_d = (-d) % 128
    if pad_rows or pad_d:
        x2 = jnp.pad(x2, ((0, pad_rows), (0, pad_d)))
    return x2, rows, block_rows


def _scale_padded(scale, d: int):
    pad_d = (-d) % 128
    return jnp.pad(scale, (0, pad_d)) if pad_d else scale


def _params(interpret: bool) -> dict:
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",))}


def _rmsnorm_fwd(x, scale, *, eps, block_rows, interpret):
    d = x.shape[-1]
    x2, rows, block_rows = _rows(x, block_rows)
    R, Dp = x2.shape
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps, d=d),
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, Dp), lambda i: (i, 0)),
            pl.BlockSpec((Dp,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, Dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, Dp), x.dtype),
        interpret=interpret,
        **_params(interpret),
    )(x2, _scale_padded(scale, d))
    return out[:rows, :d].reshape(x.shape)


def _rmsnorm_residual_fwd(x, residual, scale, *, eps, block_rows,
                          interpret):
    d = x.shape[-1]
    x2, rows, block_rows = _rows(x, block_rows)
    r2, _, _ = _rows(residual, block_rows)
    R, Dp = x2.shape
    normed, newres = pl.pallas_call(
        functools.partial(_kernel_residual, eps=eps, d=d),
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, Dp), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, Dp), lambda i: (i, 0)),
            pl.BlockSpec((Dp,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, Dp), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, Dp), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, Dp), x.dtype),
            jax.ShapeDtypeStruct((R, Dp), x.dtype),
        ],
        interpret=interpret,
        **_params(interpret),
    )(x2, r2, _scale_padded(scale, d))
    return (normed[:rows, :d].reshape(x.shape),
            newres[:rows, :d].reshape(x.shape))


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool = False):
    """x: (..., d); scale: (d,).  Returns rmsnorm(x) * scale.

    Differentiable; the backward is XLA's (VJP of ``ref.rmsnorm``) until a
    Pallas backward lands."""
    kernel = functools.partial(_rmsnorm_fwd, eps=eps, block_rows=block_rows,
                               interpret=interpret)
    return ref.oracle_vjp(kernel, functools.partial(ref.rmsnorm, eps=eps))(
        x, scale)


def _rmsnorm_residual_oracle(x, residual, scale, *, eps):
    new_res = (x.astype(jnp.float32) + residual.astype(jnp.float32)
               ).astype(x.dtype)
    return ref.rmsnorm(new_res, scale, eps), new_res


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_residual(x, residual, scale, *, eps: float = 1e-6,
                     block_rows: int = 256, interpret: bool = False):
    """Fused (x + residual) -> new_residual, rmsnorm(new_residual) * scale.

    Returns (normed, new_residual).  Differentiable; the backward is XLA's
    (VJP of ``ref.rmsnorm`` over the residual add) until a Pallas backward
    lands."""
    kernel = functools.partial(_rmsnorm_residual_fwd, eps=eps,
                               block_rows=block_rows, interpret=interpret)
    oracle = functools.partial(_rmsnorm_residual_oracle, eps=eps)
    return ref.oracle_vjp(kernel, oracle)(x, residual, scale)
