"""Mamba2 SSD (state-space duality) chunked-scan Pallas TPU kernel.

TPU adaptation of the SSD algorithm (paper: arXiv 2405.21060, GPU Triton
kernels): the sequence is split into chunks; within a chunk the dual
(quadratic, MXU-friendly) form computes the causal-decay-masked C B^T x
contribution as two small matmuls, and the recurrent inter-chunk state is
carried in VMEM scratch across the innermost grid dimension (TPU grids are
sequential, so the state simply persists between chunk steps — the TPU
analogue of the GPU kernel's cross-CTA state passing).

Grid: (batch, head blocks, num_chunks).  A grid step covers ``hb`` heads of
one group over one chunk:
* its ``B`` and ``C`` blocks are fetched once, and ``C B^T`` is computed
  once for the ``hb`` heads;
* ``dt`` is laid out ``(b, h / hb, num_chunks, hb, chunk)``, the chunk
  along lanes, so the step's ``(hb, chunk)`` block is dense and whole at
  any chunk (a trailing unit dim would pad every element to a 128-lane
  tile);
* ``A`` sits whole in SMEM; the block's values become a column that
  scales its ``dt`` rows, and one ``(hb, c) @ triu(ones)`` product at
  ``HIGHEST`` gives every head's inclusive cumsum of ``dt * A`` (Pallas TPU
  has no cumsum lowering), one ``@ ones`` its chunk total;
* one transpose lays the cumsums and ``dt`` along lanes, where a lane
  rotation brings a head's columns to the front;
* the heads then run against the ``(hb, p, n)`` state scratch (reset at
  chunk 0), up to ``_UNROLL`` of them unrolled in one loop iteration so
  that the scheduler overlaps their matmuls and elementwise work.  A chunk
  that is a multiple of 256 splits the intra-chunk term into halves and
  skips the upper right quarter, where the causal mask leaves nothing.
The matmuls take f32 operands at the default precision, as the state and
the decayed ``C`` and ``B`` are f32; the mask is applied before the
``exp``.

``hb`` is the largest divisor of the heads per group whose blocks, scratch
and temporaries fit ``_VMEM_BUDGET`` (``_head_block``): one rule for every
configuration's head count, state size and dtype.  It may be 1.

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

# Below v5e's 16 MiB default scoped VMEM, with room for Mosaic's own.
_VMEM_BUDGET = 12 << 20
# Heads unrolled into one loop iteration: at most this many.
_UNROLL = 8
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _up(v: int, m: int) -> int:
    return -(-v // m) * m


def _vmem_bytes(hb: int, p: int, n: int, chunk: int, itemsize: int) -> int:
    """VMEM a grid step of ``hb`` heads takes, in (8, 128) tiles."""
    c, pl_, nl = _up(chunk, 8), _up(p, 128), _up(n, 128)
    cl = _up(chunk, 128)
    blocks = 2 * (2 * hb * c * pl_ * itemsize       # x and y
                  + _up(hb, 8) * cl * 4             # dt
                  + 2 * c * nl * itemsize)          # B and C
    # the state, cumsums and totals; the stacked rows, their transpose and
    # its rotation
    scratch = (hb * _up(p, 8) * nl + _up(hb, 8) * (cl + nl)
               + 3 * _up(2 * hb, 128) * cl) * 4
    temps = 4 * (5 * c * cl + 4 * c * nl + 3 * c * pl_)    # f32, per head
    return blocks + scratch + temps


def _head_block(h: int, g: int, p: int, n: int, chunk: int,
                itemsize: int) -> int:
    """The largest divisor of the heads per group that fits the budget."""
    group = h // g
    fits = [d for d in range(1, group + 1) if group % d == 0
            and _vmem_bytes(d, p, n, chunk, itemsize) <= _VMEM_BUDGET]
    return max(fits, default=1)


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, state_scr, cum_scr,
            total_scr, cols_scr):
    ihb = pl.program_id(1)
    ic = pl.program_id(2)
    hb, c_len = dt_ref.shape[3], dt_ref.shape[4]
    n = b_ref.shape[3]
    width = cols_scr.shape[0]

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    bm = b_ref[0, 0].astype(jnp.float32)       # (c, n)
    cm = c_ref[0, 0].astype(jnp.float32)       # (c, n)
    ii = jax.lax.broadcasted_iota(jnp.int32, (c_len, c_len), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c_len, c_len), 1)
    causal = jj <= ii
    cb = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=jnp.float32)

    # the block's A as a column, one select per head
    hrow = jax.lax.broadcasted_iota(jnp.int32, (hb, 1), 0)
    a_col = jnp.zeros((hb, 1), jnp.float32)
    for k in range(hb):
        a_col = jnp.where(hrow == k, a_ref[ihb * hb + k], a_col)
    dt = dt_ref[0, 0, 0]                                    # (hb, c)
    da = dt * a_col

    def sums(m):
        return jax.lax.dot_general(da, m, _NN,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
    cum_scr[...] = sums((ii <= jj).astype(jnp.float32))     # inclusive cumsum
    total_scr[...] = sums(jnp.ones((c_len, n), jnp.float32))  # over n lanes
    # the cumsums and dt, each head's along sublanes, for lane rotation
    rows = [cum_scr[...], dt]
    if width > 2 * hb:
        rows.append(jnp.zeros((width - 2 * hb, c_len), jnp.float32))
    cols_scr[...] = jnp.concatenate(rows, axis=0)
    cols = cols_scr[...].T                                  # (c, width)

    unroll = max(d for d in range(1, _UNROLL + 1) if hb % d == 0)
    half = c_len // 2 if c_len % 256 == 0 else 0

    def intra(cum_c, cum_r, xdt):
        """(C B^T * L) @ xdt, L[i, j] = exp(cum_i - cum_j) for j <= i; the
        exp is masked before it is taken (above the diagonal the decay
        runs backwards).  A chunk that is a multiple of 256 skips its
        upper right quarter, where L is 0."""
        if not half:
            L = jnp.exp(jnp.where(causal, cum_c - cum_r, -jnp.inf))
            return jax.lax.dot_general(cb * L, xdt, _NN,
                                       preferred_element_type=jnp.float32)
        tri = causal[:half, :half]
        r0 = cum_r[:, :half]
        r1 = pltpu.roll(cum_r, half, 1)[:, :half]
        x0, x1 = xdt[:half], xdt[half:]
        blocks = (
            (cb[:half, :half] * jnp.exp(jnp.where(tri, cum_c[:half] - r0,
                                                  -jnp.inf)), x0),
            (cb[half:, :half] * jnp.exp(cum_c[half:] - r0), x0),
            (cb[half:, half:] * jnp.exp(jnp.where(tri, cum_c[half:] - r1,
                                                  -jnp.inf)), x1))
        y0, y10, y11 = (jax.lax.dot_general(
            s_, x_, _NN, preferred_element_type=jnp.float32)
            for s_, x_ in blocks)
        return jnp.concatenate([y0, y10 + y11], axis=0)

    def head(k, col, lane):
        """Head k of the block; its cumsum and dt sit at lanes ``lane`` and
        ``hb + lane`` of ``col``."""
        cum_c = col[:, lane:lane + 1]                       # (c, 1)
        dt_c = col[:, hb + lane:hb + lane + 1]              # (c, 1)
        cum_r = cum_scr[pl.ds(k, 1), :]                     # (1, c)
        total = total_scr[pl.ds(k, 1), :]                   # (1, n)

        xdt = x_ref[0, k].astype(jnp.float32) * dt_c        # (c, p)
        y_intra = intra(cum_c, cum_r, xdt)
        state = state_scr[k]                                # (p, n)
        y_inter = jax.lax.dot_general(cm * jnp.exp(cum_c), state, _NT,
                                      preferred_element_type=jnp.float32)
        new_state = jax.lax.dot_general(xdt, bm * jnp.exp(total - cum_c),
                                        _TN,
                                        preferred_element_type=jnp.float32)
        state_scr[k] = state * jnp.exp(total) + new_state
        o_ref[0, k] = (y_intra + y_inter).astype(o_ref.dtype)

    def heads(j, carry):
        # one lane rotation brings ``unroll`` heads' columns to the front;
        # their bodies are unrolled so the scheduler overlaps them
        k0 = j * unroll
        col = pltpu.roll(cols, (width - k0) % width, 1)
        for lane in range(unroll):
            head(k0 + lane, col, lane)
        return carry

    jax.lax.fori_loop(0, hb // unroll, heads, 0)


def _ssd_fwd(x, dt, A, B, C, *, chunk: int, interpret: bool):
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    nc = s // chunk
    hb = _head_block(h, g, p, n, chunk, x.dtype.itemsize)
    per_group = (h // g) // hb                 # head blocks per group

    xt = jnp.moveaxis(x, 2, 1)                 # (b, h, s, p)
    # (b, h / hb, nc, hb, chunk): a step's dt block is a whole (hb, chunk)
    # tile, whatever the chunk
    dtt = dt.reshape(b, nc, chunk, h // hb, hb).transpose(0, 3, 1, 4, 2)
    bt = jnp.moveaxis(B, 2, 1)                 # (b, g, s, n)
    ct = jnp.moveaxis(C, 2, 1)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    out = pl.pallas_call(
        _kernel,
        grid=(b, h // hb, nc),
        in_specs=[
            pl.BlockSpec((1, hb, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, hb, chunk),
                         lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda ib, ih, ic: (ib, ih // per_group, ic, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda ib, ih, ic: (ib, ih // per_group, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, hb, chunk, p),
                               lambda ib, ih, ic: (ib, ih, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((hb, p, n), jnp.float32),
                        pltpu.VMEM((hb, chunk), jnp.float32),
                        pltpu.VMEM((hb, n), jnp.float32),
                        pltpu.VMEM((_up(2 * hb, 128), chunk), jnp.float32)],
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
