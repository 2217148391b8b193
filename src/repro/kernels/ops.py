"""Public kernel API with backend dispatch.

Models call these wrappers, never the kernels directly:

* on TPU -> Pallas kernels (``flash_attention``, ``rmsnorm``, ``ssd_scan``),
* on CPU (this container, smoke tests, dry-run) -> pure-jnp oracles from
  ``ref.py`` (identical math; XLA fuses them well enough for correctness
  work),
* ``REPRO_KERNEL_IMPL`` env var forces ``ref`` / ``pallas`` /
  ``pallas_interpret`` (the last runs the kernel bodies in Python on CPU —
  that is how the test suite validates the TPU kernels here).

Under a mesh (``sharding_rules.use_rules``) the Pallas kernels run inside a
``shard_map`` over every mesh axis not already manual: XLA cannot
partition a Mosaic kernel itself.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding_rules import current_ctx
from repro.kernels import ref as _ref
from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd


def _impl() -> str:
    forced = os.environ.get("REPRO_KERNEL_IMPL", "")
    if forced:
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _per_shard(kernel, args, batched, *, n_out: int = 1):
    """``kernel(*args)``, or under a mesh the same call per shard: batched
    args and every output split on their leading (batch) dim over the
    batch axes that are still automatic, everything else replicated."""
    ctx = current_ctx()
    auto = () if ctx is None else tuple(
        a for a in ctx.mesh.axis_names if a not in ctx.manual)
    if not auto:
        return kernel(*args)

    def spec(x):
        return ctx.partition_spec(("batch",) + (None,) * (x.ndim - 1),
                                  x.shape)

    out = spec(args[0])
    in_specs = tuple(spec(x) if b else P() for x, b in zip(args, batched))
    return jax.shard_map(kernel, in_specs=in_specs,
                         out_specs=out if n_out == 1 else (out,) * n_out,
                         axis_names=set(auto), check_vma=False)(*args)


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              q_pos=None, kv_pos=None, kv_valid=None, softcap: float = 0.0,
              q_offset: int = 0, scale: Optional[float] = None,
              num_sink: int = 0, block_q: int = 512, block_k: int = 512):
    """Multi-head (GQA) attention.  q: (B,S,H,D); k, v: (B,T,K,D)."""
    impl = _impl()
    ragged = q_pos is not None or kv_pos is not None or kv_valid is not None \
        or softcap > 0.0 or num_sink > 0
    if impl.startswith("pallas") and not ragged:
        kernel = functools.partial(
            _fa.flash_attention, causal=causal, window=window,
            q_offset=q_offset, scale=scale, block_q=block_q,
            block_k=block_k, interpret=impl == "pallas_interpret")
        return _per_shard(kernel, (q, k, v), (True, True, True))
    if q_offset and q_pos is None:
        B, S = q.shape[:2]
        q_pos = jnp.broadcast_to(q_offset + jnp.arange(S)[None, :], (B, S))
    # long full-sequence paths use the chunked (flash-equivalent) oracle so
    # peak memory stays O(block * T) — required for the 32k prefill cells.
    simple = (q_pos is None and kv_pos is None and kv_valid is None
              and softcap == 0.0 and q_offset == 0)
    if simple and q.shape[1] >= 1024:
        return _ref.mha_chunked(q, k, v, causal=causal, window=window,
                                num_sink=num_sink, scale=scale)
    return _ref.mha(q, k, v, causal=causal, window=window, q_pos=q_pos,
                    kv_pos=kv_pos, kv_valid=kv_valid, softcap=softcap,
                    scale=scale, num_sink=num_sink)


def rmsnorm(x, scale, *, eps: float = 1e-6):
    impl = _impl()
    if impl.startswith("pallas"):
        kernel = functools.partial(_rn.rmsnorm, eps=eps,
                                   interpret=impl == "pallas_interpret")
        return _per_shard(kernel, (x, scale), (True, False))
    return _ref.rmsnorm(x, scale, eps)


def rmsnorm_residual(x, residual, scale, *, eps: float = 1e-6):
    """Returns (normed, new_residual) for fused residual-add + norm."""
    impl = _impl()
    if impl.startswith("pallas"):
        kernel = functools.partial(_rn.rmsnorm_residual, eps=eps,
                                   interpret=impl == "pallas_interpret")
        return _per_shard(kernel, (x, residual, scale), (True, True, False),
                          n_out=2)
    new_res = x + residual
    return _ref.rmsnorm(new_res, scale, eps), new_res


def ssd(x, dt, A, B, C, *, chunk: int = 256):
    """Chunked SSD scan (training/prefill).  See ssd_scan.py for shapes."""
    impl = _impl()
    s = x.shape[1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        widths = [(0, 0)] * 4
        widths[1] = (0, pad)
        x = jnp.pad(x, widths)
        B = jnp.pad(B, widths)
        C = jnp.pad(C, widths)
        dt = jnp.pad(dt, [(0, 0), (0, pad), (0, 0)])
    if impl.startswith("pallas"):
        kernel = functools.partial(_ssd.ssd_scan, chunk=chunk,
                                   interpret=impl == "pallas_interpret")
        y = _per_shard(kernel, (x, dt, A, B, C),
                       (True, True, False, True, True))
    else:
        y, _ = _ref.ssd_chunked(x, dt, A, B, C, chunk=chunk)
    return y[:, :s] if pad else y


def ssd_prefill(x, dt, A, B, C, *, chunk: int = 256):
    """SSD scan that also returns the final state (for prefill -> decode).

    Always the jnp chunked path (state output needed)."""
    s = x.shape[1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        widths = [(0, 0)] * 4
        widths[1] = (0, pad)
        # pad dt with zeros -> exp(0 * A) = 1, no state decay from padding,
        # and zero dt zeroes the padded tokens' state contribution.
        x = jnp.pad(x, widths)
        B = jnp.pad(B, widths)
        C = jnp.pad(C, widths)
        dt = jnp.pad(dt, [(0, 0), (0, pad), (0, 0)])
    y, state = _ref.ssd_chunked(x, dt, A, B, C, chunk=chunk)
    return (y[:, :s] if pad else y), state


def ssd_step(state, x, dt, A, B, C):
    """Single-token SSD recurrence (decode); memory-bound, jnp path."""
    return _ref.ssd_step(state, x, dt, A, B, C)
