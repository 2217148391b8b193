"""Collective (overlapped all-gather) matmul via shard_map + ppermute.

Beyond-paper distributed-optimization trick for the TP axis: instead of
``all_gather(x) @ w`` (a bandwidth burst, then compute), the gather is
decomposed into ring steps — each step matmuls the shard it already holds
while ppermute-ing the next shard around the ring, hiding ICI latency
behind the MXU ("Overlap Communication with Computation", Wang et al.).

Used by the perf hillclimb when the roofline shows the collective term
dominating a TP matmul; correctness is asserted against the plain gather
matmul in tests/test_collectives.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def ring_weight_matmul(x, w, mesh: Mesh, *, axis: str = "model"):
    """x: (m, k) sharded on m over ``axis``; w: (k, f) sharded on f.

    Computes x @ w (m-sharded, f-replicated result per shard of m) while
    ring-rotating weight shards so each ICI transfer overlaps one local
    matmul.  Equivalent to jnp.dot(x, w) (tested)."""
    n = mesh.shape[axis]
    f = w.shape[1]
    assert f % n == 0, (f, n)

    def body_fn(x_local, w_local):
        idx = jax.lax.axis_index(axis)
        nloc = jax.lax.psum(1, axis)
        perm = [(i, (i + 1) % nloc) for i in range(n)]
        fs = w_local.shape[1]

        def step(i, carry):
            out, wblk = carry
            src = (idx - i) % nloc          # which f-slice this block is
            part = jnp.dot(x_local, wblk,
                           preferred_element_type=jnp.float32)
            out = jax.lax.dynamic_update_slice(out, part, (0, src * fs))
            wblk = jax.lax.ppermute(wblk, axis, perm)
            return out, wblk

        out0 = jax.lax.pvary(jnp.zeros((x_local.shape[0], f), jnp.float32),
                             (axis,))
        out, _ = jax.lax.fori_loop(0, n, step, (out0, w_local))
        return out

    return jax.shard_map(
        body_fn, mesh=mesh,
        in_specs=(P(axis, None), P(None, axis)),
        out_specs=P(axis, None),
    )(x, w)
