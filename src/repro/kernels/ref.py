"""Pure-jnp oracles for every Pallas kernel.

These are the correctness references the kernel tests assert against
(``interpret=True`` kernel output vs these, allclose over shape/dtype sweeps)
and the CPU execution path of ``ops.py`` (this container has no TPU).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def oracle_vjp(kernel, oracle):
    """``kernel`` forward, ``oracle``'s VJP (recomputed) as the backward.

    The Pallas kernels have no backward of their own; this makes them
    differentiable.  Both callables take the same differentiable positional
    arrays (non-differentiable options are bound beforehand) and return
    the same structure; the residuals are just the inputs."""
    @jax.custom_vjp
    def f(*args):
        return kernel(*args)

    def fwd(*args):
        return kernel(*args), args

    def bwd(args, g):
        out, vjp = jax.vjp(oracle, *args)
        g = jax.tree_util.tree_map(lambda c, o: c.astype(o.dtype), g, out)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------
def attention_mask(q_pos, kv_pos, *, causal: bool, window: int,
                   kv_valid: Optional[jnp.ndarray] = None,
                   num_sink: int = 0):
    """Boolean mask (B, S, T): True = attend.

    q_pos: (B,S) absolute positions of queries; kv_pos: (B,T) of keys
    (negative = invalid/ring slot not yet written); kv_valid: (B,) number of
    valid cache slots (decode), or None.  num_sink: positions < num_sink stay
    visible through sliding windows (attention sinks / hymba meta tokens).
    """
    m = kv_pos[:, None, :] >= 0
    if causal:
        m &= kv_pos[:, None, :] <= q_pos[:, :, None]
    if window > 0:
        in_window = q_pos[:, :, None] - kv_pos[:, None, :] < window
        if num_sink > 0:
            in_window |= kv_pos[:, None, :] < num_sink
        m &= in_window
    if kv_valid is not None:
        m &= kv_pos[:, None, :] < kv_valid[:, None, None]
    return m


def mha(q, k, v, *, causal: bool = True, window: int = 0,
        q_pos=None, kv_pos=None, kv_valid=None, softcap: float = 0.0,
        scale: Optional[float] = None, num_sink: int = 0):
    """Multi-head attention oracle with GQA.

    q: (B,S,H,D); k, v: (B,T,K,D) with H % K == 0.  fp32 softmax.
    """
    B, S, H, D = q.shape
    _, T, K, _ = k.shape
    assert H % K == 0, (H, K)
    G = H // K
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    if kv_pos is None:
        kv_pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    scale = scale if scale is not None else D ** -0.5

    qg = q.reshape(B, S, K, G, D)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    mask = attention_mask(q_pos, kv_pos, causal=causal, window=window,
                          kv_valid=kv_valid, num_sink=num_sink)
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows (e.g. padding) -> zeros, not NaN
    any_valid = mask.any(-1)[:, None, None, :]
    probs = jnp.where(any_valid[..., None], probs, 0.0)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)


def mha_chunked(q, k, v, *, causal: bool = True, window: int = 0,
                num_sink: int = 0, scale: Optional[float] = None,
                block_q: int = 512):
    """Memory-efficient exact attention: lax.scan over query blocks with a
    checkpointed body, so peak memory is O(block_q * T) instead of O(S * T).

    This is the XLA-path analogue of the Pallas flash kernel (same math,
    same masking) used for long-sequence train/prefill cells on backends
    where the Pallas kernel can't lower (e.g. the CPU dry-run).
    """
    B, S, H, D = q.shape
    _, T, K, _ = k.shape
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, S)
    pad = (-S) % block_q
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = q.shape[1] // block_q
    qb = q.reshape(B, nq, block_q, H, D)
    qb = jnp.moveaxis(qb, 1, 0)                       # (nq, B, bq, H, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    kv_pos = jnp.arange(T)

    def body(_, args):
        iq, qblk = args
        qf = qblk.reshape(B, block_q, K, G, D).astype(jnp.float32)
        logits = jnp.einsum("bskgd,btkd->bkgst", qf, kf) * scale
        q_pos = iq * block_q + jnp.arange(block_q)
        m = jnp.ones((block_q, T), bool)
        if causal:
            m &= kv_pos[None, :] <= q_pos[:, None]
        if window > 0:
            in_w = q_pos[:, None] - kv_pos[None, :] < window
            if num_sink > 0:
                in_w |= kv_pos[None, :] < num_sink
            m &= in_w
        logits = jnp.where(m[None, None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        any_valid = m.any(-1)[None, None, None]
        probs = jnp.where(any_valid[..., None], probs, 0.0)
        ob = jnp.einsum("bkgst,btkd->bskgd", probs, vf)
        return None, ob.reshape(B, block_q, H, D).astype(q.dtype)

    body = jax.checkpoint(body, prevent_cse=False)
    _, out = jax.lax.scan(body, None, (jnp.arange(nq), qb))
    out = jnp.moveaxis(out, 0, 1).reshape(B, nq * block_q, H, D)
    return out[:, :S]


# --------------------------------------------------------------------------
# rmsnorm
# --------------------------------------------------------------------------
def rmsnorm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# mamba2 SSD (state-space duality) chunked scan
# --------------------------------------------------------------------------
def ssd_naive(x, dt, A, B, C, *, initial_state=None):
    """Sequential recurrence oracle (the ground truth the chunked forms match).

    x: (b, s, h, p); dt: (b, s, h); A: (h,) (negative values);
    B, C: (b, s, g, n) with h % g == 0.  Returns (y, final_state) with
    y: (b, s, h, p), state: (b, h, p, n).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32)   # (b,s,h,n)
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    decay = jnp.exp(dtf * A[None, None, :])               # (b,s,h)

    def step(state, inp):
        xt, bt, ct, dct, dtt = inp                        # (b,h,p),(b,h,n),...
        state = state * dct[..., None, None] \
            + jnp.einsum("bhp,bhn->bhpn", xt * dtt[..., None], bt)
        y = jnp.einsum("bhpn,bhn->bhp", state, ct)
        return state, y

    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), jnp.float32)
    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(Bh, 1, 0),
          jnp.moveaxis(Ch, 1, 0), jnp.moveaxis(decay, 1, 0),
          jnp.moveaxis(dtf, 1, 0))
    state, ys = jax.lax.scan(step, initial_state, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), state


def ssd_chunked(x, dt, A, B, C, *, chunk: int = 64, initial_state=None):
    """Chunked SSD oracle — the parallel form the Pallas kernel implements.

    Splits the sequence into chunks; computes the intra-chunk quadratic term
    and carries inter-chunk state with a scan.  Mathematically identical to
    ``ssd_naive`` (up to fp error).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    rep = h // g

    xf = x.astype(jnp.float32).reshape(b, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, h)
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32).reshape(b, nc, chunk, h, n)
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32).reshape(b, nc, chunk, h, n)

    da = dtf * A[None, None, None, :]                      # (b,nc,c,h)
    cum = jnp.cumsum(da, axis=2)                           # inclusive cumsum
    total = cum[:, :, -1:, :]                              # (b,nc,1,h)

    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for j <= i  (decay j->i).
    # Masked before the exp: above the diagonal cum_i - cum_j is the decay
    # run backwards, which overflows once a chunk's dt*A sums past 88, and
    # exp's VJP would then carry 0 * inf = NaN through the masked entries.
    li = cum[:, :, :, None, :]                             # (b,nc,c,1,h)
    lj = cum[:, :, None, :, :]                             # (b,nc,1,c,h)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.exp(jnp.where(causal[None, None, :, :, None], li - lj, -jnp.inf))

    xdt = xf * dtf[..., None]
    scores = jnp.einsum("bzihn,bzjhn->bzijh", Ch, Bh) * L  # (b,nc,c,c,h)
    y_intra = jnp.einsum("bzijh,bzjhp->bzihp", scores, xdt)

    # chunk states: sum_j exp(total - cum_j) B_j x_j dt_j
    tail = jnp.exp(total - cum)                            # (b,nc,c,h)
    chunk_state = jnp.einsum("bzjhn,bzjhp->bzhpn", Bh * tail[..., None], xdt)

    # inter-chunk recurrence over nc
    chunk_decay = jnp.exp(total[:, :, 0, :])               # (b,nc,h)
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), jnp.float32)

    def step(state, inp):
        cs, cd = inp                                       # (b,h,p,n),(b,h)
        prev = state
        state = state * cd[..., None, None] + cs
        return state, prev

    states_in = (jnp.moveaxis(chunk_state, 1, 0), jnp.moveaxis(chunk_decay, 1, 0))
    final_state, prev_states = jax.lax.scan(step, initial_state, states_in)
    prev_states = jnp.moveaxis(prev_states, 0, 1)          # (b,nc,h,p,n)

    # inter-chunk contribution: C_i exp(cum_i) @ prev_state
    y_inter = jnp.einsum("bzihn,bzhpn->bzihp", Ch * jnp.exp(cum)[..., None],
                         prev_states)
    y = (y_intra + y_inter).reshape(b, s, h, p)
    return y.astype(x.dtype), final_state


def ssd_step(state, x, dt, A, B, C):
    """Single-token SSD recurrence for decode.

    state: (b,h,p,n); x: (b,h,p); dt: (b,h); B, C: (b,g,n).
    """
    h = x.shape[1]
    g = B.shape[1]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(C, rep, axis=1).astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    decay = jnp.exp(dtf * A[None, :])
    state = state * decay[..., None, None] + jnp.einsum(
        "bhp,bhn->bhpn", x.astype(jnp.float32) * dtf[..., None], Bh)
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch)
    return y.astype(x.dtype), state
