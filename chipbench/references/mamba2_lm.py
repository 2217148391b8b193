"""Plain reference of a Mamba-2 LM (arXiv:2405.21060): RMSNorm, then the
SSD mixer, added to the residual; tied embeddings.  float32, one sequence
at a time, each layer recomputed in the backward pass.

The mixer: ``x, z, B, C`` and ``dt`` are separate projections of the
normed input; ``x, B, C`` go through a causal depthwise convolution (width
``d_conv``, with bias) and SiLU; ``dt = softplus(. + dt_bias)``,
``A = -exp(A_log)``.  The scan is written in its quadratic form,

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s,

by blocks of query positions, not as the chunked recurrence the program
runs.  Then ``y + D x``, gated by ``silu(z)``, RMSNorm, out projection.

The parameter layout and init are the program's, written out here so that
the reference makes the same weights from the same seed (see
``dense_lm.py``); the convolution weight has std 0.5, ``A_log`` and
``dt_bias`` start at zero, ``D`` and the norm scales at one.
"""
from __future__ import annotations

import plain
from plain import Leaf

_QUERY_BLOCK = 256


def param_specs(m):
    d, L, v = m["d_model"], m["n_layer"], m["vocab_size"]
    din = m["expand"] * d
    n, g = m["d_state"], m["ngroups"]
    nh = din // m["headdim"]
    conv = din + 2 * g * n
    std = plain.fan_in_std
    return {
        "embed": {"tokens": Leaf((v, d), std=0.02)},
        "final_norm": {"scale": Leaf((d,), "ones")},
        "layers": {
            "ln1": {"scale": Leaf((L, d), "ones")},
            "ssm": {
                "A_log": Leaf((L, nh), "zeros"),
                "D": Leaf((L, nh), "ones"),
                "conv_b": Leaf((L, conv), "zeros"),
                "conv_w": Leaf((L, m["d_conv"], conv), std=0.5),
                "dt_bias": Leaf((L, nh), "zeros"),
                "gate_norm": Leaf((L, din), "ones"),
                "in_B": Leaf((L, d, g * n), std=std(d)),
                "in_C": Leaf((L, d, g * n), std=std(d)),
                "in_dt": Leaf((L, d, nh), std=std(d)),
                "in_x": Leaf((L, d, din), std=std(d)),
                "in_z": Leaf((L, d, din), std=std(d)),
                "out": Leaf((L, din, d), std=std(din)),
            },
        },
    }


def _ssd(x, dt, a, bm, cm, heads_per_group, dot):
    """Quadratic-form SSD.  x: (S,H,P); dt: (S,H); a: (H,); bm, cm:
    (S,G,N).  Returns (S,H,P)."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    cum = jnp.cumsum(dt * a, axis=0)                       # (S,H)
    bh = jnp.repeat(bm, heads_per_group, axis=1)           # (S,H,N)
    ch = jnp.repeat(cm, heads_per_group, axis=1)
    xdt = x * dt[..., None]
    blk = min(_QUERY_BLOCK, s)
    src = jnp.arange(s)

    def block(i):
        t = i * blk + jnp.arange(blk)
        c_t = jax.lax.dynamic_slice_in_dim(ch, i * blk, blk)
        cum_t = jax.lax.dynamic_slice_in_dim(cum, i * blk, blk)
        scores = dot("thn,shn->tsh", c_t, bh)              # (blk,S,H)
        seg = cum_t[:, None, :] - cum[None, :, :]
        keep = (src[None, :] <= t[:, None])[..., None]
        decay = jnp.exp(jnp.where(keep, seg, -jnp.inf))
        return dot("tsh,shp->thp", scores * decay, xdt)

    ys = jax.lax.map(jax.checkpoint(block), jnp.arange(s // blk))
    return ys.reshape(x.shape)


def row_loss(p, tokens, targets, mask, m, dot):
    """Summed cross-entropy of one sequence (``tokens``: (S,))."""
    import jax
    import jax.numpy as jnp

    eps = m["rms_norm_eps"]
    din = m["expand"] * m["d_model"]
    n, g, hd, w = m["d_state"], m["ngroups"], m["headdim"], m["d_conv"]
    nh = din // hd
    s = tokens.shape[0]

    def layer(x, lp):
        q = lp["ssm"]
        y = plain.rmsnorm(x, lp["ln1"]["scale"], eps)
        xs = dot("sd,de->se", y, q["in_x"])
        z = dot("sd,de->se", y, q["in_z"])
        bm = dot("sd,de->se", y, q["in_B"])
        cm = dot("sd,de->se", y, q["in_C"])
        dt = jax.nn.softplus(dot("sd,dh->sh", y, q["in_dt"]) + q["dt_bias"])
        u = jnp.concatenate([xs, bm, cm], axis=-1)
        conv = q["conv_b"] + sum(
            jnp.pad(u, ((i, 0), (0, 0)))[:s] * q["conv_w"][w - 1 - i]
            for i in range(w))
        u = jax.nn.silu(conv)
        xs, bm, cm = u[:, :din], u[:, din:din + g * n], u[:, din + g * n:]
        xh = xs.reshape(s, nh, hd)
        a = -jnp.exp(q["A_log"])
        ys = _ssd(xh, dt, a, bm.reshape(s, g, n), cm.reshape(s, g, n),
                  nh // g, dot)
        ys = (ys + xh * q["D"][None, :, None]).reshape(s, din)
        ys = plain.rmsnorm(ys * jax.nn.silu(z), q["gate_norm"], eps)
        return x + dot("se,ed->sd", ys, q["out"]), None

    emb = p["embed"]["tokens"]
    x, _ = jax.lax.scan(jax.checkpoint(layer), emb[tokens], p["layers"])
    x = plain.rmsnorm(x, p["final_norm"]["scale"], eps)
    logits = dot("sd,vd->sv", x, emb)
    ce = jax.nn.logsumexp(logits, axis=-1) \
        - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(ce * mask)


def flops_per_token(m):
    """Forward and backward operations per trained token: 6 per weight of
    every matrix product (the tied unembedding included) and of the
    convolution, and 3 times the chunked scan's forward per position
    (``kernels/ssd_scan.py``)."""
    d, L, v = m["d_model"], m["n_layer"], m["vocab_size"]
    din = m["expand"] * d
    n, g, p, c = m["d_state"], m["ngroups"], m["headdim"], m["chunk_size"]
    h = din // p
    conv = din + 2 * g * n
    weights = L * (d * (2 * din + 2 * g * n + h) + din * d
                   + m["d_conv"] * conv) + d * v
    pairs = (c + 1) / 2
    scan = L * 2 * (g * pairs * n + h * pairs * p + 2 * h * p * n)
    return 6 * weights + 3 * scan
