"""Plain reference of Granite 4.0-H (``granitemoehybrid`` without experts):
Mamba-2 and attention layers interleaved as ``layer_types`` lists them,
every layer

    x = x + r * mixer(RMSNorm(x));  x = x + r * MLP(RMSNorm(x))

with ``r`` the residual multiplier, the mixer a Mamba-2 SSD mixer or
grouped-query attention without positions (``nope``) at the softmax scale
``attention_multiplier``, and the MLP SwiGLU at
``shared_intermediate_size``.  The embedding is multiplied by
``embedding_multiplier``, the tied unembedding's logits divided by
``logits_scaling``.  float32, one sequence at a time, each layer
recomputed in the backward pass.

The Mamba-2 mixer is ``mamba2_lm.py``'s: ``x, z, B, C, dt`` as separate
projections of the normed input (the published ``in_proj`` is one matrix
whose output is split the same way), a causal depthwise convolution with
bias and SiLU on ``x, B, C``, ``dt = softplus(. + dt_bias)``, ``A =
-exp(A_log)``, the scan in its quadratic form by blocks of query positions,
``+ D x``, gated by ``silu(z)``, RMSNorm over the inner width (one group),
out projection.  Attention is written out plainly, by blocks of query
positions so that the logits of 32 heads over 4096 keys are never whole.

Departures from the published model: the layers are cut to
``num_hidden_layers`` of ``layer_types`` and the vocabulary to
``vocab_size`` rows, as the configuration file states; the weights are
random from the seed, by the program's init, written out here (see
``dense_lm.py``): the embedding at std 0.02, projections at
``1/sqrt(fan_in)``, the convolution at 0.5, ``A_log`` and ``dt_bias`` at
zero, ``D`` and norm scales at one.  Each kind of layer is stacked on a
leading axis of its own, in the program's layout.
"""
from __future__ import annotations

import numpy as np

import plain
from plain import Leaf

_QUERY_BLOCK = 256


_KINDS = ("mamba", "attention")


def _kinds(m):
    return list(m["layer_types"][:m["num_hidden_layers"]])


def _mamba_dims(m):
    d = m["hidden_size"]
    din = m["mamba_expand"] * d
    nh, hd = m["mamba_n_heads"], m["mamba_d_head"]
    if nh * hd != din or not m["mamba_conv_bias"] or m["mamba_proj_bias"]:
        raise ValueError("the reference writes out Mamba-2 with heads "
                         "filling the inner width, a conv bias and no "
                         "projection bias")
    return d, din, nh, hd, m["mamba_d_state"], m["mamba_n_groups"]


def param_specs(m):
    d, din, nh, _, n, g = _mamba_dims(m)
    h, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    f, v = m["shared_intermediate_size"], m["vocab_size"]
    kinds = _kinds(m)
    std = plain.fan_in_std
    conv = din + 2 * g * n
    w = m["mamba_d_conv"]

    def common(L):
        return {"ln1": {"scale": Leaf((L, d), "ones")},
                "ln2": {"scale": Leaf((L, d), "ones")},
                "mlp": {"wi": Leaf((L, d, f), std=std(d)),
                        "wg": Leaf((L, d, f), std=std(d)),
                        "wo": Leaf((L, f, d), std=std(f))}}

    layers = {}
    L = kinds.count("mamba")
    if L:
        layers["mamba"] = dict(common(L), ssm={
            "A_log": Leaf((L, nh), "zeros"),
            "D": Leaf((L, nh), "ones"),
            "conv_b": Leaf((L, conv), "zeros"),
            "conv_w": Leaf((L, w, conv), std=0.5),
            "dt_bias": Leaf((L, nh), "zeros"),
            "gate_norm": Leaf((L, din), "ones"),
            "in_B": Leaf((L, d, g * n), std=std(d)),
            "in_C": Leaf((L, d, g * n), std=std(d)),
            "in_dt": Leaf((L, d, nh), std=std(d)),
            "in_x": Leaf((L, d, din), std=std(d)),
            "in_z": Leaf((L, d, din), std=std(d)),
            "out": Leaf((L, din, d), std=std(din)),
        })
    L = kinds.count("attention")
    if L:
        layers["attention"] = dict(common(L), attn={
            "wq": Leaf((L, d, h * hd), std=std(d)),
            "wk": Leaf((L, d, kv * hd), std=std(d)),
            "wv": Leaf((L, d, kv * hd), std=std(d)),
            "wo": Leaf((L, h * hd, d), std=std(h * hd))})
    return {"embed": {"tokens": Leaf((v, d), std=0.02)},
            "final_norm": {"scale": Leaf((d,), "ones")},
            "layers": layers}


def _ssd(x, dt, a, bm, cm, dot):
    """Quadratic-form SSD.  x: (S,H,P); dt: (S,H); a: (H,); bm, cm:
    (S,G,N).  Returns (S,H,P).  The ``C_t . B_s`` scores are per group,
    the decays per head."""
    import jax
    import jax.numpy as jnp

    s, nh, _ = x.shape
    g = bm.shape[1]
    cum = jnp.cumsum(dt * a, axis=0)                       # (S,H)
    xdt = (x * dt[..., None]).reshape(s, g, nh // g, -1)   # (S,G,R,P)
    blk = min(_QUERY_BLOCK, s)
    src = jnp.arange(s)

    def block(i):
        t = i * blk + jnp.arange(blk)
        c_t = jax.lax.dynamic_slice_in_dim(cm, i * blk, blk)
        cum_t = jax.lax.dynamic_slice_in_dim(cum, i * blk, blk)
        scores = dot("tgn,sgn->tsg", c_t, bm)              # (blk,S,G)
        seg = cum_t[:, None, :] - cum[None, :, :]          # (blk,S,H)
        keep = (src[None, :] <= t[:, None])[..., None]
        decay = jnp.exp(jnp.where(keep, seg, -jnp.inf))
        w = decay.reshape(blk, s, g, nh // g) * scores[..., None]
        return dot("tsgr,sgrp->tgrp", w, xdt).reshape(blk, nh, -1)

    ys = jax.lax.map(jax.checkpoint(block), jnp.arange(s // blk))
    return ys.reshape(x.shape)


def _mamba(q, y, m, dot):
    import jax
    import jax.numpy as jnp

    d, din, nh, hd, n, g = _mamba_dims(m)
    w = m["mamba_d_conv"]
    s = y.shape[0]
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
    ys = _ssd(xh, dt, -jnp.exp(q["A_log"]), bm.reshape(s, g, n),
              cm.reshape(s, g, n), dot)
    ys = (ys + xh * q["D"][None, :, None]).reshape(s, din)
    ys = plain.rmsnorm(ys * jax.nn.silu(z), q["gate_norm"],
                       m["rms_norm_eps"])
    return dot("se,ed->sd", ys, q["out"])


def _attention(a, y, m, dot):
    """Causal GQA attention without positions, by blocks of queries."""
    import jax
    import jax.numpy as jnp

    h, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    s = y.shape[0]
    q = dot("sd,dn->sn", y, a["wq"]).reshape(s, kv, h // kv, hd)
    k = dot("sd,dn->sn", y, a["wk"]).reshape(s, kv, hd)
    v = dot("sd,dn->sn", y, a["wv"]).reshape(s, kv, hd)
    blk = min(_QUERY_BLOCK, s)
    src = jnp.arange(s)

    def block(i):
        t = i * blk + jnp.arange(blk)
        q_t = jax.lax.dynamic_slice_in_dim(q, i * blk, blk)
        logits = dot("tkgd,skd->kgts", q_t, k) * m["attention_multiplier"]
        keep = src[None, :] <= t[:, None]
        probs = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
        return dot("kgts,skd->tkgd", probs, v)

    o = jax.lax.map(jax.checkpoint(block), jnp.arange(s // blk))
    return dot("sn,nd->sd", o.reshape(s, h * hd), a["wo"])


def row_loss(p, tokens, targets, mask, m, dot):
    """Summed cross-entropy of one sequence (``tokens``: (S,))."""
    import jax
    import jax.numpy as jnp

    if m["position_embedding_type"] != "nope":
        raise ValueError("the reference writes out attention without "
                         "positions")
    eps, r = m["rms_norm_eps"], m["residual_multiplier"]
    mixers = {"mamba": lambda lp, y: _mamba(lp["ssm"], y, m, dot),
              "attention": lambda lp, y: _attention(lp["attn"], y, m, dot)}

    def mlp(x, lp):
        y = plain.rmsnorm(x, lp["ln2"]["scale"], eps)
        w = lp["mlp"]
        gate = jax.nn.silu(dot("sd,df->sf", y, w["wg"]))
        return r * dot("sf,fd->sd", gate * dot("sd,df->sf", y, w["wi"]),
                       w["wo"])

    # Each branch is recomputed on its own in the backward pass, so that
    # one branch's float32 intermediates at 4096 positions are live at a
    # time, beside the optimizer state that ``plain.run_steps`` holds.
    def layer(kind):
        def mixer(x, lp):
            return r * mixers[kind](lp, plain.rmsnorm(x, lp["ln1"]["scale"],
                                                      eps))

        def run(x, lp):
            x = x + jax.checkpoint(mixer)(x, lp)
            return x + jax.checkpoint(mlp)(x, lp)
        return run

    # One scan over the layers in order: layer i takes its weights by index
    # from its kind's stack and runs that kind's layer.  (Slicing a stack
    # per run would copy it, and gather its gradient from pieces.)
    kinds = _kinds(m)
    present = [k for k in _KINDS if k in kinds]
    which = np.array([present.index(k) for k in kinds], np.int32)
    index = np.array([kinds[:i].count(k) for i, k in enumerate(kinds)],
                     np.int32)

    def step(x, wi):
        w, i = wi
        lps = [jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, jnp.minimum(i, a.shape[0] - 1), keepdims=False),
            p["layers"][k]) for k in present]
        branches = [lambda x, lps, j=j, k=k: layer(k)(x, lps[j])
                    for j, k in enumerate(present)]
        return jax.lax.switch(w, branches, x, lps), None

    emb = p["embed"]["tokens"]
    x = emb[tokens] * m["embedding_multiplier"]
    x, _ = jax.lax.scan(jax.checkpoint(step), x, (which, index))
    x = plain.rmsnorm(x, p["final_norm"]["scale"], eps)
    logits = dot("sd,vd->sv", x, emb) / m["logits_scaling"]
    ce = jax.nn.logsumexp(logits, axis=-1) \
        - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(ce * mask)


def flops_per_token(m):
    """Forward and backward operations per trained token: 6 per weight of
    every matrix product (the tied unembedding included) and of the
    convolution; 3 times the forward of causal attention (``QK^T`` and
    ``PV``, 2 operations per head dim per pair each, ``(S + 1) / 2`` pairs a
    token on average) and of the chunked scan (``kernels/ssd_scan.py``)."""
    d, din, nh, hd, n, g = _mamba_dims(m)
    h, kv, ahd = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    f, v, s = (m["shared_intermediate_size"], m["vocab_size"],
               m["train"]["seq_len"])
    kinds = _kinds(m)
    n_mamba, n_attn = kinds.count("mamba"), kinds.count("attention")
    mlp = 3 * d * f
    mamba = d * (2 * din + 2 * g * n + nh) + din * d \
        + m["mamba_d_conv"] * (din + 2 * g * n) + mlp
    attn = 2 * d * h * ahd + 2 * d * kv * ahd + mlp
    weights = n_mamba * mamba + n_attn * attn + d * v
    attention = n_attn * 4 * h * ahd * (s + 1) / 2
    pairs = (m["chunk_size"] + 1) / 2
    scan = n_mamba * 2 * (g * pairs * n + nh * pairs * hd + 2 * nh * hd * n)
    return 6 * weights + 3 * (attention + scan)
