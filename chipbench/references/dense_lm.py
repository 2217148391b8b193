"""Plain reference of a dense decoder LM with grouped-query attention, QKV
bias, rotary positions (rotate-half, ``theta ** (-i / half)``), RMSNorm
before attention and MLP, a SwiGLU MLP and tied embeddings: Qwen2
(arXiv:2407.10671).  float32, one sequence at a time, each layer
recomputed in the backward pass.

The parameter layout and init are the program's, written out here so
that the reference makes the same weights from the same seed: leaves in
sorted-key order, layers stacked on a leading axis, normal weights with
std ``1/sqrt(fan_in)``, the embedding at std 0.02, biases zero, norm
scales one.
"""
from __future__ import annotations

import numpy as np

import plain
from plain import Leaf


def param_specs(m):
    d, L = m["hidden_size"], m["num_hidden_layers"]
    h, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    f, v = m["intermediate_size"], m["vocab_size"]
    std = plain.fan_in_std
    attn = {"wq": Leaf((L, d, h * hd), std=std(d)),
            "wk": Leaf((L, d, kv * hd), std=std(d)),
            "wv": Leaf((L, d, kv * hd), std=std(d)),
            "wo": Leaf((L, h * hd, d), std=std(h * hd))}
    if m["attention_bias"]:
        attn.update(bq=Leaf((L, h * hd), "zeros"),
                    bk=Leaf((L, kv * hd), "zeros"),
                    bv=Leaf((L, kv * hd), "zeros"))
    return {
        "embed": {"tokens": Leaf((v, d), std=0.02)},
        "final_norm": {"scale": Leaf((d,), "ones")},
        "layers": {
            "attn": attn,
            "ln1": {"scale": Leaf((L, d), "ones")},
            "ln2": {"scale": Leaf((L, d), "ones")},
            "mlp": {"wi": Leaf((L, d, f), std=std(d)),
                    "wg": Leaf((L, d, f), std=std(d)),
                    "wo": Leaf((L, f, d), std=std(f))},
        },
    }


def _rotary(x, theta):
    import jax.numpy as jnp
    s, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(s, dtype=np.float64)[:, None] * freqs
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def row_loss(p, tokens, targets, mask, m, dot):
    """Summed cross-entropy of one sequence (``tokens``: (S,))."""
    import jax
    import jax.numpy as jnp

    h, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    s = tokens.shape[0]
    causal = np.tril(np.ones((s, s), bool))

    def layer(x, lp):
        a = lp["attn"]
        y = plain.rmsnorm(x, lp["ln1"]["scale"], eps)
        q = dot("sd,dn->sn", y, a["wq"])
        k = dot("sd,dn->sn", y, a["wk"])
        v = dot("sd,dn->sn", y, a["wv"])
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rotary(q.reshape(s, h, hd), theta)
        k = _rotary(k.reshape(s, kv, hd), theta)
        v = v.reshape(s, kv, hd)
        k = jnp.repeat(k, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
        logits = dot("shd,thd->hst", q, k) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
        o = dot("hst,thd->shd", probs, v).reshape(s, h * hd)
        x = x + dot("sn,nd->sd", o, a["wo"])
        y = plain.rmsnorm(x, lp["ln2"]["scale"], eps)
        mlp = lp["mlp"]
        g = jax.nn.silu(dot("sd,df->sf", y, mlp["wg"]))
        x = x + dot("sf,fd->sd", g * dot("sd,df->sf", y, mlp["wi"]),
                    mlp["wo"])
        return x, None

    emb = p["embed"]["tokens"]
    x, _ = jax.lax.scan(jax.checkpoint(layer), emb[tokens], p["layers"])
    x = plain.rmsnorm(x, p["final_norm"]["scale"], eps)
    logits = dot("sd,vd->sv", x, emb)
    ce = jax.nn.logsumexp(logits, axis=-1) \
        - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(ce * mask)


def flops_per_token(m):
    """Forward and backward operations per trained token: 6 per weight of
    every matrix product (the tied unembedding included, the embedding
    gather not), and causal attention's 6 per head dim per query-key pair,
    ``(S + 1) / 2`` pairs a token on average."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    h, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    f, v, s = m["intermediate_size"], m["vocab_size"], m["train"]["seq_len"]
    weights = L * (d * h * hd * 2 + 2 * d * kv * hd + 3 * d * f) + d * v
    attention = L * 6 * h * hd * 2 * (s + 1) / 2
    return 6 * weights + attention
