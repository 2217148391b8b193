"""Layer library shared by all 10 architectures.

Conventions:
* params are pytrees of fp32 arrays; compute casts to ``COMPUTE_DTYPE``
  (bf16) at use-sites — fp32 master weights, bf16 math (TPU MXU native).
* projections keep *flattened* feature dims — q: (D, H*hd) — because every
  assigned arch has H*hd and K*hd divisible by the 16-way model axis even
  when H itself is not (yi-34b: 56 heads).  Reshape to heads happens after
  the sharding-constrained matmul.
* every activation passes through ``constrain`` with logical axes so the
  same model code lowers correctly on 1 CPU device and on the 512-chip mesh.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding_rules import constrain
from repro.kernels import ops
from repro.models.module import spec

COMPUTE_DTYPE = jnp.dtype(os.environ.get("REPRO_COMPUTE_DTYPE", "bfloat16"))


def cast(x):
    return x.astype(COMPUTE_DTYPE)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def rmsnorm_specs(d: int):
    return {"scale": spec((d,), ("embed",), init="ones")}


def rmsnorm(p, x, eps: float):
    return ops.rmsnorm(x, p["scale"], eps=eps)


def layernorm_specs(d: int):
    return {"scale": spec((d,), ("embed",), init="ones"),
            "bias": spec((d,), ("embed",), init="zeros")}


def layernorm(p, x, eps: float):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def norm_specs(cfg: ModelConfig):
    return layernorm_specs(cfg.d_model) if cfg.family == "encdec" \
        else rmsnorm_specs(cfg.d_model)


def norm(p, x, cfg: ModelConfig):
    return layernorm(p, x, cfg.norm_eps) if "bias" in p \
        else rmsnorm(p, x, cfg.norm_eps)


# --------------------------------------------------------------------------
# rotary position embedding
# --------------------------------------------------------------------------
def rotary(x, positions, theta: float):
    """x: (B,S,H,D) (D even); positions: (B,S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------
def attention_specs(cfg: ModelConfig, cross: bool = False):
    d, nq = cfg.d_model, cfg.num_heads * cfg.head_dim
    nkv = cfg.num_kv_heads * cfg.head_dim
    p = {
        "wq": spec((d, nq), ("embed", "heads")),
        "wk": spec((d, nkv), ("embed", "kv_heads")),
        "wv": spec((d, nkv), ("embed", "kv_heads")),
        "wo": spec((nq, d), ("heads", "embed")),
    }
    if cfg.attn_bias:
        p["bq"] = spec((nq,), ("heads",), init="zeros")
        p["bk"] = spec((nkv,), ("kv_heads",), init="zeros")
        p["bv"] = spec((nkv,), ("kv_heads",), init="zeros")
    if cfg.qk_norm and not cross:
        p["q_norm"] = spec((cfg.head_dim,), (None,), init="ones")
        p["k_norm"] = spec((cfg.head_dim,), (None,), init="ones")
    return p


def _heads_shards() -> int:
    """Number of shards the heads_act rule would apply (1 outside a mesh)."""
    from repro.distributed.sharding_rules import current_ctx
    ctx = current_ctx()
    if ctx is None:
        return 1
    n = 1
    for a in ctx.mesh_axes_for("heads_act"):
        n *= ctx.mesh.shape[a]
    return n


def _pad_plan(num_heads: int, num_kv: int, shards: int):
    """Smallest (K2, G2) with K2 >= K, G2 >= G and K2*G2 % shards == 0.

    Sharding attention by heads requires head count divisible by the model
    axis; five assigned archs (yi 56H, qwen2 14H, whisper 20H, granite 24H,
    hymba 25H) are not.  Padding GQA groups (and kv heads when needed) costs
    (K2*G2/H - 1) extra attention flops — always far below the 16x waste of
    replicating attention over the model axis, and it keeps the parameter
    layout unchanged (activations are padded, not weights)."""
    if shards <= 1 or num_heads % shards == 0:
        return None
    g = num_heads // num_kv
    best = None
    for k2 in range(num_kv, num_kv + shards + 1):
        for g2 in range(g, g + shards + 1):
            if (k2 * g2) % shards == 0:
                if best is None or k2 * g2 < best[0] * best[1]:
                    best = (k2, g2)
    return best


def _pad_attention_heads(q, k, v, cfg: ModelConfig, plan):
    K2, G2 = plan
    B, S, H, D = q.shape
    K = cfg.num_kv_heads
    G = H // K
    q = q.reshape(B, S, K, G, D)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, K2 - K), (0, G2 - G), (0, 0)))
    q = q.reshape(B, S, K2 * G2, D)
    if K2 != K:
        pad = ((0, 0), (0, 0), (0, K2 - K), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    q = constrain(q, "batch", "seq", "heads_act", None)
    k = constrain(k, "batch", "kv_seq", "kv_heads_act", None)
    v = constrain(v, "batch", "kv_seq", "kv_heads_act", None)
    return q, k, v


def _unpad_attention_heads(out, cfg: ModelConfig, plan):
    K2, G2 = plan
    B, S, _, D = out.shape
    K = cfg.num_kv_heads
    G = cfg.num_heads // K
    out = out.reshape(B, S, K2, G2, D)[:, :, :K, :G]
    return out.reshape(B, S, cfg.num_heads, D)


def _project_qkv(p, cfg: ModelConfig, x, kv_x):
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dn->bsn", cast(x), cast(p["wq"]))
    k = jnp.einsum("bsd,dn->bsn", cast(kv_x), cast(p["wk"]))
    v = jnp.einsum("bsd,dn->bsn", cast(kv_x), cast(p["wv"]))
    if "bq" in p:
        q = q + cast(p["bq"])
        k = k + cast(p["bk"])
        v = v + cast(p["bv"])
    # Constrain on the HEADS dim after the reshape, not the flat dim: a flat
    # constraint with H % axis != 0 makes GSPMD treat the reshape as a
    # partial contraction and all-reduce the attention logits per block
    # (observed: 235MB x 1536 all-reduces on qwen2).  When heads don't
    # divide the axis the full-seq path pads GQA groups (_pad_plan) instead.
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, k.shape[1], cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, v.shape[1], cfg.num_kv_heads, cfg.head_dim)
    if _pad_plan(cfg.num_heads, cfg.num_kv_heads, _heads_shards()) is None:
        q = constrain(q, "batch", "seq", "heads_act", None)
        k = constrain(k, "batch", "kv_seq", "kv_heads_act", None)
        v = constrain(v, "batch", "kv_seq", "kv_heads_act", None)
    if "q_norm" in p:
        q = ops.rmsnorm(q, p["q_norm"], eps=cfg.norm_eps)
        k = ops.rmsnorm(k, p["k_norm"], eps=cfg.norm_eps)
    return q, k, v


def attention(p, cfg: ModelConfig, x, *, positions=None, causal=True,
              window: int = 0, num_sink: int = 0, kv_x=None, rope=True,
              scale: Optional[float] = None,
              out_axes=("batch", "seq", "embed_act")):
    """Full-sequence attention (train / prefill / encoder / cross).

    ``scale``: the softmax scale (None: ``1/sqrt(head_dim)``).

    ``out_axes``: logical sharding of the output — under manual sequence
    parallelism the residual stream is seq-sharded on the model axis, so the
    wo contraction's psum lowers to a reduce-scatter (half the wire bytes).
    """
    kv_x = x if kv_x is None else kv_x
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, kv_x)
    if rope:
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        q = rotary(q, positions, cfg.rope_theta)
        k = rotary(k, positions, cfg.rope_theta)
    plan = _pad_plan(cfg.num_heads, cfg.num_kv_heads, _heads_shards())
    if plan is not None:
        q, k, v = _pad_attention_heads(q, k, v, cfg, plan)
    out = ops.attention(q, k, v, causal=causal, window=window,
                        num_sink=num_sink, scale=scale)
    if plan is not None:
        out = _unpad_attention_heads(out, cfg, plan)
    else:
        out = constrain(out, "batch", "seq", "heads_act", None)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    y = jnp.einsum("bsn,nd->bsd", out, cast(p["wo"]))
    return constrain(y, *out_axes)


def attention_decode(p, cfg: ModelConfig, x, kv_cache, *, positions,
                     window: int = 0, num_sink: int = 0, rope=True,
                     ring: bool = False, cross_kv=None):
    """Single-step decode.  x: (B,1,D); positions: (B,) absolute positions.

    kv_cache: {"k","v"}: (B,T,K,hd).  ``ring=True`` means the cache is a
    ring buffer of size T (== window, only valid when every layer is
    windowed); otherwise T is the full context and windowing is applied as a
    mask.  Returns (out, new_cache).
    """
    B = x.shape[0]
    if cross_kv is not None:
        q = jnp.einsum("bsd,dn->bsn", cast(x), cast(p["wq"]))
        if "bq" in p:
            q = q + cast(p["bq"])
        q = q.reshape(B, 1, cfg.num_heads, cfg.head_dim)
        q = constrain(q, "batch", "seq", "heads_act", None)
        k, v = cross_kv
        out = ops.attention(q, k, v, causal=False)
        out = constrain(out, "batch", "seq", "heads_act", None)
        out = out.reshape(B, 1, cfg.num_heads * cfg.head_dim)
        y = jnp.einsum("bsn,nd->bsd", out, cast(p["wo"]))
        return constrain(y, "batch", "seq", "embed_act"), kv_cache

    q, k_new, v_new = _project_qkv(p, cfg, x, x)
    if rope:
        q = rotary(q, positions[:, None], cfg.rope_theta)
        k_new = rotary(k_new, positions[:, None], cfg.rope_theta)

    T = kv_cache["k"].shape[1]
    slot = positions % T if ring else positions
    bidx = jnp.arange(B)
    k_cache = kv_cache["k"].at[bidx, slot].set(
        k_new[:, 0].astype(kv_cache["k"].dtype))
    v_cache = kv_cache["v"].at[bidx, slot].set(
        v_new[:, 0].astype(kv_cache["v"].dtype))
    # decode caches shard the *sequence* dim on the model axis (always
    # divisible, unlike kv-head counts) -> flash-decoding style partial
    # softmax with a small cross-shard reduction.
    k_cache = constrain(k_cache, "batch", "kv_seq", None, None)
    v_cache = constrain(v_cache, "batch", "kv_seq", None, None)

    j = jnp.arange(T)[None, :]
    pos_b = positions[:, None]
    if ring:
        # absolute position held by each ring slot; unwritten slots land in
        # the future or negative -> masked via kv_pos rules.
        kv_pos = pos_b - ((pos_b - j) % T)
        kv_pos = jnp.where(kv_pos > pos_b, -(10 ** 9), kv_pos)
        kv_valid = None
    else:
        kv_pos = jnp.broadcast_to(j, (B, T))
        kv_valid = positions + 1

    out = ops.attention(q, k_cache, v_cache, causal=True,
                        q_pos=pos_b, kv_pos=kv_pos, kv_valid=kv_valid,
                        window=window, num_sink=num_sink)
    out = constrain(out, "batch", "seq", "heads_act", None)
    out = out.reshape(B, 1, cfg.num_heads * cfg.head_dim)
    y = jnp.einsum("bsn,nd->bsd", out, cast(p["wo"]))
    y = constrain(y, "batch", "seq", "embed_act")
    return y, {"k": k_cache, "v": v_cache}


# --------------------------------------------------------------------------
# MLP (dense)
# --------------------------------------------------------------------------
def mlp_specs(cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp_activation == "gelu":
        return {
            "wi": spec((d, f), ("embed", "mlp")),
            "bi": spec((f,), ("mlp",), init="zeros"),
            "wo": spec((f, d), ("mlp", "embed")),
            "bo": spec((d,), ("embed",), init="zeros"),
        }
    return {
        "wi": spec((d, f), ("embed", "mlp")),
        "wg": spec((d, f), ("embed", "mlp")),
        "wo": spec((f, d), ("mlp", "embed")),
    }


def mlp(p, cfg: ModelConfig, x, *, out_axes=("batch", "seq", "embed_act")):
    if "bi" in p:
        h = jnp.einsum("bsd,df->bsf", cast(x), cast(p["wi"])) + cast(p["bi"])
        h = jax.nn.gelu(h)
        h = constrain(h, "batch", "seq", "mlp_act")
        y = jnp.einsum("bsf,fd->bsd", h, cast(p["wo"])) + cast(p["bo"])
    else:
        g = jnp.einsum("bsd,df->bsf", cast(x), cast(p["wg"]))
        h = jnp.einsum("bsd,df->bsf", cast(x), cast(p["wi"]))
        h = jax.nn.silu(g) * h
        h = constrain(h, "batch", "seq", "mlp_act")
        y = jnp.einsum("bsf,fd->bsd", h, cast(p["wo"]))
    return constrain(y, *out_axes)


# --------------------------------------------------------------------------
# MoE (top-k routing, expert-parallel dispatch)
# --------------------------------------------------------------------------
EP_DESIGN = 16   # production model-axis size; fixes the virtual layout


def _moe_parts(cfg: ModelConfig) -> int:
    """f-split factor of the virtual-expert layout.

    When E < EP_DESIGN (mixtral: 8 experts, 16-way axis) each expert is
    split into ``parts`` f-slices, giving V = E*parts virtual experts that
    shard cleanly on the model axis — EP x per-expert-TP hybrid with no
    weight replication and no idle ranks.  Mathematically identical to the
    unsplit expert (SwiGLU is elementwise in f; the wo contraction's f-sum
    becomes the EP combine psum)."""
    E, f = cfg.num_experts, cfg.expert_d_ff
    if 0 < E < EP_DESIGN and EP_DESIGN % E == 0:
        p = EP_DESIGN // E
        if f % p == 0:
            return p
    return 1


def moe_specs(cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    parts = _moe_parts(cfg)
    p = {"router": spec((d, e), ("embed", "experts"), scale=0.02)}
    if parts > 1:
        # virtual TP-split layout: (V, d, f/parts), model-sharded on V.
        # wo keeps the LOGICAL fan-in f (not f/parts) for faithful init.
        v, fl = e * parts, f // parts
        p.update({
            "wi": spec((v, d, fl), ("experts_virt", "embed", None),
                       fan_in_dims=(1,)),
            "wg": spec((v, d, fl), ("experts_virt", "embed", None),
                       fan_in_dims=(1,)),
            "wo": spec((v, fl, d), ("experts_virt", None, "embed"),
                       scale=1.0 / float(np.sqrt(f))),
        })
    else:
        # E >= axis (granite: 40): weights replicated over the model axis
        # (small: d_ff=512) and sliced per-rank at dispatch; capacity-split
        # replicas keep every rank busy when E % axis != 0.
        p.update({
            "wi": spec((e, d, f), ("experts", "embed", None),
                       fan_in_dims=(1,)),
            "wg": spec((e, d, f), ("experts", "embed", None),
                       fan_in_dims=(1,)),
            "wo": spec((e, f, d), ("experts", None, "embed"),
                       fan_in_dims=(1,)),
        })
    return p


def _dense_expert_weights(p, cfg: ModelConfig):
    """Un-virtualize (V, d, f/parts) -> (E, d, f) for the reference path."""
    parts = _moe_parts(cfg)
    if parts == 1:
        return p["wi"], p["wg"], p["wo"]
    E, f = cfg.num_experts, cfg.expert_d_ff
    d, fl = cfg.d_model, f // parts
    wi = p["wi"].reshape(E, parts, d, fl).transpose(0, 2, 1, 3).reshape(E, d, f)
    wg = p["wg"].reshape(E, parts, d, fl).transpose(0, 2, 1, 3).reshape(E, d, f)
    wo = p["wo"].reshape(E, parts * fl, d)
    return wi, wg, wo


def _route(p, cfg: ModelConfig, xf):
    """Router: returns (top_g, top_e, aux_loss)."""
    E, K = cfg.num_experts, cfg.experts_per_token
    logits = jnp.einsum("td,de->te", cast(xf), cast(p["router"]))
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)   # (T, E)
    top_g, top_e = jax.lax.top_k(gates, K)                        # (T, K)
    top_g = top_g / jnp.clip(top_g.sum(-1, keepdims=True), 1e-9)
    density = jnp.mean(jax.nn.one_hot(top_e[:, 0], E), axis=0)
    mean_gate = jnp.mean(gates, axis=0)
    aux = cfg.router_aux_loss * E * jnp.sum(density * mean_gate)
    return top_g, top_e, aux


def _sorted_assignments(top_g, top_e, T: int, E: int):
    """Sort (token, k) assignments by expert; returns (se, sg, st, pos_in_e)."""
    K = top_e.shape[1]
    flat_e = top_e.reshape(-1)
    flat_g = top_g.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    order = jnp.argsort(flat_e, stable=True)
    se, sg, st = flat_e[order], flat_g[order], flat_t[order]
    same = jax.nn.one_hot(se, E, dtype=jnp.int32)                 # (TK, E)
    pos_in_e = (jnp.cumsum(same, axis=0) - same)[jnp.arange(se.shape[0]), se]
    return se, sg, st, pos_in_e


def _slot_tables(se, sg, st, pos_in_e, *, num_slots: int, cap: int,
                 slot_of, cap_pos):
    """Scatter sorted assignments into dense (num_slots*cap,) tables."""
    ids = jnp.where(cap_pos < cap, slot_of * cap + cap_pos,
                    num_slots * cap)                              # OOB -> drop
    tok = jnp.zeros((num_slots * cap,), jnp.int32).at[ids].set(st, mode="drop")
    gate = jnp.zeros((num_slots * cap,), jnp.float32).at[ids].set(
        sg, mode="drop")
    used = jnp.zeros((num_slots * cap,), jnp.float32).at[ids].set(
        1.0, mode="drop")
    return tok, gate, used


def _moe_reference(p, cfg: ModelConfig, x,
                   out_axes=("batch", "seq", "embed_act")):
    """Capacity-bounded gather dispatch on one logical device (smoke tests,
    serve cells; the oracle the EP path is tested against)."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S
    xf = x.reshape(T, D)
    top_g, top_e, aux = _route(p, cfg, xf)
    se, sg, st, pos_in_e = _sorted_assignments(top_g, top_e, T, E)
    C = max(min(int(np.ceil(T * K / E * cfg.capacity_factor)), T), 1)
    tok, gate, used = _slot_tables(se, sg, st, pos_in_e, num_slots=E, cap=C,
                                   slot_of=se, cap_pos=pos_in_e)

    wi, wg, wo = _dense_expert_weights(p, cfg)
    xe = cast(xf)[tok].reshape(E, C, D)
    xe = xe * used.reshape(E, C, 1).astype(xe.dtype)
    xe = constrain(xe, "experts_act", "moe_cap", "embed_act")
    g = jnp.einsum("ecd,edf->ecf", xe, cast(wg))
    h = jax.nn.silu(g) * jnp.einsum("ecd,edf->ecf", xe, cast(wi))
    h = constrain(h, "experts_act", "moe_cap", "mlp_act")
    ye = jnp.einsum("ecf,efd->ecd", h, cast(wo))
    ye_flat = ye.reshape(E * C, D) * (gate * used)[:, None].astype(ye.dtype)
    y = jnp.zeros((T, D), ye_flat.dtype).at[tok].add(ye_flat)
    return constrain(y.reshape(B, S, D), *out_axes), aux


def _ep_axes():
    from repro.distributed.sharding_rules import current_ctx
    ctx = current_ctx()
    if ctx is None:
        return None, ()
    return ctx, ctx.mesh_axes_for("experts_virt", include_manual=True)


def moe(p, cfg: ModelConfig, x, *, out_axes=("batch", "seq", "embed_act")):
    """Top-k MoE.  Inside a manual-DP region with a model axis this runs the
    expert-parallel path: routing and dispatch tables are computed per data
    shard (no cross-shard dispatch collectives — tokens are replicated over
    the model axis, so each EP rank locally selects the tokens routed to ITS
    experts), the expert FFN runs sharded over the model axis, and the only
    collective is the combine psum of the (T_local, D) output.  The v0
    dense-dispatch path all-gathered (E, C, D) buffers and all-reduced 8-16
    GB per layer (EXPERIMENTS.md §Perf granite iteration).

    Returns (y, aux_loss)."""
    ctx, ep_axes = _ep_axes()
    ep = 1
    for a in ep_axes:
        ep *= ctx.mesh.shape[a]
    # EP path requires the batch axes to be manual (train manual-DP / the
    # serve manual wrapper); otherwise x is still globally sharded and the
    # reference path's constraints handle it.
    batch_manual = ctx is not None and all(
        a in ctx.manual
        for a in ctx.mesh_axes_for("batch", include_manual=True))
    if (ctx is None or ep <= 1 or not batch_manual or len(ep_axes) != 1
            or os.environ.get("REPRO_MOE_EP", "1") == "0"):
        return _moe_reference(p, cfg, x, out_axes)

    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    parts = _moe_parts(cfg)
    T = B * S
    xf = x.reshape(T, D)
    top_g, top_e, aux = _route(p, cfg, xf)
    se, sg, st, pos_in_e = _sorted_assignments(top_g, top_e, T, E)

    if parts > 1:
        # E < axis: V = E*parts f-split virtual experts; every part of an
        # expert receives the SAME capacity slots (partial-f compute).
        V = E * parts
        C = max(min(int(np.ceil(T * K / E * cfg.capacity_factor)), T), 1)
        tok_e, gate_e, used_e = _slot_tables(
            se, sg, st, pos_in_e, num_slots=E, cap=C, slot_of=se,
            cap_pos=pos_in_e)
        tok = jnp.tile(tok_e.reshape(E, 1, C), (1, parts, 1)).reshape(-1)
        gate = jnp.tile(gate_e.reshape(E, 1, C), (1, parts, 1)).reshape(-1)
        used = jnp.tile(used_e.reshape(E, 1, C), (1, parts, 1)).reshape(-1)
    else:
        # E >= axis: V = round-up(E, ep) virtual slots, v -> expert v % E —
        # experts with two slots (capacity replicas) keep the padded ranks
        # busy; replicas share weights exactly (same slice), so the model is
        # unchanged.
        V = int(np.ceil(E / ep) * ep)
        C = max(int(np.ceil(T * K / V * cfg.capacity_factor)), 1)
        n_virt = (V - se - 1) // E + 1          # replicas of this expert
        replica = pos_in_e % n_virt
        cap_pos = pos_in_e // n_virt
        v_of = replica * E + se
        tok, gate, used = _slot_tables(se, sg, st, pos_in_e, num_slots=V,
                                       cap=C, slot_of=v_of, cap_pos=cap_pos)

    Vloc = V // ep
    axis = ep_axes[0]

    def body(xf, wi, wg, wo, tok, gate, used):
        r = jax.lax.axis_index(axis)
        if parts > 1:
            wi_l, wg_l, wo_l = wi, wg, wo       # already (Vloc, d, f/parts)
        else:
            idx = (r * Vloc + jnp.arange(Vloc)) % E
            wi_l, wg_l, wo_l = wi[idx], wg[idx], wo[idx]
        xe = cast(xf)[tok].reshape(Vloc, -1, D)
        xe = xe * used.reshape(Vloc, -1, 1).astype(xe.dtype)
        g = jnp.einsum("ecd,edf->ecf", xe, cast(wg_l))
        h = jax.nn.silu(g) * jnp.einsum("ecd,edf->ecf", xe, cast(wi_l))
        ye = jnp.einsum("ecf,efd->ecd", h, cast(wo_l))
        w8 = (gate * used).reshape(Vloc, -1, 1).astype(ye.dtype)
        y = jnp.zeros((T, D), ye.dtype).at[tok].add(
            (ye * w8).reshape(-1, D))
        return jax.lax.psum(y, axis)

    from jax.sharding import PartitionSpec as P
    w_spec = P(axis) if parts > 1 else P()
    y = jax.shard_map(
        body, in_specs=(P(), w_spec, w_spec, w_spec, P(axis), P(axis),
                        P(axis)),
        out_specs=P(), axis_names={axis}, check_vma=False)(
        xf, p["wi"], p["wg"], p["wo"], tok, gate, used)
    y = y.reshape(B, S, D)
    return constrain(y, *out_axes), aux


# --------------------------------------------------------------------------
# embedding / unembedding
# --------------------------------------------------------------------------
def embed_specs(cfg: ModelConfig):
    p = {"tokens": spec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                        init="embed", scale=0.02)}
    if not cfg.tie_embeddings:
        p["unembed"] = spec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                            scale=0.02)
    return p


def embed(p, cfg: ModelConfig, tokens):
    # NOTE: no strong-typed scalar math here — an `x * np.sqrt(1.0)`
    # (np.float64) silently promoted the WHOLE residual stream to f32:
    # 2x the saved-activation HBM, 2x every residual psum (found via the
    # trip-weighted traffic profile, EXPERIMENTS.md §Perf iteration 3).
    x = cast(p["tokens"])[tokens]
    return constrain(x, "batch", "seq", "embed_act")


def unembed(p, cfg: ModelConfig, x):
    w = cast(p["tokens"]).T if cfg.tie_embeddings else cast(p["unembed"])
    logits = jnp.einsum("bsd,dv->bsv", cast(x), w)
    if cfg.logit_softcap > 0:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return constrain(logits, "batch", "seq", "vocab_act")


def unembed_xent(p, cfg: ModelConfig, x, targets, mask):
    """Vocab-sharded fused unembed + cross-entropy.

    The dense path materializes (B, S, V) f32 logits — 4.9 GB/device for
    qwen2's 152k vocab at one 4k microbatch — and the label gather over a
    model-sharded V triggers SPMD involuntary full rematerialization.  Here
    each model rank computes only its (B, S, V/16) logit slice; the
    cross-shard reduction is three (B, S) psums (max / sum-exp / gold).
    Falls back to the dense path off-mesh.  Returns (ce_sum, denom)."""
    from repro.distributed.sharding_rules import current_ctx
    ctx = current_ctx()
    axes = ctx.mesh_axes_for("vocab_act", include_manual=True) if ctx else ()
    axes = tuple(a for a in axes if a not in (ctx.manual if ctx else ()))
    n = 1
    for a in axes:
        n *= ctx.mesh.shape[a]
    batch_manual = ctx is not None and all(
        a in ctx.manual
        for a in ctx.mesh_axes_for("batch", include_manual=True))
    if ctx is None or n <= 1 or len(axes) != 1 or not batch_manual:
        logits = unembed(p, cfg, x)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(logits.astype(jnp.float32),
                                   targets[..., None], axis=-1)[..., 0]
        ce = (lse - gold) * mask
        return ce.sum(), jnp.maximum(mask.sum(), 1.0)

    axis = axes[0]
    V = cfg.vocab_size
    Vp = int(np.ceil(V / n) * n)
    w = cast(p["tokens"]) if cfg.tie_embeddings else cast(p["unembed"]).T
    if Vp != V:
        w = jnp.pad(w, ((0, Vp - V), (0, 0)))           # (Vp, D) row-padded
    Vloc = Vp // n
    softcap = cfg.logit_softcap

    def body(x, w_loc, targets, mask):
        r = jax.lax.axis_index(axis)
        off = r * Vloc
        logits = jnp.einsum("bsd,vd->bsv", cast(x), w_loc).astype(jnp.float32)
        if softcap > 0:
            logits = softcap * jnp.tanh(logits / softcap)
        # mask padded vocab rows
        j = off + jnp.arange(Vloc)
        logits = jnp.where(j[None, None, :] < V, logits, -1e30)
        # stop_gradient is exact here (dLSE/dm = 0 analytically) and keeps
        # pmax out of the backward graph (no pmax differentiation rule).
        m_loc = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
        m = jax.lax.pmax(m_loc, axis)
        se = jax.lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1),
                          axis)
        lse = m + jnp.log(se)
        t_loc = jnp.clip(targets - off, 0, Vloc - 1)
        in_range = (targets >= off) & (targets < off + Vloc)
        gold_loc = jnp.take_along_axis(logits, t_loc[..., None],
                                       axis=-1)[..., 0]
        gold = jax.lax.psum(jnp.where(in_range, gold_loc, 0.0), axis)
        ce = (lse - gold) * mask
        return ce.sum(), jnp.maximum(mask.sum(), 1.0)

    from jax.sharding import PartitionSpec as P
    kw = {} if (ctx.manual) else {"mesh": ctx.mesh}
    ce_sum, denom = jax.shard_map(
        body, in_specs=(P(), P(axis, None), P(), P()),
        out_specs=(P(), P()), axis_names={axis}, check_vma=False, **kw)(
        x, w, targets, mask)
    return ce_sum, denom
