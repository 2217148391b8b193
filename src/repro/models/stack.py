"""Unified decoder/encoder block stack for all families.

One block definition covers dense / moe / ssm / hybrid / encdec / vlm; the
layer stack is ``lax.scan`` over stacked params so compile time and HLO size
are independent of depth (60-88 layer configs lower as one block).  Remat
policy is applied to the scan body for training.

A ``layer_pattern`` model (granite-4.0-h) interleaves layer kinds instead:
each kind has its own stacked params and block, and each maximal run of one
kind is a scan over its slice of that kind's stack.  Each mixer and MLP
runs under a ``jax.named_scope`` (``mixer.mamba``, ``mixer.attention``,
``mlp``), so the step's op names attribute every op to a layer kind.
"""
from __future__ import annotations

import functools
import itertools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding_rules import constrain
from repro.models import layers as ll
from repro.models import ssm as ssm_mod
from repro.models.module import spec, stack_specs


# --------------------------------------------------------------------------
# specs
# --------------------------------------------------------------------------
def kind_specs(cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    """One layer of a ``layer_pattern`` model: norm, mixer, norm, MLP."""
    mixer = {"ssm": ssm_mod.ssm_specs(cfg)} if kind == "mamba" \
        else {"attn": ll.attention_specs(cfg)}
    return {"ln1": ll.norm_specs(cfg), **mixer, "ln2": ll.norm_specs(cfg),
            "mlp": ll.mlp_specs(cfg)}


def block_specs(cfg: ModelConfig, *, cross: bool = False) -> Dict[str, Any]:
    if cfg.family == "ssm":
        return {"ln1": ll.norm_specs(cfg), "ssm": ssm_mod.ssm_specs(cfg)}
    p = {"ln1": ll.norm_specs(cfg), "attn": ll.attention_specs(cfg),
         "ln2": ll.norm_specs(cfg)}
    if cross:
        p["ln_cross"] = ll.norm_specs(cfg)
        p["cross"] = ll.attention_specs(cfg, cross=True)
    if cfg.family == "moe":
        p["moe"] = ll.moe_specs(cfg)
    else:
        p["mlp"] = ll.mlp_specs(cfg)
    if cfg.family == "hybrid":
        p["ssm"] = ssm_mod.ssm_specs(cfg)
        p["mix_norm_attn"] = ll.rmsnorm_specs(cfg.d_model)
        p["mix_norm_ssm"] = ll.rmsnorm_specs(cfg.d_model)
    return p


def stack_param_specs(cfg: ModelConfig, num_layers: Optional[int] = None,
                      cross: bool = False):
    if cfg.layer_pattern:
        kinds = cfg.layer_kinds
        return {k: stack_specs(kind_specs(cfg, k), kinds.count(k))
                for k in sorted(set(kinds))}
    n = num_layers if num_layers is not None else cfg.num_layers
    return stack_specs(block_specs(cfg, cross=cross), n)


def _use_rope(cfg: ModelConfig) -> bool:
    return cfg.family != "encdec"


def manual_layer_hook(cfg: ModelConfig, *, cross: bool = False):
    """Per-layer FSDP gather hook (bf16) for any scan over stacked layer
    params — run_stack, decode, and the K/V-collection scans.  Returns None
    outside a manual region (pure pjit / single device)."""
    from repro.distributed import dp_shard
    from repro.distributed.sharding_rules import current_ctx
    from repro.models.module import logical_axes
    ctx = current_ctx()
    if ctx is None or not ctx.manual:
        return None
    return dp_shard.layer_hook(logical_axes(block_specs(cfg, cross=cross)))


def _global_flags(cfg: ModelConfig) -> np.ndarray:
    flags = np.zeros(cfg.num_layers, dtype=bool)
    for i in cfg.global_attn_layers:
        flags[i] = True
    return flags


# --------------------------------------------------------------------------
# full-sequence block (train / prefill / encoder)
# --------------------------------------------------------------------------
RES_AXES = ("batch", "seq", "embed_act")
RES_AXES_SP = ("batch", "seq_res", "embed_act")


def _attn_branch(p, cfg, h, positions, is_global, causal, res_axes=RES_AXES):
    rope = _use_rope(cfg)
    if cfg.global_attn_layers and cfg.sliding_window:
        full = functools.partial(ll.attention, p["attn"], cfg, causal=causal,
                                 window=0, num_sink=0, rope=rope,
                                 out_axes=res_axes)
        win = functools.partial(ll.attention, p["attn"], cfg, causal=causal,
                                window=cfg.sliding_window,
                                num_sink=cfg.num_meta_tokens, rope=rope,
                                out_axes=res_axes)
        return jax.lax.cond(is_global,
                            lambda hh, pp: full(hh, positions=pp),
                            lambda hh, pp: win(hh, positions=pp),
                            h, positions)
    return ll.attention(p["attn"], cfg, h, positions=positions, causal=causal,
                        window=cfg.sliding_window,
                        num_sink=cfg.num_meta_tokens if cfg.sliding_window else 0,
                        rope=rope, out_axes=res_axes)


def block(p, cfg: ModelConfig, x, *, positions, is_global, causal=True,
          enc_out=None, ssm_state_out: bool = False, sp: bool = False):
    """One layer.  Returns (x, aux_loss[, ssm_cache]).

    ``sp``: manual sequence parallelism — the residual stream x is sharded
    on the model axis along seq (norms/adds run on 1/16th of the tokens and
    the saved activation stack shrinks 16x); attention/MLP/MoE inputs are
    all-gathered and their outputs reduce-scattered (AG+RS = half the wire
    bytes of the all-reduce they replace)."""
    aux = jnp.zeros((), jnp.float32)
    res_axes = RES_AXES_SP if sp else RES_AXES
    h = ll.norm(p["ln1"], x, cfg)
    if sp:
        h = constrain(h, *RES_AXES)         # all-gather for full-seq attn
    ssm_cache = None
    if cfg.family == "ssm":
        with jax.named_scope("mixer.mamba"):
            if ssm_state_out:
                y, ssm_cache = ssm_mod.ssm(p["ssm"], cfg, h, return_state=True)
            else:
                y = ssm_mod.ssm(p["ssm"], cfg, h)
        x = x + y
        return (x, aux, ssm_cache) if ssm_state_out else (x, aux)

    with jax.named_scope("mixer.attention"):
        attn_y = _attn_branch(p, cfg, h, positions, is_global, causal,
                              res_axes)
    if cfg.family == "hybrid":
        with jax.named_scope("mixer.mamba"):
            if ssm_state_out:
                ssm_y, ssm_cache = ssm_mod.ssm(p["ssm"], cfg, h,
                                               return_state=True)
            else:
                ssm_y = ssm_mod.ssm(p["ssm"], cfg, h)
        mixed = 0.5 * (ll.rmsnorm(p["mix_norm_attn"], attn_y, cfg.norm_eps)
                       + ll.rmsnorm(p["mix_norm_ssm"], ssm_y, cfg.norm_eps))
        x = x + mixed
    else:
        x = x + attn_y

    if enc_out is not None and "cross" in p:
        hc = ll.norm(p["ln_cross"], x, cfg)
        if sp:
            hc = constrain(hc, *RES_AXES)
        x = x + ll.attention(p["cross"], cfg, hc, positions=positions,
                             causal=False, kv_x=enc_out, rope=False,
                             out_axes=res_axes)

    h2 = ll.norm(p["ln2"], x, cfg)
    if sp:
        h2 = constrain(h2, *RES_AXES)
    with jax.named_scope("mlp"):
        if cfg.family == "moe":
            y, aux_moe = ll.moe(p["moe"], cfg, h2, out_axes=res_axes)
            aux = aux + aux_moe
        else:
            y = ll.mlp(p["mlp"], cfg, h2, out_axes=res_axes)
    x = x + y
    return (x, aux, ssm_cache) if ssm_state_out else (x, aux)


def kind_block(p, cfg: ModelConfig, kind: str, x, *, positions):
    """One layer of a ``layer_pattern`` model: each branch (the mixer, then
    the MLP) normed, scaled by ``residual_multiplier`` and added."""
    m = cfg.residual_multiplier
    h = ll.norm(p["ln1"], x, cfg)
    with jax.named_scope(f"mixer.{kind}"):
        if kind == "mamba":
            y = ssm_mod.ssm(p["ssm"], cfg, h)
        else:
            y = ll.attention(p["attn"], cfg, h, positions=positions,
                             causal=True,
                             rope=cfg.position_embedding == "rope",
                             scale=cfg.attention_multiplier or None)
    x = x + y * m
    h = ll.norm(p["ln2"], x, cfg)
    with jax.named_scope("mlp"):
        y = ll.mlp(p["mlp"], cfg, h)
    return x + y * m


def kind_runs(kinds):
    """Maximal runs of one kind: (kind, its first layer's index in that
    kind's stack, length)."""
    runs, seen = [], {}
    for kind, group in itertools.groupby(kinds):
        n = len(list(group))
        runs.append((kind, seen.get(kind, 0), n))
        seen[kind] = seen.get(kind, 0) + n
    return runs


def _remat(body, policy: str):
    if policy == "none":
        return body
    return jax.checkpoint(body, policy={
        "full": None,
        "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        "nothing": jax.checkpoint_policies.nothing_saveable,
    }[policy], prevent_cse=False)


def _run_pattern(params, cfg: ModelConfig, x, *, positions,
                 remat_policy: str):
    """The layers of a ``layer_pattern`` model in order: one scan per
    maximal run of one kind, over that run's slice of the kind's stack."""
    from repro.distributed.sharding_rules import current_ctx
    ctx = current_ctx()
    if ctx is not None and ctx.manual:
        raise NotImplementedError(
            f"{cfg.name}: a layer_pattern model has no manual-DP layer hook")
    # A run's slice of a stack is a copy, and its gradient is concatenated
    # back: both in the compute dtype for the weight matrices, which every
    # layer casts to it anyway (the same values, half the bytes of float32).
    stacks = {k: jax.tree_util.tree_map(
        lambda a: ll.cast(a) if a.ndim > 2 else a, v)
        for k, v in params.items()}
    for kind, start, n in kind_runs(cfg.layer_kinds):
        def body(xc, p_layer, kind=kind):
            return kind_block(p_layer, cfg, kind, xc,
                              positions=positions), None
        run = jax.tree_util.tree_map(lambda a: a[start:start + n],
                                     stacks[kind])
        x, _ = jax.lax.scan(_remat(body, remat_policy), x, run)
    return x


def run_stack(params, cfg: ModelConfig, x, *, positions, causal=True,
              enc_out=None, num_layers: Optional[int] = None,
              remat_policy: str = "none", collect_ssm_state: bool = False):
    """Scan the block over stacked params.

    Inside a manual-DP region (train step wrapped in shard_map over the
    batch axes — distributed/dp_shard.py) each scanned layer slice passes
    through a per-layer FSDP gather hook: data-sharded weight dims are
    all-gathered in bf16 right before use and the gather's transpose
    reduce-scatters the bf16 grads — ZeRO-3 with minimal explicit traffic.

    Returns (x, aux) or (x, aux, ssm_caches) when collect_ssm_state."""
    from repro.distributed.sharding_rules import current_ctx

    if cfg.layer_pattern:
        if collect_ssm_state or enc_out is not None or not causal:
            raise NotImplementedError(
                f"{cfg.name}: a layer_pattern model runs causal training "
                f"only (pattern {cfg.layer_pattern})")
        x = _run_pattern(params, cfg, x, positions=positions,
                         remat_policy=remat_policy)
        return x, jnp.zeros((), jnp.float32)

    n = num_layers if num_layers is not None else cfg.num_layers
    flags = jnp.asarray(_global_flags(cfg)[:n]) if cfg.global_attn_layers \
        else jnp.zeros(n, bool)

    ctx = current_ctx()
    cross = isinstance(params, dict) and "cross" in params
    param_hook = manual_layer_hook(cfg, cross=cross)
    sp = False
    if ctx is not None and ctx.manual:
        # manual sequence parallelism for attention-family residual streams
        # (SSM/hybrid scans need the full sequence; prefix tokens would
        # misalign the shard boundaries).
        sp = (cfg.uses_attention and not cfg.ssm_state_dim
              and cfg.num_meta_tokens == 0 and cfg.num_patches == 0
              and not collect_ssm_state
              and bool(ctx.mesh_axes_for("seq_res"))
              and x.shape[1] % ctx.mesh.shape["model"] == 0)
    if sp:
        x = constrain(x, *RES_AXES_SP)

    def body(carry, xs):
        xc, aux = carry
        p_layer, glob = xs
        if param_hook is not None:
            p_layer = param_hook(p_layer)
        if collect_ssm_state:
            xc, aux_l, ssm_cache = block(
                p_layer, cfg, xc, positions=positions, is_global=glob,
                causal=causal, enc_out=enc_out, ssm_state_out=True)
            return (xc, aux + aux_l), ssm_cache
        xc, aux_l = block(p_layer, cfg, xc, positions=positions,
                          is_global=glob, causal=causal, enc_out=enc_out,
                          sp=sp)
        return (xc, aux + aux_l), None

    body = _remat(body, remat_policy)
    (x, aux), ssm_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                        (params, flags))
    if collect_ssm_state:
        return x, aux, ssm_caches
    return x, aux


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------
def cache_shapes(cfg: ModelConfig, batch: int, max_len: int,
                 *, ring: bool, kv_dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Shapes/dtypes for the stacked decode cache (leading dim = layers).

    ``kv_dtype``: bf16 default; fp8 (float8_e4m3fn) halves cache HBM for
    MHA archs whose 32k caches exceed the 16 GB budget (production KV-cache
    quantization; reads upcast to fp32 inside attention)."""
    L = cfg.num_layers
    out: Dict[str, Any] = {}
    if cfg.uses_attention:
        T = min(max_len, cfg.sliding_window) if ring else max_len
        kvshape = (L, batch, T, cfg.num_kv_heads, cfg.head_dim)
        out["k"] = (kvshape, kv_dtype)
        out["v"] = (kvshape, kv_dtype)
    if cfg.ssm_state_dim:
        shapes = ssm_mod.ssm_cache_shapes(cfg, batch)
        out["ssm_conv"] = ((L,) + shapes["conv"][0], shapes["conv"][1])
        out["ssm_state"] = ((L,) + shapes["state"][0], shapes["state"][1])
    if cfg.encoder_layers:
        enc_kv = (L, batch, cfg.max_source_positions, cfg.num_kv_heads,
                  cfg.head_dim)
        out["cross_k"] = (enc_kv, kv_dtype)
        out["cross_v"] = (enc_kv, kv_dtype)
    return out


def use_ring_cache(cfg: ModelConfig) -> bool:
    return (cfg.sliding_window > 0 and not cfg.global_attn_layers
            and cfg.num_meta_tokens == 0)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, abstract=False,
               kv_dtype=jnp.bfloat16):
    ring = use_ring_cache(cfg)
    shapes = cache_shapes(cfg, batch, max_len, ring=ring, kv_dtype=kv_dtype)
    if abstract:
        return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in shapes.items()}
    return {k: jnp.zeros(s, d) for k, (s, d) in shapes.items()}


def decode_block(p, cfg: ModelConfig, x, cache_layer, *, positions,
                 is_global, ring: bool):
    """One decode layer.  cache_layer: per-layer slice of the stacked cache."""
    aux = jnp.zeros((), jnp.float32)
    h = ll.norm(p["ln1"], x, cfg)
    new_cache = dict(cache_layer)

    if cfg.family == "ssm":
        y, ssm_c = ssm_mod.ssm_decode(
            p["ssm"], cfg, h,
            {"conv": cache_layer["ssm_conv"], "state": cache_layer["ssm_state"]})
        new_cache["ssm_conv"], new_cache["ssm_state"] = ssm_c["conv"], ssm_c["state"]
        return x + y, new_cache, aux

    kv = {"k": cache_layer["k"], "v": cache_layer["v"]}
    rope = _use_rope(cfg)
    if cfg.global_attn_layers and cfg.sliding_window:
        def full_fn(hh):
            return ll.attention_decode(p["attn"], cfg, hh, kv,
                                       positions=positions, window=0,
                                       num_sink=0, rope=rope, ring=False)
        def win_fn(hh):
            return ll.attention_decode(p["attn"], cfg, hh, kv,
                                       positions=positions,
                                       window=cfg.sliding_window,
                                       num_sink=cfg.num_meta_tokens,
                                       rope=rope, ring=False)
        attn_y, new_kv = jax.lax.cond(is_global, full_fn, win_fn, h)
    else:
        attn_y, new_kv = ll.attention_decode(
            p["attn"], cfg, h, kv, positions=positions,
            window=cfg.sliding_window,
            num_sink=cfg.num_meta_tokens if cfg.sliding_window else 0,
            rope=rope, ring=ring)
    new_cache["k"], new_cache["v"] = new_kv["k"], new_kv["v"]

    if cfg.family == "hybrid":
        y_ssm, ssm_c = ssm_mod.ssm_decode(
            p["ssm"], cfg, h,
            {"conv": cache_layer["ssm_conv"], "state": cache_layer["ssm_state"]})
        new_cache["ssm_conv"], new_cache["ssm_state"] = ssm_c["conv"], ssm_c["state"]
        mixed = 0.5 * (ll.rmsnorm(p["mix_norm_attn"], attn_y, cfg.norm_eps)
                       + ll.rmsnorm(p["mix_norm_ssm"], y_ssm, cfg.norm_eps))
        x = x + mixed
    else:
        x = x + attn_y

    if "cross" in p and "cross_k" in cache_layer:
        hc = ll.norm(p["ln_cross"], x, cfg)
        y, _ = ll.attention_decode(
            p["cross"], cfg, hc, {}, positions=positions, rope=False,
            cross_kv=(cache_layer["cross_k"], cache_layer["cross_v"]))
        x = x + y

    h2 = ll.norm(p["ln2"], x, cfg)
    if cfg.family == "moe":
        y, aux_moe = ll.moe(p["moe"], cfg, h2)
        aux = aux + aux_moe
    else:
        y = ll.mlp(p["mlp"], cfg, h2)
    return x + y, new_cache, aux


def run_stack_decode(params, cfg: ModelConfig, x, cache, *, positions):
    """Scan decode over layers; cache is scanned as xs and re-emitted as ys."""
    n = cfg.num_layers
    ring = use_ring_cache(cfg)
    flags = jnp.asarray(_global_flags(cfg)) if cfg.global_attn_layers \
        else jnp.zeros(n, bool)

    param_hook = manual_layer_hook(cfg, cross="cross" in params)

    def body(carry, xs):
        xc = carry
        p_layer, glob, cache_layer = xs
        if param_hook is not None:
            p_layer = param_hook(p_layer)
        xc, new_cache, _aux = decode_block(p_layer, cfg, xc, cache_layer,
                                           positions=positions, is_global=glob,
                                           ring=ring)
        return xc, new_cache

    x, new_cache = jax.lax.scan(body, x, (params, flags, cache))
    return x, new_cache
