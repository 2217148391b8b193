"""Model classes: DecoderLM (dense / moe / ssm / hybrid / vlm) and
EncDecLM (whisper).  These are what the launchers, trainer and serving
engine consume; each exposes spec trees, loss / prefill / decode functions
and ShapeDtypeStruct input specs per assigned shape cell.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding_rules import constrain
from repro.models import layers as ll
from repro.models import stack as stk
from repro.models.module import (abstract_params, init_params, logical_axes,
                                 spec)


def _sinusoidal(positions, d):
    """positions: (B,S) -> (B,S,d) fixed sinusoidal embedding."""
    half = d // 2
    freqs = np.exp(-np.log(10000.0) * np.arange(half) / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def cross_entropy(logits, targets, mask):
    """fp32 CE with z-loss-free logsumexp; mask: (B,S) {0,1}."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = (lse - gold) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    return ce.sum() / denom


class DecoderLM:
    """Decoder-only LM covering dense, moe, ssm, hybrid and vlm families."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ---- specs -----------------------------------------------------------
    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        p = {
            "embed": ll.embed_specs(cfg),
            "layers": stk.stack_param_specs(cfg),
            "final_norm": ll.norm_specs(cfg),
        }
        if cfg.num_meta_tokens:
            p["meta_tokens"] = spec((cfg.num_meta_tokens, cfg.d_model),
                                    (None, "embed"), scale=0.02)
        if cfg.num_patches:
            p["patch_proj"] = {
                "w": spec((cfg.patch_embed_dim, cfg.d_model),
                          (None, "embed")),
                "b": spec((cfg.d_model,), ("embed",), init="zeros"),
            }
        return p

    def init(self, rng):
        return init_params(self.param_specs(), rng)

    def abstract_params(self):
        return abstract_params(self.param_specs())

    def logical_axes(self):
        return logical_axes(self.param_specs())

    # ---- embedding composition --------------------------------------------
    def _compose_input(self, params, batch):
        """Embed tokens, prepend patch embeds (vlm) and meta tokens (hymba).

        Returns (x, positions, text_start)."""
        cfg = self.cfg
        x = ll.embed(params["embed"], cfg, batch["tokens"])
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        B = x.shape[0]
        prefix = 0
        if cfg.num_patches and "patch_embeds" in batch:
            pe = jnp.einsum("bpk,kd->bpd", ll.cast(batch["patch_embeds"]),
                            ll.cast(params["patch_proj"]["w"]))
            pe = pe + ll.cast(params["patch_proj"]["b"])
            x = jnp.concatenate([pe, x], axis=1)
            prefix += cfg.num_patches
        if cfg.num_meta_tokens:
            meta = jnp.broadcast_to(
                ll.cast(params["meta_tokens"])[None],
                (B, cfg.num_meta_tokens, cfg.d_model))
            x = jnp.concatenate([meta, x], axis=1)
            prefix += cfg.num_meta_tokens
        S = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x = constrain(x, "batch", "seq", "embed_act")
        return x, positions, prefix

    # ---- train loss --------------------------------------------------------
    def loss(self, params, batch, *, remat_policy: str = "dots"):
        cfg = self.cfg
        x, positions, prefix = self._compose_input(params, batch)
        x, aux = stk.run_stack(params["layers"], cfg, x, positions=positions,
                               causal=True, remat_policy=remat_policy)
        x = ll.norm(params["final_norm"], x, cfg)
        if cfg.logits_scaling != 1.0:
            # logits / s == (x / s) @ E^T, at S x D instead of S x V
            x = x / cfg.logits_scaling
        if prefix:
            x = x[:, prefix:]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(batch["targets"].shape, jnp.float32)
        ce_sum, denom = ll.unembed_xent(params["embed"], cfg, x,
                                        batch["targets"], mask)
        loss = ce_sum / denom + aux
        metrics = {"loss": loss, "aux_loss": aux,
                   "tokens": mask.sum()}
        return loss, metrics

    # ---- inference ---------------------------------------------------------
    def _require_decoder(self):
        if not self.cfg.has_decoder:
            raise NotImplementedError(
                f"{self.cfg.name}: decoding a layer_pattern model is not "
                f"implemented (pattern {self.cfg.layer_pattern}); it trains "
                f"only")

    def prefill(self, params, batch, cache):
        """Run the prompt, fill the cache, return last-position logits."""
        self._require_decoder()
        cfg = self.cfg
        x, positions, prefix = self._compose_input(params, batch)
        B, S = x.shape[0], x.shape[1]

        if cfg.uses_attention:
            # run full-sequence attention while also materializing K/V into
            # the cache: recompute K/V per layer from the stack params.
            pass
        collect = bool(cfg.ssm_state_dim)
        out = stk.run_stack(params["layers"], cfg, x, positions=positions,
                            causal=True, remat_policy="none",
                            collect_ssm_state=collect)
        if collect:
            h, aux, ssm_caches = out
        else:
            h, aux = out
            ssm_caches = None
        h = ll.norm(params["final_norm"], h, cfg)
        logits = ll.unembed(params["embed"], cfg, h[:, -1:])

        new_cache = dict(cache)
        if cfg.uses_attention:
            # collect K/V already in the CACHE dtype: the (L,B,S,K,hd)
            # stack is cache-sized; stacking bf16/f32 then converting made
            # XLA materialize replicated f32 copies (100 GiB/dev on phi-3
            # prefill_32k).
            k, v = self._kv_for_prompt(params["layers"], x, positions,
                                       out_dtype=cache["k"].dtype)
            T = cache["k"].shape[2]
            write = min(S, T)
            # ring cache slots follow pos % T: keep the last T tokens and
            # roll them so token at absolute pos p lands in slot p % T.
            if stk.use_ring_cache(cfg) and S >= T:
                shift = (S - T) % T
                new_cache["k"] = jnp.roll(k[:, :, S - T:], shift, axis=2)
                new_cache["v"] = jnp.roll(v[:, :, S - T:], shift, axis=2)
            elif write == T and S == T:
                new_cache["k"], new_cache["v"] = k, v
            else:
                new_cache["k"] = jax.lax.dynamic_update_slice(
                    cache["k"], k[:, :, :write], (0, 0, 0, 0, 0))
                new_cache["v"] = jax.lax.dynamic_update_slice(
                    cache["v"], v[:, :, :write], (0, 0, 0, 0, 0))
        if ssm_caches is not None:
            new_cache["ssm_conv"] = ssm_caches["conv"].astype(
                cache["ssm_conv"].dtype)
            new_cache["ssm_state"] = ssm_caches["state"].astype(
                cache["ssm_state"].dtype)
        return logits, new_cache

    def _kv_for_prompt(self, stacked, x, positions, out_dtype=None):
        """K/V for every layer of a prompt, collected inside the layer scan.
        Returns a (L,B,S,K,hd) pair, seq-sharded like the cache and already
        in the cache dtype."""
        cfg = self.cfg
        hook = stk.manual_layer_hook(cfg)

        def body(carry, p_layer_and_flag):
            xc = carry
            p_layer, glob = p_layer_and_flag
            if hook is not None:
                p_layer = hook(p_layer)
            h = ll.norm(p_layer["ln1"], xc, cfg)
            q, k, v = ll._project_qkv(p_layer["attn"], cfg, h, h)
            if stk._use_rope(cfg):
                k = ll.rotary(k, positions, cfg.rope_theta)
            if out_dtype is not None:
                k, v = k.astype(out_dtype), v.astype(out_dtype)
            k = constrain(k, "batch", "kv_seq", "kv_heads_act", None)
            v = constrain(v, "batch", "kv_seq", "kv_heads_act", None)
            xc, _aux = stk.block(p_layer, cfg, xc, positions=positions,
                                 is_global=glob, causal=True)
            return xc, (k, v)

        flags = jnp.asarray(stk._global_flags(cfg)) if cfg.global_attn_layers \
            else jnp.zeros(cfg.num_layers, bool)
        _, (ks, vs) = jax.lax.scan(body, x, (stacked, flags))
        # constrain the STACKED result too: GSPMD back-propagates the
        # sharding into the scan's ys buffer (the per-iteration constraint
        # alone left the loop accumulator replicated over the model axis).
        ks = constrain(ks, "layers", "batch", "kv_seq", "kv_heads_act", None)
        vs = constrain(vs, "layers", "batch", "kv_seq", "kv_heads_act", None)
        return ks, vs

    @property
    def _prefix_len(self) -> int:
        """Internal tokens prepended to the text (meta tokens + patches)."""
        return self.cfg.num_meta_tokens + self.cfg.num_patches

    def init_cache(self, batch: int, max_len: int, abstract: bool = False,
                   kv_dtype=None):
        self._require_decoder()
        cfg = self.cfg
        internal = max_len + self._prefix_len
        import jax.numpy as _jnp
        return stk.init_cache(cfg, batch, internal, abstract=abstract,
                              kv_dtype=kv_dtype or _jnp.bfloat16)

    def decode_step(self, params, cache, tokens, positions):
        """tokens: (B,1); positions: (B,) text positions (the model offsets
        by the meta/patch prefix internally).  Returns (logits, new_cache)."""
        self._require_decoder()
        cfg = self.cfg
        x = ll.embed(params["embed"], cfg, tokens)
        x = constrain(x, "batch", "seq", "embed_act")
        pos_internal = positions + self._prefix_len
        x, new_cache = stk.run_stack_decode(params["layers"], cfg, x, cache,
                                            positions=pos_internal)
        x = ll.norm(params["final_norm"], x, cfg)
        logits = ll.unembed(params["embed"], cfg, x)
        return logits, new_cache

    # ---- shape cells -------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == "train":
            text = S - cfg.num_patches if cfg.num_patches else S
            d = {"tokens": jax.ShapeDtypeStruct((B, text), i32),
                 "targets": jax.ShapeDtypeStruct((B, text), i32),
                 "loss_mask": jax.ShapeDtypeStruct((B, text), jnp.float32)}
            if cfg.num_patches:
                d["patch_embeds"] = jax.ShapeDtypeStruct(
                    (B, cfg.num_patches, cfg.patch_embed_dim), jnp.bfloat16)
            return d
        if shape.kind == "prefill":
            text = S - cfg.num_patches if cfg.num_patches else S
            d = {"tokens": jax.ShapeDtypeStruct((B, text), i32)}
            if cfg.num_patches:
                d["patch_embeds"] = jax.ShapeDtypeStruct(
                    (B, cfg.num_patches, cfg.patch_embed_dim), jnp.bfloat16)
            return d
        # decode: one new token against a cache of size seq_len
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32),
                "positions": jax.ShapeDtypeStruct((B,), i32)}


class EncDecLM:
    """Whisper-style encoder-decoder; the audio conv frontend is a stub —
    inputs are precomputed frame embeddings (B, max_source_positions, D)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        return {
            "embed": ll.embed_specs(cfg),
            "encoder": stk.stack_param_specs(cfg, cfg.encoder_layers),
            "enc_norm": ll.norm_specs(cfg),
            "layers": stk.stack_param_specs(cfg, cross=True),
            "final_norm": ll.norm_specs(cfg),
        }

    def init(self, rng):
        return init_params(self.param_specs(), rng)

    def abstract_params(self):
        return abstract_params(self.param_specs())

    def logical_axes(self):
        return logical_axes(self.param_specs())

    def encode(self, params, frames):
        """frames: (B, T_src, D) stub embeddings -> encoder output."""
        cfg = self.cfg
        B, T, _ = frames.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        x = ll.cast(frames) + _sinusoidal(pos, cfg.d_model).astype(
            ll.COMPUTE_DTYPE)
        x = constrain(x, "batch", "kv_seq", "embed_act")
        x, _aux = stk.run_stack(params["encoder"], cfg, x, positions=pos,
                                causal=False, num_layers=cfg.encoder_layers,
                                remat_policy="none")
        return ll.norm(params["enc_norm"], x, cfg)

    def _embed_dec(self, params, tokens, positions):
        cfg = self.cfg
        x = ll.embed(params["embed"], cfg, tokens)
        x = x + _sinusoidal(positions, cfg.d_model).astype(x.dtype)
        return constrain(x, "batch", "seq", "embed_act")

    def loss(self, params, batch, *, remat_policy: str = "dots"):
        cfg = self.cfg
        enc = self.encode(params, batch["frames"])
        B, S = batch["tokens"].shape
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x = self._embed_dec(params, batch["tokens"], pos)
        x, aux = stk.run_stack(params["layers"], cfg, x, positions=pos,
                               causal=True, enc_out=enc,
                               remat_policy=remat_policy)
        x = ll.norm(params["final_norm"], x, cfg)
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(batch["targets"].shape, jnp.float32)
        ce_sum, denom = ll.unembed_xent(params["embed"], cfg, x,
                                        batch["targets"], mask)
        loss = ce_sum / denom + aux
        return loss, {"loss": loss, "aux_loss": aux, "tokens": mask.sum()}

    def init_cache(self, batch: int, max_len: int, abstract: bool = False,
                   kv_dtype=None):
        import jax.numpy as _jnp
        return stk.init_cache(self.cfg, batch, max_len, abstract=abstract,
                              kv_dtype=kv_dtype or _jnp.bfloat16)

    def _cross_kv(self, params, enc):
        """Precompute per-decoder-layer cross K/V: (L,B,T,K,hd) pair."""
        cfg = self.cfg
        wk = params["layers"]["cross"]["wk"]          # (L, D, K*hd)
        wv = params["layers"]["cross"]["wv"]
        from repro.distributed import dp_shard
        from repro.distributed.sharding_rules import current_ctx
        ctx = current_ctx()
        if ctx is not None and ctx.manual:
            dims = dp_shard.rule_manual_dims(ctx, ("layers", "embed",
                                                   "kv_heads"), ctx.manual)
            wrap = tuple(a for a in ctx.mesh.shape if a not in ctx.manual)
            import jax.numpy as _jnp
            auto = dp_shard._auto_entries(ctx, ("layers", "embed",
                                                "kv_heads"), wk.shape,
                                          ctx.manual)
            wk = dp_shard.gather_leaf(wk, dims, dtype=_jnp.bfloat16,
                                      auto_entries=auto, wrap_axes=wrap)
            wv = dp_shard.gather_leaf(wv, dims, dtype=_jnp.bfloat16,
                                      auto_entries=auto, wrap_axes=wrap)
        k = jnp.einsum("btd,ldn->lbtn", ll.cast(enc), ll.cast(wk))
        v = jnp.einsum("btd,ldn->lbtn", ll.cast(enc), ll.cast(wv))
        if "bk" in params["layers"]["cross"]:
            k = k + ll.cast(params["layers"]["cross"]["bk"])[:, None, None]
            v = v + ll.cast(params["layers"]["cross"]["bv"])[:, None, None]
        L, B, T, _ = k.shape
        k = k.reshape(L, B, T, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(L, B, T, cfg.num_kv_heads, cfg.head_dim)
        return k, v

    def prefill(self, params, batch, cache):
        cfg = self.cfg
        enc = self.encode(params, batch["frames"])
        B, S = batch["tokens"].shape
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x = self._embed_dec(params, batch["tokens"], pos)

        hook = stk.manual_layer_hook(cfg, cross=True)
        kv_dt = cache["k"].dtype

        def body(carry, xs):
            xc = carry
            p_layer, _glob = xs
            if hook is not None:
                p_layer = hook(p_layer)
            h = ll.norm(p_layer["ln1"], xc, cfg)
            _q, k, v = ll._project_qkv(p_layer["attn"], cfg, h, h)
            k = constrain(k.astype(kv_dt), "batch", "kv_seq",
                          "kv_heads_act", None)
            v = constrain(v.astype(kv_dt), "batch", "kv_seq",
                          "kv_heads_act", None)
            xc, _aux = stk.block(p_layer, cfg, xc, positions=pos,
                                 is_global=False, causal=True, enc_out=enc)
            return xc, (k, v)

        flags = jnp.zeros(cfg.num_layers, bool)
        h, (ks, vs) = jax.lax.scan(body, x, (params["layers"], flags))
        h = ll.norm(params["final_norm"], h, cfg)
        logits = ll.unembed(params["embed"], cfg, h[:, -1:])

        ck, cv = self._cross_kv(params, enc)
        new_cache = dict(cache)
        T = cache["k"].shape[2]
        write = min(S, T)
        if write == T and S == T:
            new_cache["k"], new_cache["v"] = ks, vs
        else:
            new_cache["k"] = jax.lax.dynamic_update_slice(
                cache["k"], ks[:, :, :write], (0, 0, 0, 0, 0))
            new_cache["v"] = jax.lax.dynamic_update_slice(
                cache["v"], vs[:, :, :write], (0, 0, 0, 0, 0))
        new_cache["cross_k"] = ck.astype(cache["cross_k"].dtype)
        new_cache["cross_v"] = cv.astype(cache["cross_v"].dtype)
        return logits, new_cache

    def decode_step(self, params, cache, tokens, positions):
        cfg = self.cfg
        x = self._embed_dec(params, tokens, positions[:, None])
        x, new_cache = stk.run_stack_decode(params["layers"], cfg, x, cache,
                                            positions=positions)
        x = ll.norm(params["final_norm"], x, cfg)
        logits = ll.unembed(params["embed"], cfg, x)
        return logits, new_cache

    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        frames = jax.ShapeDtypeStruct(
            (B, cfg.max_source_positions, cfg.d_model), jnp.bfloat16)
        if shape.kind == "train":
            return {"frames": frames,
                    "tokens": jax.ShapeDtypeStruct((B, S), i32),
                    "targets": jax.ShapeDtypeStruct((B, S), i32),
                    "loss_mask": jax.ShapeDtypeStruct((B, S), jnp.float32)}
        if shape.kind == "prefill":
            return {"frames": frames,
                    "tokens": jax.ShapeDtypeStruct((B, S), i32)}
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32),
                "positions": jax.ShapeDtypeStruct((B,), i32)}


def build_model(cfg: ModelConfig):
    if cfg.family == "encdec":
        return EncDecLM(cfg)
    return DecoderLM(cfg)
