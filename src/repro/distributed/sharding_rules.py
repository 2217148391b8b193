"""Logical-axis -> mesh-axis sharding rules (MaxText-style), with an
auto-divisibility guard so every (arch x shape x mesh) cell compiles.

Parameters and activations are annotated with *logical* axis names; a rule set
maps those to physical mesh axes.  ``build_sharding`` drops any mesh axis that
does not evenly divide the corresponding dimension (e.g. granite's vocab=49155
on a 16-way model axis) and records the drop, instead of failing to lower —
such drops are replication, which is always correct, and the roofline report
surfaces the cost.
"""
from __future__ import annotations

import contextlib
import contextvars
import logging
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)

MeshAxes = Union[None, str, Tuple[str, ...]]

# --- rule sets -------------------------------------------------------------
# batch-like axes shard over ("pod","data") when the pod axis exists; the
# helper filters mesh axes that are absent from the mesh, so one rule set
# serves single-pod and multi-pod meshes.

TRAIN_RULES: Dict[str, MeshAxes] = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    "seq_res": "model",        # residual stream between layers (manual-SP:
                               # stack.run_stack gathers before attention/MLP
                               # and reduce-scatters their outputs)
    "kv_seq": None,
    "qkv": "model",            # flattened heads*head_dim activation dim
    "heads_act": "model",      # per-head activation dim (guarded: replicates
    "kv_heads_act": "model",   # when head count doesn't divide the axis)
    "mlp_act": "model",
    "embed_act": None,
    "vocab_act": "model",
    "experts_act": None,
    "moe_cap": ("pod", "data"),    # MoE dispatch capacity slots (DP-sharded)
    "ssm_inner_act": "model",
    # params
    "vocab": "model",
    "embed": "data",           # FSDP: gather-per-layer under scan
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": None,
    "experts_virt": "model",   # virtual EP layout (E<16 archs; see layers.moe)
    "expert_mlp": "model",
    "ssm_inner": "model",
    "ssm_heads": "model",
    "ssm_state": None,
    "conv": None,
    "layers": None,
    "pos": None,
}

# Megatron-style sequence parallelism for the residual stream: norms/embeds
# run on seq-sharded activations; enabled for long-sequence training cells.
TRAIN_SP_RULES = dict(TRAIN_RULES, seq="model")

# Serving: weight-stationary sharding — params replicated over the batch
# axes (no optimizer state to amortize; per-step FSDP gathers would
# dominate decode latency) and TP over model; batch over data; KV-cache
# *sequence* dim over model (flash-decoding style partial softmax —
# kv-head counts don't divide 16, seq always does).
SERVE_RULES: Dict[str, MeshAxes] = dict(
    TRAIN_RULES,
    batch=("pod", "data"),
    kv_seq="model",
    embed=None,
    seq_res=None,
    vocab="model",
)

# >20B params: bf16 weights / 16-way TP crowd HBM next to the KV cache, so
# serving keeps the FSDP data-axis sharding and pays per-layer bf16 gathers
# (mistral-large: 15.4 GiB/dev replicated vs 1 GiB sharded + 0.3 s/token of
# gather wire — the capacity/latency trade recorded in DESIGN.md).
SERVE_RULES_BIG = dict(SERVE_RULES, embed="data")

# Long-context prefill: shard the sequence dimension as well.
PREFILL_RULES = dict(SERVE_RULES, seq=None)
PREFILL_RULES_BIG = dict(SERVE_RULES_BIG, seq=None)


_ACTIVE: contextvars.ContextVar = contextvars.ContextVar("sharding_ctx",
                                                         default=None)


class ShardingCtx:
    def __init__(self, mesh: Mesh, rules: Dict[str, MeshAxes]):
        self.mesh = mesh
        self.rules = dict(rules)
        self.dropped: list = []
        # mesh axes currently under manual (shard_map) control: constrain()
        # and partition_spec() must not mention them (the array dims they
        # shard are already local inside the manual region).
        self.manual: frozenset = frozenset()

    @contextlib.contextmanager
    def manual_region(self, axes):
        prev = self.manual
        self.manual = frozenset(axes) | prev
        try:
            yield self
        finally:
            self.manual = prev

    def mesh_axes_for(self, logical: Optional[str],
                      *, include_manual: bool = False) -> Tuple[str, ...]:
        if logical is None:
            return ()
        axes = self.rules.get(logical)
        if axes is None:
            return ()
        if isinstance(axes, str):
            axes = (axes,)
        out = tuple(a for a in axes if a in self.mesh.shape)
        if not include_manual:
            out = tuple(a for a in out if a not in self.manual)
        return out

    def partition_spec(self, logical_axes: Sequence[Optional[str]],
                       dims: Optional[Sequence[int]] = None) -> P:
        """Map logical axes to a PartitionSpec; drop non-dividing mesh axes."""
        entries = []
        used = set()
        for i, name in enumerate(logical_axes):
            axes = self.mesh_axes_for(name)
            axes = tuple(a for a in axes if a not in used)
            if dims is not None and axes:
                shards = 1
                kept = []
                for a in axes:
                    n = self.mesh.shape[a]
                    if dims[i] % (shards * n) == 0:
                        kept.append(a)
                        shards *= n
                    else:
                        self.dropped.append((name, a, dims[i]))
                axes = tuple(kept)
            used.update(axes)
            if not axes:
                entries.append(None)
            elif len(axes) == 1:
                entries.append(axes[0])
            else:
                entries.append(axes)
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    def named_sharding(self, logical_axes, dims=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.partition_spec(logical_axes, dims))


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: Dict[str, MeshAxes]):
    ctx = ShardingCtx(mesh, rules)
    token = _ACTIVE.set(ctx)
    try:
        with mesh:
            yield ctx
    finally:
        _ACTIVE.reset(token)


def current_ctx() -> Optional[ShardingCtx]:
    return _ACTIVE.get()


def constrain(x, *logical_axes: Optional[str]):
    """with_sharding_constraint by logical activation axes; no-op outside a
    ``use_rules`` context (so smoke tests on 1 device run unannotated).

    Inside a manual region (shard_map over the DP axes) the constraint uses
    a bare PartitionSpec — the context's abstract mesh — and never mentions
    manual axes (``mesh_axes_for`` filters them)."""
    ctx = current_ctx()
    if ctx is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(f"constrain rank mismatch: {logical_axes} vs {x.shape}")
    pspec = ctx.partition_spec(logical_axes, x.shape)
    if ctx.manual:
        return jax.lax.with_sharding_constraint(x, pspec)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, pspec))


def params_shardings(model, ctx: ShardingCtx):
    """NamedSharding tree for a model's params from its logical-axes tree."""
    return jax.tree_util.tree_map(
        lambda axes, arr: ctx.named_sharding(axes, arr.shape),
        model.logical_axes(), model.abstract_params(),
        is_leaf=lambda t: isinstance(t, tuple) and all(
            a is None or isinstance(a, str) for a in t),
    )


def batch_shardings(specs: Dict, ctx: ShardingCtx):
    """NamedSharding per batch field: leading dim on the batch axes, the
    rest replicated."""
    return {k: ctx.named_sharding(("batch",) + (None,) * (v.ndim - 1),
                                  v.shape)
            for k, v in specs.items()}


def rules_for(kind: str, *, seq_parallel: bool = False,
              big_params: bool = False) -> Dict[str, MeshAxes]:
    if kind == "train":
        return TRAIN_SP_RULES if seq_parallel else TRAIN_RULES
    if kind == "prefill":
        return PREFILL_RULES_BIG if big_params else PREFILL_RULES
    if kind == "decode":
        return SERVE_RULES_BIG if big_params else SERVE_RULES
    raise ValueError(kind)
