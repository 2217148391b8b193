"""Plain reference training steps: a configuration's own reference model
(``references/<name>.py``), AdamW and the first steps of training, in
``jax.numpy``.  Nothing here imports the program under test or takes
anything it made: the weights come from the run's seed by the init the
configuration's reference file writes out, and the batches from a plain
read of the seeded items.

``precision="highest"`` is the reference: float32 everywhere, matrix
products at ``Precision.HIGHEST``.  ``precision="fp8"`` is the control: the
same steps with every matrix product, forward and backward, on operands
rounded to float8 e4m3 (one scale per tensor, its largest magnitude at
448): the precision below the bfloat16 compute the configurations state.

A reference model file gives ``param_specs(model)`` (a nested dict of
``Leaf`` in the program's parameter layout) and ``row_loss(params, tokens,
targets, mask, model, dot)``, the summed cross-entropy of one sequence.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One parameter: shape and init (``normal`` with ``std``, ``zeros``,
    ``ones``)."""
    shape: Tuple[int, ...]
    init: str = "normal"
    std: float = 1.0


def fan_in_std(fan_in: int) -> float:
    return float(np.float32(1.0 / np.sqrt(fan_in)))


def init_params(specs, seed: int):
    """Weights from the seed, in one jitted call on the default device:
    leaf ``i`` of the flattened spec tree draws from the ``i``-th key of
    ``split(PRNGKey(seed), n)``."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, Leaf))

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, s in zip(keys, leaves):
            if s.init == "zeros":
                out.append(jnp.zeros(s.shape, jnp.float32))
            elif s.init == "ones":
                out.append(jnp.ones(s.shape, jnp.float32))
            else:
                out.append(jax.random.normal(k, s.shape, jnp.float32)
                           * np.float32(s.std))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed))


def _fp8(x):
    """Round to float8 e4m3, one scale per tensor (its largest magnitude
    maps to 448, the format's largest)."""
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _fp8_dot():
    """An einsum whose operands are rounded to float8 going forward and
    whose incoming gradient is rounded to float8 going back, so that the
    backward products run on float8 operands too."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def rounded_grad(y):
        return y

    rounded_grad.defvjp(lambda y: (y, None), lambda _, g: (_fp8(g),))

    def rounded(x):
        return x + jax.lax.stop_gradient(_fp8(x) - x)

    def dot(sub, a, b):
        return rounded_grad(jnp.einsum(sub, rounded(a), rounded(b),
                                       precision=jax.lax.Precision.HIGHEST))
    return dot


def make_dot(precision: str) -> Callable:
    """``dot(subscripts, a, b)``: an einsum in the run's precision."""
    import jax
    import jax.numpy as jnp

    if precision == "highest":
        def dot(sub, a, b):
            return jnp.einsum(sub, a, b,
                              precision=jax.lax.Precision.HIGHEST)
        return dot
    if precision == "fp8":
        return _fp8_dot()
    raise ValueError(f"unknown precision {precision!r}")


def rmsnorm(x, scale, eps: float):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def leaf_norms(tree) -> Dict[str, float]:
    """Euclidean norm of every leaf, by its path."""
    import jax
    import jax.numpy as jnp
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    vals = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        l.astype(jnp.float32)))) for l in jax.tree_util.tree_leaves(t)])(tree)
    return {jax.tree_util.keystr(p): float(v)
            for (p, _), v in zip(flat, vals)}


def lr_at(opt: Dict[str, Any], step: int) -> float:
    """The configuration's schedule: linear warmup, then cosine (or linear,
    or constant) decay to ``min_lr_ratio`` of the peak."""
    warm = min(1.0, step / max(1, opt["warmup_steps"]))
    frac = min(1.0, max(0.0, (step - opt["warmup_steps"])
                        / max(1, opt["total_steps"] - opt["warmup_steps"])))
    decay = {"cosine": 0.5 * (1.0 + np.cos(np.pi * frac)),
             "linear": 1.0 - frac, "constant": 1.0}[opt["schedule"]]
    decay = opt["min_lr_ratio"] + (1.0 - opt["min_lr_ratio"]) * decay
    return float(np.float32(opt["peak_lr"] * warm * decay))


def _adamw(opt: Dict[str, Any]):
    """AdamW with clipping to ``grad_clip_norm`` of the global norm and
    weight decay on every parameter; one jitted update."""
    import jax
    import jax.numpy as jnp

    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    def update(p, g, mu, nu, step, lr):
        leaves = jax.tree_util.tree_leaves(g)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in leaves))
        clip = jnp.minimum(1.0, opt["grad_clip_norm"] / (norm + 1e-9))
        g = jax.tree_util.tree_map(lambda x: x * clip, g)
        mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x,
                                    mu, g)
        nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x,
                                    nu, g)
        c1 = 1.0 - b1 ** step
        c2 = 1.0 - b2 ** step
        p = jax.tree_util.tree_map(
            lambda w, m, v: w - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * w), p, mu, nu)
        return p, g, mu, nu, norm

    return jax.jit(update, donate_argnums=(0, 2, 3))


def run_steps(ref, model: Dict[str, Any], seed: int,
              batches: List[Dict[str, np.ndarray]], *,
              precision: str = "highest") -> Dict[str, Any]:
    """Train the reference from the seed's weights through ``batches``
    (one per step), a sequence at a time, and return what the check
    compares: each step's loss and global gradient norm, the first clipped
    gradient's leaf norms, and each leaf's change over the steps."""
    import jax
    import jax.numpy as jnp

    dot = make_dot(precision)
    opt = model["train"]["optimizer"]
    params = init_params(ref.param_specs(model), seed)
    p0 = jax.device_get(params)

    def row(p, tokens, targets, mask):
        return ref.row_loss(p, tokens, targets, mask, model, dot)

    vg = jax.jit(jax.value_and_grad(row))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=(0,))
    scale = jax.jit(lambda g, s: jax.tree_util.tree_map(lambda x: x / s, g),
                    donate_argnums=(0,))
    update = _adamw(opt)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    mu, nu = zeros(params), zeros(params)
    out = {"loss": [], "grad_norm": [], "first_grad": None}
    for step, b in enumerate(batches, start=1):
        total, grads = 0.0, None
        for r in range(b["tokens"].shape[0]):
            loss_r, g_r = vg(params, b["tokens"][r], b["targets"][r],
                             b["loss_mask"][r])
            total += float(loss_r)
            grads = g_r if grads is None else add(grads, g_r)
        denom = max(float(b["loss_mask"].sum()), 1.0)
        grads = scale(grads, np.float32(denom))
        params, clipped, mu, nu, norm = update(
            params, grads, mu, nu, np.float32(step),
            np.float32(lr_at(opt, step)))
        if step == 1:
            out["first_grad"] = leaf_norms(clipped)
        del grads, clipped
        out["loss"].append(total / denom)
        out["grad_norm"].append(float(norm))
    p3 = jax.device_get(params)
    del params, mu, nu
    out["change"] = change_norms(p0, p3)
    return out


def change_norms(before, after) -> Dict[str, float]:
    """Norm of each leaf's change, from two host copies of the params.
    The float32 difference is exact: a few steps move a weight by far less
    than half its magnitude."""
    import jax
    flat0, _ = jax.tree_util.tree_flatten_with_path(before)
    flat1 = jax.tree_util.tree_leaves(after)
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        (np.asarray(b) - np.asarray(a)).ravel()))
        for (p, a), b in zip(flat0, flat1)}
