"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).

Topology: TPU v5e pods, 16x16 = 256 chips per pod; multi-pod = 2 pods (512
chips) with a leading "pod" axis (DCI-connected; pure data parallelism
crosses pods, model parallelism never does).

Every mesh is built with Auto axis types: the model code places activations
with ``with_sharding_constraint`` over logical axes (sharding_rules.py),
which only Auto axes accept.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis Auto (JAX >= 0.7 defaults to
    Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1, *, devices=None):
    """Smoke-scale mesh from whatever devices exist (tests: 1 or 8 CPU
    devices; the chip smoke: the host's 4 chips)."""
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    assert n % model_axis == 0, (n, model_axis)
    return make_mesh((n // model_axis, model_axis), ("data", "model"),
                     devices=devices)


def mesh_chips(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))


def batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
