"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: (data=16, model=16) = 256 chips (TPU v5e
pod).  Multi-pod: (pod=2, data=16, model=16) = 512 chips — the ``pod`` axis
composes with ``data`` for the gradient all-reduce (hierarchical: ICI ring
inside the pod, DCN across pods) and carries the compressed-gradient
collective (optim/compression.py).

The axes generalize: any (pod, data, model) product works, which is the
1000+-node posture — scale `pod` out over DCN, keep `model` inside the ICI
domain.

Every axis is ``AxisType.Auto``: the model code states layouts with
``with_sharding_constraint`` and committed parameter shardings and lets the
partitioner propagate the rest.  (``jax.make_mesh``'s default, ``Explicit``,
would make gathers, ``dynamic_update_slice`` and unpad slices on sharded
operands type errors.)
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """Arbitrary mesh (tests use small host-device meshes, e.g. (2, 4));
    ``devices`` picks a subset (default: the first ``prod(shape)``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes (pod folds into DP for the batch dimension)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def set_mesh(mesh):
    """``with set_mesh(mesh):`` — the ambient mesh for jit and sharding hints."""
    return jax.set_mesh(mesh)
