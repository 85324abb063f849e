"""Production mesh builders.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods × 256 chips as (pod=2, data=16, model=16) — the 'pod' axis
carries hierarchical data parallelism (gradient all-reduce crosses the
pod-to-pod DCN links; see repro.optim.compression for the int8 path).

Functions, not module constants — importing this module never touches jax
device state.
"""
from __future__ import annotations

import jax


def _auto(axes):
    """Auto (compiler-propagated) sharding on every axis — what the repo's
    ``with_sharding_constraint``/shard_map code is written against; jax's
    ``make_mesh`` default is now Explicit axes."""
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over locally-available devices (tests / examples)."""
    n = len(jax.devices())
    assert data * model <= n, f"need {data * model} devices, have {n}"
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(("data", "model")))


def dp_axes(mesh):
    """The (possibly hierarchical) batch-parallel axes of a mesh."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def all_axes(mesh):
    return tuple(mesh.axis_names)
