"""Production meshes (deliverable e).

Defined as FUNCTIONS so importing this module never touches jax device
state.  Single pod: 16 x 16 = 256 chips (data x model).  Multi-pod: 2 pods
x 256 = 512 chips; the ``pod`` axis is pure data parallelism whose gradient
all-reduce is the only cross-pod collective (DCN-friendly).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "batch_axes"]


def _auto(n_axes: int) -> tuple:
    """Auto-sharded axes: ``jax.make_mesh`` defaults to Explicit axes, which
    the model stack's gathers reject."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh() -> jax.sharding.Mesh:
    """Whatever devices exist, as (data, model) with model innermost."""
    n = len(jax.devices())
    model = 1
    for m in (16, 8, 4, 2):
        if n % m == 0 and n >= m:
            model = m
            break
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=_auto(2))


def batch_axes(mesh: jax.sharding.Mesh, global_batch: int) -> tuple[str, ...]:
    """The data-parallel axes usable for a given batch (divisibility)."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    out: list[str] = []
    prod = 1
    for a in axes:
        if global_batch % (prod * mesh.shape[a]) == 0:
            out.append(a)
            prod *= mesh.shape[a]
    return tuple(out)
