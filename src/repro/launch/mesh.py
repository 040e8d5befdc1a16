"""Production meshes.  A function (not a module-level constant) so importing
this module never touches jax device state.

Every mesh is built through :func:`make_mesh`, with Auto axis types: the
model annotates tensors with ``with_sharding_constraint``, which only
accepts Auto axes (``jax.make_mesh`` defaults to Explicit)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto (see the module doc)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke runs (axes present, sizes 1)."""
    return make_mesh((1, 1), ("data", "model"))


def make_mesh_from_devices(spec: str | None = None):
    """Largest (data, model) mesh over the visible devices.

    The default puts every device on the model axis — shape ``(1, n)``
    — which is what tensor-parallel serving wants (cache heads and
    weight fan-out shard, batch stays whole; see ``ServeEngine`` ``tp=``).
    A single visible device degenerates to the (1, 1) host mesh, so the
    axes are always present and sharding annotations never need a
    no-mesh special case.  ``spec`` — ``"DATA,MODEL"`` — overrides the
    shape; the sizes must multiply to the visible device count."""
    n = jax.device_count()
    if spec is None:
        shape = (1, n)
    else:
        try:
            shape = tuple(int(p) for p in spec.split(","))
        except ValueError:
            shape = ()
        if len(shape) != 2 or any(p < 1 for p in shape):
            raise ValueError(
                f"mesh spec {spec!r}: want 'DATA,MODEL' positive sizes")
        if shape[0] * shape[1] != n:
            raise ValueError(
                f"mesh spec {spec!r}: {shape[0]}x{shape[1]} != "
                f"{n} visible devices")
    return make_mesh(shape, ("data", "model"))
