"""Production meshes.  A FUNCTION (not a module constant) so importing this
module never touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
tests and benchmarks must keep seeing 1 device)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """Mesh whose axes are all ``Auto``: the sharding rules here are GSPMD
    constraints and ``shard_map`` specs, not sharding-in-types, so the
    ``Explicit`` axes ``jax.make_mesh`` defaults to would refuse them."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """The batch-sharding axes: ('pod','data') on the multi-pod mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over host devices (tests use 8 forced host devices)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


def parse_sim_mesh_shape(shape) -> tuple:
    """Normalize a sim-mesh shape — int, ``"8"``/``"8x1"``/``"4x2"`` string,
    or tuple — to a validated ``(data, model)`` pair."""
    if isinstance(shape, str):
        shape = tuple(int(s) for s in shape.lower().replace("×", "x")
                      .split("x"))
    elif isinstance(shape, int):
        shape = (shape,)
    if len(shape) > 2:
        raise ValueError(
            f"sim meshes have at most (data, model) axes, got {shape}")
    n_data = int(shape[0])
    n_model = int(shape[1]) if len(shape) > 1 else 1
    if n_data < 1 or n_model < 1:
        raise ValueError(f"mesh axes must be ≥ 1, got {shape}")
    return n_data, n_model


def make_sim_mesh(shape):
    """Mesh for mesh-sharded FL simulation (``sim_run --mesh-shape``): the
    ``data`` axis shards the cluster member axis of the dispatch-path plane
    programs, and a non-trivial ``model`` axis column-shards the parameter
    plane / bank / teacher stacks (2D dispatch for member models too large
    to replicate per device).  ``shape`` is an int (data-axis size), an
    ``"8"`` / ``"8x1"`` / ``"4x2"`` string, or a tuple ``(data[, model])``."""
    return make_host_mesh(*parse_sim_mesh_shape(shape))
