"""jit'd pytree wrapper for the fedagg kernel: ravel → kernel → unravel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fedagg.kernel import weighted_aggregate

# Bytes of one (C, block_d) fp32 input panel: double-buffered, plus the
# weighted product, it stays well inside the default scoped VMEM.
PANEL_BYTES = 2 << 20


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pick_block(C: int, D: int) -> int:
    """Widest 128-multiple column block whose (C, block) fp32 panel fits
    ``PANEL_BYTES``; all of D when that is narrower.  D need not divide by
    it: the kernel's last block overhangs."""
    bd = max(128, PANEL_BYTES // (4 * C) // 128 * 128)
    return D if D <= bd else bd


def aggregate_plane(plane, weights, *, interpret: bool | None = None):
    """Weighted aggregate straight on a flat parameter plane (C, D) → (D,).

    The plane path of the dispatch pipeline: the plane is already one
    contiguous buffer, so — unlike ``aggregate_tree`` — there is no per-call
    flatten/concatenate.

    Under ``shard_map`` this is the PER-DEVICE inner loop of the sharded
    plane aggregation (``aggregation.aggregate_plane_sharded`` and the
    mesh-sharded dispatch program): C is then the device's LOCAL member-row
    count — the zero-weight padding rows that make C divisible by the mesh
    axis contract to nothing — and one psum over ``data`` outside completes
    the all-reduce.  On a 2D (data × model) mesh D is the device's LOCAL
    column slice; column slices never need reducing, so no collective is
    added."""
    interpret = _interpret_default() if interpret is None else interpret
    C, D = plane.shape
    return weighted_aggregate(plane.astype(jnp.float32),
                              weights.astype(jnp.float32),
                              block_d=_pick_block(C, D), interpret=interpret)


def aggregate_tree(params_stack, weights, *, interpret: bool | None = None):
    """params_stack: pytree with leading client axis C → aggregated pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(params_stack)
    C = leaves[0].shape[0]
    cat = jnp.concatenate([l.reshape(C, -1) for l in leaves], axis=1)
    out = aggregate_plane(cat, weights, interpret=interpret)
    parts = []
    pos = 0
    for leaf in leaves:
        sz = leaf[0].size
        parts.append(out[pos:pos + sz].reshape(leaf.shape[1:]).astype(leaf.dtype))
        pos += sz
    return jax.tree_util.tree_unflatten(treedef, parts)
