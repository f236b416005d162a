"""Flat parameter plane: the device-resident currency of the dispatch path.

A cluster's parameters are raveled ONCE at setup into a contiguous fp32
vector padded to a lane-friendly multiple (``PLANE_ALIGN``), so that the
multi-round ``lax.scan`` dispatch, the Pallas ``kernels/fedagg`` weighted
aggregate, ``fedavg_delta`` and the buffered-async merges all operate on a
single ``(capacity, D_pad)`` buffer with no per-call ``tree_flatten`` /
``concatenate`` / ``pad``.  Pytrees reappear only at evaluation/reporting
boundaries (``PlaneSpec.to_params``) and inside the per-member model forward
(where XLA fuses the unravel slices away).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

# Multiple every plane length is padded to: the 128-lane TPU register tile,
# so every per-device column slice starts on a lane boundary.
PLANE_ALIGN = 128


@dataclass(frozen=True)
class PlaneSpec:
    """Ravel/unravel recipe for one cluster level's parameter pytree."""
    d: int                      # true parameter count
    d_pad: int                  # padded plane length (multiple of PLANE_ALIGN)
    unravel: Callable           # (d,) -> params pytree (jax-traceable)

    def to_plane(self, params) -> jnp.ndarray:
        """params pytree -> (d_pad,) fp32 plane (jax-traceable)."""
        flat, _ = ravel_pytree(params)
        flat = flat.astype(jnp.float32)
        if self.d_pad > self.d:
            flat = jnp.concatenate(
                [flat, jnp.zeros((self.d_pad - self.d,), jnp.float32)])
        return flat

    def to_params(self, plane: jnp.ndarray):
        """(d_pad,) plane -> params pytree (jax-traceable)."""
        return self.unravel(plane[:self.d])


def make_plane_spec(params_template, *, model_size: int = 1) -> PlaneSpec:
    """``model_size`` > 1 column-shards the plane over a mesh ``model``
    axis: D is padded to a multiple of ``model_size × PLANE_ALIGN`` so every
    device's column slice is itself PLANE_ALIGN-aligned."""
    flat, unravel = ravel_pytree(params_template)
    d = flat.shape[0]
    align = PLANE_ALIGN * max(1, int(model_size))
    d_pad = -(-d // align) * align
    return PlaneSpec(d=d, d_pad=d_pad, unravel=unravel)


@dataclass(frozen=True)
class TPPlaneSpec:
    """Tensor-parallel plane recipe: a (d_pad,) plane whose LAYOUT matches
    the mesh ``model``-axis split of every leaf.

    The plane is ``msize`` contiguous chunks of ``d_loc`` entries; chunk
    ``i`` holds shard ``i`` of every TP-sharded leaf (its shard dim split
    ``msize``-ways, shard index moved in front of the leaf's own axes
    before raveling) and a full copy of every replicated leaf.  Sharding
    the flat plane ``P(model)`` therefore places each leaf's shard on
    exactly the device that consumes it: ``to_params`` under GSPMD is a
    chain of *local* reshapes/slices (no collective), unlike the legacy
    row-major ravel whose unravel needs the full plane per device.  The
    cost is that replicated leaves are stored ``msize``× (biases, norms —
    noise next to the sharded matmul weights), and that TP planes are NOT
    byte-compatible with ``PlaneSpec`` planes of the same params: convert
    through pytrees (``to_params``/``to_plane``), never by copying planes
    across layouts.

    All plane algebra stays valid: aggregation/delta/bank merges are linear
    and act identically on every duplicated copy, and ``d_loc`` is padded to
    a PLANE_ALIGN multiple so every device's chunk is lane-aligned.
    """
    d: int                  # true (unduplicated) parameter count
    d_pad: int              # plane length = msize · d_loc
    msize: int              # model-axis size the layout is built for
    d_loc: int              # per-chunk length (PLANE_ALIGN multiple)
    treedef: object         # params pytree structure
    recs: tuple             # per leaf: (shape, dtype, shard_dim|None,
    #                         chunk offset, per-chunk size)
    axis: str = "model"     # mesh axis name the layout shards along

    def leaf_specs(self):
        """Pytree of per-leaf PartitionSpecs (the family TP rules actually
        honored by the layout — non-divisible leaves already demoted)."""
        leaves = []
        for shape, _, k, _, _ in self.recs:
            sp = [None] * len(shape)
            if k is not None:
                sp[k] = self.axis
            leaves.append(P(*sp))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def to_plane(self, params) -> jnp.ndarray:
        """params pytree -> (d_pad,) fp32 TP-layout plane (jax-traceable,
        vmap-safe over a leading member axis)."""
        leaves = self.treedef.flatten_up_to(params)
        m = self.msize
        pieces = []
        for leaf, (shape, _, k, _, s_loc) in zip(leaves, self.recs):
            x = jnp.asarray(leaf, jnp.float32)
            if k is None:
                pieces.append(jnp.broadcast_to(x.reshape(1, -1), (m, s_loc)))
            else:
                ck = shape[k] // m
                split = shape[:k] + (m, ck) + shape[k + 1:]
                x = jnp.moveaxis(x.reshape(split), k, 0)
                pieces.append(x.reshape(m, s_loc))
        pad = self.d_loc - sum(r[4] for r in self.recs)
        if pad:
            pieces.append(jnp.zeros((m, pad), jnp.float32))
        return jnp.concatenate(pieces, axis=1).reshape(m * self.d_loc)

    def to_params(self, plane: jnp.ndarray, mesh=None):
        """(d_pad,) plane -> params pytree.  With ``mesh`` (inside a GSPMD
        program) every intermediate carries its sharding constraint so XLA
        keeps the whole chain device-local — each device reads only its own
        chunk; the sliced leaves come out TP-sharded, never replicated."""
        m = self.msize
        x2 = plane.reshape(m, self.d_loc)
        if mesh is not None:
            x2 = jax.lax.with_sharding_constraint(
                x2, NamedSharding(mesh, P(self.axis, None)))
        leaves = []
        for shape, dt, k, off, s_loc in self.recs:
            piece = jax.lax.slice(x2, (0, off), (m, off + s_loc))
            if k is None:
                leaf = piece[0].reshape(shape)
            else:
                ck = shape[k] // m
                split = (m,) + shape[:k] + (ck,) + shape[k + 1:]
                leaf = jnp.moveaxis(piece.reshape(split), 0, k)
                leaf = leaf.reshape(shape)
            leaf = leaf.astype(dt)
            if mesh is not None:
                sp = [None] * len(shape)
                if k is not None:
                    sp[k] = self.axis
                leaf = jax.lax.with_sharding_constraint(
                    leaf, NamedSharding(mesh, P(*sp)))
            leaves.append(leaf)
        return self.treedef.unflatten(leaves)


def _tp_leaf_axis(spec, axis: str):
    """Index of the ``axis``-sharded dim in a PartitionSpec, or None."""
    for i, s in enumerate(spec):
        names = s if isinstance(s, tuple) else (s,)
        if axis in names:
            return i
    return None


def make_tp_plane_spec(params_template, specs, *, msize: int,
                       axis: str = "model") -> TPPlaneSpec:
    """Build the TP plane layout for one level from its params template and
    the family's PartitionSpec pytree (``FLModelFamily.param_specs`` rules —
    typically bridged from ``launch/sharding.tp_specs``).  Leaves whose
    sharded dim is not divisible by ``msize`` are demoted to replicated,
    matching the ``param_specs`` fallback."""
    leaves, treedef = jax.tree_util.tree_flatten(params_template)
    spec_leaves = treedef.flatten_up_to(specs)
    recs = []
    off = 0
    d = 0
    for leaf, spec in zip(leaves, spec_leaves):
        shape = tuple(leaf.shape)
        k = _tp_leaf_axis(spec, axis)
        if k is not None and (k >= len(shape) or shape[k] % msize != 0):
            k = None
        size = int(np.prod(shape)) if shape else 1
        s_loc = size // msize if k is not None else size
        recs.append((shape, jnp.asarray(leaf).dtype, k, off, s_loc))
        off += s_loc
        d += size
    d_loc = -(-off // PLANE_ALIGN) * PLANE_ALIGN
    return TPPlaneSpec(d=d, d_pad=msize * d_loc, msize=msize, d_loc=d_loc,
                       treedef=treedef, recs=tuple(recs), axis=axis)


def plane_specs(data_axis: str = "data", model_axis: str | None = None):
    """PartitionSpecs for every plane-shaped buffer of the dispatch path.

    Mirrors ``launch/sharding.param_specs``' role for the FL plane world:
    one place decides how each buffer splits over the (data, model) mesh.
    Member rows (shard packs, step masks, weights, bank rows) shard along
    ``data_axis``; plane COLUMNS shard along ``model_axis`` when given (the
    2D mesh for member models too large to replicate per device) — the
    global (D,) plane, the (capacity, D) member/bank planes, and (R, D)
    teacher/history stacks all split column-wise, and aggregation contracts
    per-device on the (data, model) subgrid with a psum over ``data`` only
    (columns never need reduction).  ``model_axis=None`` degenerates to the
    1D member-sharded layout (plane replicated)."""
    m = model_axis
    return {
        "plane": P(m) if m else P(),      # (D,) global parameter plane
        "members": P(data_axis, m),       # (capacity, D) member/bank planes
        "stack": P(None, m),              # (R, D) teacher/history stacks
        "rows": P(data_axis),             # (capacity,) weights/gains
        "masks": P(data_axis, None),      # (capacity, S) step masks
        "losses": P(None, data_axis),     # (R, capacity) per-round losses
    }


def pad_member_rows(plane: jnp.ndarray, weights: jnp.ndarray, rows: int):
    """Pad a (C, D) member plane and its (C,) weight vector with zero rows up
    to ``rows`` (jax-traceable).  This is the PR-2 padding invariant applied
    to the member axis: a zero-weight row contributes nothing to any weighted
    contraction, so callers may round C up to whatever divisibility a mesh
    axis (or capacity bucket) demands instead of asserting it."""
    C = plane.shape[0]
    if rows < C:
        raise ValueError(f"cannot pad {C} member rows down to {rows}")
    if rows == C:
        return plane, jnp.asarray(weights, jnp.float32)
    pad = rows - C
    plane = jnp.concatenate(
        [plane, jnp.zeros((pad, plane.shape[1]), plane.dtype)])
    weights = jnp.concatenate(
        [jnp.asarray(weights, jnp.float32), jnp.zeros((pad,), jnp.float32)])
    return plane, weights
