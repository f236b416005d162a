"""FedAvg aggregation of client-stacked WPMs (§III-B).

Two interchangeable implementations (tested equal):
  * ``aggregate``           — tree-mapped weighted sum over the client axis.
  * ``shard_map psum``      — clients sharded along the mesh `data` axis;
    each device reduces its local clients, then one psum finishes the job.
    This is the paper's "upload WPM to server" step realized as an
    all-reduce, and the Pallas ``kernels/fedagg`` kernel is its per-device
    inner loop.

Both exist in flat-plane form too (``aggregate_plane[_sharded]`` etc.): the
dispatch path's (C, D) parameter plane shards along the same ``data`` axis,
and non-divisible member counts ride any mesh via zero-weight padding rows
(``core.plane.pad_member_rows``) instead of a divisibility assert.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def aggregate(params_stack, weights):
    """params_stack: pytree with leading client dim C; weights: (C,) summing to 1."""
    w = jnp.asarray(weights)
    return jax.tree.map(
        lambda x: jnp.tensordot(w.astype(x.dtype), x, axes=(0, 0)), params_stack)


def normalized_weights(n_list) -> jnp.ndarray:
    """Normalize raw non-negative weights to sum 1 — with a zero-total guard:
    an all-violator round (every live member banked/dropped) has Σn = 0, and
    an unguarded n/Σn would NaN-poison every downstream aggregate/plane.
    The all-zero case returns zeros, which every aggregation in this module
    treats as the partial-aggregation no-op."""
    n = jnp.asarray(n_list, dtype=jnp.float32)
    total = jnp.sum(n)
    return n / jnp.where(total > 0.0, total, 1.0)


def aggregate_sharded(mesh, params_stack, weights, axis: str = "data"):
    """Clients sharded along `axis`; returns replicated aggregated params.

    The client count does not have to divide the mesh axis: the stack is
    padded with zero-weight rows (``core.plane.pad_member_rows`` invariant)
    up to the next multiple, so arbitrary live member counts ride any mesh.
    """
    C = weights.shape[0]
    rows = _plane_rows_for_mesh(mesh, C, axis)
    w = jnp.asarray(weights, jnp.float32)
    if rows != C:
        params_stack = jax.tree.map(
            lambda x: jnp.concatenate(
                [x, jnp.zeros((rows - C,) + x.shape[1:], x.dtype)]),
            params_stack)
        w = jnp.concatenate([w, jnp.zeros((rows - C,), jnp.float32)])

    def local_agg(stack, wl):
        local = jax.tree.map(
            lambda x: jnp.tensordot(wl.astype(x.dtype), x, axes=(0, 0)), stack)
        return jax.tree.map(lambda x: jax.lax.psum(x, axis), local)

    specs_in = jax.tree.map(lambda _: P(axis), params_stack)
    fn = jax.shard_map(
        local_agg, mesh=mesh,
        in_specs=(specs_in, P(axis)),
        out_specs=jax.tree.map(lambda _: P(), params_stack))
    return fn(params_stack, w)


def fedavg_delta(global_params, params_stack, weights):
    """Server update as an aggregated delta (useful with server optimizers).
    A zero total weight (nobody contributed) yields a ZERO delta — the
    server-step no-op — rather than the poisoned ``-global_params``."""
    w = jnp.asarray(weights, jnp.float32)
    total = jnp.sum(w)
    agg = aggregate(params_stack, weights)
    return jax.tree.map(
        lambda a, g: jnp.where(total > 0.0, a - g, jnp.zeros_like(g)),
        agg, global_params)


# ------------------------------------------------------------ flat plane
# Plane counterparts of the pytree ops above: the dispatch path carries
# cluster parameters as one contiguous (C, D_pad) fp32 buffer (core/plane.py)
# so aggregation is a single contraction with no per-call tree_flatten /
# concatenate / pad.  On TPU the contraction routes through the Pallas
# ``kernels/fedagg`` kernel; elsewhere it lowers to one dot.


def _use_fedagg_kernel() -> bool:
    return jax.default_backend() == "tpu"


def aggregate_plane(plane, weights, *, use_kernel: bool | None = None):
    """plane: (C, D) fp32; weights: (C,) raw or normalized → (D,) Σ w_i p_i."""
    w = jnp.asarray(weights, jnp.float32)
    if use_kernel is None:
        use_kernel = _use_fedagg_kernel()
    if use_kernel:
        from repro.kernels.fedagg.ops import aggregate_plane as _kernel_plane
        return _kernel_plane(plane, w)
    return jnp.tensordot(w, plane, axes=(0, 0))


def fedavg_delta_plane(global_plane, plane, weights):
    """Server update as an aggregated delta, on the plane.  Zero total
    weight → zero delta (the server-step no-op), never ``-global_plane``."""
    w = jnp.asarray(weights, jnp.float32)
    return jnp.where(jnp.sum(w) > 0.0,
                     aggregate_plane(plane, w) - global_plane,
                     jnp.zeros_like(global_plane))


def merge_buffered_plane(partial_plane, bank_plane, bank_weights, *,
                         use_kernel: bool | None = None):
    """Plane form of ``merge_buffered``: fold banked rows (already normalized
    by the live+buffered total) into a partial plane sum — one contraction,
    no per-contribution tree_map.  ``use_kernel=False`` forces the plain
    tensordot (required inside GSPMD global-view programs, where the Pallas
    fedagg custom call cannot be partitioned)."""
    return partial_plane + aggregate_plane(bank_plane, bank_weights,
                                           use_kernel=use_kernel)


# ------------------------------------------------------- sharded flat plane
# Multi-device counterparts of the plane ops: the (C, D) member plane is
# sharded along the mesh ``data`` axis, each device contracts its LOCAL
# member rows (the Pallas ``kernels/fedagg`` plane kernel on TPU, one
# tensordot elsewhere — exactly ``aggregate_plane``), and a single psum
# finishes the §III-B "upload WPM to server" all-reduce.  The member count
# never has to divide the mesh axis: rows are padded with zero weights
# (``core.plane.pad_member_rows``), which every weighted contraction
# ignores by construction.


def _plane_rows_for_mesh(mesh, C: int, axis: str) -> int:
    """Smallest row count ≥ C divisible by the mesh ``axis`` size."""
    n = mesh.shape[axis]
    return -(-C // n) * n


def aggregate_plane_sharded(mesh, plane, weights, *, axis: str = "data",
                            model_axis: str | None = None,
                            use_kernel: bool | None = None):
    """plane: (C, D) fp32 sharded along ``axis`` (and, with ``model_axis``,
    column-sharded along it); weights: (C,) raw or normalized → (D,)
    Σ w_i p_i, data-replicated (column-sharded along ``model_axis`` when
    given).  Each device contracts its LOCAL (data, model) subgrid and ONE
    psum over ``axis`` finishes the job — columns never need reduction, so
    the model axis contributes no collective at all."""
    from repro.core.plane import pad_member_rows

    plane, w = pad_member_rows(
        plane, jnp.asarray(weights, jnp.float32),
        _plane_rows_for_mesh(mesh, plane.shape[0], axis))
    D = plane.shape[1]
    m = mesh.shape[model_axis] if model_axis else 1
    pad_d = (-D) % m
    if pad_d:
        # zero columns contract to zero columns — sliced back off below
        plane = jnp.concatenate(
            [plane, jnp.zeros((plane.shape[0], pad_d), plane.dtype)], axis=1)

    def local_agg(p, wl):
        return jax.lax.psum(
            aggregate_plane(p, wl, use_kernel=use_kernel), axis)

    fn = jax.shard_map(local_agg, mesh=mesh,
                       in_specs=(P(axis, model_axis), P(axis)),
                       out_specs=P(model_axis))
    out = fn(plane, w)
    return out[:D] if pad_d else out


def fedavg_delta_plane_sharded(mesh, global_plane, plane, weights, *,
                               axis: str = "data",
                               model_axis: str | None = None):
    """Sharded server update as an aggregated delta on the plane.  A zero
    total weight yields a zero delta (same guard as ``fedavg_delta``)."""
    w = jnp.asarray(weights, jnp.float32)
    agg = aggregate_plane_sharded(mesh, plane, w, axis=axis,
                                  model_axis=model_axis)
    return jnp.where(jnp.sum(w) > 0.0, agg - global_plane,
                     jnp.zeros_like(global_plane))


def merge_buffered_plane_sharded(mesh, partial_plane, bank_plane,
                                 bank_weights, *, axis: str = "data",
                                 model_axis: str | None = None):
    """Sharded ``merge_buffered_plane``: the banked rows live on the same
    mesh axes as the member plane; their discounted contraction joins the
    partial sum through the same local-reduce + psum-over-``axis`` path."""
    return partial_plane + aggregate_plane_sharded(
        mesh, bank_plane, bank_weights, axis=axis, model_axis=model_axis)


# ------------------------------------------------------------ buffered async
def compress_bank_rows(rows: list, us: list, cap: int):
    """Fit a banked backlog into ``cap`` carry slots: when membership shrank
    below the backlog (event between dispatch blocks), ALL rows compress
    into ONE weighted-average row.  Σu and Σu·p are preserved exactly, so
    the round-0 bank merge — which only ever sees the products u·p and the
    total — is unchanged.  Returns (rows, us) untouched when they fit."""
    if len(rows) <= cap:
        return rows, us
    u = jnp.asarray(us, jnp.float32)
    total = float(u.sum())
    return ([aggregate_plane(jnp.stack(rows), u / total)], [total])


def staleness_weights(n_list, age_list, discount: float) -> list[float]:
    """Raw weights for banked (late) contributions: the member's data weight
    n_b geometrically discounted by how many rounds its update sat in the
    buffer — ``discount**age`` with age ≥ 1 (an update banked in round r
    joins round r+1's aggregate at the first discount step)."""
    return [float(n) * discount ** max(1, int(age))
            for n, age in zip(n_list, age_list)]


def version_staleness_weights(n_list, version_list, current_version: int,
                              discount: float) -> list[float]:
    """Async-server form of :func:`staleness_weights`: staleness is measured
    in *server versions* — the plane version a contribution was computed
    against vs. the version it merges at — instead of banked round-age.  A
    ledger entry tagged ``v`` merging at version ``V`` weighs
    ``n · discount**max(1, V - v)``; with versions advancing one per
    committed round this is numerically identical to the round-age form,
    which is what makes the synchronized-arrival anchor bit-exact."""
    return staleness_weights(
        n_list, [int(current_version) - int(v) for v in version_list],
        discount)


def anchored_merge_weights(anchor_weight: float, us) -> tuple[float, list[float]]:
    """Normalize an anchored stale merge — ``anchor_weight`` is the current
    plane's weight (Σ n_eff of the cluster), ``us`` the raw discounted
    ledger weights — under the ``normalized_weights`` zero-total contract:
    when everything underflows (``discount**lag → 0`` on deeply stale
    entries AND the cluster emptied, so the anchor is 0 too), the anchor
    keeps weight 1 and the ledger gets zeros — a zero delta, never a NaN
    plane."""
    total = float(anchor_weight) + float(sum(us))
    if total <= 0.0:
        return 1.0, [0.0 for _ in us]
    return float(anchor_weight) / total, [float(u) / total for u in us]


def merge_buffered(partial, contribs, norm_weights):
    """Fold banked contributions into a partial FedAvg sum.

    ``partial`` is Σ ŵ_i p_i over this round's live members where the ŵ_i
    were normalized by the TOTAL weight (live + buffered), so Σŵ_i < 1;
    adding Σ û_b p_b over the banked params (û_b = norm_weights, also
    normalized by the total) completes a convex combination — one FedAvg
    over live and stale contributors alike."""
    out = partial
    for p, nw in zip(contribs, norm_weights):
        w = float(nw)
        out = jax.tree.map(lambda a, b: a + w * b.astype(a.dtype), out, p)
    return out
