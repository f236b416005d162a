"""Weighted client-stack reduction kernel (the FedAvg server step).

Input: client-stacked flat parameters (C, D) and normalized weights (C,);
output the n_i-weighted average (D,).  The grid tiles D; each step loads the
full (C, block_d) column panel into VMEM and reduces it against the weight
column on the VPU in fp32.  This is the per-device inner loop of the
shard_map psum aggregation (core/aggregation.py).

Every block is 2-D so that Mosaic's layouts match XLA's at any D: the
weights ride as a (C, 1) column and the result as a lane-dense (1, D) row
(a 1-D (block_d,) output block is refused by Mosaic unless it matches XLA's
T(1024) tiling of a 1-D f32 array).  The grid is ``cdiv(D, block_d)``: a
last block that overhangs D reads unspecified columns and its out-of-range
writes are dropped, which a column-wise reduction never mixes into the
columns that are kept.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _agg_kernel(w_ref, x_ref, o_ref):
    w = w_ref[...].astype(jnp.float32)             # (C, 1)
    x = x_ref[...].astype(jnp.float32)             # (C, bd)
    o_ref[...] = jnp.sum(w * x, axis=0, keepdims=True).astype(o_ref.dtype)


def weighted_aggregate(stack, weights, *, block_d: int = 2048,
                       interpret: bool = True):
    """stack: (C, D); weights: (C,) → (D,).  ``block_d`` is a multiple of
    128 or covers all of D."""
    C, D = stack.shape
    block_d = min(block_d, D)
    assert block_d == D or block_d % 128 == 0, (D, block_d)
    # under shard_map the result varies over every mesh axis its inputs do
    vma = jax.typeof(stack).vma | jax.typeof(weights).vma
    return pl.pallas_call(
        _agg_kernel,
        grid=(pl.cdiv(D, block_d),),
        in_specs=[
            pl.BlockSpec((C, 1), lambda i: (0, 0)),
            pl.BlockSpec((C, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, D), stack.dtype, vma=vma),
        interpret=interpret,
    )(weights.reshape(C, 1), stack)[0]
