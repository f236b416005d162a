"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a ``v5e:2x2``
topology that is described, not attached, and refuses what the chip would
refuse (block layouts that do not match XLA's tiling, too much VMEM), which
interpret mode never checks.  The shapes are the real ones: the plane
lengths of the paper's full-width CNN (``--base-width 1.0`` on
``synth-cifar``, levels 0-2, plus level 0's column slice on a 2x2 mesh),
flash attention at bf16 S1024 GQA, the KD loss at a 32k vocabulary, and the
full-width CNN's member step as the benchmark's FedAvg cluster runs it.

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import aggregation
from repro.core.client import make_cluster_update
from repro.core.families import cnn_family
from repro.kernels.distill import ops as distill_ops
from repro.kernels.fedagg import ops as fedagg_ops
from repro.kernels.flash import ops as flash_ops

# padded plane lengths of the full-width CNN: levels 0, 1, 2, and level 0's
# per-device column slice on a 2x2 (data x model) mesh
PLANE_LENGTHS = (1_631_744, 410_368, 103_808, 815_872)
CAPACITY = 32                # the master cluster's padded member count


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("D", PLANE_LENGTHS)
def test_fedagg_compiles_at_plane_length(D, one_chip, no_persistent_cache):
    plane = jax.ShapeDtypeStruct((CAPACITY, D), jnp.float32,
                                 sharding=one_chip)
    w = jax.ShapeDtypeStruct((CAPACITY,), jnp.float32, sharding=one_chip)
    txt = _compiled_text(
        lambda p, w: fedagg_ops.aggregate_plane(p, w, interpret=False),
        plane, w)
    assert "tpu_custom_call" in txt


def test_fedagg_compiles_inside_sharded_aggregation(topo, monkeypatch,
                                                    no_persistent_cache):
    """The dispatch block's per-device kernel under shard_map over a 4x1
    mesh of the described chips: its result must carry the data axis as a
    varying manual axis, or the shard_map refuses it."""
    monkeypatch.setattr(fedagg_ops, "_interpret_default", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    rows = NamedSharding(mesh, P("data"))
    plane = jax.ShapeDtypeStruct((CAPACITY, PLANE_LENGTHS[0]), jnp.float32,
                                 sharding=rows)
    w = jax.ShapeDtypeStruct((CAPACITY,), jnp.float32, sharding=rows)
    txt = _compiled_text(
        lambda p, w: aggregation.aggregate_plane_sharded(
            mesh, p, w, use_kernel=True), plane, w)
    assert "tpu_custom_call" in txt
    assert "all-reduce" in txt


def test_flash_forward_compiles_bf16_gqa(one_chip, no_persistent_cache):
    q = jax.ShapeDtypeStruct((2, 1024, 8, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 1024, 2, 128), jnp.bfloat16,
                              sharding=one_chip)
    txt = _compiled_text(
        lambda q, k, v: flash_ops.flash_attention(q, k, v, causal=True,
                                                  interpret=False),
        q, kv, kv)
    assert "tpu_custom_call" in txt


def test_distill_compiles_past_one_row_block(one_chip, no_persistent_cache):
    logits = jax.ShapeDtypeStruct((1024, 32000), jnp.float32,
                                  sharding=one_chip)
    labels = jax.ShapeDtypeStruct((1024,), jnp.int32, sharding=one_chip)
    txt = _compiled_text(
        lambda s, y, t: distill_ops.kd_loss(s, y, t, interpret=False),
        logits, labels, logits)
    assert "tpu_custom_call" in txt


def test_cnn_member_step_compiles_without_select_and_scatter(
        one_chip, no_persistent_cache):
    """The full-width CNN's member step at 28x28x1: 64 members (the
    cluster's capacity) x 4 SGD steps x batch 16.  Its pooled layers use
    the fused ReLU-pool, so the chip's program holds no select-and-scatter
    (XLA's max-pool backward) and no reduce-window."""
    members, steps, batch = 64, 4, 16
    fam = cnn_family(classes=10, in_channels=1, alpha=0.5, base_width=1.0,
                     input_hw=28)
    p = jax.eval_shape(lambda: fam.init(jax.random.PRNGKey(0), 0))

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda l: spec((members,) + l.shape, l.dtype), p)
    batches = {"x": spec((members, steps, batch, 28, 28, 1)),
               "y": spec((members, steps, batch), jnp.int32)}
    step = make_cluster_update(lambda prm, b: fam.loss_and_logits(0, prm, b),
                               0.05)
    txt = step.lower(params, batches, spec((members, steps))).compile(
    ).as_text()
    assert "convolution" in txt
    assert "select-and-scatter" not in txt
    assert "reduce-window" not in txt
