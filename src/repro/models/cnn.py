"""The paper's experimental model: C(128)-C(64)-C(128)-C(256)-C(512)-D(classes).

§V-A of Fed-RAC.  Width-scalable by the cluster compression factor α — the
paper compresses only the conv layers ("dropout of 0.5, i.e. M2 = 0.5(M1)"),
so ``filters(level)`` scales every conv width by α^level and leaves the dense
head at ``classes``.  Used by the FL experiments/benchmarks (Tables IV-VII).

Every other conv is followed by a 2×2 max-pool (stride 2, odd sizes cropped
as VALID does).  Those layers apply ReLU and the pool as one op,
``relu_maxpool2``: the forward takes each window's max and then the ReLU
(equal, bit for bit, to pooling the ReLU), so no full-resolution ReLU output
exists.  Its backward gives each window's gradient to the window's first
maximum in row-major order, and only where that maximum is positive — the
element XLA's ``select-and-scatter`` (``ge``) followed by ReLU's gradient
would pick — as one elementwise mask, so the backward holds no
``select-and-scatter``.  The other convs use plain ``jax.nn.relu``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BASE_FILTERS = (128, 64, 128, 256, 512)
DN = ("NHWC", "HWIO", "NHWC")


def filters(alpha: float = 1.0, level: int = 0, base_width: float = 1.0):
    """base_width scales the whole family (CPU-budget experiments use 0.25);
    alpha**level is the paper's per-cluster compression."""
    s = base_width * alpha ** level
    return tuple(max(4, int(round(f * s))) for f in BASE_FILTERS)


def init_params(key, *, in_channels: int = 1, classes: int = 10,
                alpha: float = 1.0, level: int = 0, base_width: float = 1.0,
                dtype=jnp.float32):
    fs = filters(alpha, level, base_width)
    params = {"convs": []}
    cin = in_channels
    for i, f in enumerate(fs):
        k = jax.random.fold_in(key, i)
        w = jax.random.normal(k, (3, 3, cin, f)) * math.sqrt(2.0 / (9 * cin))
        params["convs"].append({"w": w.astype(dtype), "b": jnp.zeros((f,), dtype)})
        cin = f
    kd = jax.random.fold_in(key, 99)
    params["dense"] = {
        "w": (jax.random.normal(kd, (cin, classes)) * cin ** -0.5).astype(dtype),
        "b": jnp.zeros((classes,), dtype)}
    return params


def _windows(z):
    """(..., H, W, C) cropped to even H, W and viewed as
    (..., H/2, 2, W/2, 2, C)."""
    *lead, h, w, c = z.shape
    z = z[..., :h // 2 * 2, :w // 2 * 2, :]
    return z.reshape(*lead, h // 2, 2, w // 2, 2, c)


@jax.custom_vjp
def relu_maxpool2(z):
    """relu then a 2×2 stride-2 VALID max-pool over (..., H, W, C)."""
    return jax.nn.relu(jnp.max(_windows(z), axis=(-4, -2)))


def _relu_maxpool2_fwd(z):
    y = relu_maxpool2(z)
    return y, (z, y)


def _relu_maxpool2_bwd(res, g):
    """Each window's gradient goes to its first maximum in row-major order,
    and only where that maximum is positive (y = relu(window max) stands in
    for the max there).  An odd last row or column, cropped by the forward,
    gets zero."""
    z, y = res
    zw = _windows(z)
    i8 = jnp.int8
    first = jnp.where(zw[..., 0, :, 0, :] == y, i8(0),
                      jnp.where(zw[..., 0, :, 1, :] == y, i8(1),
                                jnp.where(zw[..., 1, :, 0, :] == y, i8(2),
                                          i8(3))))
    pos = jnp.arange(4, dtype=jnp.int8).reshape(2, 1, 2, 1)   # 2·row + col
    up = lambda t: t[..., :, None, :, None, :]    # (..., H/2, 1, W/2, 1, C)
    dz = jnp.where((up(first) == pos) & up(y > 0), up(g),
                   jnp.zeros((), g.dtype))
    *lead, h, w, c = z.shape
    dz = dz.reshape(*lead, h // 2 * 2, w // 2 * 2, c)
    # zeros joined on, not jnp.pad: after a pad XLA:CPU sums the bias
    # gradient in another order than it does after select-and-scatter
    if w % 2:
        dz = jnp.concatenate([dz, jnp.zeros((*lead, h // 2 * 2, 1, c),
                                            dz.dtype)], axis=-2)
    if h % 2:
        dz = jnp.concatenate([dz, jnp.zeros((*lead, 1, w, c), dz.dtype)],
                             axis=-3)
    return (dz,)


relu_maxpool2.defvjp(_relu_maxpool2_fwd, _relu_maxpool2_bwd)


def forward(params, x):
    """x: (B,H,W,C) -> logits (B,classes)."""
    for i, p in enumerate(params["convs"]):
        x = jax.lax.conv_general_dilated(x, p["w"], (1, 1), "SAME",
                                         dimension_numbers=DN) + p["b"]
        if i % 2 == 1 and min(x.shape[1], x.shape[2]) >= 2:   # pool every other
            x = relu_maxpool2(x)
        else:
            x = jax.nn.relu(x)
    x = jnp.mean(x, axis=(1, 2))                              # global avg pool
    return x @ params["dense"]["w"] + params["dense"]["b"]


def loss_fn(params, batch):
    logits = forward(params, batch["x"])
    labels = batch["y"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    loss = jnp.mean(lse - picked)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, acc


def param_count(params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))
