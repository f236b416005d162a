"""The CNN's fused ReLU and 2×2 max-pool (``cnn.relu_maxpool2``) against the
form it replaced, ``jax.nn.relu`` then ``lax.reduce_window``, written out
here.  Both must give the same bits: the forward, the cotangent of the conv
output on ties (the first maximum of each window in row-major order takes
it, and nothing where the window's maximum is not positive), and every
parameter gradient of a member step vmapped over members under ``jit``.
The compiled member step must hold no ``select-and-scatter`` and no
``reduce-window``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.client import make_cluster_update
from repro.core.families import cnn_family
from repro.models import cnn

MEMBERS, BATCH = 4, 8


def relu_pool(z):
    """The replaced form over (..., H, W, C)."""
    lead = (1,) * (z.ndim - 3)
    return jax.lax.reduce_window(jax.nn.relu(z), -jnp.inf, jax.lax.max,
                                 lead + (2, 2, 1), lead + (2, 2, 1), "VALID")


def reference_forward(params, x):
    for i, p in enumerate(params["convs"]):
        x = jax.lax.conv_general_dilated(x, p["w"], (1, 1), "SAME",
                                         dimension_numbers=cnn.DN) + p["b"]
        if i % 2 == 1 and min(x.shape[1], x.shape[2]) >= 2:
            x = relu_pool(x)
        else:
            x = jax.nn.relu(x)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["dense"]["w"] + params["dense"]["b"]


def ce(forward):
    def loss(params, batch):
        logits = forward(params, batch["x"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, batch["y"][:, None], -1)[:, 0]
        return jnp.mean(lse - picked)
    return loss


def tied(key, shape, step=0.5):
    """Normal draws rounded to ``step``: many equal values, so windows
    with tied maxima, and windows that are entirely negative."""
    return jnp.round(jax.random.normal(key, shape) * 2 / step) * step / 2


def members(hw, cin, level):
    """Member parameters and one batch each, rounded so that conv outputs
    tie; non-zero biases so that windows tie at and around zero too."""
    keys = jax.random.split(jax.random.PRNGKey(hw * 10 + level), MEMBERS)
    ps = jax.vmap(lambda k: cnn.init_params(
        k, in_channels=cin, level=level, alpha=0.5, base_width=0.125))(keys)
    ps = jax.tree.map(lambda w: jnp.round(w * 8) / 8, ps)
    for i, c in enumerate(ps["convs"]):
        c["b"] = tied(jax.random.PRNGKey(100 + i), c["b"].shape, 0.25)
    batch = {"x": tied(jax.random.PRNGKey(hw), (MEMBERS, BATCH, hw, hw, cin)),
             "y": jax.random.randint(jax.random.PRNGKey(1), (MEMBERS, BATCH),
                                     0, 10)}
    return ps, batch


def first_pooled_input(params, x):
    for p in params["convs"][:2]:
        z = jax.lax.conv_general_dilated(x, p["w"], (1, 1), "SAME",
                                         dimension_numbers=cnn.DN) + p["b"]
        x = jax.nn.relu(z)
    return z


def assert_ties_and_dead_windows(z):
    """Some window's positive maximum is tied, and some window has no
    positive element."""
    wins = np.asarray(cnn._windows(jnp.asarray(z)))
    top = wins.max(axis=(-4, -2))
    ties = (wins == top[..., :, None, :, None, :]).sum(axis=(-4, -2)) > 1
    assert (ties & (top > 0)).any() and (top <= 0).any()


def assert_same_bits(got, want):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("shape", [(2, 28, 28, 8), (2, 14, 14, 4),
                                   (2, 7, 7, 4), (3, 7, 6, 2), (2, 32, 32, 3)],
                         ids=lambda s: "x".join(map(str, s)))
def test_op_matches_relu_then_reduce_window(shape):
    """Forward and cotangent, bit for bit, under jit and vmap, with ties,
    all-negative windows and odd sizes cropped."""
    z = tied(jax.random.PRNGKey(sum(shape)), (MEMBERS,) + shape)
    z = z.at[:, 0, :2, :2, 0].set(-1.5)                  # all negative
    z = z.at[:, 0, :2, 2:4, 0].set(jnp.array([[1.0, 1.0], [1.0, 0.5]]))
    g = jax.random.normal(jax.random.PRNGKey(3), z.shape[:-3] + (
        shape[1] // 2, shape[2] // 2, shape[3]))

    def vjp(op):
        return jax.jit(jax.vmap(lambda z, g: (lambda y, f: (y, f(g)[0]))(
            *jax.vjp(op, z))))

    y, dz = vjp(cnn.relu_maxpool2)(z, g)
    y_ref, dz_ref = vjp(relu_pool)(z, g)
    assert_same_bits((y, dz), (y_ref, dz_ref))
    # the planted windows: the tie's gradient goes to its first element
    np.testing.assert_array_equal(dz[:, 0, :2, :2, 0], 0.0)
    np.testing.assert_array_equal(dz[:, 0, 0, 2, 0], g[:, 0, 0, 1, 0])
    np.testing.assert_array_equal(dz[:, 0, :2, 3, 0], 0.0)
    np.testing.assert_array_equal(dz[:, 0, 1, 2, 0], 0.0)
    assert_ties_and_dead_windows(z)


@pytest.mark.parametrize("level", [0, 1], ids=["master", "slave"])
@pytest.mark.parametrize("hw,cin", [(28, 1), (14, 1), (7, 1), (32, 3)],
                         ids=["28", "14", "7", "32"])
def test_member_forward_and_grads_bitwise(hw, cin, level):
    """The whole CNN, members vmapped under jit: logits, loss and every
    parameter gradient equal to the replaced form's, bit for bit."""
    ps, batch = members(hw, cin, level)
    assert_ties_and_dead_windows(jax.vmap(first_pooled_input)(ps, batch["x"]))

    def run(forward):
        logits = jax.jit(jax.vmap(forward))(ps, batch["x"])
        return logits, jax.jit(jax.vmap(jax.value_and_grad(ce(forward))))(
            ps, batch)

    assert_same_bits(run(cnn.forward), run(reference_forward))


@pytest.mark.parametrize("hw", [28, 7])
def test_member_step_has_no_select_and_scatter(hw):
    """The member step, τ SGD steps vmapped over members under jit, as XLA
    is handed it and as XLA:CPU compiles it.  XLA:CPU rewrites a
    select-and-scatter into a scatter, so the replaced form, traced the same
    way, shows that the check finds both ops where they are."""
    fam = cnn_family(classes=10, in_channels=1, base_width=0.125,
                     input_hw=hw)
    p = jax.eval_shape(lambda: fam.init(jax.random.PRNGKey(0), 0))
    ps = jax.tree.map(lambda l: jax.ShapeDtypeStruct((MEMBERS,) + l.shape,
                                                     l.dtype), p)
    batches = {"x": jax.ShapeDtypeStruct((MEMBERS, 2, BATCH, hw, hw, 1),
                                         jnp.float32),
               "y": jax.ShapeDtypeStruct((MEMBERS, 2, BATCH), jnp.int32)}
    masks = jax.ShapeDtypeStruct((MEMBERS, 2), jnp.float32)

    def lowered(forward):
        loss = ce(forward)
        step = make_cluster_update(lambda prm, b: (loss(prm, b), None), 0.05)
        return step.lower(ps, batches, masks)

    low = lowered(cnn.forward)
    for txt in (low.as_text(dialect="hlo"), low.compile().as_text()):
        assert "select-and-scatter" not in txt
        assert "reduce-window" not in txt
    ref = lowered(reference_forward).as_text(dialect="hlo")
    assert "select-and-scatter" in ref and "reduce-window" in ref
