"""Property-based tests for the weight-math invariants PRs 2–4 fixed by
hand — randomized statements of what used to be single-example regressions:

  * zero-weight padding rows (``core.plane.pad_member_rows``) leave the
    renormalized FedAvg exactly unchanged (the invariant behind capacity
    buckets AND mesh-axis divisibility);
  * ``normalized_weights`` never emits NaN — a zero total yields zeros;
  * ``staleness_weights`` discounts are monotone in age and clamp age ≥ 1;
  * bank-overflow compression (``aggregation.compress_bank_rows``)
    preserves Σu and Σu·p exactly;
  * plane flatten/unflatten round-trips bit-exactly across every model
    family and 2D-mesh column count (``make_plane_spec(model_size=…)``);
  * the class-balanced sampler (``device_sampler.balanced_indices`` over
    ``build_class_table`` tables) realizes the round-robin quota scheme of
    the host-side numpy reference under arbitrary class skew: every batch
    slot draws from exactly the class the reference assigns it, and narrow
    tables never leak out-of-class or out-of-window indices.

Runs through the optional-hypothesis shim: with hypothesis installed (the
``[dev]`` extra — CI), each property fuzzes; without it the ``@given``
tests skip, and the seeded ``*_examples`` smoke paths below keep every
checker executable anyway.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.base import ModelConfig
from repro.core import aggregation as agg
from repro.core.families import cnn_family, lm_family, mlp_family
from repro.core.plane import PLANE_ALIGN, make_plane_spec, pad_member_rows
from repro.data.device_sampler import (balanced_indices, build_class_table,
                                       round_key)


# ------------------------------------------------------------ checkers
def check_pad_rows_fedavg_exact(values, weights, extra):
    """Padding (C, D) member rows with zero-weight rows up to C+extra rows
    leaves the RENORMALIZED FedAvg exactly where it was."""
    C = len(weights)
    D = max(1, len(values) // C)
    plane = jnp.asarray(np.resize(np.asarray(values, np.float32), (C, D)))
    w = agg.normalized_weights(weights)
    pp, pw = pad_member_rows(plane, w, plane.shape[0] + extra)
    assert pp.shape[0] == pw.shape[0] == plane.shape[0] + extra
    np.testing.assert_allclose(
        np.asarray(agg.aggregate_plane(pp, agg.normalized_weights(pw))),
        np.asarray(agg.aggregate_plane(plane, w)), rtol=1e-6, atol=1e-6)


def check_normalized_weights_guard(weights):
    w = np.asarray(agg.normalized_weights(weights))
    assert np.isfinite(w).all(), f"NaN/inf from {weights}"
    total = float(np.asarray(weights, np.float32).sum())
    if total > 0.0:
        np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-5)
    else:
        np.testing.assert_array_equal(w, 0.0)


def check_staleness_monotone(n_list, discount):
    """Older banked updates never weigh more; age 0 is clamped to age 1."""
    ages = list(range(len(n_list)))
    w0 = agg.staleness_weights(n_list, ages, discount)
    w1 = agg.staleness_weights(n_list, [a + 1 for a in ages], discount)
    for n, a, wa, wb in zip(n_list, ages, w0, w1):
        if a >= 1:
            assert wb <= wa + 1e-12, (n, a, wa, wb)
    assert agg.staleness_weights([5.0], [0], discount) == \
        agg.staleness_weights([5.0], [1], discount)


def check_compress_preserves_mass(rows_values, us, cap):
    """Compression into ``cap`` slots preserves Σu and Σu·p exactly — the
    only two quantities the bank merge ever reads."""
    rows = [jnp.asarray(np.asarray(r, np.float32)) for r in rows_values]
    out_rows, out_us = agg.compress_bank_rows(rows, us, cap)
    assert len(out_rows) == len(out_us) <= max(cap, len(rows) and 1)
    if len(rows) <= cap:
        assert out_rows is rows and out_us is us      # untouched
        return
    assert len(out_rows) == 1
    np.testing.assert_allclose(sum(out_us), sum(us), rtol=1e-6)
    want = sum(float(u) * np.asarray(r) for u, r in zip(us, rows))
    got = sum(float(u) * np.asarray(r) for u, r in zip(out_us, out_rows))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


_LM_CFG = ModelConfig(name="prop-lm", family="dense", n_layers=1, d_model=16,
                      n_heads=1, n_kv_heads=1, head_dim=16, d_ff=32,
                      vocab_size=16, rope_theta=1e4)
FAMILIES = {
    "mlp": lambda: mlp_family(),
    "cnn": lambda: cnn_family(classes=10, in_channels=1, base_width=0.125),
    "lm": lambda: lm_family(_LM_CFG, alpha=0.5),
}


def check_balanced_sampler_quota(seed, C, classes, batch, steps, m):
    """``balanced_indices`` vs the numpy reference quota scheme, under a
    random class skew per member: (1) slot b of member i draws from class
    ``present_i[b % |present_i|]`` (present classes ascending — the
    round-robin ⌈batch/n⌉ quota split), verified by mapping drawn indices
    back through each member's labels; (2) every drawn index lies in the
    class's first ``min(count, m)`` sample positions (the narrow-table
    uniformity window), so table padding is never drawn."""
    rng = np.random.default_rng(seed)
    ys = []
    for _ in range(C):
        present = rng.permutation(classes)[:int(rng.integers(1, classes + 1))]
        # skewed populations: some present classes rare, some dominant
        ys.append(np.asarray(rng.choice(
            present, size=int(rng.integers(3, 40)),
            p=rng.dirichlet(np.full(len(present), 0.5)))))
    if m is None:  # shared cluster-wide width, like FedRAC's table build
        m = max(1, max(int((y == c).sum()) for y in ys
                       for c in range(classes)))
    tables, counts = map(np.stack, zip(*(build_class_table(y, classes, m)
                                         for y in ys)))
    idx = np.asarray(balanced_indices(round_key(seed, 0), steps, batch,
                                      jnp.asarray(tables),
                                      jnp.asarray(counts)))
    assert idx.shape == (C, steps, batch)
    width = tables.shape[-1]
    for i in range(C):
        y = ys[i]
        present = np.where(counts[i] > 0)[0]            # ascending order
        ref_cls = present[np.arange(batch) % len(present)]   # numpy quota
        # (1) drawn sample's label == reference class, every slot and step
        np.testing.assert_array_equal(
            y[idx[i]], np.broadcast_to(ref_cls, (steps, batch)),
            err_msg=f"member {i}: quota/class assignment diverged")
        # (2) draws stay inside each class's uniform window
        for cls in np.unique(ref_cls):
            window = np.where(y == cls)[0][:min(int(counts[i][cls]), width)]
            drawn = idx[i][:, ref_cls == cls].ravel()
            assert np.isin(drawn, window).all(), \
                f"member {i} class {cls}: draw outside first-{len(window)} " \
                f"window"


def check_plane_roundtrip(family_name, level, model_size, seed):
    """to_params(to_plane(p)) is bit-exact for every family/level, and the
    padded length divides by model_size × PLANE_ALIGN (the 2D-mesh column
    alignment that keeps each device's column slice lane-aligned)."""
    fam = FAMILIES[family_name]()
    params = fam.init(jax.random.PRNGKey(seed), level)
    spec = make_plane_spec(params, model_size=model_size)
    assert spec.d_pad % (model_size * PLANE_ALIGN) == 0
    assert spec.d_pad >= spec.d
    plane = spec.to_plane(params)
    assert plane.shape == (spec.d_pad,) and plane.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(plane[spec.d:]), 0.0)
    back = spec.to_params(plane)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# ------------------------------------------------------------ hypothesis
@given(st.lists(st.floats(-1e3, 1e3, width=32), min_size=4, max_size=24),
       st.lists(st.floats(0.0, 1e4, width=32), min_size=2, max_size=6),
       st.integers(0, 9))
@settings(max_examples=30, deadline=None)
def test_prop_pad_rows_fedavg_exact(values, weights, extra):
    check_pad_rows_fedavg_exact(values, weights, extra)


@given(st.lists(st.floats(0.0, 1e6, width=32), min_size=1, max_size=12))
@settings(max_examples=50, deadline=None)
def test_prop_normalized_weights_guard(weights):
    check_normalized_weights_guard(weights)


# bounds of a width-32 strategy must be float32-exact: 0.1 and 0.05 are not
@given(st.lists(st.floats(float(np.float32(0.1)), 1e3, width=32),
                min_size=1, max_size=8),
       st.floats(float(np.float32(0.05)), 1.0, width=32))
@settings(max_examples=30, deadline=None)
def test_prop_staleness_monotone(n_list, discount):
    check_staleness_monotone(n_list, discount)


@given(st.integers(1, 6), st.integers(1, 8), st.integers(0, 99))
@settings(max_examples=25, deadline=None)
def test_prop_compress_preserves_mass(cap, n_rows, seed):
    rng = np.random.default_rng(seed)
    rows = rng.normal(size=(n_rows, 32)).astype(np.float32)
    us = rng.uniform(0.1, 5.0, size=n_rows).tolist()
    check_compress_preserves_mass(list(rows), us, cap)


@given(st.sampled_from(sorted(FAMILIES)), st.integers(0, 2),
       st.sampled_from([1, 2, 4, 8]), st.integers(0, 99))
@settings(max_examples=12, deadline=None)
def test_prop_plane_roundtrip(family_name, level, model_size, seed):
    check_plane_roundtrip(family_name, level, model_size, seed)


@given(st.integers(0, 9999), st.integers(1, 5), st.integers(2, 8),
       st.integers(1, 12), st.integers(1, 3),
       st.one_of(st.none(), st.integers(1, 6)))
@settings(max_examples=20, deadline=None)
def test_prop_balanced_sampler_quota(seed, C, classes, batch, steps, m):
    check_balanced_sampler_quota(seed, C, classes, batch, steps, m)


# ---------------------------------------------------- seeded smoke paths
# Executable without hypothesis (the shim skips the @given tests): a few
# seeded draws through the same checkers keep the invariants enforced on
# bare installs and double as known-edge-case regressions.
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pad_rows_examples(seed):
    rng = np.random.default_rng(seed)
    C = int(rng.integers(2, 7))
    check_pad_rows_fedavg_exact(
        rng.normal(size=(C * 16,)).astype(np.float32),
        rng.uniform(0.0, 10.0, size=C).tolist(), int(rng.integers(0, 8)))


@pytest.mark.parametrize("weights", [[0.0], [0.0, 0.0, 0.0], [3.0, 1.0],
                                     [1e-30, 0.0], [0.0, 7.0, 0.0]])
def test_normalized_weights_examples(weights):
    check_normalized_weights_guard(weights)


@pytest.mark.parametrize("discount", [0.05, 0.6, 1.0])
def test_staleness_examples(discount):
    check_staleness_monotone([1.0, 2.0, 3.0, 4.0], discount)


@pytest.mark.parametrize("cap,n_rows", [(2, 5), (1, 4), (3, 3), (4, 2)])
def test_compress_examples(cap, n_rows):
    rng = np.random.default_rng(cap * 10 + n_rows)
    check_compress_preserves_mass(
        list(rng.normal(size=(n_rows, 64)).astype(np.float32)),
        rng.uniform(0.1, 5.0, size=n_rows).tolist(), cap)


@pytest.mark.parametrize("family_name", sorted(FAMILIES))
@pytest.mark.parametrize("model_size", [1, 2, 8])
def test_plane_roundtrip_examples(family_name, model_size):
    check_plane_roundtrip(family_name, 1, model_size, seed=3)


@pytest.mark.parametrize("seed,m", [(0, None), (1, 2), (2, 4), (3, 1)])
def test_balanced_sampler_examples(seed, m):
    # m=1 and m=2 force narrow tables (< most class populations); m=None
    # lets build_class_table size the table to the largest class
    check_balanced_sampler_quota(seed, C=3, classes=6, batch=8, steps=2, m=m)
