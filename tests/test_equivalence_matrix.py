"""Cross-path equivalence matrix: ONE golden suite for the five execution
paths × mesh shapes × aggregation schedules.

The paths under test:
  * ``loop``      — an independent per-pid reference loop (host FedAvg);
  * ``vmap``      — ``FedRAC.cluster_round`` (batched one-round program);
  * ``dispatch``  — scan-fused blocks (``FedRAC.dispatch_rounds``) at block
                    widths R ∈ {1, 8};
  * dispatch on a mesh — 1D member-sharded (``8x1``) and 2D
    (data × model) plane-column-sharded (``4x2``, ``2x4``) shard_map
    programs, plus the degenerate ``1x1``.

Historically the legacy paths drew batches from a host numpy stream and the
dispatch path from the in-program ``data/device_sampler`` stream, so
cross-path comparisons were only statistical.  ``StreamBridgedFedRAC``
closes that gap: its ``_client_batches`` replays the device-sampler draws
(keyed on absolute round × global member slot) on the host, so EVERY path
sees bit-identical batches and the whole matrix must agree to rtol 2e-4 on
the final parameters AND the per-round per-member losses — replacing the
scattered pairwise checks that previously lived in ``test_dispatch.py`` /
``test_mesh_plane.py``.

Coverage tiers (same scheme as ``test_mesh_plane.py``): the no-mesh and
``1x1`` columns always run; the 8-device columns run in-process when the
backend has ≥8 devices (CI mesh/mesh2d lanes) and through one slow
subprocess wrapper for tier-1.
"""
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.run_state import make_checkpointer
from repro.core import aggregation
from repro.core import server as srv
from repro.core.client import local_update
from repro.core.families import mlp_family
from repro.core.resources import participants_from_matrix
from repro.data import device_sampler
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import make_classification, train_test_split
from repro.launch.mesh import make_sim_mesh
from repro.sim import HeterogeneitySim, SimConfig, make_trace, sample_profiles
from repro.sim.faults import FaultInjector, FaultPlan, SimulatedCrash

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL, ATOL = 2e-4, 1e-5
ROUNDS = 6

eightway = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 forced host devices (CI mesh lanes or the slow "
           "subprocess wrapper below)")


class StreamBridgedFedRAC(srv.FedRAC):
    """FedRAC whose legacy host batching replays the dispatch path's
    device-sampler stream, keyed on (absolute round, global member slot) —
    the bridge that makes loop/vmap/dispatch numerically comparable."""

    def _client_batches(self, pid, r, balanced):
        d = self.client_data[pid]
        slot = self._member_slot(pid)
        key = device_sampler.round_key(self.cfg.seed, r)
        steps, batch = self.cfg.steps_per_round, self.cfg.local_batch
        if balanced:
            table, counts = self._class_table(pid)
            idx = device_sampler.balanced_indices(
                key, steps, batch, jnp.asarray(table)[None],
                jnp.asarray(counts)[None], offset=slot)
        else:
            idx = device_sampler.uniform_indices(
                key, steps, batch,
                jnp.asarray([len(d["y"])], jnp.int32), offset=slot)
        idx = np.asarray(idx)[0]
        return {"x": d["x"][idx], "y": d["y"][idx]}

    def _member_slot(self, pid: int) -> int:
        for members in self.assignment.members.values():
            if pid in members:
                return list(members).index(pid)
        raise KeyError(pid)


def _build(mesh_shape=None, n=8, seed=0, family=None, **cfg_kw):
    ds = make_classification("synth-mnist", 400, seed=seed)
    train, test = train_test_split(ds)
    idx = dirichlet_partition(train.y, n, alpha=2.0, seed=seed)
    parts = participants_from_matrix(sample_profiles(n, seed=seed),
                                     n_data=[len(p) for p in idx])
    cd = [{"x": train.x[p], "y": train.y[p]} for p in idx]
    # auto-calibrated MAR splits the 8 participants ~3 master / ~5 slave,
    # so the KD column trains a real slave cluster (and C=3/5 exercises the
    # zero-row padding on every mesh width)
    cfg = srv.FLConfig(steps_per_round=3, lr=0.08, seed=seed, local_batch=8,
                       **({"compact_to": 2,
                           "rounds_per_dispatch": 8} | cfg_kw))
    mesh = make_sim_mesh(mesh_shape) if mesh_shape else None
    eng = StreamBridgedFedRAC(parts, cd, family or mlp_family(), cfg,
                              classes=10, mesh=mesh).setup()
    testb = {"x": jnp.asarray(test.x), "y": jnp.asarray(test.y)}
    return eng, testb


def _teacher(eng):
    return eng.family.init(jax.random.PRNGKey(42), 0)


# ------------------------------------------------------------ the five paths
def _run_loop(eng, level, members, rounds, teacher=None):
    """Independent golden reference: per-pid local_update + host FedAvg."""
    cfg = eng.cfg
    loss_fn = jax.tree_util.Partial(eng.family.loss_and_logits, level)
    t_loss_fn = jax.tree_util.Partial(eng.family.loss_and_logits, 0)
    params = eng.family.init(jax.random.PRNGKey(cfg.seed + level), level)
    weights = aggregation.normalized_weights(
        [eng.assignment.n_eff.get(p, 1) for p in members])
    losses_all = []
    for r in range(rounds):
        new_params, losses = [], []
        for pid in members:
            batches = jax.tree.map(jnp.asarray, eng._client_batches(
                pid, r, cfg.class_balanced and level == 0))
            tl = None
            if teacher is not None and cfg.use_kd:
                tl = jax.vmap(lambda b: t_loss_fn(teacher, b)[1])(batches)
            p_new, loss = local_update(loss_fn, params, batches, cfg.lr,
                                       teacher_logits=tl, kd_T=cfg.kd_T,
                                       kd_alpha=cfg.kd_alpha)
            new_params.append(p_new)
            losses.append(float(loss))
        stack = jax.tree.map(lambda *xs: jnp.stack(xs), *new_params)
        params = aggregation.aggregate(stack, weights)
        losses_all.append(losses)
    return params, np.asarray(losses_all, np.float32)


def _run_vmap(eng, level, members, rounds, teacher=None):
    """One batched cluster_round program per round (the legacy fast path)."""
    params = eng.family.init(
        jax.random.PRNGKey(eng.cfg.seed + level), level)
    weights = [eng.assignment.n_eff.get(p, 1) for p in members]
    losses = []
    for r in range(rounds):
        params, l = eng.cluster_round(level, members, params, r,
                                      teacher=teacher, weights=weights)
        losses.append(np.asarray(l))
    return params, np.stack(losses)


def _run_dispatch(eng, level, members, rounds, R, teacher=None):
    """Scan-fused blocks of width R (on whatever mesh ``eng`` carries)."""
    plane = eng.plane_of(level, eng.family.init(
        jax.random.PRNGKey(eng.cfg.seed + level), level))
    losses, r = [], 0
    while r < rounds:
        L = min(R, rounds - r)
        out = eng.dispatch_rounds(level, members, plane, r, L,
                                  teacher=teacher)
        plane = out.plane
        losses.append(np.asarray(out.losses))
        r += L
    return eng.params_of(level, plane), np.concatenate(losses)


def _assert_cell(golden, got, tag):
    gp, gl = golden
    p, l = got
    for x, y in zip(jax.tree.leaves(gp), jax.tree.leaves(p)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=RTOL,
                                   atol=ATOL, err_msg=f"params[{tag}]")
    np.testing.assert_allclose(gl, l, rtol=RTOL, atol=ATOL,
                               err_msg=f"losses[{tag}]")


@functools.lru_cache(maxsize=None)
def _golden(scenario):
    """Golden column: the independent loop on the no-mesh engine (cached —
    every matrix cell compares against the same one reference run)."""
    eng, _ = _build()
    level = 0 if scenario == "fedavg" else 1
    members = list(eng.assignment.members[level])
    teacher = _teacher(eng) if scenario == "kd" else None
    return _run_loop(eng, level, members, ROUNDS, teacher), level, members


# ----------------------------------------------------------- sync schedules
@pytest.mark.parametrize("scenario", ["fedavg", "kd"])
def test_matrix_sync_fast(scenario):
    """Always-on subset: {loop, vmap, dispatch R∈{1,8}} unsharded plus the
    degenerate 1x1 mesh, for the balanced FedAvg master and the KD slave."""
    golden, level, members = _golden(scenario)
    for tag, run in (
            ("vmap", lambda e, t: _run_vmap(e, level, members, ROUNDS, t)),
            ("disp-r1", lambda e, t: _run_dispatch(e, level, members,
                                                   ROUNDS, 1, t)),
            ("disp-r8", lambda e, t: _run_dispatch(e, level, members,
                                                   ROUNDS, 8, t))):
        eng, _ = _build()
        teacher = _teacher(eng) if scenario == "kd" else None
        _assert_cell(golden, run(eng, teacher), f"{scenario}/{tag}")
    eng, _ = _build(mesh_shape="1x1")
    teacher = _teacher(eng) if scenario == "kd" else None
    _assert_cell(golden, _run_dispatch(eng, level, members, ROUNDS, 8,
                                       teacher), f"{scenario}/1x1-r8")


@pytest.mark.parametrize("scenario", ["fedavg", "kd"])
def test_matrix_loop_engine_runs_fused(scenario):
    """The independent-loop column can opt into the fused path: a
    ``vmap_clusters=False`` engine with ``allow_loop_dispatch=True`` builds
    the same scan-fused block programs and matches the golden loop — so
    loop-mode debugging configs no longer pay one program per member per
    round when they only want the legacy batching semantics elsewhere."""
    golden, level, members = _golden(scenario)
    eng, _ = _build(vmap_clusters=False, allow_loop_dispatch=True)
    teacher = _teacher(eng) if scenario == "kd" else None
    _assert_cell(golden, _run_dispatch(eng, level, members, ROUNDS, 8,
                                       teacher),
                 f"{scenario}/loop-fused-r8")


def test_loop_dispatch_requires_opt_in():
    """R>1 on a loop engine stays an explicit contract: the engine ctor
    rejects it unless ``allow_loop_dispatch`` opts in (the error message
    names the escape hatch)."""
    with pytest.raises(ValueError, match="allow_loop_dispatch"):
        _build(vmap_clusters=False)
    eng, _ = _build(vmap_clusters=False, allow_loop_dispatch=True)
    assert not eng.cfg.vmap_clusters and eng.cfg.rounds_per_dispatch == 8


@eightway
@pytest.mark.parametrize("mesh_shape", ["8x1", "4x2", "2x4"])
@pytest.mark.parametrize("scenario", ["fedavg", "kd"])
def test_matrix_sync_eightway(scenario, mesh_shape):
    """8-device columns: member-sharded (8x1) and 2D plane-column-sharded
    (4x2 / 2x4) dispatch at R ∈ {1, 8} against the unsharded golden loop —
    with one compile per program and donation still enforced."""
    golden, level, members = _golden(scenario)
    eng, _ = _build(mesh_shape=mesh_shape)
    teacher = _teacher(eng) if scenario == "kd" else None
    for R in (1, 8):
        _assert_cell(golden, _run_dispatch(eng, level, members, ROUNDS, R,
                                           teacher),
                     f"{scenario}/{mesh_shape}-r{R}")
    stats = eng.compile_stats()
    retraced = {k: v for k, v in stats.items() if v != 1}
    assert not retraced, f"programs retraced on {mesh_shape}: {retraced}"
    # donated-plane reuse must still raise on the 2D mesh
    plane = eng.plane_of(level, eng.family.init(jax.random.PRNGKey(7), level))
    out = eng.dispatch_rounds(level, members, plane, 0, 2, teacher=teacher)
    assert plane.is_deleted() and not out.plane.is_deleted()
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(plane)


@eightway
@pytest.mark.parametrize("mesh_shape", ["8x1", "4x2", "2x4"])
def test_matrix_kd_sim_eightway(mesh_shape):
    """KD at simulator granularity on 8 devices: fused blocks return the
    master's per-round ``want_history`` plane stack and scan the slaves'
    per-round ``teacher_planes`` — both column-sharded on the 2D meshes
    (the ``sp["stack"]`` specs and the teacher column gather) — and the
    result matches the unsharded dispatch engine."""
    outs = {}
    for shape in (None, mesh_shape):
        eng, testb = _build(mesh_shape=shape)
        sim = HeterogeneitySim(eng, make_trace("stable", 8, ROUNDS),
                               SimConfig(rounds=ROUNDS))
        sim._run_dispatch(testb)
        outs[shape] = sim.params
    assert len(outs[None]) > 1, "no slave cluster — teacher stacks unused"
    for lvl in outs[None]:
        for x, y in zip(jax.tree.leaves(outs[None][lvl]),
                        jax.tree.leaves(outs[mesh_shape][lvl])):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=RTOL, atol=ATOL,
                                       err_msg=f"kd-sim/{mesh_shape}/L{lvl}")


# ------------------------------------------------------------ buffered async
def _run_buffered_sim(mesh_shape, R, rounds=5, seed=0, mode="sync",
                      max_staleness=0, compact_to=1):
    """Buffered schedule under a straggling cluster (the slower half misses
    the deadline every round → banks, flushes next round).  Returns
    (final params, structural telemetry, per-round mean losses).  The
    stream bridge makes the comparison numeric, not just structural.
    ``mode="async"`` runs the continuous-time async server instead; with
    ``max_staleness=0`` (synchronized arrivals) it must reproduce the
    buffered path bit-for-bit."""
    from repro.core import cost_model
    eng, testb = _build(mesh_shape=mesh_shape, seed=seed,
                        compact_to=compact_to,
                        aggregation="buffered", rounds_per_dispatch=R)
    spec = eng.specs[0]
    t = sorted(cost_model.round_time(
        p, spec.flops_per_sample, spec.model_bytes, spec.E,
        eng.assignment.n_eff.get(p.pid, p.n_data)) for p in eng.parts)
    spec.mar = 0.5 * (t[len(t) // 2 - 1] + t[len(t) // 2])
    kw = ({"mode": "async", "max_staleness": max_staleness}
          if mode == "async" else {})
    sim = HeterogeneitySim(eng, make_trace("stable", len(eng.parts), rounds),
                           SimConfig(rounds=rounds, mar_policy="buffer",
                                     **kw))
    rep = sim.run(testb)
    tel = [(r.round, [(c.level, sorted(c.active), sorted(c.banked),
                       c.flushed) for c in r.clusters]) for r in rep.rows]
    losses = np.asarray([[c.mean_loss for c in r.clusters]
                         for r in rep.rows], np.float32)
    return sim.params, tel, losses


@functools.lru_cache(maxsize=None)
def _buffered_golden():
    """Legacy-engine buffered run (cached golden for all buffered cells)."""
    return _run_buffered_sim(None, 1)


def _assert_buffered_cell(golden, got, tag):
    gp, gtel, gl = golden
    p, tel, l = got
    assert tel == gtel, f"telemetry[{tag}]"
    banked = sum(len(b) for _, cs in gtel for _, _, b, _ in cs)
    assert banked > 0, "straggler setup never banked — matrix cell vacuous"
    np.testing.assert_allclose(gl, l, rtol=RTOL, atol=ATOL,
                               err_msg=f"mean_losses[{tag}]")
    for lvl in gp:
        for x, y in zip(jax.tree.leaves(gp[lvl]), jax.tree.leaves(p[lvl])):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=RTOL, atol=ATOL,
                                       err_msg=f"params[{tag}]")


@pytest.mark.parametrize("mesh_shape,R", [(None, 8), ("1x1", 8)])
def test_matrix_buffered_fast(mesh_shape, R):
    """Buffered column, always-on subset: legacy engine (golden) vs fused
    dispatch and the degenerate 1x1 mesh — same bank/flush telemetry, same
    mean losses, same final params."""
    _assert_buffered_cell(_buffered_golden(), _run_buffered_sim(mesh_shape, R),
                          f"buffered/{mesh_shape}-r{R}")


@eightway
@pytest.mark.parametrize("mesh_shape", ["8x1", "4x2", "2x4"])
def test_matrix_buffered_eightway(mesh_shape):
    """Buffered column at 8 devices: the bank rides the sharded scan carry
    (2D meshes: column-sharded) and still matches the legacy engine."""
    _assert_buffered_cell(_buffered_golden(), _run_buffered_sim(mesh_shape, 8),
                          f"buffered/{mesh_shape}-r8")


# ------------------------------------------------- async ≡ sync-arrivals
# The async-server anchor: ``mode="async"`` with ``max_staleness=0``
# (synchronized arrivals — every cluster merges at the shared barrier)
# must reproduce the buffered path BIT-exactly (np.array_equal, not the
# matrix rtol): same final params, same bank/flush telemetry, same
# per-round mean losses.  Version-based staleness discounts degenerate to
# the buffered round-age discounts round for round, so any drift here is
# an async-scheduler bug, not numerics.
def _assert_async_cell(golden, got, tag):
    gp, gtel, gl = golden
    p, tel, l = got
    assert tel == gtel, f"telemetry[{tag}]"
    assert np.array_equal(gl, l, equal_nan=True), f"mean_losses[{tag}]"
    for lvl in gp:
        for x, y in zip(jax.tree.leaves(gp[lvl]), jax.tree.leaves(p[lvl])):
            assert np.array_equal(np.asarray(x), np.asarray(y)), \
                f"params[{tag}] L{lvl} not bit-equal"


@pytest.mark.parametrize("R", [1, 8])
def test_matrix_async_sync_arrivals_fast(R):
    """Async column, always-on subset: legacy per-round jit (R=1, against
    the cached buffered golden) and fused dispatch (R=8) — synchronized
    arrivals reproduce the buffered engine bit-for-bit."""
    golden = (_buffered_golden() if R == 1
              else _run_buffered_sim(None, R))
    got = _run_buffered_sim(None, R, mode="async", max_staleness=0)
    _assert_async_cell(golden, got, f"async/sync-arrivals-r{R}")


def test_matrix_async_kd_barrier():
    """Async column with a real slave cluster (compact_to=2): the KD
    teacher rides ``MasterBlock`` — at synchronized arrivals the slave
    block aligns with the master's dispatch and gets the exact per-round
    teacher stack, so the whole two-cluster run stays bit-exact."""
    golden = _run_buffered_sim(None, 8, rounds=6, compact_to=2)
    got = _run_buffered_sim(None, 8, rounds=6, compact_to=2,
                            mode="async", max_staleness=0)
    _assert_async_cell(golden, got, "async/kd-barrier-r8")


@eightway
def test_matrix_async_eightway():
    """Async column at 8 devices: the 4x2 (data × model) mesh cell — the
    async scheduler drives the same column-sharded dispatch programs and
    synchronized arrivals still match the buffered run bit-exactly."""
    _assert_async_cell(_run_buffered_sim("4x2", 8),
                       _run_buffered_sim("4x2", 8, mode="async",
                                         max_staleness=0),
                       "async/4x2-r8")


# ------------------------------------------------------------ resume column
# kill/resume ≡ uninterrupted, at BIT-exactness (np.array_equal, not the
# rtol used across execution paths): every cell crashes at round boundary 3
# via an in-process SimulatedCrash, then a FRESH engine (new-process
# stand-in) resumes from the checkpoint and must reproduce the control
# run's final params, per-round rows, and summary totals exactly.
SIM_ROUNDS = 5


def _resume_cell_builder(mesh_shape=None, R=8, buffered=False, mode="sync",
                         max_staleness=None):
    """() -> (engine, test batch, SimConfig, trace) for one resume cell."""
    kw = ({"mode": "async", "max_staleness": max_staleness}
          if mode == "async" else {})

    def build():
        if buffered:
            from repro.core import cost_model
            eng, testb = _build(mesh_shape=mesh_shape, compact_to=1,
                                aggregation="buffered", rounds_per_dispatch=R)
            spec = eng.specs[0]
            t = sorted(cost_model.round_time(
                p, spec.flops_per_sample, spec.model_bytes, spec.E,
                eng.assignment.n_eff.get(p.pid, p.n_data))
                for p in eng.parts)
            spec.mar = 0.5 * (t[len(t) // 2 - 1] + t[len(t) // 2])
            simcfg = SimConfig(rounds=SIM_ROUNDS, mar_policy="buffer", **kw)
            trace = make_trace("stable", 8, SIM_ROUNDS, seed=5)
        else:
            eng, testb = _build(mesh_shape=mesh_shape, rounds_per_dispatch=R)
            simcfg = SimConfig(rounds=SIM_ROUNDS, mar_policy="mask", **kw)
            trace = make_trace("mixed", 8, SIM_ROUNDS, seed=5)
        return eng, testb, simcfg, trace
    return build


def _resume_run(ckpt_dir, builder, kill=None, resume=False):
    eng, testb, simcfg, trace = builder()
    ck = (make_checkpointer(str(ckpt_dir), every=1, resume=resume)
          if ckpt_dir is not None else None)
    faults = (FaultInjector(FaultPlan(kill_at_round=kill,
                                      raise_instead=True))
              if kill is not None else None)
    sim = HeterogeneitySim(eng, trace, simcfg, checkpoint=ck, faults=faults)
    try:
        rep = sim.run(testb)
    except SimulatedCrash:
        return None
    params = {lvl: [np.asarray(x) for x in jax.tree.leaves(p)]
              for lvl, p in sim.params.items()}
    rows = [(r.round, r.duration,
             [(c.level, c.time, c.mean_loss, sorted(c.active),
               sorted(c.dropped), sorted(c.offline),
               sorted(c.masked.items()), sorted(c.violations),
               sorted(c.banked), sorted(c.unselected), c.flushed, c.bytes,
               c.acc) for c in r.clusters]) for r in rep.rows]
    summary = {k: v for k, v in rep.summary().items()
               if k not in ("compiles", "transfers")}   # process-local
    return params, rows, summary


def _assert_resume_cell(ctrl, res, tag):
    assert res is not None, f"[{tag}] resumed run crashed"
    for lvl in ctrl[0]:
        for a, b in zip(ctrl[0][lvl], res[0][lvl]):
            assert np.array_equal(a, b), f"params[{tag}] L{lvl} not bit-equal"
    assert ctrl[1] == res[1], f"rows[{tag}]"
    assert ctrl[2] == res[2], f"summary[{tag}]"


RESUME_CELLS = {
    "legacy": lambda: _resume_cell_builder(R=1),
    "disp-r8": lambda: _resume_cell_builder(R=8),
    "buffered": lambda: _resume_cell_builder(buffered=True),
    # async cell: two clusters on independent clocks, unbounded staleness,
    # mixed arrival/departure trace; ``kill=3`` counts MERGE EVENTS (the
    # async checkpoint cadence), and the resumed run — per-cluster clocks,
    # server versions, in-flight ledger and pending blocks all off the
    # checkpoint — must match its own uninterrupted control bit-exactly
    "async": lambda: _resume_cell_builder(R=1, mode="async",
                                          max_staleness=None),
}


@pytest.mark.parametrize("cell", sorted(RESUME_CELLS))
def test_matrix_resume_fast(cell, tmp_path):
    """Resume column, always-on subset: legacy per-round jit, fused
    dispatch R=8, and the buffered/bank schedule (banked rows + ages ride
    the checkpoint) — each kill/resume bit-identical to its control."""
    builder = RESUME_CELLS[cell]()
    ctrl = _resume_run(None, builder)
    assert _resume_run(tmp_path, builder, kill=3) is None
    _assert_resume_cell(ctrl, _resume_run(tmp_path, builder, resume=True),
                        f"resume/{cell}")


@eightway
def test_matrix_resume_eightway(tmp_path):
    """Resume column at 8 devices: the 4x2 (data × model) mesh cell — the
    checkpointed planes are re-committed to the 2D sharding on restore and
    the resumed run still matches its own control bit-exactly."""
    builder = _resume_cell_builder(mesh_shape="4x2")
    ctrl = _resume_run(None, builder)
    assert _resume_run(tmp_path, builder, kill=3) is None
    _assert_resume_cell(ctrl, _resume_run(tmp_path, builder, resume=True),
                        "resume/4x2-r8")


# ------------------------------------------------------- sampler × 2D mesh
@eightway
def test_sampler_draws_independent_of_model_axis():
    """data/device_sampler regression on the 2D mesh: in-program draws are
    keyed on (absolute round, GLOBAL member slot) only, so a device's draw
    depends on its ``data`` coordinate alone — every ``model`` column draws
    bit-identically, and all equal the unsharded draw."""
    mesh = make_sim_mesh("4x2")
    from jax.sharding import PartitionSpec as P
    C, steps, batch = 8, 3, 4
    n = jnp.arange(5, 5 + C, dtype=jnp.int32) * 7
    key = device_sampler.round_key(3, 11)

    def draw(n_loc):
        off = jax.lax.axis_index("data") * n_loc.shape[0]
        idx = device_sampler.uniform_indices(key, steps, batch, n_loc,
                                             offset=off)
        # out_spec P('data', ...) demands model-axis replication: shard_map's
        # rep check would refuse to stitch draws that varied by model column
        return jax.lax.pmean(idx.astype(jnp.float32), "model")

    fn = jax.shard_map(draw, mesh=mesh, in_specs=(P("data"),),
                       out_specs=P("data", None, None))
    sharded = np.asarray(fn(n))
    full = np.asarray(device_sampler.uniform_indices(key, steps, batch, n))
    np.testing.assert_array_equal(sharded, full.astype(np.float32))


# --------------------------------------------------------------- TP column
# On 2D meshes the engine now defaults to the GSPMD tensor-parallel member
# forward (``FLConfig.tp_forward``), so every 4x2/2x4 cell above already
# exercises TP for the MLP family.  The cells below cover what those don't:
# the legacy shard_map gather path (``tp_forward=False``), the CNN/LM
# families' TP specs, and the per-device-memory acceptance criterion.
@eightway
@pytest.mark.parametrize("mesh_shape", ["4x2", "2x4"])
@pytest.mark.parametrize("scenario", ["fedavg", "kd"])
def test_matrix_legacy_gather_eightway(scenario, mesh_shape):
    """``tp_forward=False`` keeps the pre-TP shard_map path (transient
    column all-gather + replicated forward) working against the golden."""
    golden, level, members = _golden(scenario)
    eng, _ = _build(mesh_shape=mesh_shape, tp_forward=False)
    assert not eng._tp
    teacher = _teacher(eng) if scenario == "kd" else None
    _assert_cell(golden, _run_dispatch(eng, level, members, ROUNDS, 8,
                                       teacher),
                 f"legacy-gather/{scenario}/{mesh_shape}")


def _build_tp_family(famname, mesh_shape=None, **cfg_kw):
    """Engine over the CNN or (token-data) LM family for the TP cells."""
    if famname == "cnn":
        from repro.core.families import cnn_family
        fam = cnn_family(classes=10, in_channels=1, base_width=0.125)
        return _build(mesh_shape=mesh_shape, family=fam,
                      class_balanced=False, **cfg_kw)[0]
    from repro.configs.base import ModelConfig
    from repro.core.families import lm_family
    from repro.data.synthetic import make_lm_corpus, lm_batches
    base = ModelConfig(name="matrix-lm", family="dense", n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=4, head_dim=8,
                       d_ff=64, vocab_size=64, rope_theta=1e4)
    corpus = make_lm_corpus(64, 8_000, seed=0)
    chunks = np.array_split(corpus, 8)
    cd = [{"tokens": lm_batches(ch, 32, 17, 1, seed=i)[0]}
          for i, ch in enumerate(chunks)]
    parts = participants_from_matrix(sample_profiles(8, seed=0),
                                     n_data=[64] * 8)

    class TokenFedRAC(srv.FedRAC):
        def _batch_from_gathered(self, g):
            return {"tokens": g["tokens"], "y": g["tokens"][:, :, -1]}

    cfg = srv.FLConfig(steps_per_round=3, lr=0.05, seed=0, local_batch=4,
                       compact_to=2, rounds_per_dispatch=8,
                       class_balanced=False, **cfg_kw)
    mesh = make_sim_mesh(mesh_shape) if mesh_shape else None
    return TokenFedRAC(parts, cd, lm_family(base, alpha=0.5), cfg,
                       classes=64, mesh=mesh).setup()


def _bank_for(eng, level, cap):
    """Two seeded bank rows in THIS engine's plane layout (TP and legacy
    planes are not byte-compatible — banks only convert through pytrees)."""
    rows = jnp.stack([eng.plane_of(level, eng.family.init(
        jax.random.PRNGKey(100 + i), level)) for i in range(2)])
    D = rows.shape[1]
    return (eng.place_member_plane(
                jnp.zeros((cap, D), jnp.float32).at[:2].set(rows)),
            eng.place_member_sharded(
                jnp.zeros((cap,), jnp.float32).at[:2].set(
                    jnp.asarray([0.5, 0.25]))),
            eng.place_member_sharded(jnp.zeros((cap,), jnp.float32)))


@eightway
@pytest.mark.parametrize("famname", ["cnn", "lm"])
@pytest.mark.parametrize("scenario", ["fedavg", "kd", "buffered"])
def test_matrix_tp_families_eightway(famname, scenario):
    """TP ≡ replicated for the CNN and LM families on the 2x4 mesh:
    identical dispatch blocks (same sampler stream, same bank rows) on the
    TP engine and the unsharded engine must agree to matrix tolerance —
    with one compile per program (the LM KD cell also runs the teacher
    forward TP-sharded)."""
    level = 0 if scenario == "fedavg" else 1
    outs = {}
    for shape in (None, "2x4"):
        eng = _build_tp_family(famname, mesh_shape=shape)
        if shape is not None:
            assert eng._tp, "TP inactive on the 2D mesh"
        members = list(eng.assignment.members[level])
        cap = eng._capacity(len(members))
        teacher = (eng.family.init(jax.random.PRNGKey(42), 0)
                   if scenario != "fedavg" else None)
        bank = _bank_for(eng, level, cap) if scenario == "buffered" else None
        plane = eng.plane_of(level, eng.family.init(
            jax.random.PRNGKey(eng.cfg.seed + level), level))
        out = eng.dispatch_rounds(level, members, plane, 0, ROUNDS,
                                  teacher=teacher, bank=bank)
        outs[shape] = (eng.params_of(level, out.plane),
                       np.asarray(out.losses))
        if shape is not None:
            stats = eng.compile_stats()
            bad = {k: v for k, v in stats.items() if v != 1}
            assert not bad, bad
    _assert_cell(outs[None], outs["2x4"], f"tp/{famname}/{scenario}")


@eightway
def test_tp_member_forward_sharding_eightway():
    """Acceptance criterion for the TP member forward: per-device plane
    bytes scale as D/model_size, and the lowered dispatch program contains
    NO plane-magnitude all-gather — the transient column gather the TP
    path exists to kill (the legacy path all-gathers the full (D,) plane
    into every device each round)."""
    from repro.launch.hlo_analysis import collective_bytes
    eng, _ = _build(mesh_shape="2x4")
    level, members = 0, list(eng.assignment.members[0])
    cap = eng._capacity(len(members))
    spec = eng.plane_spec(level)
    plane = eng.plane_of(level, eng.family.init(jax.random.PRNGKey(3), level))
    out = eng.dispatch_rounds(level, members, plane, 0, 8)
    # each device holds exactly its 1/msize column slice of the plane
    shard_sizes = {s.data.size for s in out.plane.addressable_shards}
    assert shard_sizes == {spec.d_pad // spec.msize}, shard_sizes
    # lower the cached program and audit its collectives
    balanced = eng.cfg.class_balanced and level == 0
    pack = eng._shard_pack(level, members, cap, balanced)
    prog = eng._dispatch_programs(level, False, cap, 8, balanced, False,
                                  False, pack=pack)
    masks = eng.place_member_sharded(
        jnp.ones((cap, eng.cfg.steps_per_round), jnp.float32))
    w = eng.place_member_sharded(jnp.ones((cap,), jnp.float32))
    low = prog.lower(out.plane, pack["shards"], pack["n"], pack["tables"],
                     pack["counts"], jnp.asarray(0, jnp.int32), masks, w,
                     None)
    cb = collective_bytes(low.compile().as_text())
    plane_bytes = spec.d_pad * 4
    assert cb["bytes"].get("all-gather", 0) < plane_bytes // 2, cb["bytes"]


# ------------------------------------------------------ subprocess (tier-1)
@pytest.mark.slow
def test_matrix_under_forced_host_devices():
    """Tier-1 coverage of the 8-device matrix columns: rerun the
    ``eightway`` cells in a subprocess with 8 forced host devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.abspath(__file__), "-k", "eightway or model_axis"],
        capture_output=True, text=True, timeout=560, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr[-3000:]
    assert "26 passed" in r.stdout, r.stdout
