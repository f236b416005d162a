"""Program spans of the round path: every stretch of host work in a dispatch
block sits in a named span, the spans land on the JAX profiler's host line
under the same names, the disabled tracer records nothing, and the block
program's operations carry named scopes."""
import collections
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core import server as srv
from repro.core.families import mlp_family
from repro.core.resources import participants_from_matrix
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import make_classification, train_test_split
from repro.obs import NULL_TRACER, make_observability, span_coverage
from repro.sim import HeterogeneitySim, SimConfig, make_trace, sample_profiles

# the host spans of one dispatch block, engine and server
BLOCK_SPANS = {"round_block", "mar_decisions", "dispatch", "shard_pack",
               "place_inputs", "block_exec", "loss_sync", "round_stats",
               "record_rounds", "round_boundary"}
SCOPES = ("sampler", "teacher_forward", "member_step", "aggregate")


def _sim(aggregation="sync", rounds=8, R=4, mode="sync", obs=None,
         compact_to=2):
    """Eight participants in two KD levels on a small MLP."""
    ds = make_classification("synth-mnist", 400, seed=0)
    train, test = train_test_split(ds)
    idx = dirichlet_partition(train.y, 8, alpha=2.0, seed=0)
    parts = participants_from_matrix(sample_profiles(8, seed=0),
                                     n_data=[len(p) for p in idx])
    cd = [{"x": train.x[p], "y": train.y[p]} for p in idx]
    cfg = srv.FLConfig(steps_per_round=3, lr=0.08, seed=0, local_batch=8,
                       compact_to=compact_to, rounds_per_dispatch=R,
                       aggregation=aggregation)
    eng = srv.FedRAC(parts, cd, mlp_family(), cfg, classes=10).setup()
    sim = HeterogeneitySim(
        eng, make_trace("stable", 8, rounds),
        SimConfig(rounds=rounds, mode=mode,
                  mar_policy="buffer" if aggregation == "buffered"
                  else "mask"), obs=obs)
    return sim, {"x": jnp.asarray(test.x), "y": jnp.asarray(test.y)}


def _inside(e, outer):
    return (outer["ts"] <= e["ts"]
            and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"])


def _uncovered_blocks(evs) -> list:
    """The ``round_block`` spans whose child spans cover less than 95 % of
    the block's time outside ``loss_sync`` (the host's wait for the
    program), with (covered, duration, loss_sync) in µs."""
    out = []
    for b in (e for e in evs if e["name"] == "round_block"):
        kids = [e for e in evs if e is not b and _inside(e, b)]
        sync = sum(e["dur"] for e in kids if e["name"] == "loss_sync")
        covered = b["dur"] * span_coverage(
            [b] + [e for e in kids if e["name"] != "loss_sync"], "round_block")
        if covered < 0.95 * (b["dur"] - sync):
            out.append((covered, b["dur"], sync))
    return out


@pytest.mark.parametrize("aggregation", ["sync", "buffered"])
def test_round_block_host_work_is_in_spans(aggregation):
    """Inside every ``round_block`` the child spans cover at least 95 % of
    the block's time outside ``loss_sync``; the documented spans appear,
    and the server's spans nest in ``dispatch``.  The host's time between
    spans is a few µs a span, so a busy machine that deschedules the
    process there can tip one block of this tiny job: the job runs again
    on the same engine, up to three times, and one run must hold for every
    block (a stretch of work outside any span fails every run)."""
    sim, test = _sim(aggregation)
    for _ in range(3):
        obs = make_observability()
        sim.fl.obs = obs
        sim = HeterogeneitySim(sim.fl, sim.trace, sim.cfg, obs=obs)
        sim.run(test)
        evs = obs.tracer.events()
        missed = _uncovered_blocks(evs)
        if not missed:
            break
    assert not missed, missed
    names = {e["name"] for e in evs}
    assert BLOCK_SPANS <= names, BLOCK_SPANS - names
    assert ("bank_carry" in names) == (aggregation == "buffered")
    assert sum(e["name"] == "round_block" for e in evs) == 2
    dispatches = [e for e in evs if e["name"] == "dispatch"]
    for name in ("shard_pack", "place_inputs", "block_exec",
                 "block_outputs"):
        inner = [e for e in evs if e["name"] == name]
        assert len(inner) == len(dispatches)
        assert all(any(_inside(e, d) for d in dispatches) for e in inner)
    # instruments without a reader are gone
    assert "pack_h2d" not in names
    counters = obs.registry.counters
    assert not [k for k in counters if k.startswith("agg/")
                or k in ("fl/pack_builds", "fl/dispatch_rounds")]
    assert counters["fl/h2d_bytes"].value > 0


@pytest.mark.parametrize("mode,R", [("sync", 1), ("async", 2)])
def test_legacy_and_async_loops_sync_and_boundary_in_spans(mode, R):
    obs = make_observability()
    sim, test = _sim(rounds=2, R=R, mode=mode, obs=obs)
    sim.run(test)
    names = collections.Counter(e["name"] for e in obs.tracer.events())
    assert names["loss_sync"] >= 2 and names["round_boundary"] >= 2, names


def _host_span_counts(log_dir, names) -> collections.Counter:
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    from jax.profiler import ProfileData

    got = collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            got.update(e.name for e in line.events if e.name in names)
    return got


def _profiled(tmp_path, obs):
    sim, test = _sim("buffered", rounds=4, R=2, obs=obs)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        sim.run(test)


def test_spans_land_on_the_profiler_host_line(tmp_path):
    """Under the JAX profiler every program span is also a host event of
    the same name on the profile; retroactive compile spans stay in the
    tracer only."""
    obs = make_observability()
    _profiled(tmp_path, obs)
    want = collections.Counter(e["name"] for e in obs.tracer.events()
                               if e["name"] != "compile")
    assert BLOCK_SPANS <= set(want)
    assert _host_span_counts(tmp_path, set(want) | {"compile"}) == want


def test_disabled_tracer_records_nothing(tmp_path):
    """A run on the null tracer records no span, in the tracer or on the
    profile."""
    _profiled(tmp_path, None)
    assert NULL_TRACER.events() == []
    assert not _host_span_counts(tmp_path, BLOCK_SPANS)


def test_registry_imports_without_jax():
    code = ("import sys; import repro.obs.registry, repro.obs; "
            "sys.exit('jax' in sys.modules)")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    assert subprocess.run([sys.executable, "-c", code], env=env).returncode == 0


def test_dispatch_program_carries_named_scopes(monkeypatch):
    """The compiled KD, banked block program names the sampler, the teacher
    forward, the member step and the aggregation in its ``op_name``s."""
    seen = []
    orig = srv.FedRAC._dispatch_programs

    def spy(self, level, use_kd, capacity, R, balanced, banked, *a, **kw):
        prog = orig(self, level, use_kd, capacity, R, balanced, banked,
                    *a, **kw)

        def call(*args):
            if use_kd and banked:
                seen.append((getattr(prog, "fn", prog), jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)))
            return prog(*args)
        return call

    monkeypatch.setattr(srv.FedRAC, "_dispatch_programs", spy)
    sim, test = _sim("buffered", rounds=2, R=2)
    sim.run(test)
    assert seen
    fn, args = seen[0]
    text = fn.lower(*args).compile().as_text()
    for scope in SCOPES:
        assert re.search(rf'op_name="[^"]*/{scope}/', text), scope
