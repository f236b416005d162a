"""The harness's CPU path: ``bench/run.py`` refuses to run without a chip,
and a cell's inner functions run at a tiny width on XLA:CPU, where the
program's checked blocks must match the plain float32 reference, and the
bfloat16 control and the planted faults must fail the cell's limits.

Nothing here is a device number: the rehearsal returns only the compared
numbers."""
import dataclasses
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from conftest import tiny

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 11


def test_run_exits_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "cnn-mnist-fedavg", "--seed", "1", "--seconds", "1"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "accelerator" in out.stderr


@pytest.fixture(scope="module")
def c10_job():
    """One tiny Fed-RAC job on CIFAR-10's shape (3 clusters, KD, banked
    members) and its reference replay."""
    from bench import check, harness

    spec = tiny("fedrac-cnn-cifar10", "stable")
    job = harness.run_job(spec, SEED, 0.0)
    prog, layouts = harness.program_outputs(job)
    config = spec["config"]
    reference = harness.family_module(spec, "references")
    args = (reference, config["model"],
            harness.federation(config, spec["mix"]),
            config["participants"]["table_iii"], job.shards, SEED, layouts)
    ref = check.replay(*args, check.REFERENCE)
    init = check.initial(reference, config["model"], ref["params"][0].keys(),
                         SEED)
    return spec, prog, args, (ref["losses"], ref["decisions"],
                              ref["params"], init)


def test_program_matches_the_reference(c10_job):
    from bench import check

    spec, prog, _, ref = c10_job
    assert set(prog["losses"][0]) == {0, 1, 2}           # KD slaves ran
    assert any("banked" in d.values() for d in prog["decisions"][0].values())
    got = check.numbers(prog, *ref)
    # fp32 on the CPU: the same arithmetic up to the order of sums.  The
    # first block agrees to rounding; later rounds may drift apart as the
    # ReLU and max-pool ties amplify rounding, so they are not held here.
    first = sorted(prog["params"])[0]
    gaps = check.loss_gaps(prog["losses"], ref[0])[:first]
    assert max(g for by_level in gaps for g in by_level.values()) < 1e-5
    assert got["grad_gap"] < 1e-5
    assert got["grad_diff"] < 1e-4
    assert got["mar_mismatch"] == 0


def test_control_fails_the_limits(c10_job):
    """The control, the reference computed in bfloat16, put in the
    program's place, departs from the reference by orders of magnitude
    more than the program does on the CPU, and fails the cell's limits."""
    from bench import check

    spec, prog, args, ref = c10_job
    control = check.as_program(check.replay(*args, check.CONTROL),
                               sorted(prog["params"]))
    got, own = check.numbers(control, *ref), check.numbers(prog, *ref)
    assert got["grad_diff"] > 100 * own["grad_diff"]
    assert any(got[k] > lim for k, lim in spec["limits"].items()), got


def _stuck(monkeypatch):
    """A step that returns its state unchanged."""
    import jax.numpy as jnp
    from repro.core import server

    dispatch = server.FedRAC.dispatch_rounds

    def stuck(self, level, members, plane, *a, **kw):
        keep = jnp.array(plane, copy=True)
        out = dispatch(self, level, members, plane, *a, **kw)
        return dataclasses.replace(out, plane=keep)

    monkeypatch.setattr(server.FedRAC, "dispatch_rounds", stuck)


def _half_batch(monkeypatch):
    """Half of every batch left out, the loss averaged over the rest."""
    import jax
    from repro.core import server

    make = server.make_cluster_update

    def half(loss_fn, lr, **kw):
        update = make(loss_fn, lr, **kw)

        def cut(x):
            return x[:, :, :x.shape[2] // 2]

        def run(params, batches, masks, teachers=None):
            return update(params, jax.tree.map(cut, batches), masks,
                          None if teachers is None else cut(teachers))
        return run

    monkeypatch.setattr(server, "make_cluster_update", half)


@pytest.mark.parametrize("fault", [_stuck, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_fault_makes_correct_false(fault, monkeypatch):
    from bench import harness

    fault(monkeypatch)
    res = harness.run(tiny("fedrac-cnn-mnist", "fedavg"), SEED, 0.2, False,
                      time.perf_counter_ns())
    assert res["correct"] is False, res["checks"]
