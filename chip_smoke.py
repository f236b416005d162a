#!/usr/bin/env python3
"""Chip smoke test: the Fed-RAC dispatch path, end to end, on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # a four-chip host: 4x1 and 2x2 meshes

It drives the simulator launcher ``repro.launch.sim_run`` (``parse_args``,
``build``, ``make_sim``, as its ``run`` does) on the paper's CNN at its
published widths (``--base-width 1.0``: C128-C64-C128-C256-C512-D10,
1,631,690 fp32 parameters at the master level) over the 40 Table III
participants, compacted to three clusters: the master FedAvg block, the KD
slave blocks with their per-round teacher stacks, the buffered bank and the
donated planes all run in one job of scan-fused 4-round dispatch blocks.
Weights are random from ``--seed``.

One chip checks, one line each: finite per-round losses and a falling
master loss, one compile per program, the Pallas ``fedagg`` kernel in the
master block program (``tpu_custom_call``), that kernel against
``jnp.tensordot`` at full fp32 precision, and the same job at
``--rounds-per-dispatch 2`` ending on the same master parameters.
``--four-chips`` runs only the job on a ``4x1`` mesh (shard_map over the
member rows) and on a ``2x2`` mesh (GSPMD tensor-parallel member forward)
and compares each with the same job on one device, in one process.

Without a TPU it exits non-zero and prints no result.  The last line of
stdout is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

# A stable fleet and an eval every 4 rounds let every block fuse the full
# dispatch width (an event or an eval ends a block early), so each run
# compiles one program per cluster.
JOB = ["--dataset", "synth-cifar", "--participants", "40",
       "--base-width", "1.0", "--compact-to", "3", "--mar-policy", "buffer",
       "--trace", "stable", "--rounds", "8", "--eval-every", "4"]
# Two runs of one job (R=2 vs R=4, a mesh vs one device) see the same
# batches and run the same convs at the same default matmul precision; only
# fusion, the order of fp32 reductions and the psum split may differ.
# Nothing differs before the first aggregation, so every cluster's round-0
# loss agrees to the equivalence matrix's tolerance.  After it, the CNN's
# ReLU and max-pool ties amplify a rounding difference about tenfold per
# round: a 1e-7 relative perturbation of the initial master plane moves the
# plane after 8 rounds by 1.3e-3 in relative L2 (XLA:CPU, width 0.125), and
# a 4x1 or 2x2 mesh lands 1.0e-3 and 0.8e-3 away.  So the final master
# parameters are held to 1e-2 in relative L2; a lost psum or a device
# training the wrong members moves them by order 1.
LOSS_RTOL = 2e-4
DRIFT_TOL = 1e-2


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r});"
                 " this check runs only on the chip")
    return dev


def check(name: str, ok: bool, detail: str) -> bool:
    print(f"[{'PASS' if ok else 'FAIL'}] {name}: {detail}")
    return ok


class _ArgRecorder:
    """Calls a dispatch program and keeps the shapes and shardings of its
    first call's arguments, so the program can be lowered again after the
    run (its inputs are donated)."""

    def __init__(self, prog):
        self.prog = prog
        self.specs = None

    def __call__(self, *args):
        if self.specs is None:
            self.specs = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), args)
        return self.prog(*args)

    def lowered_text(self) -> str:
        return self.prog.fn.lower(*self.specs).as_text()


class Run(NamedTuple):
    eng: object          # the FedRAC engine
    sim: object          # the HeterogeneitySim, final params in .params
    report: object       # its SimReport
    obs: object          # fenced observability bundle
    recorders: dict      # program label -> _ArgRecorder


def run_job(extra: list[str]) -> Run:
    """One simulator run of ``JOB + extra`` through the launcher's own
    functions."""
    from repro.launch import sim_run
    from repro.obs import make_observability

    args = sim_run.parse_args(JOB + extra)
    eng, testb = sim_run.build(args)
    recorders = {}
    make_program = eng._dispatch_programs

    def recorded(*a, **kw):
        prog = make_program(*a, **kw)
        if prog._label not in recorders:
            recorders[prog._label] = _ArgRecorder(prog)
        return recorders[prog._label]

    eng._dispatch_programs = recorded
    obs = make_observability(fence=True)     # spans cover device execution
    sim = sim_run.make_sim(args, eng, obs=obs)
    return Run(eng, sim, sim.run(testb), obs, recorders)


def master_params(sim) -> np.ndarray:
    """The master's final parameters as one vector (layout-independent)."""
    return np.asarray(ravel_pytree(sim.params[0])[0])


def block_seconds(obs) -> dict:
    """Fenced wall seconds of each dispatch block that compiled nothing,
    by (level, R)."""
    ev = obs.tracer.events()
    compiles = [(e["ts"], e["ts"] + e["dur"]) for e in ev
                if e["name"] == "compile"]
    out = {}
    for e in ev:
        if e["name"] != "block_exec":
            continue
        t0, t1 = e["ts"], e["ts"] + e["dur"]
        if any(t0 <= c0 and c1 <= t1 for c0, c1 in compiles):
            continue
        a = e["args"]
        out.setdefault((a["level"], a["R"]), []).append(e["dur"] / 1e6)
    return out


def losses(run: Run) -> np.ndarray:
    """(rounds, clusters) mean member loss per round."""
    return np.array([[c.mean_loss for c in r.clusters]
                     for r in run.report.rows])


def check_run(tag: str, run: Run) -> bool:
    """Losses finite everywhere, master loss falling, one compile each."""
    losses_ = losses(run)
    m = losses_[:, 0]
    ok = check(f"{tag} losses finite", bool(np.isfinite(losses_).all()),
               f"{losses_.shape[0]} rounds x {losses_.shape[1]} clusters")
    ok &= check(f"{tag} master loss falls", bool(m[-1] < m[0]),
                "per round " + " ".join(f"{x:.4f}" for x in m))
    stats = run.eng.compile_stats()
    ok &= check(f"{tag} one compile per program",
                all(v == 1 for v in stats.values()),
                f"{len(stats)} programs, compiles "
                f"{sorted(stats.values())}")
    return ok


def compare_runs(name: str, run: Run, ref: Run) -> bool:
    """Same round-0 losses, and final master parameters within the drift
    that fp32 rounding alone produces (see DRIFT_TOL)."""
    l, l_ref = losses(run)[0], losses(ref)[0]
    ok = check(f"{name}: round-0 losses", bool(np.allclose(
        l, l_ref, rtol=LOSS_RTOL, atol=0.0)),
        f"{np.round(l, 6).tolist()} vs {np.round(l_ref, 6).tolist()} "
        f"(rtol {LOSS_RTOL})")
    a, b = master_params(run.sim), master_params(ref.sim)
    drift = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    ok &= check(f"{name}: final master params", drift <= DRIFT_TOL,
                f"relative L2 {drift:.3e}, max |diff| "
                f"{np.abs(a - b).max():.3e} (tolerance {DRIFT_TOL} "
                f"relative L2)")
    return ok


def check_kernel_vs_tensordot(plane: np.ndarray, C: int = 16,
                              seed: int = 0) -> bool:
    """fedagg on a (C, D) stack of perturbed master planes against
    ``jnp.tensordot`` at ``highest`` precision.  Both sum C fp32 products in
    different orders, so each column may differ by at most the forward error
    bound of two such sums: (C + 1) · 2^-23 · Σ_c |w_c x_cd|."""
    from repro.core import aggregation

    rng = np.random.default_rng(seed)
    p = jnp.asarray(plane, jnp.float32)
    X = p[None] + 0.01 * jnp.asarray(
        rng.standard_normal((C, p.shape[0]), np.float32)) * jnp.abs(p).max()
    w = aggregation.normalized_weights(rng.uniform(1.0, 50.0, C))
    got = jax.jit(lambda x, w: aggregation.aggregate_plane(
        x, w, use_kernel=True))(X, w)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda x, w: jnp.tensordot(w, x, axes=(0, 0)))(X, w)
        mass = jax.jit(lambda x, w: jnp.tensordot(
            jnp.abs(w), jnp.abs(x), axes=(0, 0)))(X, w)
    bound = (C + 1) * 2.0 ** -23 * np.asarray(mass)
    err = np.abs(np.asarray(got) - np.asarray(want))
    return check("fedagg == tensordot(highest)", bool((err <= bound).all()),
                 f"C={C} D={p.shape[0]}: max |diff| {err.max():.3e}, "
                 f"max |diff|/bound {(err / bound).max():.3f} "
                 f"(bound (C+1)*2^-23*sum|w x|)")


def one_chip(dev) -> bool:
    print(f"# device_kind={dev.device_kind} devices={len(jax.devices())}")
    r4 = run_job(["--rounds-per-dispatch", "4"])
    ok = check_run("R=4", r4)
    for k, v in sorted(r4.obs.registry.gauges.items()):
        if k.startswith("fl/compile_s/"):
            print(f"# compile_s {k[len('fl/compile_s/'):]} {v.value:.3f}")
    for (lvl, R), secs in sorted(block_seconds(r4.obs).items()):
        print(f"# block_s level={lvl} R={R} "
              + " ".join(f"{s:.4f}" for s in secs))
    master = [r for lbl, r in r4.recorders.items()
              if lbl.startswith("dispatch_L0_")]
    ok &= check("fedagg kernel in master block",
                bool(master) and all("tpu_custom_call" in r.lowered_text()
                                     for r in master),
                ", ".join(sorted(r.prog._label for r in master)))
    ok &= check_kernel_vs_tensordot(r4.eng.plane_of(0, r4.sim.params[0]))
    r2 = run_job(["--rounds-per-dispatch", "2"])
    ok &= check_run("R=2", r2)
    ok &= compare_runs("R=2 vs R=4", r2, r4)
    print(f"# peak_bytes_in_use "
          f"{(dev.memory_stats() or {}).get('peak_bytes_in_use')}")
    return ok


def four_chips() -> bool:
    devs = jax.devices()
    if len(devs) != 4:
        sys.exit(f"chip_smoke: --four-chips needs 4 devices, JAX has "
                 f"{len(devs)}")
    ref = run_job(["--rounds-per-dispatch", "4"])
    ok = check_run("1 device", ref)
    for shape in ("4x1", "2x2"):
        run = run_job(["--rounds-per-dispatch", "4", "--mesh-shape", shape])
        ok &= check_run(shape, run)
        mesh = run.eng.mesh
        spans = {d.id for d in mesh.devices.flat} == {d.id for d in devs}
        # every array argument of every block program (plane, bank, shard
        # pack, masks, weights, teacher stack) lives on all four chips
        placed = all(len(s.sharding.device_set) == 4
                     for r in run.recorders.values()
                     for s in jax.tree.leaves(r.specs) if s.ndim)
        ok &= check(f"{shape} mesh spans the four chips", spans and placed,
                    f"mesh {dict(mesh.shape)}, block arguments placed "
                    f"across 4 devices: {placed}")
        ok &= compare_runs(f"{shape} vs one device", run, ref)
    return ok


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4x1 and 2x2 mesh jobs against one "
                         "device (needs a four-chip host)")
    opts = ap.parse_args(argv)
    dev = require_tpu()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache
    print(f"# compile cache {use_compile_cache()}")
    ok = four_chips() if opts.four_chips else one_chip(dev)
    if not ok:
        sys.exit("chip_smoke: a check failed")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
