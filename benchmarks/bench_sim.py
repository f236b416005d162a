"""Simulator benchmarks: cluster-execution throughput, compile-stable
padding, and the device-resident multi-round dispatch pipeline.

  PYTHONPATH=src python benchmarks/bench_sim.py
      [--mode cluster|padding|dispatch|all]
      [--family lm|cnn] [--members 12] [--rounds 20] [--json out.json]

``--mode cluster`` times ``FedRAC._train_cluster`` on one cluster of C
members both ways: the legacy per-pid Python loop (C jitted calls + host
round-trips per round) and the batched path (one ``make_cluster_update``
vmap call per round).  Reports each path's best-of-``--reps``
client-steps/sec (C × steps_per_round × rounds / wall time), synced via
``block_until_ready`` and excluding compile; reps are interleaved so
transient host load hits both paths equally.

Two regimes:
* ``--family lm`` (default) — an edge-scale transformer (matmul-dominated,
  ~µs-scale steps): the per-member dispatch overhead the vmap removes is a
  real fraction of the round, and the batched path wins (~1.1-1.25× for
  C=16-24 on this container's CPU; margins at C<12 sit inside host noise).
* ``--family cnn`` — the paper's CNN: XLA CPU lowers a conv vmapped over
  *per-member weights* poorly, so the loop is at parity or ahead on CPU.
  On accelerators the batched path is additionally one pjit program
  instead of C dispatches.

``--mode padding`` runs a drift-heavy ``repro.sim`` trace (a master member
bounced across the cluster boundary every round → ≥5 Procedure-2
reassignments) with capacity padding on vs off and reports wall-clock and
XLA compile counts: the unpadded path retraces its round program on every
cluster-cardinality change, the padded path compiles once per capacity
bucket.

``--mode dispatch`` times the device-resident round pipeline on a
dispatch-bound micro-LM cluster (per-round XLA compute of a few ms, so the
per-round host work — numpy sampling, stacking, transfer, program dispatch —
is a real fraction of the round): ``rounds_per_dispatch=R`` fuses R rounds
into one lax.scan program with in-program batch sampling and flat-plane
aggregation.  Reports each path's median-of-``--reps`` client-steps/s
(interleaved reps, medians rather than best-of: container load is the
dominant noise source).  Target on this container's CPU: ≥1.5× at R=8.

``--mode mesh`` re-executes this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and times the
plane-SHARDED fused dispatch (member axis split over an 8-way ``data``
mesh; per-round aggregation = local fedagg contraction + one psum) against
the legacy one-round path and the unsharded fused path on the MLP family.
Headline: sharded-R=8 vs legacy ≥1.2× on this container (the 8 virtual
host devices share 2 physical cores, so the sharding itself is ~neutral
here; the row pins the scaling machinery, real meshes supply the compute).

``--mode fleet`` benchmarks the vectorized fleet-scale stack (no model
training): columnar trace generation (legacy scalar loops vs batched draws
at n=10⁵ — same seeds, bit-identical events, ≥50× target) and the full
trace + sampled-Dunn Procedure 1 + 3-round ``FleetSim`` pipeline at
10⁴/10⁵/10⁶ participants.  No O(n²) arrays anywhere, so 10⁶ runs in
container memory.

``--mode mesh2d`` is the same comparison on a ``4x2`` (data × model) mesh:
member rows split 4-way AND every plane-shaped buffer (global plane,
buffered bank, teacher/history stacks) splits its COLUMNS 2-way along
``model`` — the layout for member models too large to replicate per
device.  Parameters all-gather transiently per round; aggregation stays
one local (rows × columns) contraction + one psum over ``data``.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import jax                           # noqa: E402
import jax.numpy as jnp              # noqa: E402
import numpy as np                   # noqa: E402

from common import Timer             # noqa: E402
from repro.configs.base import ModelConfig                 # noqa: E402
from repro.core import server as srv                       # noqa: E402
from repro.core.families import (cnn_family, lm_family,    # noqa: E402
                                 mlp_family)
from repro.core.resources import participants_from_matrix  # noqa: E402
from repro.data.partition import dirichlet_partition       # noqa: E402
from repro.data.synthetic import (lm_batches, make_classification,  # noqa: E402
                                  make_lm_corpus, train_test_split)
from repro.sim import (HeterogeneitySim, ResourceDrift, SimConfig,  # noqa: E402
                       make_trace)
from repro.sim.traces import sample_profiles               # noqa: E402


def build_cnn(n_members: int, steps: int, seed: int, base_width: float, *,
              samples: int | None = None, dirichlet: float = 10.0,
              with_test: bool = False, **cfg_kw):
    """CNN engine builder shared by the cluster and padding benches.
    Defaults: one cluster, nobody demoted, exact-C tracing so the
    loop-vs-vmap comparison is not skewed by padded capacity rows;
    the padding bench overrides via cfg_kw."""
    ds = make_classification("synth-mnist", samples or 120 * n_members,
                             seed=seed)
    train, test = train_test_split(ds)
    idx = dirichlet_partition(train.y, n_members, alpha=dirichlet, seed=seed)
    parts = participants_from_matrix(sample_profiles(n_members, seed=seed),
                                     n_data=[len(p) for p in idx])
    cd = [{"x": train.x[p], "y": train.y[p]} for p in idx]
    fam = cnn_family(classes=10, in_channels=1, base_width=base_width)
    cfg = srv.FLConfig(steps_per_round=steps, lr=0.08, seed=seed,
                       **({"compact_to": 1, "mar": 1e9,
                           "pad_clusters": False} | cfg_kw))
    eng = srv.FedRAC(parts, cd, fam, cfg, classes=10).setup()
    if with_test:
        return eng, {"x": jnp.asarray(test.x), "y": jnp.asarray(test.y)}
    return eng


def build_lm(n_members: int, steps: int, seed: int):
    base = ModelConfig(name="edge-lm", family="dense", n_layers=1,
                       d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
                       d_ff=128, vocab_size=64, rope_theta=1e4)
    fam = lm_family(base, alpha=0.5)
    corpus = make_lm_corpus(64, 20_000, seed=seed)
    parts = participants_from_matrix(sample_profiles(n_members, seed=seed),
                                     n_data=[64] * n_members)
    chunks = np.array_split(corpus, n_members)
    cd = [{"tokens": lm_batches(ch, 32, 17, 1, seed=i)[0]}
          for i, ch in enumerate(chunks)]

    class LMFedRAC(srv.FedRAC):
        def _client_batches(self, pid, r, balanced):
            d = self.client_data[pid]
            rng = np.random.default_rng(pid * 31 + r)
            idx = rng.integers(0, d["tokens"].shape[0],
                               (self.cfg.steps_per_round, 8))
            t = d["tokens"][idx]
            return {"tokens": t, "y": t[:, :, -1]}

    cfg = srv.FLConfig(steps_per_round=steps, lr=0.1, seed=seed,
                       compact_to=1, mar=1e9, class_balanced=False,
                       pad_clusters=False)
    return LMFedRAC(parts, cd, fam, cfg, classes=64).setup()


# ------------------------------------------------------------ dispatch bench
class TokenShardFedRAC(srv.FedRAC):
    """FedRAC over {"tokens"} shards: host batches via numpy (legacy path),
    device batches via the ``_batch_from_gathered`` hook (dispatch path)."""

    def _client_batches(self, pid, r, balanced):
        d = self.client_data[pid]
        rng = np.random.default_rng(pid * 31 + r)
        idx = rng.integers(0, d["tokens"].shape[0],
                           (self.cfg.steps_per_round, self.cfg.local_batch))
        t = d["tokens"][idx]
        return {"tokens": t, "y": t[:, :, -1]}

    def _batch_from_gathered(self, g):
        return {"tokens": g["tokens"], "y": g["tokens"][:, :, -1]}


def build_micro_lm(n_members: int, steps: int, seed: int, R: int,
                   batch: int = 4, d_model: int = 16, seq: int = 9,
                   vocab: int = 16, n_heads: int = 1, n_layers: int = 1,
                   mesh=None, **cfg_kw):
    """Dispatch-bound cluster: a micro LM whose per-round XLA program runs in
    a few ms, so per-round host overhead dominates the legacy path.  The TP
    bench widens it (``n_heads``/``d_model`` divisible by the model axis)
    and puts it on a 2D ``mesh``."""
    base = ModelConfig(name="micro-lm", family="dense", n_layers=n_layers,
                       d_model=d_model, n_heads=n_heads, n_kv_heads=n_heads,
                       head_dim=d_model // n_heads, d_ff=2 * d_model,
                       vocab_size=vocab, rope_theta=1e4)
    fam = lm_family(base, alpha=0.5)
    corpus = make_lm_corpus(vocab, 4000, seed=seed)
    parts = participants_from_matrix(sample_profiles(n_members, seed=seed),
                                     n_data=[64] * n_members)
    chunks = np.array_split(corpus, n_members)
    cd = [{"tokens": lm_batches(ch, batch, seq, 1, seed=i)[0]}
          for i, ch in enumerate(chunks)]
    cfg = srv.FLConfig(steps_per_round=steps, lr=0.1, seed=seed,
                       compact_to=1, mar=1e9, class_balanced=False,
                       pad_clusters=False, local_batch=batch,
                       rounds_per_dispatch=R, **cfg_kw)
    return TokenShardFedRAC(parts, cd, fam, cfg, classes=vocab,
                            mesh=mesh).setup()


def build_micro_mlp(n_members: int, steps: int, seed: int, R: int,
                    batch: int = 8, mesh=None):
    """The headline dispatch-bound cluster: a two-layer MLP whose per-round
    XLA program is a handful of ops, so the legacy path's per-round host
    work dominates.  ``mesh`` shards the member axis of the dispatch
    program (``--mode mesh``)."""
    ds = make_classification("synth-mnist", 60 * n_members, seed=seed)
    train, _ = train_test_split(ds)
    idx = dirichlet_partition(train.y, n_members, alpha=10.0, seed=seed)
    parts = participants_from_matrix(sample_profiles(n_members, seed=seed),
                                     n_data=[len(p) for p in idx])
    cd = [{"x": train.x[p], "y": train.y[p]} for p in idx]
    cfg = srv.FLConfig(steps_per_round=steps, lr=0.08, seed=seed,
                       compact_to=1, mar=1e9, pad_clusters=False,
                       local_batch=batch, class_balanced=False,
                       rounds_per_dispatch=R)
    return srv.FedRAC(parts, cd, mlp_family(), cfg, classes=10,
                      mesh=mesh).setup()


def _phase_breakdown(eng, members, rounds: int, *, fresh: bool = True
                     ) -> dict:
    """Per-phase breakdown of an instrumented dispatch run: attach a fenced
    observability bundle to ``eng`` and re-run ``rounds`` rounds, reading
    compile wall-time, fenced block execution time, h2d/d2h bytes and psum
    count from the registry/tracer.  With ``fresh=True`` the engine must not
    have compiled its dispatch programs yet (compile_s lands in the
    breakdown); pass ``fresh=False`` for an already-warm engine (compile_s
    reads 0 — the counters are call-site accounting and still fill in).
    The HEADLINE timings above never run instrumented: fencing serializes
    the pipeline, so phases come from this separate pass."""
    from repro.obs import make_observability
    obs = make_observability(fence=True)
    eng.obs = obs
    p, _ = eng._train_cluster(0, members, rounds, None, record_every=10 ** 9)
    jax.block_until_ready(jax.tree.leaves(p))
    reg = obs.registry
    compile_s = (reg.histograms["fl/compile_s"].total
                 if "fl/compile_s" in reg.histograms else 0.0)
    exec_s = sum(e["dur"] for e in obs.tracer.events()
                 if e["name"] == "block_exec") / 1e6
    return {"compile_s": round(compile_s, 4),
            # block_exec spans include the first call's compile; subtract
            "execute_s": round(max(exec_s - compile_s, 0.0), 4),
            "h2d_bytes": int(reg.counter("fl/h2d_bytes").value),
            "d2h_bytes": int(reg.counter("fl/d2h_bytes").value),
            "psum_count": int(reg.counter("fl/psum_count").value),
            "dispatch_blocks": int(reg.counter("fl/dispatch_blocks").value)}


def _time_dispatch_pair(build, n: int, steps: int, seed: int, R: int,
                        rounds: int, reps: int) -> dict:
    engs = {1: build(n, steps, seed, 1), R: build(n, steps, seed, R)}
    members = {k: list(e.assignment.members[0]) for k, e in engs.items()}
    for k, eng in engs.items():                      # compile both paths
        eng._train_cluster(0, members[k], max(k, 2), None,
                           record_every=10 ** 9)
    sps = {1: [], R: []}
    for _ in range(reps):                            # interleaved medians
        for k, eng in engs.items():
            with Timer() as t:
                p, _ = eng._train_cluster(0, members[k], rounds, None,
                                          record_every=10 ** 9)
                jax.block_until_ready(jax.tree.leaves(p))
            sps[k].append(n * steps * rounds / t.dt)
    r1 = statistics.median(sps[1])
    rR = statistics.median(sps[R])
    return {"members": n, "rounds": rounds, "R": R, "steps": steps,
            "legacy_steps_per_s": round(r1, 1),
            "dispatch_steps_per_s": round(rR, 1),
            "speedup": round(rR / r1, 3)}


def run_dispatch_bench(n: int = 12, R: int = 8, reps: int = 4,
                       seed: int = 0, with_lm: bool = True) -> dict:
    """R-round fused dispatch vs the legacy one-round-per-dispatch path on
    the dispatch-bound MLP cluster (headline, ≥1.5× target) and — for
    context — the micro-LM, whose larger per-round op count leaves less
    host overhead to remove (~1.3× on this container)."""
    out = {"mlp": _time_dispatch_pair(build_micro_mlp, n, 2, seed, R,
                                      rounds=64, reps=reps)}
    if with_lm:
        out["lm"] = _time_dispatch_pair(build_micro_lm, n, 1, seed, R,
                                        rounds=32, reps=reps)
    return out


# ------------------------------------------------------------ mesh bench
def run_mesh_bench(n: int = 24, R: int = 8, reps: int = 3, seed: int = 0,
                   mesh_shape: str = "8", rounds: int = 64,
                   steps: int = 2) -> dict:
    """Plane-sharded multi-device dispatch on the dispatch-bound MLP family:
    the member axis of the fused R-round program splits over the mesh
    ``data`` axis (per-round aggregation = local fedagg contraction + one
    psum over ``data``), and a 2D ``mesh_shape`` like ``"4x2"``
    additionally column-shards the plane/bank/teacher buffers along
    ``model`` (each device stores D/model_size plane columns; parameters
    all-gather transiently per round — the ``--mode mesh2d`` row).  Reports
    median client-steps/s for the legacy one-round path, the unsharded
    fused path, and the mesh-sharded fused path — the headline is mesh vs
    legacy (≥1.2× on this container's 2-core CPU, where the virtual devices
    add no compute; on real multi-host meshes the sharding itself scales
    the fleet and the 2D split divides per-device plane memory).  Requires
    ≥ prod(mesh_shape) devices: run via ``--mode mesh``/``--mode mesh2d``
    (subprocess sets XLA_FLAGS) or force host devices yourself."""
    from repro.launch.mesh import make_sim_mesh, parse_sim_mesh_shape
    shape = parse_sim_mesh_shape(mesh_shape)
    n_dev = int(np.prod(shape))
    if jax.device_count() < n_dev:
        raise RuntimeError(
            f"mesh bench needs ≥{n_dev} devices (have {jax.device_count()});"
            " use --mode mesh/mesh2d, which re-execute under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_dev}")
    engs = {"legacy_r1": build_micro_mlp(n, steps, seed, 1),
            "fused_r8": build_micro_mlp(n, steps, seed, R),
            "mesh_r8": build_micro_mlp(n, steps, seed, R,
                                       mesh=make_sim_mesh(shape))}
    members = {k: list(e.assignment.members[0]) for k, e in engs.items()}
    for k, e in engs.items():                        # compile all paths
        e._train_cluster(0, members[k], max(R, 2), None, record_every=10**9)
    sps = {k: [] for k in engs}
    for _ in range(reps):                            # interleaved medians
        for k, e in engs.items():
            with Timer() as t:
                p, _ = e._train_cluster(0, members[k], rounds, None,
                                        record_every=10**9)
                jax.block_until_ready(jax.tree.leaves(p))
            sps[k].append(n * steps * rounds / t.dt)
    med = {k: statistics.median(v) for k, v in sps.items()}
    # warm-engine instrumented pass: psum/h2d/d2h counters fill in (compile
    # already happened, so compile_s reads 0 here by design)
    phases = _phase_breakdown(engs["mesh_r8"], members["mesh_r8"], rounds,
                              fresh=False)
    return {"members": n, "rounds": rounds, "R": R, "steps": steps,
            "devices": n_dev, "mesh_shape": "x".join(map(str, shape)),
            "legacy_steps_per_s": round(med["legacy_r1"], 1),
            "fused_steps_per_s": round(med["fused_r8"], 1),
            "mesh_steps_per_s": round(med["mesh_r8"], 1),
            "speedup_vs_legacy": round(med["mesh_r8"] / med["legacy_r1"], 3),
            "sharding_overhead": round(med["mesh_r8"] / med["fused_r8"], 3),
            "phases": phases}


def _refuse_cpu_children_on_tpu(mode: str) -> None:
    """The forced-host-device children time XLA:CPU; on a chip host their
    rows would pass CPU numbers off under device names."""
    if jax.default_backend() == "tpu":
        raise SystemExit(
            f"bench_sim --mode {mode} times forced CPU devices in a child "
            "process and reports no chip numbers; on a TPU host run "
            "chip_smoke.py --four-chips for the mesh paths instead")


def run_mesh_bench_subprocess(n: int = 24, R: int = 8, reps: int = 3,
                              seed: int = 0, mesh_shape: str = "8") -> dict:
    """Re-execute this file with forced host devices (XLA_FLAGS must be set
    BEFORE jax initializes its backend, which importing this module already
    did in the calling process) and collect the mesh-bench JSON."""
    _refuse_cpu_children_on_tpu("mesh|mesh2d")
    from repro.launch.mesh import parse_sim_mesh_shape
    n_dev = int(np.prod(parse_sim_mesh_shape(mesh_shape)))
    fd, out = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    out = pathlib.Path(out)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_dev} "
                        + env.get("XLA_FLAGS", "")).strip()
    env["JAX_PLATFORMS"] = "cpu"
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--mode", "mesh-inner",
             "--members", str(n), "--dispatch-r", str(R), "--reps", str(reps),
             "--seed", str(seed), "--mesh-shape", str(mesh_shape),
             "--json", str(out)],
            capture_output=True, text=True, timeout=560, env=env)
        if r.returncode != 0:
            raise RuntimeError(
                f"mesh bench subprocess failed:\n{r.stderr[-2000:]}")
        return json.loads(out.read_text())["mesh"]
    finally:
        out.unlink(missing_ok=True)


# ------------------------------------------------------------ tp bench
def run_tp_bench(n: int = 8, R: int = 8, reps: int = 3, seed: int = 0,
                 mesh_shape: str = "2x4", rounds: int = 24,
                 steps: int = 2) -> dict:
    """GSPMD tensor-parallel member forward vs the legacy gather path on a
    2D (data × model) mesh, over a TP-able micro LM (heads/d_ff/vocab all
    divide the model axis).  Three rows: the unsharded fused dispatch
    (1 device), the legacy ``tp_forward=False`` path (plane columns sharded
    at rest, but each round all-gathers the full plane and replicates the
    forward), and the TP path (member forward partitioned over ``model`` —
    per-layer activation collectives only).  On this container's virtual
    CPU devices TP buys no wall-clock (same cores, more collectives); the
    headline is the memory column: per-device parameter bytes for the
    forward drop from the full plane to plane/model_size.  Requires
    ≥ prod(mesh_shape) devices — run via ``--mode tp`` (subprocess sets
    XLA_FLAGS)."""
    from repro.launch.mesh import make_sim_mesh, parse_sim_mesh_shape
    shape = parse_sim_mesh_shape(mesh_shape)
    n_dev = int(np.prod(shape))
    if jax.device_count() < n_dev:
        raise RuntimeError(
            f"tp bench needs ≥{n_dev} devices (have {jax.device_count()});"
            " use --mode tp, which re-executes under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_dev}")

    def build(mesh=None, tp=True):
        return build_micro_lm(n, steps, seed, R, d_model=32, n_heads=4,
                              vocab=64, seq=17, mesh=mesh, tp_forward=tp)

    engs = {"fused_r8": build(),
            "gather_r8": build(make_sim_mesh(shape), tp=False),
            "tp_r8": build(make_sim_mesh(shape), tp=True)}
    assert engs["tp_r8"]._tp and not engs["gather_r8"]._tp
    members = {k: list(e.assignment.members[0]) for k, e in engs.items()}
    for k, e in engs.items():                        # compile all paths
        e._train_cluster(0, members[k], max(R, 2), None, record_every=10**9)
    sps = {k: [] for k in engs}
    for _ in range(reps):                            # interleaved medians
        for k, e in engs.items():
            with Timer() as t:
                p, _ = e._train_cluster(0, members[k], rounds, None,
                                        record_every=10**9)
                jax.block_until_ready(jax.tree.leaves(p))
            sps[k].append(n * steps * rounds / t.dt)
    med = {k: statistics.median(v) for k, v in sps.items()}
    msize = shape[1]
    tp_spec = engs["tp_r8"].plane_spec(0)
    legacy_bytes = engs["gather_r8"].plane_spec(0).d_pad * 4
    return {"members": n, "rounds": rounds, "R": R, "steps": steps,
            "devices": n_dev, "mesh_shape": "x".join(map(str, shape)),
            "fused_steps_per_s": round(med["fused_r8"], 1),
            "gather_steps_per_s": round(med["gather_r8"], 1),
            "tp_steps_per_s": round(med["tp_r8"], 1),
            "tp_vs_gather": round(med["tp_r8"] / med["gather_r8"], 3),
            # forward-path parameter bytes per device: the gather path
            # re-materializes the full plane, TP touches only its column
            "fwd_bytes_per_device": tp_spec.d_pad // tp_spec.msize * 4,
            "fwd_bytes_legacy": legacy_bytes,
            "fwd_bytes_ratio": round(
                (tp_spec.d_pad // tp_spec.msize * 4) / legacy_bytes, 3),
            "model_size": msize}


def run_tp_bench_subprocess(n: int = 8, R: int = 8, reps: int = 3,
                            seed: int = 0, mesh_shape: str = "2x4") -> dict:
    """Re-execute this file with forced host devices and collect the
    tp-bench JSON (same contract as ``run_mesh_bench_subprocess``)."""
    _refuse_cpu_children_on_tpu("tp")
    from repro.launch.mesh import parse_sim_mesh_shape
    n_dev = int(np.prod(parse_sim_mesh_shape(mesh_shape)))
    fd, out = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    out = pathlib.Path(out)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_dev} "
                        + env.get("XLA_FLAGS", "")).strip()
    env["JAX_PLATFORMS"] = "cpu"
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--mode", "tp-inner",
             "--members", str(n), "--dispatch-r", str(R), "--reps", str(reps),
             "--seed", str(seed), "--mesh-shape", str(mesh_shape),
             "--json", str(out)],
            capture_output=True, text=True, timeout=560, env=env)
        if r.returncode != 0:
            raise RuntimeError(
                f"tp bench subprocess failed:\n{r.stderr[-2000:]}")
        return json.loads(out.read_text())["tp"]
    finally:
        out.unlink(missing_ok=True)


def time_path(eng, members, rounds, steps, vmap: bool) -> float:
    eng.cfg.vmap_clusters = vmap
    eng._train_cluster(0, members, 1, None, record_every=10**9)  # compile
    with Timer() as t:
        params, _ = eng._train_cluster(0, members, rounds, None,
                                       record_every=10**9)
        jax.block_until_ready(jax.tree.leaves(params))
    return len(members) * steps * rounds / t.dt


def best_of(reps, eng, members, rounds, steps):
    """Interleave the two paths and keep each one's best rep, so transient
    host load hits both equally."""
    best = {False: 0.0, True: 0.0}
    for _ in range(reps):
        for vmap in (False, True):
            best[vmap] = max(best[vmap],
                             time_path(eng, members, rounds, steps, vmap))
    return best


# ------------------------------------------------------------ padding bench
def _build_sim_engine(n: int, samples: int, steps: int, seed: int,
                      base_width: float, pad: bool):
    return build_cnn(n, steps, seed, base_width, samples=samples,
                     dirichlet=2.0, with_test=True, local_batch=8,
                     compact_to=2, mar=None, pad_clusters=pad)


def _drift_trace(eng, n: int, rounds: int):
    """Bounce three master members across the cluster boundary on staggered
    phases: every extreme drift is a Procedure-2 reassignment, and the
    staggering walks each cluster through several distinct cardinalities —
    the unpadded path retraces at every new C, the padded one reuses its
    capacity-bucket programs."""
    trace = make_trace("stable", n, rounds)
    pids = list(eng.assignment.members[0][:3])
    state = {pid: 1.0 for pid in pids}               # cumulative multiplier
    for r in range(rounds - 1):
        pid = pids[r % len(pids)]
        mult = 0.02 if state[pid] >= 1.0 else 50.0   # flip direction
        state[pid] *= mult
        trace.events.append((float(r), ResourceDrift(
            pid, s_mult=mult, r_mult=mult, a_mult=1.0)))
    return trace


def run_padding_bench(n: int = 10, samples: int = 600, rounds: int = 8,
                      steps: int = 3, seed: int = 0,
                      base_width: float = 0.125) -> dict:
    out = {"participants": n, "rounds": rounds}
    for pad in (True, False):
        eng, testb = _build_sim_engine(n, samples, steps, seed, base_width,
                                       pad)
        trace = _drift_trace(eng, n, rounds)
        sim = HeterogeneitySim(eng, trace, SimConfig(rounds=rounds))
        t0 = time.perf_counter()
        rep = sim.run(testb)
        dt = time.perf_counter() - t0
        try:
            stats = eng.compile_stats()
        except RuntimeError:        # jax build without jit _cache_size
            stats = {}
        out["padded" if pad else "unpadded"] = {
            "wall_s": round(dt, 3),
            "xla_compiles": sum(stats.values()) if stats else None,
            "round_programs": len(stats) if stats else None,
            "migrations": sum(ev.count("→") for r in rep.rows
                              for ev in r.events),
        }
    return out


def run_cluster_bench(args) -> dict:
    if args.family == "lm":
        eng = build_lm(args.members, args.steps, args.seed)
    else:
        eng = build_cnn(args.members, args.steps, args.seed, args.base_width)
    members = list(eng.assignment.members[0])
    assert len(members) == args.members, "expected a single full cluster"

    best = best_of(args.reps, eng, members, args.rounds, args.steps)
    looped, vmapped = best[False], best[True]
    print(f"{args.family} cluster of C={len(members)} members, "
          f"{args.steps} local steps × {args.rounds} rounds")
    print(f"  per-pid loop : {looped:10.1f} client-steps/s")
    print(f"  batched vmap : {vmapped:10.1f} client-steps/s "
          f"({vmapped / looped:.2f}× speedup)")
    return {"looped": looped, "vmapped": vmapped}


# ------------------------------------------------------------ fleet bench
def run_fleet_bench(sizes=(10_000, 100_000, 1_000_000), rounds: int = 3,
                    seed: int = 0, legacy_n: int = 100_000) -> dict:
    """Vectorized fleet stack end-to-end: columnar trace build + sampled-Dunn
    Procedure 1 + ``rounds`` FleetSim rounds at each fleet size, plus the
    trace-generation speedup row (scalar legacy loops vs batched draws on the
    mixed scenario's three generators, identical seeds → identical events).
    No step ever materializes an O(n²) array, so 10⁶ fits CPU memory."""
    from repro.core.resources import Fleet
    from repro.sim import FleetSim, FleetSimConfig, make_fleet_trace
    from repro.sim.traces import (legacy_drift_events, legacy_dropout_events,
                                  legacy_straggler_events)
    out = {}
    legacy_s, vec_s = 1e9, 1e9
    for _ in range(2):                       # mixed-scenario defaults/seeds;
        with Timer() as t:                   # min-of-reps beats 1-core noise
            legacy_dropout_events(legacy_n, rounds, 0.08, seed)
            legacy_drift_events(legacy_n, rounds, 0.05, seed + 1)
            legacy_straggler_events(legacy_n, rounds, 0.08, seed + 2)
        legacy_s = min(legacy_s, t.dt)
    for _ in range(5):
        with Timer() as t:
            make_fleet_trace("mixed", legacy_n, rounds, seed=seed)
        vec_s = min(vec_s, t.dt)
    out["trace"] = {"n": legacy_n, "rounds": rounds,
                    "legacy_s": round(legacy_s, 4),
                    "vectorized_s": round(vec_s, 5),
                    "speedup": round(legacy_s / vec_s, 1)}
    for n in sizes:
        fleet = Fleet.from_matrix(sample_profiles(n, seed=seed))
        with Timer() as t:
            trace = make_fleet_trace("mixed", n, rounds, seed=seed)
        trace_s = t.dt
        with Timer() as t:                   # Procedure 1 + MAR calibration
            sim = FleetSim(fleet, trace, FleetSimConfig(
                rounds=rounds, select="fedcs", seed=seed))
        cluster_s = t.dt
        with Timer() as t:
            rep = sim.run()
        sim_s = t.dt
        s = rep.summary()
        out[f"fleet_{n}"] = {
            "n": n, "rounds": rounds, "k": rep.k,
            "events": sum(r.events for r in rep.rows),
            "trace_s": round(trace_s, 4), "cluster_s": round(cluster_s, 4),
            "sim_s": round(sim_s, 4),
            "rounds_per_s": round(rounds / sim_s, 2),
            "participation": s["participation_rate"]}
    return out


# ------------------------------------------------------------ ckpt bench
def run_ckpt_bench(sizes=(10_000, 100_000), rounds: int = 2, seed: int = 0,
                   reps: int = 3) -> dict:
    """Crash-safety overhead: full run-state snapshot save (manifest +
    CRC32 + atomic rename) and validated restore on a FleetSim at each
    fleet size — wall time (min-of-``reps``) and payload bytes.  The
    snapshot is the engine's own ``_capture_state`` (fleet arrays, levels,
    per-round row columns, bank/selection counters), i.e. exactly what
    ``sim_run --ckpt-dir`` writes each boundary."""
    from repro.ckpt.manifest import CheckpointManager
    from repro.ckpt.run_state import RUN_STATE_VERSION
    from repro.core.resources import Fleet
    from repro.sim import FleetSim, FleetSimConfig, make_fleet_trace
    out = {}
    for n in sizes:
        fleet = Fleet.from_matrix(sample_profiles(n, seed=seed))
        trace = make_fleet_trace("mixed", n, rounds, seed=seed)
        sim = FleetSim(fleet, trace, FleetSimConfig(rounds=rounds, seed=seed))
        sim.run()
        meta, arrays = sim._capture_state(rounds, sim.report.rows)
        meta["run_state"] = {"version": RUN_STATE_VERSION,
                             "kind": "fleet-sim"}
        nbytes = int(sum(a.nbytes for a in arrays.values()))
        save_s, load_s = 1e9, 1e9
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep=2)
            for i in range(reps):
                with Timer() as t:
                    mgr.save(i + 1, meta, arrays)
                save_s = min(save_s, t.dt)
            for _ in range(reps):
                with Timer() as t:
                    got = mgr.load_latest()
                load_s = min(load_s, t.dt)
            assert got is not None
        out[f"ckpt_{n}"] = {
            "n": n, "rounds": rounds, "arrays": len(arrays), "bytes": nbytes,
            "save_s": round(save_s, 5), "restore_s": round(load_s, 5),
            "save_mb_per_s": round(nbytes / save_s / 1e6, 1),
            "restore_mb_per_s": round(nbytes / load_s / 1e6, 1)}
    return out


# ------------------------------------------------------------ async bench
def run_async_bench(n: int = 12, rounds: int = 10, R: int = 4,
                    seed: int = 0, spike_rate: float = 0.5) -> dict:
    """Continuous-time async parameter server vs the global round barrier on
    a straggler-heavy trace (Table-III profiles, transient ×4 compute
    spikes): the same two-cluster buffered engine runs once with the sync
    barrier and once in ``mode="async"`` (unbounded staleness), and the
    headline is SIMULATED wall-clock to the target loss — the barrier
    charges every round at the slowest cluster's pace (Σ_r max_l t),
    independent clocks only ever charge each cluster its own time
    (max_l Σ_r t ≤ Σ_r max_l, strict under straggling), so the async run
    must reach the sync run's final master loss no later than the barrier
    does."""
    def one(mode):
        eng, testb = build_cnn(n, 3, seed, 0.125, samples=60 * n,
                               dirichlet=2.0, with_test=True, local_batch=8,
                               compact_to=2, mar=None, pad_clusters=True,
                               aggregation="buffered", rounds_per_dispatch=R)
        trace = make_trace("straggler", n, rounds, seed=seed,
                           spike_rate=spike_rate)
        kw = ({"mode": "async", "max_staleness": None}
              if mode == "async" else {})
        sim = HeterogeneitySim(eng, trace, SimConfig(
            rounds=rounds, mar_policy="buffer", eval_every=10 ** 9, **kw))
        with Timer() as t:
            rep = sim.run(testb)
        # master (level 0) per-round loss against that CLUSTER's own clock:
        # barrier time under sync (t_end — every cluster waits), the
        # master's own cumulative clock under async
        loss, t_cluster, t_barrier = [], [], []
        acc = 0.0
        for r in rep.rows:
            c0 = next(c for c in r.clusters if c.level == 0)
            acc += c0.time
            loss.append(c0.mean_loss)
            t_cluster.append(acc)
            t_barrier.append(r.t_end)
        wall = (rep.registry.gauge("async/wall_clock_s").value
                if mode == "async" else rep.summary()["wall_clock_s"])
        return {"loss": loss,
                "t": t_cluster if mode == "async" else t_barrier,
                "wall_clock_s": float(wall),
                "banked": rep.summary()["banked_total"],
                "host_s": t.dt}

    res = {m: one(m) for m in ("sync", "async")}
    target = max(res["sync"]["loss"][-1], res["async"]["loss"][-1])

    def t_to_target(r):
        return next(t for t, l in zip(r["t"], r["loss"]) if l <= target)
    out = {"members": n, "rounds": rounds, "R": R,
           "spike_rate": spike_rate, "target_loss": round(target, 4)}
    for m in ("sync", "async"):
        out[m] = {"t_to_target_s": round(t_to_target(res[m]), 4),
                  "wall_clock_s": round(res[m]["wall_clock_s"], 4),
                  "final_loss": round(res[m]["loss"][-1], 4),
                  "banked": res[m]["banked"],
                  "host_s": round(res[m]["host_s"], 3)}
    out["speedup_to_target"] = round(
        out["sync"]["t_to_target_s"]
        / max(out["async"]["t_to_target_s"], 1e-9), 3)
    return out


# ------------------------------------------------------------ run.py hooks
def bench_sim_async():
    """benchmarks/run.py suite: async server vs barrier on the straggler
    trace — simulated seconds to the sync run's final master loss (the row
    time) plus total simulated wall-clock per mode."""
    res = run_async_bench()
    for m in ("sync", "async"):
        r = res[m]
        yield (f"sim/async_{m if m == 'async' else 'barrier'}",
               r["t_to_target_s"] * 1e6,
               f"t_to_target_s={r['t_to_target_s']};"
               f"wall_clock_s={r['wall_clock_s']};"
               f"final_loss={r['final_loss']};banked={r['banked']};"
               f"target_loss={res['target_loss']};"
               f"speedup_to_target={res['speedup_to_target']}")


def bench_sim_ckpt():
    """benchmarks/run.py suite: run-state checkpoint save/validated-restore
    wall time and payload bytes at fleet sizes 10⁴/10⁵."""
    res = run_ckpt_bench()
    for n in (10_000, 100_000):
        r = res[f"ckpt_{n}"]
        yield (f"sim/ckpt_{n}", (r["save_s"] + r["restore_s"]) * 1e6,
               f"save_s={r['save_s']};restore_s={r['restore_s']};"
               f"bytes={r['bytes']};arrays={r['arrays']};"
               f"save_mb_per_s={r['save_mb_per_s']};"
               f"restore_mb_per_s={r['restore_mb_per_s']}")
def bench_sim_mesh():
    """benchmarks/run.py suite: plane-sharded dispatch at 8 forced host
    devices (subprocess — XLA_FLAGS must precede jax backend init) vs the
    legacy one-round path and the unsharded fused path."""
    res = run_mesh_bench_subprocess(n=24, R=8, reps=3)
    for tag, key in (("legacy_r1", "legacy_steps_per_s"),
                     ("fused_r8", "fused_steps_per_s"),
                     ("sharded_r8", "mesh_steps_per_s")):
        sps = res[key]
        row = (f"sim/mesh_{tag}", 1e6 / max(sps, 1e-9),
               f"client_steps_per_s={sps};devices={res['devices']};"
               f"speedup_vs_legacy={res['speedup_vs_legacy']};"
               f"sharding_overhead={res['sharding_overhead']}")
        yield row + ((res["phases"],) if tag == "sharded_r8"
                     and res.get("phases") else ())


def bench_sim_mesh2d():
    """benchmarks/run.py suite: 2D (data × model) plane-sharded dispatch on
    a forced-host-device ``4x2`` mesh — member rows split 4-way, plane/bank/
    teacher columns split 2-way (each device stores half the plane)."""
    res = run_mesh_bench_subprocess(n=24, R=8, reps=3, mesh_shape="4x2")
    sps = res["mesh_steps_per_s"]
    yield ("sim/mesh2d_sharded_r8", 1e6 / max(sps, 1e-9),
           f"client_steps_per_s={sps};devices={res['devices']};"
           f"mesh_shape={res['mesh_shape']};"
           f"speedup_vs_legacy={res['speedup_vs_legacy']};"
           f"sharding_overhead={res['sharding_overhead']}"
           ) + ((res["phases"],) if res.get("phases") else ())


def bench_sim_tp():
    """benchmarks/run.py suite: GSPMD tensor-parallel member forward on a
    forced-host-device ``2x4`` mesh vs the legacy gather path — wall-clock
    rows plus the per-device forward-parameter-bytes ratio (the reason the
    TP path exists: D/model_size instead of the full plane)."""
    res = run_tp_bench_subprocess(n=8, R=8, reps=3)
    for tag, key in (("fused_r8", "fused_steps_per_s"),
                     ("gather_r8", "gather_steps_per_s"),
                     ("tp_r8", "tp_steps_per_s")):
        sps = res[key]
        yield (f"sim/tp_{tag}", 1e6 / max(sps, 1e-9),
               f"client_steps_per_s={sps};devices={res['devices']};"
               f"mesh_shape={res['mesh_shape']};"
               f"tp_vs_gather={res['tp_vs_gather']};"
               f"fwd_bytes_per_device={res['fwd_bytes_per_device']};"
               f"fwd_bytes_legacy={res['fwd_bytes_legacy']};"
               f"fwd_bytes_ratio={res['fwd_bytes_ratio']}")


def bench_sim_dispatch():
    """benchmarks/run.py suite: fused multi-round dispatch vs legacy rounds
    on the dispatch-bound MLP cluster (CPU-budget scale; the micro-LM
    context row stays CLI-only)."""
    res = run_dispatch_bench(n=12, R=8, reps=3, with_lm=False)["mlp"]
    # fresh instrumented engine so compile_s lands in the breakdown; the
    # headline medians above stay un-instrumented (fencing serializes)
    eng = build_micro_mlp(12, 2, 0, 8)
    phases = _phase_breakdown(eng, list(eng.assignment.members[0]),
                              rounds=64)
    for tag, key in (("r1", "legacy_steps_per_s"),
                     ("r8", "dispatch_steps_per_s")):
        sps = res[key]
        row = (f"sim/dispatch_{tag}", 1e6 / max(sps, 1e-9),
               f"client_steps_per_s={sps};speedup={res['speedup']}")
        yield row + ((phases,) if tag == "r8" else ())


def bench_sim_padding():
    """benchmarks/run.py suite: padded vs unpadded drift-heavy sim rows."""
    res = run_padding_bench()
    for tag in ("padded", "unpadded"):
        r = res[tag]
        yield (f"sim/{tag}", r["wall_s"] * 1e6 / res["rounds"],
               f"compiles={r['xla_compiles']};programs={r['round_programs']};"
               f"migrations={r['migrations']}")


def bench_sim_fleet():
    """benchmarks/run.py suite: million-participant vectorized fleet rows —
    trace-generation speedup at 10⁵ (legacy scalar loops vs batched draws)
    and trace+Procedure-1+3-round FleetSim wall time at 10⁴/10⁵/10⁶."""
    res = run_fleet_bench()
    tr = res["trace"]
    yield ("sim/fleet_trace", tr["vectorized_s"] * 1e6,
           f"n={tr['n']};legacy_s={tr['legacy_s']};"
           f"vectorized_s={tr['vectorized_s']};speedup={tr['speedup']}")
    for n in (10_000, 100_000, 1_000_000):
        r = res[f"fleet_{n}"]
        total = r["trace_s"] + r["cluster_s"] + r["sim_s"]
        yield (f"sim/fleet_{n}", total * 1e6,
               f"rounds_per_s={r['rounds_per_s']};k={r['k']};"
               f"events={r['events']};trace_s={r['trace_s']};"
               f"cluster_s={r['cluster_s']};sim_s={r['sim_s']};"
               f"participation={r['participation']}")


def bench_sim_cluster():
    """benchmarks/run.py suite: looped vs vmapped cluster execution (CNN at
    CPU-budget scale; the lm regime stays CLI-only)."""
    eng = build_cnn(8, 3, 0, 0.125)
    members = list(eng.assignment.members[0])
    best = best_of(1, eng, members, 8, 3)
    for tag, key in (("loop", False), ("vmap", True)):
        sps = best[key]
        yield (f"sim/cluster_{tag}", 1e6 / max(sps, 1e-9),
               f"client_steps_per_s={sps:.1f}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="cluster",
                    choices=["cluster", "padding", "dispatch", "mesh",
                             "mesh2d", "mesh-inner", "tp", "tp-inner",
                             "fleet", "ckpt", "async", "all"],
                    help="'mesh' re-executes itself under forced host "
                         "devices and times the plane-sharded dispatch; "
                         "'mesh2d' is the same on a 4x2 (data × model) "
                         "mesh with plane columns sharded 2-way "
                         "('mesh-inner' is their subprocess entry); 'tp' "
                         "times the GSPMD tensor-parallel member forward "
                         "vs the legacy gather path on a 2x4 mesh "
                         "('tp-inner' is its subprocess entry)")
    ap.add_argument("--dispatch-r", type=int, default=8,
                    help="dispatch mode: rounds fused per program")
    ap.add_argument("--mesh-shape", default=None, metavar="DATA[xMODEL]",
                    help="mesh modes: mesh shape, e.g. '8' or '4x2' "
                         "(forced host devices = their product; defaults "
                         "to '8' for --mode mesh, '4x2' for --mode mesh2d)")
    ap.add_argument("--family", default="lm", choices=["lm", "cnn"])
    ap.add_argument("--members", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--base-width", type=float, default=0.125,
                    help="CNN family only")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sim-rounds", type=int, default=8,
                    help="padding mode: simulated rounds per path")
    ap.add_argument("--fleet-rounds", type=int, default=3,
                    help="fleet mode: FleetSim rounds per size")
    ap.add_argument("--participants", type=int, default=10,
                    help="padding mode: fleet size")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as JSON (CI tracks the suite "
                         "via benchmarks/run.py --json BENCH_core.json)")
    args = ap.parse_args(argv)
    if (args.mode in ("dispatch", "mesh", "mesh2d", "mesh-inner", "tp",
                      "tp-inner", "all")
            and args.dispatch_r < 2):
        ap.error("--dispatch-r must be ≥ 2 (R=1 IS the legacy baseline)")
    if args.mesh_shape is None:
        args.mesh_shape = ("4x2" if args.mode == "mesh2d"
                           else "2x4" if args.mode in ("tp", "tp-inner")
                           else "8")

    results = {}
    if args.mode in ("tp", "tp-inner"):
        if args.mode == "tp":
            res = run_tp_bench_subprocess(n=args.members, R=args.dispatch_r,
                                          reps=args.reps, seed=args.seed,
                                          mesh_shape=args.mesh_shape)
        else:
            res = run_tp_bench(n=args.members, R=args.dispatch_r,
                               reps=args.reps, seed=args.seed,
                               mesh_shape=args.mesh_shape)
        results["tp"] = res
        print(f"micro-lm cluster of C={res['members']} members, "
              f"{res['steps']} local steps × {res['rounds']} rounds, "
              f"{res['mesh_shape']} (data × model) mesh")
        print(f"  fused  (R={res['R']}, 1 dev)  : "
              f"{res['fused_steps_per_s']:10.1f} client-steps/s")
        print(f"  gather (R={res['R']}, {res['devices']} dev) : "
              f"{res['gather_steps_per_s']:10.1f} client-steps/s "
              f"(full plane per device: {res['fwd_bytes_legacy']} B)")
        print(f"  tp     (R={res['R']}, {res['devices']} dev) : "
              f"{res['tp_steps_per_s']:10.1f} client-steps/s "
              f"({res['tp_vs_gather']:.2f}× vs gather; forward params "
              f"{res['fwd_bytes_per_device']} B/device = "
              f"{res['fwd_bytes_ratio']:.2f}× the full plane)")
    if args.mode in ("mesh", "mesh2d", "mesh-inner"):
        if args.mode in ("mesh", "mesh2d"):
            res = run_mesh_bench_subprocess(n=args.members, R=args.dispatch_r,
                                            reps=args.reps, seed=args.seed,
                                            mesh_shape=args.mesh_shape)
        else:
            res = run_mesh_bench(n=args.members, R=args.dispatch_r,
                                 reps=args.reps, seed=args.seed,
                                 mesh_shape=args.mesh_shape)
        results["mesh"] = res
        print(f"mlp cluster of C={res['members']} members, "
              f"{res['steps']} local steps × {res['rounds']} rounds, "
              f"{res['mesh_shape']} (data × model) mesh")
        print(f"  legacy (R=1, 1 dev) : {res['legacy_steps_per_s']:10.1f} "
              f"client-steps/s")
        print(f"  fused  (R={res['R']}, 1 dev) : "
              f"{res['fused_steps_per_s']:10.1f} client-steps/s")
        print(f"  sharded(R={res['R']}, {res['devices']} dev) : "
              f"{res['mesh_steps_per_s']:10.1f} client-steps/s "
              f"({res['speedup_vs_legacy']:.2f}× vs legacy, "
              f"{res['sharding_overhead']:.2f}× vs unsharded fused)")
    if args.mode in ("cluster", "all"):
        results["cluster"] = run_cluster_bench(args)
    if args.mode in ("dispatch", "all"):
        res = run_dispatch_bench(n=args.members, R=args.dispatch_r,
                                 reps=args.reps, seed=args.seed)
        results["dispatch"] = res
        for fam, d in res.items():
            print(f"{fam} cluster of C={d['members']} members, "
                  f"{d['steps']} local steps × {d['rounds']} rounds")
            print(f"  legacy (R=1)  : {d['legacy_steps_per_s']:10.1f} "
                  f"client-steps/s")
            print(f"  fused  (R={d['R']})  : "
                  f"{d['dispatch_steps_per_s']:10.1f} client-steps/s "
                  f"({d['speedup']:.2f}× speedup)")
    if args.mode in ("fleet", "all"):
        res = run_fleet_bench(rounds=args.fleet_rounds, seed=args.seed)
        results["fleet"] = res
        tr = res["trace"]
        print(f"trace generation, mixed scenario, n={tr['n']} × "
              f"{tr['rounds']} rounds")
        print(f"  legacy loops : {tr['legacy_s']:8.3f}s")
        print(f"  vectorized   : {tr['vectorized_s']:8.4f}s "
              f"({tr['speedup']:.0f}× speedup)")
        for key, r in res.items():
            if key == "trace":
                continue
            print(f"fleet n={r['n']:>9}  k={r['k']}  "
                  f"trace={r['trace_s']:7.3f}s  "
                  f"cluster={r['cluster_s']:7.3f}s  "
                  f"sim={r['sim_s']:7.3f}s  "
                  f"({r['rounds_per_s']:.2f} rounds/s, "
                  f"{r['events']} events)")
    if args.mode in ("async", "all"):
        res = run_async_bench(seed=args.seed)
        results["async"] = res
        print(f"async server vs barrier, {res['members']} participants × "
              f"{res['rounds']} rounds (R={res['R']}, straggler trace, "
              f"spike_rate={res['spike_rate']}), "
              f"target_loss={res['target_loss']}")
        for m in ("sync", "async"):
            r = res[m]
            print(f"  {m:5s} : t_to_target={r['t_to_target_s']:8.3f}s  "
                  f"wall={r['wall_clock_s']:8.3f}s  "
                  f"final_loss={r['final_loss']:.4f}  "
                  f"banked={r['banked']}")
        print(f"  async reaches target in "
              f"{1 / max(res['speedup_to_target'], 1e-9):.2f}× the barrier "
              f"time ({res['speedup_to_target']:.2f}× speedup)")
    if args.mode in ("ckpt", "all"):
        res = run_ckpt_bench(seed=args.seed, reps=args.reps)
        results["ckpt"] = res
        for key, r in res.items():
            print(f"ckpt n={r['n']:>7}  {r['arrays']} arrays, "
                  f"{r['bytes'] / 1e6:7.2f} MB  "
                  f"save={r['save_s'] * 1e3:8.2f}ms "
                  f"({r['save_mb_per_s']:.0f} MB/s)  "
                  f"restore={r['restore_s'] * 1e3:8.2f}ms "
                  f"({r['restore_mb_per_s']:.0f} MB/s)")
    if args.mode in ("padding", "all"):
        pad = run_padding_bench(n=args.participants, rounds=args.sim_rounds,
                                steps=args.steps, seed=args.seed,
                                base_width=args.base_width)
        results["padding"] = pad
        p, u = pad["padded"], pad["unpadded"]
        print(f"drift-heavy sim, {pad['participants']} participants × "
              f"{pad['rounds']} rounds, {u['migrations']} migrations")
        print(f"  padded   : {p['wall_s']:7.2f}s  "
              f"{p['xla_compiles']} XLA compiles "
              f"({p['round_programs']} programs)")
        print(f"  unpadded : {u['wall_s']:7.2f}s  "
              f"{u['xla_compiles']} XLA compiles "
              f"({u['round_programs']} programs)")
    if args.json:
        pathlib.Path(args.json).write_text(json.dumps(results, indent=2))
        print(f"wrote {args.json}")
    return results


if __name__ == "__main__":
    main()
