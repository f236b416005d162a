"""Fed-RAC orchestrator (Algorithm 1): cluster → compact → assign →
train master by FedAvg → train slaves under master KD.

Model-family-agnostic via ``FLModelFamily`` (the paper's CNN and the LM
backbones both plug in); per-cluster client training runs through
``core.client`` so on a pod the whole cluster is one vmap/pjit program.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import aggregation, assignment as asg, clustering, compaction
from repro.core import cost_model, rounds as rnd
from repro.core.client import local_update, make_cluster_update
from repro.core.plane import make_plane_spec, make_tp_plane_spec, plane_specs
from repro.core.resources import (LAMBDA_PAPER, Fleet, Participant,
                                  resource_matrix, unit_normalize)
from repro.data import device_sampler
from repro.data.sampler import class_balanced_batches, sample_batches
from repro.models.tp import tp_shard_ctx
from repro.launch.sharding import (member_specs, replicated_specs,
                                   shard_member_tree, to_named)
from repro.obs import NULL_OBS


@dataclass
class FLModelFamily:
    """init(key, level) -> params; loss_and_logits(level, params, batch)."""
    init: Callable
    loss_and_logits: Callable
    model_bytes: Callable          # level -> bytes
    flops_per_sample: Callable     # level -> flops
    # Optional tensor-parallel rules: (level, params_template, msize, axis)
    # -> PartitionSpec pytree matching the params.  When present (and the
    # engine runs on a 2D mesh with ``tp_forward``), the dispatch path
    # GSPMD-shards the member FORWARD along the model axis instead of
    # all-gathering plane columns per round — see ``core.plane.TPPlaneSpec``.
    param_specs: Callable | None = None


@dataclass
class FLConfig:
    alpha: float = 0.5
    kd_T: float = 2.0
    kd_alpha: float = 0.3
    E: int = 2
    local_batch: int = 16
    steps_per_round: int = 4
    lr: float = 0.05
    lam: tuple = LAMBDA_PAPER
    q_target: float = 0.05
    delta: float | None = None
    theta: float = 100.0
    # MAR time budget; None → auto-calibrate so the master-cluster budget
    # admits roughly the fastest ~40% of participants (the paper fixes MAR
    # externally; auto mode keeps experiments scale-free).
    mar: float | None = None
    kappa: float = 0.7
    compact_to: int | None = None
    rounds: int = 20
    seed: int = 0
    class_balanced: bool = True
    use_kd: bool = True
    # batched cluster execution: one make_cluster_update vmap call per round
    # (all members advance together; heterogeneous τ_i / stragglers enter as
    # step masks).  False falls back to the per-pid Python loop — kept for
    # equivalence testing and benchmarks/bench_sim.py.
    vmap_clusters: bool = True
    # opt-in: let a vmap_clusters=False engine still use the scan-fused
    # dispatch path when rounds_per_dispatch>1 (the per-pid loop itself
    # cannot be fused, so training routes through dispatch_rounds) — the
    # hook that lets the equivalence matrix run its independent-loop
    # column fused as well.
    allow_loop_dispatch: bool = False
    # compile-stable padding: round every cluster's member count up to a
    # capacity bucket (next power of two, then multiples of pad_max) and pad
    # batches/masks/weights with zero rows, so Procedure-2 migrations and
    # simulator dropouts/arrivals reuse the same XLA program instead of
    # retracing it at every new cardinality.  False traces at exact C.
    pad_clusters: bool = True
    pad_max: int = 64
    # aggregation schedule: "sync" is plain FedAvg over this round's
    # contributors; "buffered" additionally merges banked (late) updates
    # from earlier rounds, discounted by staleness_discount**age — the
    # sim's MAR policy "buffer" feeds this path.
    aggregation: str = "sync"
    staleness_discount: float = 0.6
    # device-resident round pipeline: >1 fuses that many communication
    # rounds into ONE jitted lax.scan program (in-program batch sampling
    # from device-resident shards, parameters carried as a flat fp32 plane,
    # plane donated between blocks).  1 keeps the legacy one-round-per-
    # dispatch path.  Within the dispatch path the batch stream depends
    # only on the absolute round index, so any two widths R are numerically
    # equivalent; the legacy path keeps its historical numpy stream.
    rounds_per_dispatch: int = 1
    # donate the parameter plane (and bank plane) into each dispatch so
    # multi-round blocks run copy-free; the caller's handle to the donated
    # buffer is dead after the call.
    donate_plane: bool = True
    # true tensor-parallel member forward on a 2D (data × model) mesh: the
    # dispatch block runs as ONE GSPMD global-view program whose plane
    # carries the TP layout (``core.plane.TPPlaneSpec``), so the member
    # forward/backward is Megatron-sharded along the model axis and the
    # full (D,) plane is never materialized per device.  Requires the
    # family to provide ``param_specs``; False keeps the legacy shard_map
    # path that transiently all-gathers plane columns every round.
    tp_forward: bool = True
    consts: rnd.ConvergenceConstants = field(default_factory=rnd.ConvergenceConstants)


@dataclass
class DispatchOut:
    """Result of one scan-fused dispatch block (``FedRAC.dispatch_rounds``)."""
    plane: object               # (D_pad,) fp32 — replaces the donated input
    losses: object              # (R, C) per-round per-member mean losses
    bank: tuple | None          # (bank_plane, bank_w) after the last round
    history: object | None      # (R, D_pad) per-round planes (want_history)


class _TimedProgram:
    """Transparent wrapper around one jitted program that detects fresh XLA
    compiles (jit cache-size delta across a call) and records them in the
    metrics registry — a per-program compile counter and wall-time gauge
    (``fl/compiles/<label>`` / ``fl/compile_s/<label>``), fleet-wide
    ``fl/compile_total`` and ``fl/compile_s`` aggregates — plus a
    ``compile`` span on the tracer.  Only installed when observability is
    enabled; the disabled path stores the raw jitted callable."""
    __slots__ = ("fn", "_obs", "_label")

    def __init__(self, fn, obs, label: str):
        self.fn = fn
        self._obs = obs
        self._label = label

    def _cache_size(self):               # compile_stats() delegate
        return self.fn._cache_size()

    def __call__(self, *args, **kw):
        before = self.fn._cache_size()
        t0 = time.perf_counter_ns()
        out = self.fn(*args, **kw)
        if self.fn._cache_size() > before:
            # a fresh trace+compile happened inside this call: make the
            # measured wall time cover it honestly
            jax.block_until_ready(out)
            dt_ns = time.perf_counter_ns() - t0
            reg = self._obs.registry
            reg.counter(f"fl/compiles/{self._label}").inc()
            reg.gauge(f"fl/compile_s/{self._label}").set(dt_ns / 1e9)
            reg.counter("fl/compile_total").inc()
            reg.histogram("fl/compile_s").observe(dt_ns / 1e9)
            self._obs.tracer.complete("compile", t0, dt_ns, cat="fl",
                                      program=self._label)
        return out


@dataclass
class FedRACResult:
    k_optimal: int
    m: int
    di_values: dict
    labels: np.ndarray
    assignment: asg.Assignment
    history: dict            # level -> [acc per round]
    final_acc: dict          # level -> acc
    global_acc: float
    rounds_used: dict


class FedRAC:
    def __init__(self, parts: "list[Participant] | Fleet",
                 client_data: list[dict],
                 family: FLModelFamily, cfg: FLConfig, classes: int, *,
                 mesh=None, mesh_axis: str = "data",
                 mesh_model_axis: str = "model"):
        if cfg.aggregation not in ("sync", "buffered"):
            raise ValueError(f"unknown aggregation {cfg.aggregation!r}")
        if (cfg.rounds_per_dispatch > 1 and not cfg.vmap_clusters
                and not cfg.allow_loop_dispatch):
            raise ValueError(
                "rounds_per_dispatch>1 (device-resident pipeline) requires "
                "vmap_clusters=True — the per-pid loop cannot be scan-fused "
                "(set allow_loop_dispatch=True to route a loop-configured "
                "engine through the fused dispatch path anyway)")
        if mesh is not None and cfg.rounds_per_dispatch == 1:
            raise ValueError(
                "a mesh shards the device-resident dispatch path — set "
                "rounds_per_dispatch>1 (the legacy one-round path would "
                "silently ignore it)")
        # a Fleet (struct-of-arrays) is the canonical fleet-scale state;
        # self.parts stays the object API either way — Fleet rows are
        # write-through views, so update_resources/sim mutations through
        # either surface agree by construction
        if isinstance(parts, Fleet):
            self.fleet = parts
            self.parts = parts.participants()
        else:
            self.fleet = None
            self.parts = parts
        self.client_data = client_data        # per pid: {"x": ..., "y": ...}
        self.family = family
        self.cfg = cfg
        self.classes = classes
        # observability bundle (metrics registry + tracer); NULL_OBS keeps
        # every instrumented site on its single-branch no-op fast path
        self.obs = NULL_OBS
        # mesh-sharded execution: the dispatch block program runs under
        # shard_map with the capacity axis split along mesh `mesh_axis` —
        # each device trains its local member rows and one psum over that
        # axis realizes the §III-B upload as an all-reduce.  A 2D
        # (data × model) mesh additionally splits every plane COLUMN-wise
        # along `mesh_model_axis`: the global plane, buffered bank and
        # per-round teacher/history stacks live distributed (member models
        # too large for one device stop replicating), parameters are
        # all-gathered transiently for the local forward, and each device
        # aggregates only its own (member rows × column slice) subgrid —
        # the model axis needs no reduction at all.  None = single-device.
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self._mesh_n = int(mesh.shape[mesh_axis]) if mesh is not None else 1
        self._mesh_m = (int(dict(mesh.shape).get(mesh_model_axis, 1))
                        if mesh is not None else 1)
        # None when the model axis is absent or trivial: every 1D code path
        # (and its compiled programs) is exactly the pre-2D one.
        self.model_axis = mesh_model_axis if self._mesh_m > 1 else None
        self._pspecs = plane_specs(mesh_axis, self.model_axis)
        # true TP forward: the 2D-mesh dispatch block runs as one GSPMD
        # global-view program over a TP-layout plane (family supplies the
        # per-leaf rules).  Families without ``param_specs`` — and engines
        # with ``tp_forward=False`` — keep the legacy column-gather path.
        self._tp = (self._mesh_m > 1 and cfg.tp_forward
                    and family.param_specs is not None)
        # (level, use_kd, capacity, want_stack, …) -> jitted round programs
        self._programs = {}
        # dispatch-path caches: level -> PlaneSpec; (level, members) ->
        # device-resident shard pack; lazily-computed global pad lengths
        self._plane_specs = {}
        self._shard_packs = {}
        # newest pack per (level, capacity, balanced) — the delta-update
        # base when membership churns (Procedure-2 migration, sim events)
        self._pack_prev = {}
        self._shard_len_pad = None
        self._class_m_pad = None
        self._class_tables = {}           # pid -> (table, counts) host arrays
        # TP dispatch normalizes a FIXED KD teacher pytree to its level-0
        # plane once per teacher identity (strong ref pins the id)
        self._t_plane_cache = None

    # ------------------------------------------------------------ setup
    def setup(self):
        cfg = self.cfg
        V = resource_matrix(self.fleet if self.fleet is not None
                            else self.parts)
        res = clustering.optimal_clusters(V, cfg.lam, seed=cfg.seed)
        labels = clustering.order_clusters_by_resources(res.normalized,
                                                        res.labels, cfg.lam)
        self.k_optimal = res.k
        self.di_values = res.di_values
        if cfg.compact_to is not None and cfg.compact_to < res.k:
            labels = compaction.compact(labels, res.normalized, cfg.compact_to)
        self.labels = labels
        self.m = len(np.unique(labels))
        sizes = [(self.family.model_bytes(l), self.family.flops_per_sample(l))
                 for l in range(self.m)]
        mar = cfg.mar
        if mar is None:
            t_master = np.array([cost_model.round_time(
                p, sizes[0][1], sizes[0][0], cfg.E) for p in self.parts])
            mar = float(np.percentile(t_master, 40)) / (cfg.kappa ** (self.m - 1))
        self.mar = mar
        self.specs = asg.build_cluster_specs(
            sizes, cfg.consts, E=cfg.E, q_target=cfg.q_target, delta=cfg.delta,
            theta=cfg.theta, mar=mar, kappa=cfg.kappa,
            batch_size=cfg.local_batch)
        self.assignment = asg.assign(self.parts, self.specs, cfg.consts, cfg.lr)
        return self

    def update_resources(self, pid: int, *, s: float | None = None,
                         r: float | None = None, a: float | None = None):
        """§IV-A dynamic resources: update a participant's (s, r, a) and
        re-run the Procedure-2 placement — the participant upgrades or
        downgrades clusters in place.  Returns (old_level, new_level)."""
        p = self.parts[pid]
        if s is not None:
            p.s = s
        if r is not None:
            p.r = r
        if a is not None:
            p.a = a
        return asg.reassign(p, self.assignment, self.specs,
                            self.cfg.consts, self.cfg.lr)

    # ------------------------------------------------------------ training
    # Batch sampling.  The legacy one-round-per-dispatch path samples on
    # host with numpy (seed + 977·pid + round — unchanged numerics).  The
    # scan-fused dispatch path draws its indices from a seeded jax.random
    # stream keyed on (seed, absolute round, member slot) INSIDE the program
    # (data/device_sampler.py) and gathers from device-resident shards, so
    # any two dispatch widths R are numerically interchangeable (the stream
    # never depends on block boundaries).
    # The two paths' streams are statistically equivalent but distinct —
    # cross-path comparisons are statistical, cross-R comparisons exact.

    def _member_shard(self, pid: int):
        """Hook: one member's full data shard (pytree, leading axis = n_i)
        for the dispatch path.  Subclasses with non-{"x","y"} data override
        this plus ``_batch_from_gathered``."""
        return self.client_data[pid]

    def _batch_from_gathered(self, gathered):
        """Hook: post-gather transform from a (steps, batch, …) shard slice
        to the loss_fn batch format (jax-traceable — it runs inside the
        dispatch scan body)."""
        return gathered

    def _class_table(self, pid: int):
        """Per-member class index table for balanced in-program sampling,
        padded to the fleet-wide max class count so the dispatch program
        shape is stable under Procedure-2 churn."""
        if self._class_m_pad is None:
            m = 1
            for q in range(len(self.parts)):
                y = np.asarray(self._member_shard(q)["y"])
                if y.size:
                    m = max(m, int(np.bincount(y, minlength=self.classes)
                                   .max()))
            self._class_m_pad = 1 << (m - 1).bit_length()
        if pid not in self._class_tables:
            self._class_tables[pid] = device_sampler.build_class_table(
                np.asarray(self._member_shard(pid)["y"]), self.classes,
                self._class_m_pad)
        return self._class_tables[pid]

    def _client_batches(self, pid: int, rng_round: int, balanced: bool):
        d = self.client_data[pid]
        steps = self.cfg.steps_per_round
        if balanced:
            return class_balanced_batches(d["x"], d["y"], self.cfg.local_batch,
                                          steps, self.classes,
                                          seed=self.cfg.seed + 977 * pid + rng_round)
        return sample_batches(d["x"], d["y"], self.cfg.local_batch, steps,
                              seed=self.cfg.seed + 977 * pid + rng_round)

    def _capacity(self, C: int) -> int:
        """Bucket a live member count to its padded capacity: next power of
        two capped at pad_max, then multiples of pad_max — a handful of
        buckets covers every cardinality Procedure-2 churn can produce.
        (The cap keeps capacities monotone for non-power-of-two pad_max.)
        On a mesh the capacity is additionally rounded up to a multiple of
        the data-axis size so every device holds the same member-row count
        — the extra rows are the same zero-weight padding the buckets use,
        so they never touch the aggregate."""
        cfg = self.cfg
        cap = C
        if cfg.pad_clusters and C > 0:
            if C >= cfg.pad_max:
                cap = -(-C // cfg.pad_max) * cfg.pad_max
            else:
                cap = min(1 << (C - 1).bit_length(), cfg.pad_max)
        if self._mesh_n > 1 and cap > 0:
            cap = -(-cap // self._mesh_n) * self._mesh_n
        return cap

    def _stacked_batches(self, members: list[int], rng_round: int, level: int,
                         capacity: int | None = None):
        """Per-member batches stacked to (capacity, steps, batch, ...) pytrees;
        slots past len(members) are zero rows (they train under a zero
        step-mask and zero weight, so their contents never matter).
        Stacks on host so each leaf is one contiguous device transfer."""
        balanced = self.cfg.class_balanced and level == 0
        per = [self._client_batches(pid, rng_round, balanced)
               for pid in members]
        pad = (capacity or len(members)) - len(members)

        def stack(*xs):
            arr = np.stack(xs)
            if pad:
                arr = np.concatenate(
                    [arr, np.zeros((pad,) + arr.shape[1:], arr.dtype)])
            return jnp.asarray(arr)

        out = jax.tree.map(stack, *per)
        if self.obs.on:
            self.obs.registry.counter("fl/h2d_bytes").inc(
                sum(x.nbytes for x in jax.tree.leaves(out)))
        return out

    # ------------------------------------------------------------ plane
    def plane_spec(self, level: int):
        """Flat-parameter-plane recipe for one level (cached; the template
        init is shape-only).  On a 2D mesh D pads to a multiple of
        ``model_size × PLANE_ALIGN`` so each device's column slice stays
        lane-aligned."""
        if level not in self._plane_specs:
            template = self.family.init(jax.random.PRNGKey(0), level)
            if self._tp:
                specs = self.family.param_specs(level, template,
                                                self._mesh_m, self.model_axis)
                self._plane_specs[level] = make_tp_plane_spec(
                    template, specs, msize=self._mesh_m, axis=self.model_axis)
            else:
                self._plane_specs[level] = make_plane_spec(
                    template, model_size=self._mesh_m)
        return self._plane_specs[level]

    def plane_of(self, level: int, params) -> jnp.ndarray:
        """Ravel a params pytree into its (D_pad,) fp32 plane (committed to
        its mesh sharding, so every dispatch call sees one input sharding
        signature and block programs never retrace)."""
        return self.place_plane(self.plane_spec(level).to_plane(params))

    def place_plane(self, x):
        """Commit a (D,) plane to its mesh sharding: column-sharded along
        the model axis on a 2D mesh, replicated otherwise."""
        if self.mesh is None:
            return x
        return jax.device_put(x, NamedSharding(self.mesh,
                                               self._pspecs["plane"]))

    def place_plane_stack(self, x):
        """Commit an (R, D) teacher/history plane stack (rounds replicated,
        columns model-sharded on a 2D mesh)."""
        if self.mesh is None:
            return x
        return jax.device_put(x, NamedSharding(self.mesh,
                                               self._pspecs["stack"]))

    def place_member_plane(self, x):
        """Commit a (capacity, D) member/bank plane: rows member-sharded,
        columns model-sharded on a 2D mesh."""
        if self.mesh is None:
            return x
        return jax.device_put(x, NamedSharding(self.mesh,
                                               self._pspecs["members"]))

    def place_member_sharded(self, x):
        """Commit an array sharded along the member axis (no-op without a
        mesh) — bank carries and mask/weight rows enter dispatch programs
        pre-placed instead of being resharded per call."""
        if self.mesh is None:
            return x
        return jax.device_put(x, NamedSharding(self.mesh,
                                               P(self.mesh_axis)))

    def params_of(self, level: int, plane):
        """Unravel a plane back to a params pytree (evaluation/reporting
        boundary — the only place the dispatch path leaves the plane)."""
        return self.plane_spec(level).to_params(plane)

    def _delta_shards(self, level: int, members: list[int], capacity: int,
                      balanced: bool):
        """Delta shard-pack update on membership churn: when a previous pack
        exists at the same (level, capacity, balanced) signature, surviving
        member rows are PERMUTED on device (one gather + row-mask) and only
        genuinely new members' shards are built on host and scattered in —
        a Procedure-2 migration of one participant moves one row, not the
        whole (capacity, N_pad, …) stack.  Returns the new shards pytree, or
        None when a full rebuild is better (no base pack, > half the rows
        fresh) or a mesh is present (the base is row-sharded; a permutation
        would reshard — the full build path places rows once, correctly).
        Sets ``self._delta_h2d`` to the bytes actually transferred."""
        self._delta_h2d = None
        if self.mesh is not None:
            return None
        prev = self._pack_prev.get((level, capacity, balanced))
        if prev is None:
            return None
        prev_members, prev_shards = prev
        pos = {pid: i for i, pid in enumerate(prev_members)}
        src = np.zeros(capacity, np.int64)
        keep = np.zeros(capacity, bool)
        fresh = []
        for i, pid in enumerate(members):
            j = pos.get(pid)
            if j is None:
                fresh.append(i)
            else:
                src[i] = j
                keep[i] = True
        if len(fresh) > max(1, len(members) // 2):
            return None
        srcj, keepj = jnp.asarray(src), jnp.asarray(keep)

        def permute(a):
            g = a[srcj]
            mask = keepj.reshape((capacity,) + (1,) * (g.ndim - 1))
            return jnp.where(mask, g, jnp.zeros((), g.dtype))

        shards_j = jax.tree.map(permute, prev_shards)
        moved = 0
        if fresh:
            N = self._shard_len_pad
            rows = [self._member_shard(members[i]) for i in fresh]

            def fresh_leaf(*xs):
                first = np.asarray(xs[0])
                out = np.zeros((len(fresh), N) + first.shape[1:],
                               first.dtype)
                for i, x in enumerate(xs):
                    x = np.asarray(x)
                    out[i, :x.shape[0]] = x
                return out

            host_rows = jax.tree.map(fresh_leaf, *rows)
            idxj = jnp.asarray(np.asarray(fresh))
            shards_j = jax.tree.map(
                lambda a, f: a.at[idxj].set(jnp.asarray(f)),
                shards_j, host_rows)
            moved = sum(np.asarray(x).nbytes
                        for x in jax.tree.leaves(host_rows))
        self._delta_h2d = moved
        return shards_j

    def _shard_pack(self, level: int, members: list[int], capacity: int,
                    balanced: bool):
        """Device-resident member data for the dispatch path: every member's
        full shard stacked to (capacity, N_pad, …) once (padded rows are
        zeros and never drawn), plus lengths, pids, and — for balanced
        levels — class tables.  N_pad and the class-table width are fleet-
        wide power-of-two ceilings so the program shape is identical for
        every membership Procedure-2 churn can produce."""
        key = (level, tuple(members), capacity, balanced)
        if key in self._shard_packs:
            pack = self._shard_packs.pop(key)      # LRU: refresh on hit
            self._shard_packs[key] = pack
            return pack
        if self._shard_len_pad is None:
            n_max = max(max((jax.tree.leaves(self._member_shard(q))[0].shape[0]
                             for q in range(len(self.parts))), default=1), 1)
            self._shard_len_pad = 1 << (n_max - 1).bit_length()
        N = self._shard_len_pad
        shards = [self._member_shard(pid) for pid in members]
        shards_j = self._delta_shards(level, members, capacity, balanced)
        delta = shards_j is not None
        if not delta:

            def pack_leaf(*xs):
                first = np.asarray(xs[0])
                out = np.zeros((capacity, N) + first.shape[1:], first.dtype)
                for i, x in enumerate(xs):
                    x = np.asarray(x)
                    out[i, :x.shape[0]] = x
                return jnp.asarray(out)

            shards_j = jax.tree.map(pack_leaf, *shards)
        pack = {"shards": shards_j,
                "n": jnp.asarray(np.concatenate(
                    [np.asarray([jax.tree.leaves(s)[0].shape[0]
                                 for s in shards], np.int32),
                     np.zeros(capacity - len(members), np.int32)])),
                "tables": None, "counts": None}
        if balanced and members:
            self._class_table(members[0])              # sizes _class_m_pad
            tables = np.zeros((capacity, self.classes, self._class_m_pad),
                              np.int32)
            counts = np.zeros((capacity, self.classes), np.int32)
            for i, pid in enumerate(members):
                tables[i], counts[i] = self._class_table(pid)
            pack["tables"] = jnp.asarray(tables)
            pack["counts"] = jnp.asarray(counts)
        if self.mesh is not None:
            # place the pack row-sharded on the mesh ONCE; cached reuse then
            # skips the implicit per-call jit reshard
            pack = shard_member_tree(self.mesh, pack, self.mesh_axis)
        if len(self._shard_packs) >= 16:               # bound device memory
            self._shard_packs.pop(next(iter(self._shard_packs)))
        self._shard_packs[key] = pack
        if self.mesh is None:
            self._pack_prev[(level, capacity, balanced)] = (
                tuple(members), pack["shards"])
        if self.obs.on:
            nbytes = (self._delta_h2d if delta
                      else sum(x.nbytes for x in jax.tree.leaves(pack)))
            reg = self.obs.registry
            reg.counter("fl/h2d_bytes").inc(nbytes)
            if delta:
                reg.counter("fl/pack_delta").inc()
        return pack

    def _cluster_programs(self, level: int, use_kd: bool, capacity: int,
                          want_stack: bool = False):
        """Cached whole-round program for one cluster: broadcast shared params
        over the member axis, run every member's τ local steps under one vmap
        (teacher logits computed in-program for slave clusters), and fuse the
        FedAvg aggregation — a single jitted XLA program per round.
        Keyed on the padded capacity (not the live member count) so cluster
        migrations reuse the program, and on the captured hyperparameters so
        in-place FLConfig mutation (lr sweeps on one engine) invalidates the
        cache.  ``want_stack`` programs additionally return the per-member
        updated params (the buffered-aggregation banking hook)."""
        cfg = self.cfg
        key = (level, use_kd, capacity, want_stack,
               cfg.lr, cfg.kd_T, cfg.kd_alpha)
        if key not in self._programs:
            loss_fn = jax.tree_util.Partial(self.family.loss_and_logits, level)
            kw = dict(kd_T=cfg.kd_T, kd_alpha=cfg.kd_alpha) if use_kd else {}
            update = make_cluster_update(loss_fn, cfg.lr, **kw)
            t_loss_fn = jax.tree_util.Partial(self.family.loss_and_logits, 0)

            def round_fn(params, batches, step_masks, weights, teacher):
                C = step_masks.shape[0]
                p_stack = jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (C,) + x.shape),
                    params)
                teachers = None
                if use_kd:
                    teachers = jax.vmap(                       # members axis
                        jax.vmap(lambda b: t_loss_fn(teacher, b)[1])
                    )(batches)                                 # steps axis
                new_stack, losses = update(p_stack, batches, step_masks,
                                           teachers)
                agg = aggregation.aggregate(new_stack, weights)
                if want_stack:
                    return agg, losses, new_stack
                return agg, losses

            prog = jax.jit(round_fn)
            if self.obs.on:
                prog = _TimedProgram(
                    prog, self.obs,
                    f"round_L{level}_cap{capacity}_R1"
                    + ("_kd" if use_kd else "")
                    + ("_stack" if want_stack else ""))
            self._programs[key] = prog
        return self._programs[key]

    def compile_stats(self) -> dict:
        """Program-cache telemetry: {program key -> XLA compile count}.
        With padding on, every key should sit at 1 — a retrace means some
        input shape escaped the capacity bucketing."""
        out = {}
        for key, prog in self._programs.items():
            progs = prog if isinstance(prog, tuple) else (prog,)
            out[key] = sum(p._cache_size() for p in progs)
        return out

    def cluster_round(self, level: int, members: list[int], params, r: int, *,
                      teacher=None, step_masks=None, weights=None,
                      buffered=None, return_stack: bool = False):
        """One communication round for a cluster, batched: every member's τ
        local steps run under a single vmapped update, then FedAvg.

        ``step_masks`` (C, steps) zeroes out SGD steps per member — the hook
        for heterogeneous τ_i and for the simulator's straggler/dropout masks
        (a fully-zero row leaves that member at the incoming params).
        ``weights`` are raw non-negative aggregation weights per member
        (default: n_eff); they are renormalized over the members that actually
        contribute.  All-zero weights (every member dropped) leave ``params``
        unchanged — partial aggregation.

        With ``pad_clusters`` the live C is padded up to its capacity bucket
        (zero batches/masks/weights rows); padded rows carry zero aggregation
        weight, so the renormalized FedAvg over the real members is untouched
        and the XLA program is reused across cardinality changes.

        ``buffered`` is a list of (params_pytree, raw_weight) banked async
        contributions (already staleness-discounted); they join this round's
        FedAvg as extra members at their stale params.  ``return_stack=True``
        additionally returns the per-member updated params stack — the
        banking hook for the buffered schedule.

        Returns (new_params, member_losses[, member_params_stack]).
        """
        cfg = self.cfg
        C = len(members)
        if weights is None:
            weights = [self.assignment.n_eff.get(pid, 1) for pid in members]
        w = np.asarray(weights, np.float32)
        buffered = list(buffered) if buffered else []
        u = np.asarray([bw for _, bw in buffered], np.float32)
        total = float(w.sum()) + float(u.sum())
        if total <= 0.0 and not return_stack:
            # everyone dropped: partial agg no-op (with return_stack the
            # program still runs — banked members trained, their stack is
            # needed even though nobody aggregates this round)
            return params, jnp.zeros((C,), jnp.float32)
        cap = self._capacity(C)
        run_program = float(w.sum()) > 0.0 or return_stack
        stack = None
        denom = total if total > 0.0 else 1.0
        if run_program:
            batches = self._stacked_batches(members, r, level, cap)
            steps = jax.tree.leaves(batches)[0].shape[1]
            if step_masks is None:
                step_masks = jnp.ones((C, steps), jnp.float32)
            masks = np.zeros((cap, steps), np.float32)
            masks[:C] = np.asarray(step_masks, np.float32)
            w_pad = np.zeros(cap, np.float32)
            w_pad[:C] = w / denom
            use_kd = teacher is not None and cfg.use_kd
            round_fn = self._cluster_programs(level, use_kd, cap,
                                              want_stack=return_stack)
            out = round_fn(params, batches, jnp.asarray(masks),
                           jnp.asarray(w_pad), teacher)
            partial, losses = out[0], out[1]
            if return_stack:
                stack = out[2]
        else:                           # only banked updates contribute
            partial = jax.tree.map(jnp.zeros_like, params)
            losses = jnp.zeros((cap,), jnp.float32)
        if total <= 0.0:               # stack-only round: aggregate no-op
            return params, losses[:C], stack
        if buffered:
            partial = aggregation.merge_buffered(
                partial, [p for p, _ in buffered], u / total)
        losses = losses[:C]
        return (partial, losses, stack) if return_stack else (partial, losses)

    # ------------------------------------------------------------ dispatch
    def _dispatch_programs(self, level: int, use_kd: bool, capacity: int,
                           R: int, balanced: bool, banked: bool,
                           want_history: bool, t_per_round: bool = False,
                           pack=None, teacher_example=None):
        """Cached scan-fused block program: R communication rounds in ONE
        jitted XLA program.  Per scan step it draws every member's batch
        indices in-program (seeded on the absolute round index and the
        member's global slot), gathers from the device-resident shard pack,
        runs the vmapped member update, and aggregates on the flat parameter
        plane — one contraction, no host round-trip, no tree_flatten.  The
        plane (and bank plane) are donated, so blocks run copy-free.
        ``banked`` variants additionally carry the buffered-aggregation bank
        through the scan: each round merges the previous round's bank
        (pre-discounted weights) into the FedAvg and re-banks this round's
        violators at ``bank_gain``.  ``t_per_round`` programs scan a
        (R, D_master) teacher-plane stack instead of closing over one fixed
        teacher — the hook that keeps KD teachers refreshing at round
        granularity inside a fused block.

        On a mesh the whole block runs under ``shard_map`` with the member
        (capacity) axis split along ``mesh_axis``: every device trains its
        local member rows, the per-round aggregation contracts locally
        (``aggregate_plane`` — the Pallas fedagg kernel on TPU) and ONE psum
        per round completes the §III-B upload all-reduce; donation is
        preserved, and the buffered bank rows ride the carry sharded like
        the members they came from.  On a 1D mesh the plane and the
        per-round teacher stack stay replicated.  On a 2D (data × model)
        mesh they instead split COLUMN-wise along the model axis — each
        device stores only its D/model_size slice of the plane, bank and
        teacher/history stacks.  With ``tp_forward`` (and a family that
        provides ``param_specs``) the 2D block compiles as ONE GSPMD
        global-view program over a TP-layout plane
        (``core.plane.TPPlaneSpec``): the member forward/backward itself is
        Megatron-sharded along the model axis — ``to_params`` is a chain of
        device-local reshapes, XLA inserts only the per-layer activation
        collectives, and the full (D,) plane never materializes on any
        device.  The legacy 2D path (``tp_forward=False``) instead
        all-gathers the plane (and teacher) columns transiently each round
        for a replicated local forward; either way each device contracts
        its (member rows × column slice) subgrid and a single data-axis
        reduction finishes the FedAvg — columns never need reduction."""
        cfg = self.cfg
        tp = self._tp
        key = ("dispatch", level, use_kd, capacity, R, balanced, banked,
               want_history, cfg.lr, cfg.kd_T, cfg.kd_alpha, cfg.seed,
               cfg.steps_per_round, cfg.local_batch, cfg.donate_plane,
               t_per_round, self._mesh_n, self._mesh_m, tp)
        if key in self._programs:
            return self._programs[key]
        loss_fn = jax.tree_util.Partial(self.family.loss_and_logits, level)
        kw = dict(kd_T=cfg.kd_T, kd_alpha=cfg.kd_alpha) if use_kd else {}
        update = make_cluster_update(loss_fn, cfg.lr, **kw)
        t_loss_fn = jax.tree_util.Partial(self.family.loss_and_logits, 0)
        spec = self.plane_spec(level)
        t_spec = (self.plane_spec(0) if (use_kd and (t_per_round or tp))
                  else None)
        steps, batch, seed = cfg.steps_per_round, cfg.local_batch, cfg.seed
        # The TP program is written in the GLOBAL view (no named axes: full
        # capacity, offset 0, one global weight sum) — numerically the
        # unsharded program — and GSPMD partitions it via the in/out
        # shardings + constraints below.  The shard_map path keeps its
        # per-device view with explicit collectives.
        axis = self.mesh_axis if (self.mesh is not None and not tp) else None
        maxis = self.model_axis if (self.mesh is not None and not tp) else None
        use_kernel = False if tp else None    # Pallas agg can't GSPMD-split

        def _constrain(x, pspec):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, pspec))

        def _gather_cols(plane_loc):
            """Local column slice -> full plane (2D mesh), else identity."""
            if maxis is None:
                return plane_loc
            return jax.lax.all_gather(plane_loc, maxis, tiled=True)

        def _local_cols(plane_full):
            """(C, D_full) member plane -> this device's column slice."""
            if maxis is None:
                return plane_full
            d_loc = plane_full.shape[1] // self._mesh_m
            return jax.lax.dynamic_slice_in_dim(
                plane_full, jax.lax.axis_index(maxis) * d_loc, d_loc, axis=1)

        def one_round(g, bank_p, bank_w, r, shards, n_i, tables,
                      counts, step_masks, weights, teacher, offset):
            C_loc = step_masks.shape[0]       # local member rows (mesh-split)
            with jax.named_scope("sampler"):
                key = device_sampler.round_key(seed, r)
                if balanced:
                    idx = device_sampler.balanced_indices(
                        key, steps, batch, tables, counts, offset=offset)
                else:
                    idx = device_sampler.uniform_indices(
                        key, steps, batch, n_i, offset=offset)
                batches = jax.vmap(lambda sh, ix: self._batch_from_gathered(
                    jax.tree.map(lambda a: a[ix], sh)))(shards, idx)
            params = (spec.to_params(g, mesh=self.mesh) if tp
                      else spec.to_params(_gather_cols(g)))
            p_stack = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (C_loc,) + x.shape),
                params)
            if axis is not None:
                # the member copies start replicated but train on this
                # device's own rows, so the member scan's carry varies
                # over the data axis from its first step on
                p_stack = jax.tree.map(
                    lambda x: jax.lax.pcast(x, (axis,), to="varying"),
                    p_stack)
            if tp:
                # member rows over `data`, each member's leaves TP-sharded —
                # the broadcast stays a broadcast; the forward partitions
                p_stack = jax.tree.map(
                    lambda x, sp: _constrain(
                        x, P(self.mesh_axis, *sp)),
                    p_stack, spec.leaf_specs())
            teachers = None
            if use_kd:
                with jax.named_scope("teacher_forward"):
                    if tp:
                        t_params = t_spec.to_params(teacher, mesh=self.mesh)
                    elif t_per_round:
                        t_params = t_spec.to_params(_gather_cols(teacher))
                    else:
                        t_params = teacher
                    teachers = jax.vmap(jax.vmap(
                        lambda b: t_loss_fn(t_params, b)[1]))(batches)
            with jax.named_scope("member_step"):
                new_stack, losses = update(p_stack, batches, step_masks,
                                           teachers)
            with jax.named_scope("aggregate"):
                # keep only this device's column slice of the updated
                # members: the carry plane, bank rows and aggregate all live
                # column-sharded, so the full-width member plane is transient
                stacked = jax.vmap(spec.to_plane)(new_stack)
                new_plane = (_constrain(stacked, self._pspecs["members"])
                             if tp else _local_cols(stacked))
                total = jnp.sum(weights) + (jnp.sum(bank_w) if banked
                                            else 0.0)
                if axis is not None:
                    total = jax.lax.psum(total, axis)
                denom = jnp.where(total > 0.0, total, 1.0)
                local = aggregation.aggregate_plane(
                    new_plane, weights / denom, use_kernel=use_kernel)
                if banked:
                    local = aggregation.merge_buffered_plane(
                        local, bank_p, bank_w / denom, use_kernel=use_kernel)
                agg = (jax.lax.psum(local, axis) if axis is not None
                       else local)
            g_next = jnp.where(total > 0.0, agg, g)
            if tp:
                g_next = _constrain(g_next, self._pspecs["plane"])
            if maxis is not None:
                # every model column computes identical losses (same batches,
                # same gathered params); the pmean is numerically a no-op
                # that PROVES the model-axis replication the losses
                # out_spec demands
                losses = jax.lax.pmean(losses, maxis)
            return g_next, new_plane, losses

        def _offset(step_masks):
            """Global slot index of this device's first member row."""
            if axis is None:
                return jnp.int32(0)
            return jax.lax.axis_index(axis) * step_masks.shape[0]

        def _xs(r0, teacher):
            rs = r0 + jnp.arange(R, dtype=jnp.int32)
            return (rs, teacher) if t_per_round else rs

        def _trace_ctx():
            """TP activation hints (models/tp.py) are scoped at TRACE time:
            entered inside the jitted function so the member forwards trace
            with the hint context active — exactly and only for TP blocks."""
            return (tp_shard_ctx(self.mesh, self.model_axis) if tp
                    else nullcontext())

        if banked:
            def block_fn(plane, bank_plane, bank_w, shards, n_i,
                         tables, counts, r0, step_masks, weights, bank_gain,
                         teacher):
                off = _offset(step_masks)

                def body(carry, x):
                    g, bp, bw = carry
                    r, t = x if t_per_round else (x, teacher)
                    g2, new_plane, losses = one_round(
                        g, bp, bw, r, shards, n_i, tables, counts,
                        step_masks, weights, t, off)
                    ys = (losses, g2) if want_history else (losses,)
                    return (g2, new_plane, bank_gain), ys
                with _trace_ctx():
                    carry, ys = jax.lax.scan(
                        body, (plane, bank_plane, bank_w), _xs(r0, teacher))
                return carry + tuple(ys)
            donate = (0, 1) if cfg.donate_plane else ()
        else:
            def block_fn(plane, shards, n_i, tables, counts, r0,
                         step_masks, weights, teacher):
                off = _offset(step_masks)

                def body(g, x):
                    r, t = x if t_per_round else (x, teacher)
                    g2, _, losses = one_round(
                        g, None, None, r, shards, n_i, tables, counts,
                        step_masks, weights, t, off)
                    ys = (losses, g2) if want_history else (losses,)
                    return g2, ys
                with _trace_ctx():
                    g, ys = jax.lax.scan(body, plane, _xs(r0, teacher))
                return (g,) + tuple(ys)
            donate = (0,) if cfg.donate_plane else ()

        fn = block_fn
        if tp:
            # GSPMD global view: same argument layout as the shard_map wrap,
            # but expressed as jit in/out shardings — the block body carries
            # the constraints, XLA does the partitioning.
            sp = self._pspecs
            daxis = self.mesh_axis
            def ns(s):
                return NamedSharding(self.mesh, s)

            def named(tree):
                return to_named(self.mesh, tree)
            Pm = ns(P(daxis))
            Pg, Pmm = ns(sp["plane"]), ns(sp["members"])
            t_in = None
            if use_kd:                     # fixed teacher rides as a plane
                t_in = ns(sp["stack"]) if t_per_round else ns(sp["plane"])
            tail = (named(member_specs(pack["shards"], daxis)), Pm,
                    named(member_specs(pack["tables"], daxis)),
                    named(member_specs(pack["counts"], daxis)), None,
                    ns(sp["masks"]), Pm)
            ys_sh = (ns(sp["losses"]),) + ((ns(sp["stack"]),)
                                           if want_history else ())
            if banked:
                in_sh = (Pg, Pmm, Pm) + tail + (Pm, t_in)
                out_sh = (Pg, Pmm, Pm) + ys_sh
            else:
                in_sh = (Pg,) + tail + (t_in,)
                out_sh = (Pg,) + ys_sh
            prog = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=donate)
        elif axis is not None:
            sp = self._pspecs
            Pm, Pr = sp["rows"], P()
            Pg, Pmm = sp["plane"], sp["members"]
            t_in = None
            if use_kd:
                t_in = (sp["stack"] if t_per_round
                        else replicated_specs(teacher_example))
            tail = (member_specs(pack["shards"], axis), Pm,
                    member_specs(pack["tables"], axis),
                    member_specs(pack["counts"], axis), Pr, sp["masks"], Pm)
            ys_specs = (sp["losses"],) + ((sp["stack"],)
                                          if want_history else ())
            if banked:
                in_specs = (Pg, Pmm, Pm) + tail + (Pm, t_in)
                out_specs = (Pg, Pmm, Pm) + ys_specs
            else:
                in_specs = (Pg,) + tail + (t_in,)
                out_specs = (Pg,) + ys_specs
            fn = jax.shard_map(block_fn, mesh=self.mesh,
                               in_specs=in_specs, out_specs=out_specs)
            prog = jax.jit(fn, donate_argnums=donate)
        else:
            prog = jax.jit(fn, donate_argnums=donate)
        if self.obs.on:
            prog = _TimedProgram(
                prog, self.obs,
                f"dispatch_L{level}_cap{capacity}_R{R}"
                + ("_kd" if use_kd else "") + ("_bank" if banked else ""))
        self._programs[key] = prog
        return self._programs[key]

    def dispatch_rounds(self, level: int, members: list[int], plane, r0: int,
                        n_rounds: int, *, teacher=None, teacher_planes=None,
                        step_masks=None, weights=None, bank=None,
                        want_history: bool = False):
        """Device-resident block dispatch: run ``n_rounds`` rounds fused.

        ``plane`` is the cluster's (D_pad,) parameter plane — it is DONATED
        (with ``donate_plane``): the caller's handle is dead after the call
        and must be replaced by the returned plane.  ``bank`` is the
        buffered-aggregation carry ``(bank_plane (cap, D_pad), bank_w (cap,),
        bank_gain (cap,))``: rows merged into the first round at ``bank_w``,
        each round's member updates re-banked at ``bank_gain`` (zero rows =
        not banked).  The KD teacher is either ``teacher`` (one params
        pytree, fixed for the whole block — the ``FedRAC.train`` path, whose
        master is fully trained first) or ``teacher_planes`` (an
        (n_rounds, D_master) plane stack scanned through the block, one
        teacher per round — the simulator path, where the master co-trains
        and R=1 semantics demand per-round refresh).  Returns a
        ``DispatchOut`` with per-round member losses and, with
        ``want_history``, the per-round planes — the hook that keeps
        telemetry/history exact under fusion.
        """
        cfg = self.cfg
        C = len(members)
        cap = self._capacity(C)
        balanced = cfg.class_balanced and level == 0
        use_kd = cfg.use_kd and (teacher is not None
                                 or teacher_planes is not None)
        t_per_round = use_kd and teacher_planes is not None
        if t_per_round and teacher_planes.shape[0] != n_rounds:
            raise ValueError(
                f"teacher_planes carries {teacher_planes.shape[0]} rounds "
                f"for a {n_rounds}-round block")
        banked = bank is not None
        tr = self.obs.tracer
        with tr.span("shard_pack", cat="fl", level=level, capacity=cap):
            pack = self._shard_pack(level, members, cap, balanced)
        S = cfg.steps_per_round
        h2d = 0
        with tr.span("place_inputs", cat="fl", level=level):
            if isinstance(weights, jax.Array) and weights.shape == (cap,):
                w = weights               # pre-padded device array: no copy
            else:
                if weights is None:
                    weights = [self.assignment.n_eff.get(pid, 1)
                               for pid in members]
                w = np.zeros(cap, np.float32)
                w[:C] = np.asarray(weights, np.float32)
                h2d += w.nbytes
                w = self.place_member_sharded(jnp.asarray(w))
            if (isinstance(step_masks, jax.Array)
                    and step_masks.shape == (cap, S)):
                masks = step_masks        # pre-padded device array: no copy
            else:
                masks = np.zeros((cap, S), np.float32)
                masks[:C] = (np.ones((C, S), np.float32) if step_masks is None
                             else np.asarray(step_masks, np.float32))
                h2d += masks.nbytes
                masks = self.place_member_sharded(jnp.asarray(masks))
            prog = self._dispatch_programs(level, use_kd, cap, n_rounds,
                                           balanced, banked, want_history,
                                           t_per_round=t_per_round,
                                           pack=pack, teacher_example=teacher)
            if t_per_round:
                t_arg = teacher_planes
            elif use_kd and self._tp:
                # the TP program consumes the fixed teacher as a TP-layout
                # level-0 plane (its in-program forward is sharded too);
                # convert once per teacher pytree identity
                if (self._t_plane_cache is None
                        or self._t_plane_cache[0] is not teacher):
                    self._t_plane_cache = (teacher,
                                           self.plane_of(0, teacher))
                t_arg = self._t_plane_cache[1]
            else:
                t_arg = teacher
            tail = (pack["shards"], pack["n"], pack["tables"],
                    pack["counts"], jnp.asarray(r0, jnp.int32), masks, w)
        with tr.span("block_exec", cat="fl", level=level, R=n_rounds,
                     capacity=cap):
            if banked:
                bank_plane, bank_w, bank_gain = bank
                out = prog(plane, bank_plane, bank_w, *tail,
                           jnp.asarray(bank_gain, jnp.float32), t_arg)
                new_plane, bank_out = out[0], (out[1], out[2])
                rest = out[3:]
            else:
                out = prog(plane, *tail, t_arg)
                new_plane, bank_out = out[0], None
                rest = out[1:]
            tr.fence(new_plane)
        with tr.span("block_outputs", cat="fl", level=level):
            losses = rest[0][:, :C]
            history = rest[1] if want_history else None
            if self.obs.on:
                reg = self.obs.registry
                reg.counter("fl/dispatch_blocks").inc()
                if h2d:
                    reg.counter("fl/h2d_bytes").inc(h2d)
                # per-round member losses are the block's host-bound output
                reg.counter("fl/d2h_bytes").inc(
                    losses.size * losses.dtype.itemsize)
                if self.mesh is not None:
                    # one psum over the data axis per fused round (see
                    # _dispatch_programs) — accounted analytically, since
                    # runtime collectives are invisible from inside jit;
                    # the HLO cross-check lives in launch/hlo_analysis
                    reg.counter("fl/psum_count").inc(n_rounds)
        return DispatchOut(plane=new_plane, losses=losses, bank=bank_out,
                           history=history)

    def _train_cluster(self, level: int, members: list[int], n_rounds: int,
                       test, teacher=None, record_every: int = 1):
        cfg = self.cfg
        key = jax.random.PRNGKey(cfg.seed + level)
        params = self.family.init(key, level)
        if not members:
            return params, []
        if not cfg.vmap_clusters and not (cfg.allow_loop_dispatch
                                          and cfg.rounds_per_dispatch > 1):
            return self._train_cluster_loop(level, members, n_rounds, test,
                                            params, teacher, record_every)
        if cfg.rounds_per_dispatch > 1:
            return self._train_cluster_dispatch(level, members, n_rounds,
                                                test, params, teacher,
                                                record_every)
        history = []
        weights = [self.assignment.n_eff.get(pid, 1) for pid in members]
        for r in range(n_rounds):
            params, _ = self.cluster_round(level, members, params, r,
                                           teacher=teacher, weights=weights)
            if (r + 1) % record_every == 0:
                history.append(self.evaluate(level, params, test))
        return params, history

    def _train_cluster_dispatch(self, level: int, members: list[int],
                                n_rounds: int, test, params, teacher=None,
                                record_every: int = 1):
        """Chunk ``n_rounds`` into blocks of ``rounds_per_dispatch`` fused
        rounds; per-round history stays exact via scan-stacked planes when a
        record boundary falls inside a block."""
        cfg = self.cfg
        R = cfg.rounds_per_dispatch
        spec = self.plane_spec(level)
        plane = self.plane_of(level, params)
        # masks/weights are constant across blocks: pad + transfer once
        cap = self._capacity(len(members))
        weights = np.zeros(cap, np.float32)
        weights[:len(members)] = [self.assignment.n_eff.get(pid, 1)
                                  for pid in members]
        weights = self.place_member_sharded(jnp.asarray(weights))
        masks = self.place_member_sharded(
            jnp.zeros((cap, cfg.steps_per_round), jnp.float32
                      ).at[:len(members)].set(1.0))
        history = []
        r = 0
        while r < n_rounds:
            L = min(R, n_rounds - r)
            rec = [rr for rr in range(r, r + L)
                   if (rr + 1) % record_every == 0]
            want_hist = any(rr != r + L - 1 for rr in rec)
            out = self.dispatch_rounds(level, members, plane, r, L,
                                       teacher=teacher, step_masks=masks,
                                       weights=weights,
                                       want_history=want_hist)
            plane = out.plane
            for rr in rec:
                p = (spec.to_params(out.history[rr - r]) if want_hist
                     else spec.to_params(plane))
                history.append(self.evaluate(level, p, test))
            r += L
        return self.params_of(level, plane), history

    def _train_cluster_loop(self, level: int, members: list[int],
                            n_rounds: int, test, params, teacher=None,
                            record_every: int = 1):
        """Reference per-pid loop (pre-vmap path); kept for the equivalence
        test and benchmarks/bench_sim.py."""
        cfg = self.cfg
        loop_key = ("loop", level, cfg.lr, cfg.kd_T, cfg.kd_alpha)
        if loop_key not in self._programs:
            loss_fn = jax.tree_util.Partial(self.family.loss_and_logits, level)
            t_loss_fn = jax.tree_util.Partial(self.family.loss_and_logits, 0)
            self._programs[loop_key] = (
                jax.jit(lambda tp, batches: jax.vmap(
                    lambda b: t_loss_fn(tp, b)[1])(batches)),
                jax.jit(lambda p, b, tl: local_update(
                    loss_fn, p, b, cfg.lr, teacher_logits=tl,
                    kd_T=cfg.kd_T, kd_alpha=cfg.kd_alpha)),
                jax.jit(lambda p, b: local_update(loss_fn, p, b, cfg.lr)))
        teacher_logits, upd, upd_plain = self._programs[loop_key]

        history = []
        weights = aggregation.normalized_weights(
            [self.assignment.n_eff.get(pid, 1) for pid in members])
        for r in range(n_rounds):
            new_params = []
            for pid in members:
                batches = jax.tree.map(
                    jnp.asarray,
                    self._client_batches(pid, r, cfg.class_balanced and level == 0))
                if teacher is not None and cfg.use_kd:
                    tl = teacher_logits(teacher, batches)
                    p_new, _ = upd(params, batches, tl)
                else:
                    p_new, _ = upd_plain(params, batches)
                new_params.append(p_new)
            stack = jax.tree.map(lambda *xs: jnp.stack(xs), *new_params)
            params = aggregation.aggregate(stack, weights)
            if (r + 1) % record_every == 0:
                history.append(self.evaluate(level, params, test))
        return params, history

    def evaluate(self, level: int, params, test) -> float:
        _, logits = self.family.loss_and_logits(level, params, test)
        return float(jnp.mean((jnp.argmax(logits, -1) == test["y"])))

    def train(self, test, rounds_per_cluster: dict | None = None) -> FedRACResult:
        cfg = self.cfg
        members = self.assignment.members
        n_rounds = {l: (rounds_per_cluster or {}).get(l, cfg.rounds)
                    for l in range(self.m)}
        master_params, hist0 = self._train_cluster(0, members.get(0, []),
                                                   n_rounds[0], test)
        history = {0: hist0}
        final = {0: hist0[-1] if hist0 else 0.0}
        self.master_params = master_params
        self.cluster_params = {0: master_params}
        for level in range(1, self.m):
            mem = members.get(level, [])
            if not mem:
                history[level] = []
                final[level] = float("nan")
                continue
            p, h = self._train_cluster(level, mem, n_rounds[level], test,
                                       teacher=master_params)
            history[level] = h
            final[level] = h[-1] if h else 0.0
            self.cluster_params[level] = p
        accs = [a for a in final.values() if a == a]
        return FedRACResult(
            k_optimal=self.k_optimal, m=self.m, di_values=self.di_values,
            labels=self.labels, assignment=self.assignment, history=history,
            final_acc=final, global_acc=float(np.mean(accs)),
            rounds_used=n_rounds)


def rounds_to_reach(history: list[float], target: float) -> int | None:
    for i, a in enumerate(history):
        if a >= target:
            return i + 1
    return None
