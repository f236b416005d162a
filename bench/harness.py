"""One run of one benchmark cell: set-up, the measured window, the check.

The job is the program's simulator in its sync dispatch mode
(``HeterogeneitySim.run`` over a ``FedRAC`` engine built through the public
constructors, as ``repro.launch.sim_run.build`` builds it).  It runs from
round 0 until the window has closed:

1. The first ``CHECK_BLOCKS`` dispatch blocks are the steps the reference
   follows.  A checkpoint hook (the engine's ``checkpoint`` argument, which
   hands over every level's plane at each block boundary) keeps the planes
   after each of them, and then detaches itself.
2. ``LEAD_BLOCKS`` more blocks run before the window opens.  Everything the
   first blocks compiled is warm by then; set-up ends at that boundary.
3. The window opens at a block boundary and closes at the first boundary
   ``--seconds`` after it.  A fault hook (the engine's ``faults`` argument,
   called at every block boundary) ends the job there.  The rate counts the
   rounds whose blocks ended inside the window, over the time from its
   opening to the last such end.  Each block ends on the host holding every
   cluster's losses, so these are completion times.

With ``trace`` the JAX profiler records the window, bracketed by two host
annotations that put the window on the device's clock.

The configuration's ``family`` names the files that know its model:
``bench/families/<family>.py`` builds the program's engine,
``bench/references/<family>.py`` is the plain reference and
``bench/flops/<family>.py`` counts its operations.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


CHECK_BLOCKS = 3         # dispatch blocks the reference replays
LEAD_BLOCKS = 2          # blocks between the checked ones and the window
MAX_ROUNDS = 20_000      # the job's horizon; the window ends it far sooner
# what a traffic mix may set, and its value where the mix does not
MIX_DEFAULTS = {"federation": {}, "rounds_per_dispatch": 4, "eval_every": 0}
GAP_NS = 50_000          # idle stretches shorter than this are not attributed
NAME_CHARS = 200         # a device operation's name is its HLO text: the head


class WindowClosed(Exception):
    """Raised at the first block boundary after the window's end."""


def load_cell(workload: str, root: Path = ROOT) -> dict:
    """The cell's entry of ``BENCHMARK.json`` with its configuration,
    traffic mix and limits, each read from the file named after it."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"cells: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    spec = load_job(root / configs[cell["config"]]["file"], cell["traffic"],
                    root)
    spec.update(
        cell=cell,
        limits=json.loads((root / "bench" / "limits" / f"{workload}.json")
                          .read_text()),
        per_layer=[m for m in bench["per_layer"]
                   if workload in m.get("workloads", [workload])],
        end_to_end=[m for m in bench["end_to_end"]
                    if workload in m.get("workloads", [workload])])
    return spec


def load_job(config_file: Path, traffic: str, root: Path = ROOT) -> dict:
    """A configuration under a traffic mix: what a job needs, with no
    cell around it."""
    mix = dict(MIX_DEFAULTS)
    mix.update(json.loads((root / "bench" / "traffic" / f"{traffic}.json")
                          .read_text()))
    return {"root": root, "config": json.loads(config_file.read_text()),
            "mix": mix}


def reader(metric: str, root: Path = ROOT):
    """The per-layer metric's reader, ``bench/metrics/<metric>.py``."""
    return load_module(root / "bench" / "metrics" / f"{metric}.py")


def family_module(spec: dict, kind: str):
    """``bench/<kind>/<family>.py`` of the cell's configuration: ``families``
    (the engine), ``references`` (the plain reference) or ``flops``."""
    return load_module(spec["root"] / "bench" / kind
                       / f"{spec['config']['family']}.py")


def load_module(path: Path):
    """Import one file of the benchmark (a metric reader, a family's
    engine, reference or counts) by path."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table[kind]


def federation(config: dict, mix: dict) -> dict:
    fed = dict(config["federation"])
    fed.update(mix["federation"])
    return fed


class Capture:
    """Checkpoint hook: keeps every level's plane at the first ``blocks``
    block boundaries, then detaches from the simulator."""
    resume = False

    def __init__(self, blocks: int):
        self.blocks = blocks
        self.planes = []            # [(rounds completed, {level: plane})]
        self.sim = None

    def due(self, r: int) -> bool:
        return True

    def save(self, r: int, kind: str, meta: dict, arrays: dict) -> str:
        self.planes.append((r, {int(k.split("/")[1]): v
                                for k, v in arrays.items()
                                if k.startswith("plane/")}))
        if len(self.planes) >= self.blocks:
            self.sim.checkpoint = None
        return ""


@dataclass
class Window:
    """Fault hook: records each block boundary, opens the window at
    boundary ``open_at``, and ends the job once ``seconds`` have passed."""
    eng: object
    obs: object
    open_at: int
    seconds: float
    trace_dir: str | None = None
    boundaries: list = field(default_factory=list)   # (rounds, ns, snapshot)
    layouts: list = field(default_factory=list)      # per round, checked ones
    opened: int | None = None
    closed: int | None = None
    mark_ns: int | None = None

    def snapshot(self) -> dict:
        return {"counters": {k: c.value for k, c in
                             self.obs.registry.counters.items()},
                "compiles": sum(self.eng.compile_stats().values())}

    def mid_block(self, r0: int, r1: int) -> None:
        if len(self.boundaries) < CHECK_BLOCKS:
            asg = self.eng.assignment
            snap = {lvl: (list(asg.members.get(lvl, [])),
                          {p: asg.n_eff[p] for p in asg.members.get(lvl, [])})
                    for lvl in range(self.eng.m)}
            self.layouts.extend([snap] * (r1 - r0))

    def round_boundary(self, r: int) -> None:
        t = time.perf_counter_ns()
        k = len(self.boundaries)
        snap = self.snapshot() if k + 1 >= self.open_at else None
        self.boundaries.append((r, t, snap))
        if k + 1 == self.open_at:
            self.opened = k
            if self.trace_dir is not None:
                import jax
                jax.profiler.start_trace(self.trace_dir)
                with jax.profiler.TraceAnnotation("bench_window_start"):
                    self.mark_ns = time.perf_counter_ns()
        elif self.opened is not None and (
                t - self.boundaries[self.opened][1] > self.seconds * 1e9):
            self.closed = k
            if self.trace_dir is not None:
                import jax
                with jax.profiler.TraceAnnotation("bench_window_end"):
                    pass
                jax.profiler.stop_trace()
            raise WindowClosed


def client_steps(row, steps: int) -> int:
    """Local SGD steps that real members trained in one round."""
    n = 0
    for c in row.clusters:
        full = set(c.active) - set(c.masked)
        n += steps * (len(full) + len(c.banked)) + sum(c.masked.values())
    return n


def required_flops(row, model: dict, fed: dict, counts) -> float:
    """Operations one round needs: forward and backward of every sample a
    real member trained on, plus the master's teacher forward on every
    sample of a KD slave.  Padded capacity rows do not count."""
    B, S = fed["local_batch"], fed["steps_per_round"]
    total = 0.0
    for c in row.clusters:
        n = B * (S * (len(set(c.active) - set(c.masked)) + len(c.banked))
                 + sum(c.masked.values()))
        total += n * counts.train_flops(model, c.level)
        if c.level > 0:
            total += n * counts.forward_flops(model, 0)
    return total


@dataclass
class Job:
    """What one job left behind once its window closed."""
    eng: object
    obs: object
    rows: list
    window: Window
    capture: Capture
    shards: list
    peak: int | None


def run_job(spec: dict, seed: int, seconds: float,
            trace_dir: str | None = None) -> Job:
    """Generate the data, build the engine and run the job until the
    window has closed."""
    import jax
    import jax.numpy as jnp
    from bench import generate
    from repro.obs import make_observability
    from repro.sim import HeterogeneitySim, SimConfig
    from repro.sim.traces import Trace

    config, mix = spec["config"], spec["mix"]
    fed = federation(config, mix)
    t0 = time.perf_counter_ns()
    shards, test = generate.federated_data(config, seed)
    t1 = time.perf_counter_ns()
    eng = family_module(spec, "families").build_engine(
        config, fed, seed, shards, mix["rounds_per_dispatch"])
    t2 = time.perf_counter_ns()
    obs = make_observability(fence=False)
    capture = Capture(CHECK_BLOCKS)
    window = Window(eng, obs, CHECK_BLOCKS + LEAD_BLOCKS, seconds,
                    trace_dir)
    # no events: the reference replays none yet (PERF.md, Open questions)
    sim = HeterogeneitySim(eng, Trace(mix["name"], []), SimConfig(
        rounds=MAX_ROUNDS, mar_policy=fed["mar_policy"],
        schedule=fed["schedule"], eval_every=mix["eval_every"]),
        obs=obs, checkpoint=capture, faults=window)
    capture.sim = sim
    try:
        sim.run({"x": jnp.asarray(test["x"]), "y": jnp.asarray(test["y"])})
        raise RuntimeError(f"the job's {MAX_ROUNDS} rounds ended before "
                           "the window closed")
    except WindowClosed:
        pass
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    b = window.boundaries
    print(f"set-up: data {(t1 - t0) / 1e9:.3f} s, engine {(t2 - t1) / 1e9:.3f}"
          f" s, first block {(b[0][1] - t2) / 1e9:.3f} s, next "
          f"{len(b[:window.opened])} blocks {(b[window.opened][1] - b[0][1]) / 1e9:.3f} s",
          file=sys.stderr)
    return Job(eng, obs, sim.report.rows, window, capture, shards, peak)


def program_outputs(job: Job) -> tuple:
    """(outputs of the checked blocks in ``check.numbers``' form, the
    layout of each of their rounds)."""
    import jax
    import jax.numpy as jnp
    from bench import check

    R = job.capture.planes[-1][0]
    rows = job.rows[:R]
    prog = {"losses": [{c.level: c.mean_loss for c in row.clusters}
                       for row in rows],
            "decisions": [{c.level: check.decisions_of(c)
                           for c in row.clusters} for row in rows],
            "params": {r: {lvl: jax.tree.map(
                np.asarray, job.eng.params_of(lvl, jnp.asarray(p)))
                for lvl, p in planes.items()}
                for r, planes in job.capture.planes}}
    return prog, job.window.layouts[:R]


def check_job(job: Job, spec: dict, seed: int) -> dict:
    """Free the program's device state and compare its checked blocks with
    the reference.  Returns the compared numbers."""
    from bench import check

    prog, layouts = program_outputs(job)
    shards = job.shards
    job.eng = job.obs = job.window = job.capture = None
    gc.collect()
    config = spec["config"]
    return check.compare(family_module(spec, "references"), prog,
                         config["model"], federation(config, spec["mix"]),
                         config["participants"]["table_iii"], shards, seed,
                         layouts)


def check_only(spec: dict, seed: int) -> dict:
    """The compared numbers of one seed, with no measured window."""
    return check_job(run_job(spec, seed, 0.0), spec, seed)


def run(spec: dict, seed: int, seconds: float, trace: bool,
        t_start_ns: int) -> dict:
    """One measured run of the cell ``spec``: the result line's fields."""
    import jax

    config, mix = spec["config"], spec["mix"]
    model, fed = config["model"], federation(config, mix)
    counts = family_module(spec, "flops")
    dev = jax.devices()[0]
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        job = run_job(spec, seed, seconds, tmp)
        w, rows, S = job.window, job.rows, fed["steps_per_round"]
        blocks = sorted((e for e in job.obs.tracer.events()
                         if e["name"] == "round_block"),
                        key=lambda e: e["ts"])
        ends_us = [e["ts"] + e["dur"] for e in blocks]
        rounds_at = [b[0] for b in w.boundaries]
        o = w.opened
        inside = [k for k in range(o + 1, len(ends_us))
                  if ends_us[k] - ends_us[o] <= seconds * 1e6]
        last = inside[-1] if inside else o
        win_rows = rows[rounds_at[o]:rounds_at[last]]
        span_s = (ends_us[last] - ends_us[o]) / 1e6
        result = {"attempted": len(win_rows),
                  "failed": sum(1 for row in win_rows if any(
                      c.active and not np.isfinite(c.mean_loss)
                      for c in row.clusters))}
        if trace:
            metrics = per_layer_metrics(spec, job, rounds_at, ends_us, tmp,
                                        model, fed, counts, dev, result)
        else:
            steps = sum(client_steps(row, S) for row in win_rows)
            metrics = {"client_steps_per_s": steps / span_s if span_s
                       else None,
                       "peak_hbm_mb": job.peak / 1e6 if job.peak else None,
                       "setup_s": (w.boundaries[o][1] - t_start_ns) / 1e9}
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": job.peak}
    device.update(result.pop("_trace_device", {}))
    numbers = check_job(job, spec, seed)
    limits = spec["limits"]
    numbers = {k: numbers[k] for k in limits}
    result["correct"] = all(v <= limits[k] for k, v in numbers.items())
    result["metrics"] = {m["name"]: {"value": metrics[m["name"]],
                                     "unit": m["unit"]}
                         for m in (spec["per_layer"] if trace
                                   else spec["end_to_end"])
                         if metrics.get(m["name"]) is not None}
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in numbers.items()}
    return result


def per_layer_metrics(spec, job, rounds_at, ends_us, tmp, model, fed,
                      counts, dev, result) -> dict:
    """Reduce the traced window and ask each per-layer reader for its
    metric; readers that find nothing to read are left out."""
    from bench import tracefile

    window, obs, rows = job.window, job.obs, job.rows
    o, c = window.opened, window.closed
    red = tracefile.reduce_trace(tracefile.find_xplane(tmp))
    win_rows = rows[rounds_at[o]:rounds_at[c]]
    t0_us, t1_us = ends_us[o], ends_us[c]
    # tracer time of the window-start mark, to read host spans on the
    # trace's clock: the mark was taken right after boundary ``o``
    origin_us = ends_us[o] - (window.boundaries[o][1] - window.mark_ns) / 1e3
    spans = [e for e in obs.tracer.events()
             if e["ts"] >= t0_us and e["ts"] + e["dur"] <= t1_us]
    win = SimpleNamespace(
        seconds=red["window_s"], busy_s=red["busy_s"], ops=red["ops"],
        calls=red["calls"],
        rounds=len(win_rows),
        client_steps=sum(client_steps(r, fed["steps_per_round"])
                         for r in win_rows),
        flops=sum(required_flops(r, model, fed, counts) for r in win_rows),
        spans=spans, before=window.boundaries[o][2],
        after=window.boundaries[c][2],
        peaks=peaks_for(dev.device_kind))
    out = {}
    for m in spec["per_layer"]:
        out[m["name"]] = reader(m["name"], spec["root"]).read(win)
    # idle stretches, by the innermost host span of the program around them
    gaps, host = {}, [e for e in obs.tracer.events()
                      if e["ts"] + e["dur"] >= t0_us and e["ts"] <= t1_us]
    for a, b in red["gaps"]:
        if b - a < GAP_NS:
            gaps["gaps under 50 us"] = (gaps.get("gaps under 50 us", 0.0)
                                        + (b - a) / 1e9)
            continue
        mid_us = origin_us + ((a + b) / 2 - red["t0_ns"]) / 1e3
        around = [e for e in host if e["ts"] <= mid_us <= e["ts"] + e["dur"]]
        name = (min(around, key=lambda e: e["dur"])["name"] if around
                else "outside spans")
        gaps[name] = gaps.get(name, 0.0) + (b - a) / 1e9
    result["breakdown"] = {
        "device_ops": [[name[:NAME_CHARS], t] for name, t in sorted(
            red["ops"].items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:10]}
    result["_trace_device"] = {"busy_s": red["busy_s"],
                               "window_s": red["window_s"]}
    return out
