"""Batched serving driver: prefill prompts, then decode with a KV cache.

Fed-RAC flavour: the server holds the α-compressed model FAMILY and routes
each request batch to the model level matching the requester's resource
cluster — the serving-side analogue of §IV-A2 (used by examples/serve_demo).

  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --smoke \
      --batch 4 --prompt-len 32 --gen 32

``--watch-ckpt DIR`` points at a training run's crash-safe checkpoint
directory (``sim_run --ckpt-dir``): between request batches a
``PlaneWatcher`` polls the manifest and hot-reloads the newest *valid*
aggregated ``plane/<level>`` into the serving params — corrupt, partial, or
shape-incompatible checkpoints are skipped with a warning and the previous
plane keeps serving, never a crash.
"""
from __future__ import annotations

import argparse
import json
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.checkpoint import CheckpointError
from repro.ckpt.manifest import CheckpointManager
from repro.configs import get_config, list_archs
from repro.core.plane import make_plane_spec
from repro.core.scaling import compress_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import registry, transformer
from repro.obs import NULL_OBS, make_observability

log = logging.getLogger("repro.serve")


class PlaneWatcher:
    """Mid-training hot-reload of the aggregated model plane.

    Polls a run-state checkpoint directory (written by ``sim_run
    --ckpt-dir``) for steps newer than the one currently serving, walks
    them newest-first, and returns the first ``plane/<level>`` that passes
    manifest CRC + decode + shape validation, adapted into the serving
    params pytree via its ``PlaneSpec``.  Every failure mode — unreadable
    manifest, corrupt or truncated step, missing plane key, plane from a
    different model — logs a warning and keeps the previous params serving.
    """

    def __init__(self, ckpt_dir: str, params_template, level: int = 0,
                 obs=NULL_OBS):
        self.manager = CheckpointManager(ckpt_dir)
        self.spec = make_plane_spec(params_template)
        self.level = int(level)
        self.obs = obs
        self.step = -1     # newest checkpoint step already adapted

    def poll(self, params):
        """(params', reloaded): the newest valid plane newer than
        ``self.step`` adapted into params, or ``params`` unchanged."""
        key = f"plane/{self.level}"
        try:
            fresh = [s for s in self.manager.steps() if s > self.step]
        except Exception as e:
            log.warning("plane watch: manifest unreadable (%s)", e)
            return params, False
        for step in sorted(fresh, reverse=True):
            try:
                _meta, arrays = self.manager.load_step(step)
            except CheckpointError as e:
                log.warning("plane watch: skipping step %d: %s", step, e)
                continue
            plane = arrays.get(key)
            if plane is None:
                log.warning("plane watch: step %d has no %r", step, key)
                continue
            if plane.shape != (self.spec.d_pad,):
                log.warning(
                    "plane watch: step %d %s shape %s != (%d,) — plane is "
                    "from a different model; keeping previous params",
                    step, key, plane.shape, self.spec.d_pad)
                continue
            self.step = step
            if self.obs.on:
                self.obs.registry.counter("serve/plane_reloads").inc()
                self.obs.registry.gauge("serve/plane_step").set(step)
            return self.spec.to_params(jnp.asarray(plane)), True
        return params, False


def prefill_into_cache(cfg, params, tokens, max_len, obs=NULL_OBS):
    """Run the full prompt through decode steps to fill the cache.

    (Production prefill computes the cache in one forward; the step-by-step
    fill here shares the decode program — fine at example scale and exercises
    exactly the serve_step the dry-run lowers.)"""
    B, S = tokens.shape
    cache = registry.init_cache(cfg, B, max_len)
    step = jax.jit(lambda p, c, t, i: registry.decode_step(cfg, p, c, t, i))
    logits = None
    with obs.tracer.span("serve.prefill", cat="serve", batch=B,
                         prompt_len=S):
        for t in range(S):
            logits, cache = step(params, cache, tokens[:, t:t + 1],
                                 jnp.asarray(t))
        obs.tracer.fence(logits)
    if obs.on:
        obs.registry.counter("serve/prefill_tokens").inc(B * S)
    return logits, cache


def generate(cfg, params, prompts, gen_len, obs=NULL_OBS):
    B, S = prompts.shape
    max_len = S + gen_len
    logits, cache = prefill_into_cache(cfg, params, prompts, max_len, obs)
    step = jax.jit(lambda p, c, t, i: registry.decode_step(cfg, p, c, t, i))
    out = []
    vmask = transformer.vocab_mask(cfg)
    tok = jnp.argmax(jnp.where(vmask, logits[:, -1], -jnp.inf), -1)[:, None]
    t0 = time.perf_counter()
    with obs.tracer.span("serve.decode", cat="serve", batch=B,
                         gen_len=gen_len):
        for i in range(gen_len):
            out.append(np.asarray(tok))
            logits, cache = step(params, cache, tok.astype(jnp.int32),
                                 jnp.asarray(S + i))
            tok = jnp.argmax(jnp.where(vmask, logits[:, -1], -jnp.inf),
                             -1)[:, None]
    if obs.on:
        dt = time.perf_counter() - t0
        obs.registry.counter("serve/decode_steps").inc(gen_len)
        obs.registry.counter("serve/generated_tokens").inc(B * gen_len)
        if dt > 0:
            obs.registry.gauge("serve/decode_tok_per_s").set(B * gen_len / dt)
        obs.registry.histogram("serve/decode_step_s").observe(
            dt / max(gen_len, 1))
    return np.concatenate(out, axis=1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--cluster-level", type=int, default=0,
                    help="Fed-RAC cluster level (α-compressed model)")
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-text", action="store_true",
                    help="print a Prometheus-style /metrics text snapshot "
                         "after the run")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the registry snapshot as JSON ('-' for "
                         "stdout)")
    ap.add_argument("--watch-ckpt", default=None, metavar="DIR",
                    help="hot-reload the newest valid aggregated plane from "
                         "this run-state checkpoint dir between request "
                         "batches (sim_run --ckpt-dir)")
    ap.add_argument("--watch-level", type=int, default=0,
                    help="cluster level whose plane/<level> to watch")
    ap.add_argument("--watch-batches", type=int, default=3, metavar="N",
                    help="with --watch-ckpt: serve N request batches, "
                         "polling for a newer plane between each")
    ap.add_argument("--watch-poll-s", type=float, default=0.0, metavar="S",
                    help="sleep between watched batches (poll interval)")
    args = ap.parse_args(argv)
    use_compile_cache()

    obs = (make_observability(trace=False)
           if args.metrics_text or args.metrics_json else NULL_OBS)
    cfg = get_config(args.arch, smoke=args.smoke)
    cfg = compress_config(cfg, args.alpha, args.cluster_level)
    key = jax.random.PRNGKey(args.seed)
    params = registry.init_params(cfg, key)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    watcher = None
    if args.watch_ckpt:
        watcher = PlaneWatcher(args.watch_ckpt, params,
                               level=args.watch_level, obs=obs)
        params, fresh = watcher.poll(params)
        if fresh:
            print(f"# serving plane from checkpoint step {watcher.step}")
    t0 = time.time()
    batches = max(args.watch_batches, 1) if watcher is not None else 1
    for b in range(batches):
        toks = generate(cfg, params, prompts, args.gen, obs=obs)
        if watcher is not None and b + 1 < batches:
            if args.watch_poll_s:
                time.sleep(args.watch_poll_s)
            params, fresh = watcher.poll(params)
            if fresh:
                print(f"# hot-reloaded plane at checkpoint step "
                      f"{watcher.step}")
    dt = time.time() - t0
    if obs.on:
        obs.registry.gauge("serve/wall_clock_s").set(dt)
        obs.registry.counter("serve/requests").inc(args.batch * batches)
    print(f"arch={cfg.name} level={args.cluster_level} "
          f"generated {toks.shape}x{batches} in {dt:.1f}s "
          f"({batches * args.batch * args.gen / dt:.1f} tok/s)")
    print("sample:", toks[0, :16])
    if args.metrics_text:
        print(obs.registry.render_text(), end="")
    if args.metrics_json:
        snap = json.dumps(obs.registry.snapshot(), indent=2)
        if args.metrics_json == "-":
            print(snap)
        else:
            with open(args.metrics_json, "w") as f:
                f.write(snap + "\n")
    return toks


if __name__ == "__main__":
    main()
