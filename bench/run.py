#!/usr/bin/env python3
"""The chip benchmark of Fed-RAC: one run of one cell.

    python bench/run.py --workload cnn-mnist-fedavg --seed 7 --seconds 10 --trace 0

It reads the cell from ``BENCHMARK.json``, exits non-zero without printing a
result when JAX finds no accelerator or fewer chips than the cell asks for,
then sets up, measures for ``--seconds`` and checks the checked blocks
against the plain reference (``bench/harness.py``).  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each compared number beside its limit.  The same numbers are
the last lines of standard error.

Compiled programs go to the program's persistent compilation cache
(``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache`` in the checkout), so
every run after a checkout's first reads them from there.
"""
import time

T_START_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    spec = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    chips = spec["cell"]["chips"]
    if devices[0].platform == "cpu" or len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} accelerator chip(s); JAX "
                 f"finds {len(devices)} {devices[0].platform} device(s)")
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    res = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                      T_START_NS)
    line = {k: res[k] for k in ("correct", "attempted", "failed", "metrics",
                                "device", "breakdown", "checks") if k in res}
    for name, c in res["checks"].items():
        print(f"check {name}: {float(c['value'])!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
