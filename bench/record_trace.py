#!/usr/bin/env python3
"""Record the small chip trace that ``tests/bench`` reduces.

    python bench/record_trace.py --out tests/bench/data/window.xplane.pb

On one TPU chip: a few calls of the program's plane aggregation (the Pallas
``fedagg`` kernel) and of one convolution, inside the window marks that
``bench/tracefile.py`` reads, then an idle stretch of about 20 ms (the host
sleeps) and a last kernel call.  Writes the ``.xplane.pb`` and prints each
plane, its lines and the first operation names.
"""
import argparse
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from bench import tracefile
    from repro.core import aggregation

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    agg = jax.jit(lambda x, w: aggregation.aggregate_plane(x, w))
    conv = jax.jit(lambda x, k: jax.lax.conv_general_dilated(
        x, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")))
    x = jnp.ones((8, 1 << 20), jnp.float32)
    w = jnp.full((8,), 0.125, jnp.float32)
    img = jnp.ones((64, 32, 32, 128), jnp.float32)
    ker = jnp.ones((3, 3, 128, 128), jnp.float32)
    jax.block_until_ready((agg(x, w), conv(img, ker)))
    tmp = tempfile.mkdtemp(prefix="bench-record-")
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation(tracefile.WINDOW_START):
            pass
        for _ in range(3):
            jax.block_until_ready((agg(x, w), conv(img, ker)))
        time.sleep(0.02)
        jax.block_until_ready(agg(x, w))
        with jax.profiler.TraceAnnotation(tracefile.WINDOW_END):
            pass
        jax.profiler.stop_trace()
        src = tracefile.find_xplane(tmp)
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(src, args.out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    for plane in ProfileData.from_file(args.out).planes:
        print("plane", plane.name)
        for line in plane.lines:
            names = sorted({e.name for e in line.events})
            print("  line", repr(line.name), len(names), names[:12])
    print(tracefile.reduce_trace(args.out) | {"gaps": "..."})


if __name__ == "__main__":
    main()
