#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python bench/calibrate.py --workload cnn-mnist-fedavg --seeds 1 2 3 \\
        --controls 3 --out chiprun_out/calibrate.jsonl
    python bench/calibrate.py --job fedrac-cnn-cifar10 stable --seeds 4 \\
        --controls 0

For every seed, in one process: the program's checked blocks against the
float32 reference (the lower readings).  For the first ``--controls``
seeds also the control, the reference put in the program's place in
bfloat16 (the precision below the configuration's float32), and two
faults planted in the reference put in its place (half of each batch left
out; a step that leaves the weights unchanged), each against the same
reference (the upper readings).  Each line also holds every round's mean
loss per level of the program and of the reference.

``--precision highest`` runs the program's own products at
``Precision.HIGHEST`` instead of the configuration's default: a second
witness where the program and the reference part.  One JSON line per
seed.  The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(spec: dict, seed: int, controls: bool) -> dict:
    from bench import check, harness

    t0 = time.perf_counter()
    job = harness.run_job(spec, seed, 0.0)
    prog, layouts = harness.program_outputs(job)
    shards = job.shards
    del job
    gc.collect()
    config = spec["config"]
    reference = harness.family_module(spec, "references")
    args = (reference, config["model"],
            harness.federation(config, spec["mix"]),
            config["participants"]["table_iii"], shards, seed, layouts)
    ref = check.replay(*args, check.REFERENCE)
    init = check.initial(reference, config["model"], ref["params"][0].keys(),
                         seed)
    ref_args = (ref["losses"], ref["decisions"], ref["params"], init)
    out = {"seed": seed, "program": check.numbers(prog, *ref_args),
           "layout": {lvl: len(m) for lvl, (m, _) in layouts[0].items()},
           "program_losses": prog["losses"],
           "reference_losses": ref["losses"]}
    if controls:
        ends = sorted(prog["params"])
        for name, num in (("control", check.CONTROL),
                          ("fault_half_batch", check.FAULT_HALF_BATCH),
                          ("fault_stuck", check.FAULT_STUCK)):
            got = check.as_program(check.replay(*args, num), ends)
            out[name] = check.numbers(got, *ref_args)
    out["seconds"] = time.perf_counter() - t0
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", help="a cell of BENCHMARK.json")
    ap.add_argument("--job", nargs=2, metavar=("CONFIG", "TRAFFIC"),
                    help="a configuration and mix under bench/, no cell")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--precision", choices=("default", "highest"),
                    default="default")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    spec = (harness.load_cell(args.workload) if args.workload else
            harness.load_job(ROOT / "bench" / "configs"
                             / f"{args.job[0]}.json", args.job[1]))
    import jax

    if jax.devices()[0].platform == "cpu":
        sys.exit("calibrate: the readings are taken on the chip")
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.precision == "highest":
        jax.config.update("jax_default_matmul_precision", "highest")
    for i, seed in enumerate(args.seeds):
        line = json.dumps({"precision": args.precision,
                           **readings(spec, seed, i < args.controls)})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
