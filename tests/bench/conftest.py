"""Fixtures of the benchmark's CPU tests: the repository root on the path,
and a cell cut to a tiny width that XLA:CPU runs in seconds."""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def tiny(config: str, traffic: str) -> dict:
    """A cell of ``config`` under ``traffic`` at 1/16 of the CNN's widths
    on 8x8 images, 1,600 of them over the 40 participants: the same job,
    host logic and programs, at a size for the CPU, held to the limits of
    the benchmark's cell."""
    from bench import harness

    bench = ROOT / "bench"
    spec = harness.load_job(bench / "configs" / f"{config}.json", traffic)
    spec.update(
        limits=json.loads((bench / "limits" / "cnn-mnist-fedavg.json")
                          .read_text()),
        per_layer=[], end_to_end=[
            {"name": "client_steps_per_s", "unit": "steps/s"}])
    c = spec["config"]
    c["model"]["base_width"] = 1 / 16
    c["model"]["input_hw"] = 8
    c["dataset"]["shape"] = [8, 8, c["dataset"]["shape"][2]]
    c["dataset"]["train"] = 1600
    return spec
