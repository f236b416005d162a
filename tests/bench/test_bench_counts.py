"""The benchmark's operation and byte counts against hand counts."""
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def counts():
    from bench import harness
    return harness.load_module(ROOT / "bench" / "flops" / "cnn.py")


def model(name):
    path = ROOT / "bench" / "configs" / f"{name}.json"
    return json.loads(path.read_text())["model"]


@pytest.mark.parametrize("config,level,mflop", [
    # 32x32x3: 1024*(3*128 + 128*64)*18, 256*(64*128 + 128*256)*18,
    # 64*256*512*18, plus the 512x10 head
    ("fedrac-cnn-cifar10", 0, 497.821696),
    ("fedrac-cnn-cifar10", 1, 126.227456),
    ("fedrac-cnn-cifar10", 2, 32.44288),
    # 28x28x1: 784*(1*128 + 128*64)*18, 196*(64*128 + 128*256)*18,
    # 49*256*512*18, plus the head
    ("fedrac-cnn-mnist", 0, 377.534464),
])
def test_forward_flops(config, level, mflop):
    c = counts()
    assert c.forward_flops(model(config), level) / 1e6 == pytest.approx(
        mflop, rel=1e-12)
    assert c.train_flops(model(config), level) == pytest.approx(
        3 * mflop * 1e6, rel=1e-12)


@pytest.mark.parametrize("level,params", [
    (0, 1_631_690), (1, 410_346), (2, 103_802)])
def test_params(level, params):
    m = model("fedrac-cnn-cifar10")
    assert counts().param_count(m, level) == params
    assert json.loads((ROOT / "bench" / "configs" /
                       "fedrac-cnn-cifar10.json").read_text()
                      )["model"]["params_per_level"][level] == params


def test_reference_prices_the_programs_flops():
    """Eq. 2 in the reference prices the convolutions only, as the CNN
    family's ``flops_per_sample`` does."""
    from bench import harness
    from repro.core.families import cnn_family

    reference = harness.load_module(ROOT / "bench" / "references" / "cnn.py")
    m = model("fedrac-cnn-cifar10")
    fam = cnn_family(classes=10, in_channels=3, alpha=0.5, base_width=1.0,
                     input_hw=32)
    budget = reference.MarBudget(m, {"epochs": 2, "kappa": 0.7},
                                 [[1.0, 1.0, 1.0]], [100], 3)
    for level in range(3):
        assert budget.flops[level] == pytest.approx(
            fam.flops_per_sample(level), rel=1e-12)
