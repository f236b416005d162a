"""BENCHMARK.json's shape, and the harness finding every cell's files by
name: the configuration, the traffic mix, the limits and each per-layer
metric's reader."""
import json
import re
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
NUMBERS = {"loss_gap", "grad_gap", "change_gap", "grad_diff",
           "change_diff", "mar_mismatch"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p


def test_names_units_and_bounds(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_cell_finds_its_files(bench):
    from bench import harness

    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        spec = harness.load_cell(w["name"])
        assert spec["mix"]["name"] == w["traffic"]
        assert spec["config"]["name"] == w["config"]
        assert set(spec["limits"]) <= NUMBERS
        assert spec["limits"]["mar_mismatch"] == 0
        assert {m["name"] for m in spec["end_to_end"]} >= {
            "setup_s", "client_steps_per_s"}
        assert spec["per_layer"]
        for m in spec["per_layer"]:
            assert callable(harness.reader(m["name"]).read)


def test_config_files_are_under_paths(bench):
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert set(c["reduced"]) <= set(conf["reduced"])


def test_a_new_file_is_found_by_name(tmp_path, bench):
    """A later change adds a mix, a cell and a metric as new files and
    entries: the harness finds them in a copy without any edit."""
    from bench import harness

    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    mix = json.loads((root / "bench" / "traffic" / "fedavg.json").read_text())
    mix["name"] = "fedavg-r2"
    mix["rounds_per_dispatch"] = 2
    (root / "bench" / "traffic" / "fedavg-r2.json").write_text(
        json.dumps(mix))
    (root / "bench" / "limits" / "cnn-mnist-r2.json").write_text(
        (root / "bench" / "limits" / "cnn-mnist-fedavg.json").read_text())
    (root / "bench" / "metrics" / "rounds_per_s.py").write_text(
        "def read(win):\n    return win.rounds / win.seconds\n")
    doc = dict(bench)
    doc["workloads"] = bench["workloads"] + [
        {"name": "cnn-mnist-r2", "config": "fedrac-cnn-mnist",
         "traffic": "fedavg-r2", "chips": 1, "why": "two rounds a block"}]
    doc["per_layer"] = bench["per_layer"] + [
        {"name": "rounds_per_s", "unit": "1/s", "better": "higher",
         "source": "program_span", "layer": "host orchestration",
         "moves": "client_steps_per_s", "workloads": ["cnn-mnist-r2"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    spec = harness.load_cell("cnn-mnist-r2", root)
    assert spec["mix"]["rounds_per_dispatch"] == 2
    assert spec["mix"]["eval_every"] == 0          # the default
    assert [m["name"] for m in spec["per_layer"]][-1] == "rounds_per_s"
    win = type("W", (), {"rounds": 8, "seconds": 2.0})
    assert harness.reader("rounds_per_s", root).read(win) == 4.0
    with pytest.raises(SystemExit):
        harness.load_cell("no-such-cell", root)


def test_a_new_family_is_found_by_name(tmp_path):
    """A configuration of another model family brings its engine, its
    reference and its counts as files named after the family."""
    from bench import harness

    root = tmp_path / "checkout"
    for kind in ("families", "references", "flops"):
        (root / "bench" / kind).mkdir(parents=True)
        (root / "bench" / kind / "toy.py").write_text(
            f"KIND = {kind!r}\n")
    spec = {"root": root, "config": {"family": "toy"}}
    for kind in ("families", "references", "flops"):
        assert harness.family_module(spec, kind).KIND == kind


@pytest.mark.parametrize("mix", sorted(
    p.stem for p in (ROOT / "bench" / "traffic").glob("*.json")))
def test_mix_sets_only_what_the_harness_reads(mix):
    """A traffic mix holds its name, its why and settings that
    ``MIX_DEFAULTS`` names; nothing in it goes unread."""
    from bench import harness

    doc = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json").read_text())
    assert doc["name"] == mix
    assert set(doc) - {"name", "why"} <= set(harness.MIX_DEFAULTS)
