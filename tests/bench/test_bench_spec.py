"""BENCHMARK.json keeps to its contract, every name in it finds its
files, and a configuration, a traffic mix, a cell and a metric added as
new files are found without editing a file that is there. A run that
finds no chip, or no program, exits non-zero and prints no result."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import bench_tiny
from bench.spec import ROOT, Spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_benchmark_json_keeps_its_contract():
    spec = Spec()
    b = spec.bench
    assert set(b) == KEYS
    assert b["command"][1].startswith("bench/")
    assert 1 <= b["run_seconds"] <= 51
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        spec.config(c["name"])  # file found, reduced agrees
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
    cells = [w["name"] for w in b["workloads"]]
    assert len(set(cells)) == len(cells)
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(cells) // 2)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        spec.config(w["config"])
        spec.traffic(w["traffic"])
        assert spec.limits(w["name"])
        assert spec.per_layer(w["name"])
    for m in b["per_layer"]:
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert set(m["workloads"]) <= set(cells)
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           f"{m['name']}.py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_new_files_are_found_without_editing(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = {p: open(os.path.join(root, p), "rb").read()
              for p in ("bench/harness.py", "bench/spec.py")}
    # New files: a configuration, a traffic mix, a cell's limits and a
    # per-layer metric; new entries in BENCHMARK.json.
    bench_tiny.write(root, "bench", "configs", "new-model.json", obj={
        "name": "new-model", "arch": "olmo-1b", "reduced": [],
        "model": {"num_layers": 16}})
    bench_tiny.write(root, "bench", "traffic", "new-mix.json",
                     obj={"seq_len": 4096})
    bench_tiny.write(root, "bench", "limits", "new-model.new-mix.json",
                     obj={"limits": {"loss": 0.5}})
    with open(os.path.join(root, "bench", "metrics",
                           "steps_seen.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx['steps'])\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "new-model", "source": "x",
                         "file": "bench/configs/new-model.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "new-model.new-mix",
                           "config": "new-model", "traffic": "new-mix",
                           "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "steps_seen", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "input pipeline",
                           "moves": "tokens_per_s_per_chip",
                           "workloads": ["new-model.new-mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    spec = Spec(root)
    w = spec.workload("new-model.new-mix")
    assert spec.config(w["config"])["model"] == {"num_layers": 16}
    assert spec.traffic(w["traffic"]) == {"seq_len": 4096}
    assert spec.limits(w["name"]) == {"loss": 0.5}
    assert [m["name"] for m in spec.per_layer(w["name"])] == ["steps_seen"]
    assert spec.reader("steps_seen")({"steps": 8}) == 8.0
    for p, data in before.items():
        assert open(os.path.join(root, p), "rb").read() == data


def _run(cwd, timeout=300):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-0.6b-4L.lazy.1chip", "--seed", str(2 ** 31 + 11),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_no_chip_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert "no result" in proc.stderr
    assert not [x for x in proc.stdout.splitlines() if x.startswith("{")]


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"),
                    os.path.join(tmp_path, "bench"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert not [x for x in proc.stdout.splitlines() if x.startswith("{")]


@pytest.mark.parametrize("argv", [[], ["--workload", "x"],
                                  ["--workload", "x", "--seed", "1"]])
def test_run_needs_its_arguments(argv):
    from bench import harness
    with pytest.raises(SystemExit):
        harness.parse(argv)
