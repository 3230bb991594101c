"""BENCHMARK.json keeps to the benchmark contract's shape, every file
it names exists (and every module and row tree a configuration or a
mix names), and the command refuses to run without a TPU or without
the program beside it."""

import dataclasses
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import benchtiny

ROOT = benchtiny.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/")
    cells = len(bench["workloads"])
    # A full check: 2 + 14 x cells runs, each run_seconds + 60, each
    # cell 2 x 90 to compile, 1200 spare; it must fit with 24 cells.
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert cells <= 24


def test_configs_and_cells(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert c["file"].startswith("benchmark/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        # Every cut from the source is listed in both, by a key of the
        # configuration's file.
        assert c["reduced"] == cfg["reduced"]
        assert set(c["reduced"]) <= set(cfg) and len(c["reduced"]) <= 16
        assert cfg["guarantee"] in ("sequential", "valid")
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert w["config"] in names and 1 <= len(w["why"]) <= 200
        assert (ROOT / "benchmark" / "traffic" / f"{w['traffic']}.json"
                ).is_file()
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == set(names)


def test_configs_name_their_generator_and_reference(bench):
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        for kind in ("generator", "reference"):
            path = cfg[kind]
            assert path.startswith("benchmark/") and path.endswith(".py")
            assert (ROOT / path).is_file(), (c["name"], kind, path)


def test_warm_trees_are_row_trees(bench):
    from tpusched.snapshot import ClusterSnapshot

    # DeviceSnapshot.snap is a ClusterSnapshot; its fields are the row
    # trees the delta apply scatters into.
    trees = {f.name for f in dataclasses.fields(ClusterSnapshot)}
    for traffic in {w["traffic"] for w in bench["workloads"]}:
        mix = json.loads((ROOT / "benchmark" / "traffic" / f"{traffic}.json"
                          ).read_text())
        assert mix["warm_trees"], traffic
        assert set(mix["warm_trees"]) <= trees, traffic


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (ROOT / "benchmark" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        # every cell reports setup_s, another end-to-end metric and a
        # per-layer metric
        mine = [m for m in bench["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def _run(cmd, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    env.update(env_extra or {})
    return subprocess.run(cmd, cwd=cwd, capture_output=True, text=True,
                          timeout=300, env=env)


def test_refuses_without_a_tpu():
    # No JAX in this process is touched: the child finds only the CPU.
    proc = _run([sys.executable, "benchmark/run.py", "--workload",
                 "k8s5k-fast.stream", "--seed", str(benchtiny.SEED),
                 "--seconds", "1", "--trace", "0"], ROOT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_fails_with_only_the_benchmark(tmp_path):
    # A directory with BENCHMARK.json and the benchmark's paths alone:
    # the program is missing, so the run must fail and print nothing.
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run([sys.executable, "benchmark/run.py", "--workload",
                 "k8s5k-fast.stream", "--seed", "1", "--seconds", "1",
                 "--trace", "0"], tmp_path)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
