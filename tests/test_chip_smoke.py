"""chip_smoke.py's phases at a tiny shape on the CPU, so the script the
chip check runs keeps working between chip runs. The script itself
refuses to run without a TPU; these call its phases directly."""

import json
import pathlib
import subprocess
import sys

import pytest

pytest.importorskip("grpc")

import chip_smoke  # noqa: E402  (the repo root is on sys.path: conftest)

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_one_chip_phases_tiny(capsys):
    chip_smoke.one_chip(0, "cpu", n_pods=96, n_nodes=24, deltas=3,
                        oracle_shape=(20, 6))
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["phase"] for ln in lines] == [
        "serve", "serve", "block_until_ready", "memory", "oracle"]


def test_four_chip_phase_tiny(capsys):
    chip_smoke.four_chips(0, "cpu", n_pods=64, n_nodes=16, deltas=1,
                          pairwise=(48, 12))
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["identical"] for ln in lines] == [True] * (
        len(chip_smoke.MESHES) + 1)


def test_refuses_without_a_tpu():
    # No JAX in this process is touched: the child finds only the CPU.
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          capture_output=True, text=True, timeout=120,
                          cwd=REPO)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
