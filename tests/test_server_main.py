"""The sidecar's process entry, ``python -m tpusched.rpc.server``: the
flags ``main`` parses, and a real subprocess sidecar booted from a
config file that serves an Assign and writes its audit log."""

import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import pytest

from tpusched.config import Buckets, EngineConfig, load_config
from tpusched.oracle import Oracle
from tpusched.rpc import SchedulerClient, snapshot_from_proto, snapshot_to_proto
from tpusched.rpc import server as server_mod

REPO = Path(__file__).resolve().parents[1]
BOOT_S = 180.0


@pytest.fixture
def served(monkeypatch):
    """Replace serve() so main() returns after parsing; records the
    keyword arguments it would have served with."""
    calls = []

    def fake_serve(address, config, **kw):
        calls.append(dict(address=address, config=config, **kw))

    monkeypatch.setattr(server_mod, "serve", fake_serve)
    return calls


def test_flags_default_to_serve_defaults(served):
    server_mod.main([])
    assert served == [dict(
        address="127.0.0.1:50051", config=None, audit_path=None,
        watchdog_s=server_mod.WATCHDOG_S, explain=False, ledger_jsonl=None,
        buckets=None, prewarm=False)]


def test_every_flag_reaches_serve(served, tmp_path):
    cfg_path = tmp_path / "profile.yaml"
    cfg_path.write_text("mode: fast\n")
    server_mod.main([
        "--address", "0.0.0.0:7001", "--config", str(cfg_path),
        "--audit", "a.jsonl", "--watchdog-s", "7.5", "--explain",
        "--ledger-jsonl", "l.jsonl", "--buckets", "64X32"])
    assert served == [dict(
        address="0.0.0.0:7001", config=EngineConfig(mode="fast"),
        audit_path="a.jsonl", watchdog_s=7.5, explain=True,
        ledger_jsonl="l.jsonl", buckets=Buckets.fit(64, 32),
        prewarm=False)]


@pytest.mark.parametrize("spec, dims", [
    ("256x64", (256, 64)),
    ("256x64x512", (256, 64, 512)),
])
def test_buckets_flag_accepts_two_or_three_dims(served, spec, dims):
    server_mod.main(["--buckets", spec, "--prewarm"])
    assert len(served) == 1
    assert served[0]["buckets"] == Buckets.fit(*dims)
    assert served[0]["prewarm"] is True


@pytest.mark.parametrize("spec", ["256", "256x64x512x9", "x64"])
def test_buckets_flag_rejects_malformed(served, spec, capsys):
    with pytest.raises(SystemExit) as exc:
        server_mod.main(["--buckets", spec])
    assert exc.value.code == 2
    assert "--buckets" in capsys.readouterr().err
    assert served == []


def test_prewarm_without_buckets_is_refused(served, capsys):
    with pytest.raises(SystemExit) as exc:
        server_mod.main(["--prewarm"])
    assert exc.value.code == 2
    assert "--prewarm needs --buckets" in capsys.readouterr().err
    assert served == []


def _snapshot():
    """Three nodes. p0 may land on n0 or n1: n0's running pod leaves it
    the better-balanced node, n1 the less requested one, so
    BalancedAllocation alone picks n0 while the default profile picks
    n1. p1 is pinned to n2; p2 fits nowhere."""
    alloc = {"cpu": 4000.0, "memory": float(16 << 30)}
    pools = ["a", "a", "b"]
    nodes = [dict(name=f"n{i}", allocatable=dict(alloc),
                  labels={"pool": pools[i], "slot": str(i)})
             for i in range(3)]
    pods = [
        dict(name="p0", requests={"cpu": 1000.0, "memory": float(1 << 30)},
             node_selector={"pool": "a"}),
        dict(name="p1", requests={"cpu": 500.0, "memory": float(1 << 30)},
             node_selector={"slot": "2"}),
        dict(name="p2", requests={"cpu": 64000.0, "memory": float(1 << 30)}),
    ]
    running = [dict(name="r0", node="n0",
                    requests={"cpu": 2000.0, "memory": float(10 << 30)})]
    return snapshot_to_proto(nodes, pods, running)


def _oracle_placements(msg, cfg):
    snap, meta = snapshot_from_proto(msg, cfg)
    a = Oracle(snap, cfg).solve().assignment
    return {name: (meta.node_names[a[i]] if a[i] >= 0 else "")
            for i, name in enumerate(meta.pod_names)}


def _wait_port(proc, log: Path) -> int:
    deadline = time.monotonic() + BOOT_S
    while time.monotonic() < deadline:
        m = re.search(r"listening on port (\d+)", log.read_text())
        if m:
            return int(m.group(1))
        if proc.poll() is not None:
            raise AssertionError(
                f"sidecar exited {proc.returncode}:\n{log.read_text()}")
        time.sleep(0.2)
    raise AssertionError(f"sidecar did not boot in {BOOT_S}s:\n"
                         f"{log.read_text()}")


@pytest.mark.parametrize("mode", ["parity", "fast"])
def test_subprocess_sidecar_serves_config_and_audits(tmp_path, mode):
    cfg_path = tmp_path / "profile.yaml"
    cfg_path.write_text(f"mode: {mode}\nweights:\n  least_requested: 0.0\n")
    cfg = load_config(str(cfg_path))
    msg = _snapshot()
    want = _oracle_placements(msg, cfg)
    assert want == {"p0": "n0", "p1": "n2", "p2": ""}
    # The default profile places p0 elsewhere, so a sidecar that ignored
    # --config could not pass.
    assert _oracle_placements(msg, EngineConfig(mode=mode))["p0"] != "n0"

    audit = tmp_path / "audit.jsonl"
    log = tmp_path / "sidecar.log"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with open(log, "w") as err:
        proc = subprocess.Popen(
            [sys.executable, "-m", "tpusched.rpc.server",
             "--address", "127.0.0.1:0", "--config", str(cfg_path),
             "--audit", str(audit)],
            cwd=REPO, env=env, stdout=subprocess.DEVNULL, stderr=err)
    try:
        port = _wait_port(proc, log)
        with SchedulerClient(f"127.0.0.1:{port}", timeout=BOOT_S) as client:
            resp = client.assign(msg)
        got = {a.pod: a.node for a in resp.assignments}
        assert got == want
        records = [json.loads(line)
                   for line in audit.read_text().splitlines()]
        assert sorted(r["pod"] for r in records) == ["p0", "p1", "p2"]
        assert all(r["kind"] == "placement" for r in records)
        assert {r["pod"]: r["node"] or "" for r in records} == want
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
