#!/usr/bin/env python
"""The pre-PR gate: one entry point for every static check (round 15).

Chains, in order:

  regen    tools/regen_pb2.py --check   (generated pb2 in sync with
           the descriptor splice recipe)
  lint     tools/lint.py over tpusched/ tools/ tests/
           (the tpuschedlint invariant suite, empty baseline)
  lockgraph  tools/lint.py --check-hierarchy: the checked-in
           tools/lock_hierarchy.json matches a fresh regeneration
           (line drift blinds the runtime lock-order witness) and the
           static lock order is acyclic
  jitlint  tools/lint.py --jit-report: every jax.jit/_traced_jit site
           enumerated; fails on any unbounded jit family (compile-
           cache treadmill — ROADMAP item 4's anomaly source)
  kernelflow  tools/lint.py --check-ledger: the checked-in
           tools/reduction_ledger.json (every cross-pod/cross-node
           reduction site with its exactness class, padding verdict,
           and sharding-safety note) matches a fresh regeneration and
           every hazard site is fixed or reasoned-suppressed
  shardcheck  tools/shardcheck.py: the implemented sharding vs the
           ledger's SHARDING verdicts (round 22) — every verdict
           string routes to an implemented combine tree, decision-path
           keyed-merge/mask-cover/width-pad sites are reached by
           padcheck's mesh differential, decision-path order-sensitive
           sites carry reasoned suppressions, and the shardctx
           constraint pins are still in place; fails on any mismatch
           or a stale verdict
  padcheck  tools/padcheck.py: differentially execute the ledger
           sites' enclosing kernels at two bucket widths — an
           exact-marked site that diverges bitwise fails, the seeded
           hazardous fixture must be caught, and the mesh differential
           (a forced-2-device subprocess) re-runs the ledger-covered
           kernels on the (2,1)/(1,2) meshes demanding bitwise parity
           with dense; SKIPPED gracefully when jax is not installed
           (like warmaudit)
  syntax   byte-compile every tracked .py (pyflakes when the image
           has it; stdlib compile() otherwise — this image must not
           grow dependencies)
  mypy     mypy --strict over the typed beachhead (mypy.ini scopes
           it: config.py, qos.py, metrics.py, ledger.py, trace.py,
           tpusched/lint/, kernels/filter.py, kernels/score.py,
           oracle.py); SKIPPED gracefully when mypy is not installed
  warmaudit  fast `divergence --warm-audit 5` smoke at a tiny shape,
           BOTH modes sharing one engine: the PR 10 bitwise warm
           contract (warm == cold byte-identical) and the ISSUE 12
           incremental validity contract (in-kernel audit + oracle
           clean) stay gated pre-PR; SKIPPED gracefully when jax is
           not installed
  statusz  boot a real sidecar, serve one Assign cycle, scrape the
           Statusz rpc + the Metrics render, and validate the
           CycleRecord schema (tpusched.ledger.validate_record) and
           the exposition format — the round-18 flight-ledger surface
           stays wired end to end; SKIPPED when jax/grpc are absent

Prints a per-stage summary and exits non-zero if any stage fails.
Documented in tools/README.md as the thing to run before mailing a PR.
"""

from __future__ import annotations


import subprocess
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
LINT_PATHS = ("tpusched", "tools", "tests")
SYNTAX_ROOTS = ("tpusched", "tools", "tests")
MYPY_TARGETS = ("tpusched/config.py", "tpusched/qos.py",
                "tpusched/metrics.py", "tpusched/ledger.py",
                "tpusched/trace.py", "tpusched/wire.py",
                "tpusched/lint",
                "tpusched/kernels/filter.py",
                "tpusched/kernels/score.py", "tpusched/oracle.py")


def _run(cmd: "list[str]") -> "tuple[int, str]":
    proc = subprocess.run(
        cmd, cwd=REPO_ROOT, capture_output=True, text=True
    )
    return proc.returncode, (proc.stdout + proc.stderr).strip()


def stage_regen() -> "tuple[str, str]":
    rc, out = _run([sys.executable, "tools/regen_pb2.py", "--check"])
    return ("ok" if rc == 0 else "FAIL"), out


def stage_lint() -> "tuple[str, str]":
    rc, out = _run([sys.executable, "tools/lint.py", *LINT_PATHS])
    return ("ok" if rc == 0 else "FAIL"), out


def stage_lockgraph() -> "tuple[str, str]":
    rc, out = _run([sys.executable, "tools/lint.py", "--check-hierarchy"])
    return ("ok" if rc == 0 else "FAIL"), out


def stage_jitlint() -> "tuple[str, str]":
    rc, out = _run([sys.executable, "tools/lint.py", "--jit-report"])
    return ("ok" if rc == 0 else "FAIL"), out


def stage_kernelflow() -> "tuple[str, str]":
    rc, out = _run([sys.executable, "tools/lint.py", "--check-ledger"])
    return ("ok" if rc == 0 else "FAIL"), out


def stage_shardcheck() -> "tuple[str, str]":
    rc, out = _run([sys.executable, "tools/shardcheck.py"])
    return ("ok" if rc == 0 else "FAIL"), out


def stage_padcheck() -> "tuple[str, str]":
    try:
        import jax  # noqa: F401
    except ImportError:
        return "skip", "jax not installed on this image"
    rc, out = _run([sys.executable, "tools/padcheck.py"])
    return ("ok" if rc == 0 else "FAIL"), out


def _py_files() -> "list[Path]":
    out = []
    for root in SYNTAX_ROOTS:
        p = REPO_ROOT / root
        if p.is_dir():
            out.extend(sorted(p.rglob("*.py")))
    return out


def stage_syntax() -> "tuple[str, str]":
    """pyflakes when available, else a stdlib byte-compile pass (catches
    syntax errors; pyflakes additionally catches undefined names)."""
    files = _py_files()
    try:
        import pyflakes  # noqa: F401
    except ImportError:
        errors = []
        for f in files:
            try:
                compile(f.read_text(), str(f), "exec")
            except SyntaxError as e:
                errors.append(f"{f}:{e.lineno}: {e.msg}")
        tag = f"compiled {len(files)} files (pyflakes unavailable)"
        if errors:
            return "FAIL", "\n".join(errors)
        return "ok", tag
    rc, out = _run([sys.executable, "-m", "pyflakes",
                    *[str(f) for f in files]])
    return ("ok" if rc == 0 else "FAIL"), out or f"pyflakes over {len(files)} files"


def stage_mypy() -> "tuple[str, str]":
    try:
        import mypy  # noqa: F401
    except ImportError:
        return "skip", "mypy not installed on this image"
    rc, out = _run([sys.executable, "-m", "mypy",
                    "--config-file", "mypy.ini", *MYPY_TARGETS])
    return ("ok" if rc == 0 else "FAIL"), out


_WARMAUDIT_CODE = """
import json, os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from tpusched.config import EngineConfig
from tpusched.divergence import warm_audit
from tpusched.engine import Engine
eng = Engine(EngineConfig(mode="fast"))
try:
    kw = dict(cycles=5, preset="plain", n_pods=16, n_nodes=5,
              churn_frac=0.2, engine=eng)
    a = warm_audit(**kw)
    b = warm_audit(incremental=True, **kw)
finally:
    eng.close()
print(json.dumps(dict(bitwise_diverged=a["diverged_cycle"],
                      inc_diverged=b["diverged_cycle"],
                      inc_validity=b["validity_violations"],
                      inc_solves=b["incremental_solves"])))
bad = (a["diverged_cycle"] >= 0 or b["diverged_cycle"] >= 0
       or b["validity_violations"] or b["incremental_solves"] < 3)
raise SystemExit(1 if bad else 0)
"""


def stage_warmaudit() -> "tuple[str, str]":
    try:
        import jax  # noqa: F401
    except ImportError:
        return "skip", "jax not installed on this image"
    rc, out = _run([sys.executable, "-c", _WARMAUDIT_CODE])
    return ("ok" if rc == 0 else "FAIL"), out


_STATUSZ_CODE = """
import json, os, re
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from tpusched import ledger as ledgering
from tpusched.config import EngineConfig
from tpusched.rpc.client import SchedulerClient
from tpusched.rpc.codec import snapshot_to_proto
from tpusched.rpc.server import make_server

server, port, svc = make_server("127.0.0.1:0",
                                config=EngineConfig(mode="fast"))
server.start()
try:
    with SchedulerClient(f"127.0.0.1:{port}") as client:
        msg = snapshot_to_proto(
            [dict(name="n0", allocatable={"cpu": 4000.0,
                                          "memory": float(16 << 30)})],
            [dict(name="p0", requests={"cpu": 500.0,
                                       "memory": float(1 << 30)})],
            [],
        )
        client.assign(msg, packed_ok=True)
        sz = json.loads(client.statusz().statusz_json)
        metrics_text = client.metrics_text()
finally:
    server.stop(0)
    svc.close()
assert sz["records"], "sidecar served a cycle but the ledger is empty"
for rec in sz["records"]:
    ledgering.validate_record(rec)
assert sz["cycles"] >= 1 and sz["warm_mix"], sz
# Exposition-format smoke (the strict checker lives in tests/): every
# line is a TYPE/HELP comment or a sample, and the ledger families
# render in THIS server's registry.
assert metrics_text.endswith("\\n")
sample = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\\{[^{}]*\\})? [^ ]+$")
for line in metrics_text.splitlines():
    assert line and line.strip() == line, repr(line)
    if line.startswith("#"):
        assert line.startswith(("# TYPE ", "# HELP ")), repr(line)
    else:
        assert sample.match(line), repr(line)
assert "# TYPE scheduler_cycle_anomalies_total counter" in metrics_text
assert "# TYPE scheduler_cycle_solve_seconds histogram" in metrics_text
print(json.dumps(dict(records=len(sz["records"]), cycles=sz["cycles"],
                      compiles=sz["compiles"]["total"])))
"""


def stage_statusz() -> "tuple[str, str]":
    try:
        import grpc  # noqa: F401
        import jax  # noqa: F401
    except ImportError:
        return "skip", "jax/grpc not installed on this image"
    rc, out = _run([sys.executable, "-c", _STATUSZ_CODE])
    return ("ok" if rc == 0 else "FAIL"), out


_WIREZ_CODE = """
import json, os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from tpusched import wire as wiring
from tpusched.config import EngineConfig
from tpusched.rpc.client import SchedulerClient
from tpusched.rpc.codec import snapshot_to_proto
from tpusched.rpc.server import make_server

server, port, svc = make_server("127.0.0.1:0",
                                config=EngineConfig(mode="fast"))
server.start()
try:
    # wire=svc.wire: the client assembles each cycle's WireRecord into
    # the SERVER's ledger, so the Statusz wire panel below is fed.
    with SchedulerClient(f"127.0.0.1:{port}", wire=svc.wire) as client:
        msg = snapshot_to_proto(
            [dict(name="n0", allocatable={"cpu": 4000.0,
                                          "memory": float(16 << 30)})],
            [dict(name="p0", requests={"cpu": 500.0,
                                       "memory": float(1 << 30)})],
            [],
        )
        client.assign(msg, packed_ok=True)
        sz = json.loads(client.statusz().statusz_json)
        metrics_text = client.metrics_text()
finally:
    server.stop(0)
    svc.close()
assert client.wire_errors == 0, client.wire_errors
panel = sz.get("wire")
assert panel, "Statusz payload has no wire panel"
assert panel["cycles"] >= 1, panel
recs = panel["records"]
assert recs, "wire ledger observed no cycle"
for rec in recs:
    wiring.validate_record(rec)
    assert rec["rpc"] == "Assign" and rec["stitched"], rec
    assert rec["bytes_up"] > 0 and rec["bytes_down"] > 0, rec
assert panel["wall"]["p50_ms"] is not None, panel["wall"]
# Exposition smoke: the wire families render in THIS server's registry
# (the strict format checker lives in tests/).
assert "# TYPE scheduler_wire_wall_seconds histogram" in metrics_text
assert "# TYPE scheduler_wire_bytes counter" in metrics_text
assert ('scheduler_wire_bytes{direction="up",rpc="Assign"}'
        in metrics_text)
assert ('scheduler_wire_cycles_total{rpc="Assign",source="call"}'
        in metrics_text)
print(json.dumps(dict(cycles=panel["cycles"],
                      coverage=panel["coverage_frac"],
                      offset_ms=panel["offset_ms"])))
"""


def stage_wirez() -> "tuple[str, str]":
    try:
        import grpc  # noqa: F401
        import jax  # noqa: F401
    except ImportError:
        return "skip", "jax/grpc not installed on this image"
    rc, out = _run([sys.executable, "-c", _WIREZ_CODE])
    return ("ok" if rc == 0 else "FAIL"), out


_PREWARM_CODE = """
import ast, json, os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import tpusched
from tpusched import shapeclass
from tpusched.config import Buckets, EngineConfig

bk = Buckets.fit(16, 8, n_running=16)
reg = shapeclass.build_registry(EngineConfig(mode="fast"), bk,
                                explain=True, explain_k=3,
                                warm="incremental")
# 1) The registry survives its wire format exactly (a standby rebuilds
# its leader's class set from this JSON).
back = shapeclass.ShapeClassRegistry.from_json(reg.to_json())
assert back == reg, "registry JSON round-trip drifted"
assert back.to_json() == reg.to_json()
# 2) Cross-check against engine.py's ACTUAL jit families: every
# Engine._traced_jit call site names its family with a constant (or a
# constant-prefixed f-string, which TPL104 proves is bucket-bounded).
# The registry must stay inside that set, and must cover all of it
# except the eager "solve" no serving path dispatches.
path = os.path.join(os.path.dirname(tpusched.__file__), "engine.py")
names = []
for node in ast.walk(ast.parse(open(path).read())):
    if (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "_traced_jit" and node.args):
        a = node.args[0]
        if isinstance(a, ast.Constant):
            names.append((a.value, False))
        elif isinstance(a, ast.JoinedStr):
            assert a.values and isinstance(a.values[0], ast.Constant), (
                "f-string jit family without a constant prefix: "
                + ast.dump(a))
            names.append((a.values[0].value, True))
assert names, "no _traced_jit call sites found in engine.py"
fams = set(reg.families())
bad = [f for f in fams
       if not any(f.startswith(n) if pre else f == n
                  for n, pre in names)]
assert not bad, f"registry families unknown to engine.py: {bad}"
missing = [n for n, pre in names if n != "solve"
           and not (any(f.startswith(n) for f in fams) if pre
                    else n in fams)]
assert not missing, (
    f"engine jit families missing from the registry: {missing}")
print(json.dumps(dict(classes=len(reg), families=sorted(fams),
                      engine_sites=len(names))))
"""


def stage_prewarm() -> "tuple[str, str]":
    # shapeclass itself is stdlib-only, but reaching it goes through
    # the tpusched package import (flax/jax) — gate like warmaudit.
    try:
        import jax  # noqa: F401
    except ImportError:
        return "skip", "jax not installed on this image"
    rc, out = _run([sys.executable, "-c", _PREWARM_CODE])
    return ("ok" if rc == 0 else "FAIL"), out


_INGEST_CODE = """
import json, os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import grpc
from tpusched.rpc.client import SchedulerClient
from tpusched.rpc.server import make_server

# A tiny bounded gate: burst 2 admits two pods, the rest of the batch
# sheds; bound 4 keeps the queue capacity-shed path reachable too.
server, port, svc = make_server(
    "127.0.0.1:0",
    ingest=dict(capacity=8, bound=4, rate=0.5, burst=2.0))
server.start()
try:
    with SchedulerClient(f"127.0.0.1:{port}", timeout=5.0) as client:
        pods = [dict(name=f"p{i}", priority=float(i)) for i in range(5)]
        resp = client.enqueue(pods, tenant=0)
        assert resp.admitted >= 1 and resp.shed >= 1, resp
        assert resp.retry_after_s > 0, resp
        assert set(resp.shed_pods).isdisjoint({"p0", "p1"}), resp
        # A fully shed batch surfaces as RESOURCE_EXHAUSTED once the
        # client's own retry budget (which re-drives it) is exhausted —
        # the refill rate (one token per 2s) outlasts the 0.2s budget.
        client2 = SchedulerClient(f"127.0.0.1:{port}", timeout=0.2)
        try:
            client2.enqueue([dict(name="q0"), dict(name="q1")])
            code = None
        except grpc.RpcError as e:
            code = e.code()
        finally:
            client2.close()
        assert code == grpc.StatusCode.RESOURCE_EXHAUSTED, code
        sz = json.loads(client.statusz().statusz_json)
        metrics_text = client.metrics_text()
finally:
    server.stop(0)
    svc.close()
panel = sz.get("ingest")
assert panel and panel["admitted"] >= 1 and panel["shed_rate"] >= 1, panel
assert panel["queue_bound"] == 4, panel
assert "# TYPE scheduler_ingest_queue_depth gauge" in metrics_text
assert "# TYPE scheduler_ingest_shed_frac gauge" in metrics_text
assert 'scheduler_ingest_pods_total{outcome="admitted"}' in metrics_text
print(json.dumps(dict(admitted=panel["admitted"],
                      shed=panel["shed_rate"] + panel["shed_capacity"],
                      depth=panel["queue_depth"])))
"""


def stage_ingest() -> "tuple[str, str]":
    try:
        import grpc  # noqa: F401
        import jax  # noqa: F401
    except ImportError:
        return "skip", "jax/grpc not installed on this image"
    rc, out = _run([sys.executable, "-c", _INGEST_CODE])
    return ("ok" if rc == 0 else "FAIL"), out


STAGES = (
    ("regen", stage_regen),
    ("lint", stage_lint),
    ("lockgraph", stage_lockgraph),
    ("jitlint", stage_jitlint),
    ("kernelflow", stage_kernelflow),
    ("shardcheck", stage_shardcheck),
    ("syntax", stage_syntax),
    ("mypy", stage_mypy),
    ("warmaudit", stage_warmaudit),
    ("padcheck", stage_padcheck),
    ("statusz", stage_statusz),
    ("wirez", stage_wirez),
    ("prewarm", stage_prewarm),
    ("ingest", stage_ingest),
)


def main() -> int:
    results = []
    for name, fn in STAGES:
        try:
            status, detail = fn()
        except Exception as e:  # a broken checker must not pass silently
            status, detail = "FAIL", f"stage crashed: {e!r}"
        results.append((name, status, detail))
        marker = {"ok": "+", "skip": "~", "FAIL": "!"}[status]
        print(f"[{marker}] {name:<9} {status}")
        if status == "FAIL" and detail:
            print("\n".join(f"      {ln}" for ln in detail.splitlines()[:40]))
        elif detail and status != "ok":
            print(f"      {detail.splitlines()[0]}")
    failed = [n for n, s, _ in results if s == "FAIL"]
    print("check:", "FAILED " + ", ".join(failed) if failed else
          "all stages passed"
          + (" (mypy skipped)" if any(s == "skip" for _, s, _ in results)
             else ""))
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
