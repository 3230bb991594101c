"""Test env: CPU backend with 8 virtual devices (SURVEY.md §4 item 3),
so mesh/sharding tests run without TPU hardware and kernel tests are
deterministic and fast. Must run before jax initializes a backend."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Runtime lock-order witness (round 19, ISSUE 14): installed BEFORE
# any product module is imported so every hierarchy lock's creation
# site — including module-level locks created at import — goes through
# the witness factory. witness.py is loaded STANDALONE (spec, not
# `from tpusched.lint import ...`): importing the package would pull
# tpusched/__init__.py's whole product-module closure first and any
# module-level lock in it would be created raw, silently invisible to
# the witness. The module is registered in sys.modules under its real
# name so later package imports (tests, tools) get THIS instance and
# see the active witness. Locks whose creation site is not in
# tools/lock_hierarchy.json (stdlib, grpc, jax, tests) come out as raw
# _thread locks — zero overhead. The session fixture below asserts the
# model held: zero observed order inversions across the whole tier-1
# run (the static hierarchy is validated against reality, not trusted).
import importlib.util
import pathlib
import sys as _sys

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
_sys.path.insert(0, str(_REPO_ROOT))
_wspec = importlib.util.spec_from_file_location(
    "tpusched.lint.witness", _REPO_ROOT / "tpusched" / "lint" / "witness.py"
)
_witness = importlib.util.module_from_spec(_wspec)
_sys.modules["tpusched.lint.witness"] = _witness
_wspec.loader.exec_module(_witness)

_WITNESS = _witness.install(_REPO_ROOT / "tools" / "lock_hierarchy.json")
assert not any(m.startswith("tpusched") and m != "tpusched.lint.witness"
               for m in _sys.modules), (
    "a product module was imported before the lock witness installed — "
    "its module-level locks would be invisible to the tier-1 gate"
)

# Tests run on the CPU: deterministic, and 8 virtual devices for the
# mesh tests. The chip is exercised by chip_smoke.py, never by tier-1.
import jax

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compile cache (round 23, ISSUE 18): tier-1 is COMPILE-
# dominated on small CPU hosts, so run N+1 reuses run N's binaries.
# Cache keys include the full HLO + compile options, so edited kernels
# simply miss and recompile. $JAX_COMPILATION_CACHE_DIR places it;
# otherwise it lives at <checkout>/.xla_cache (gitignored).
from tpusched.shapeclass import enable_persistent_cache

enable_persistent_cache()

# Sanitizer modes (SURVEY.md §5 "Race detection / sanitizers"): CI can
# run the whole suite with NaN checking / de-optimized XLA:
#   TPUSCHED_DEBUG_NANS=1 pytest tests/
#   TPUSCHED_DEBUG_CHECKS=1 pytest tests/  (disables most XLA opts)
def _env_on(name: str) -> bool:
    return os.environ.get(name, "").lower() not in ("", "0", "false", "no")


if _env_on("TPUSCHED_DEBUG_NANS"):
    jax.config.update("jax_debug_nans", True)
if _env_on("TPUSCHED_DEBUG_CHECKS"):
    jax.config.update("jax_disable_most_optimizations", True)

import time

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# TPL005 runtime backstop (round 15, ISSUE 10): the static rule proves
# literal/f-string Thread names carry the "tpusched-" prefix, but a
# dynamically-named (or third-party-wrapped) construction slips it.
# An UNNAMED thread gets Python's default "Thread-<N> (target)" name —
# invisible to the name-keyed leak matcher below, so a leak of one
# would silently pass. Known third-party default-named threads (we
# can't name what we don't construct) are exempted by their target
# suffix; grpc's poller shows up as "Thread-1 (_serve)".
_THIRD_PARTY_THREAD_SUFFIXES = (
    "(_serve)",                  # grpc server poller
    "(cancel_all_calls_after_grace)",  # grpc server.stop(grace) timer
    "(channel_spin)",            # grpc channel watcher
    "(process_request_thread)",  # stdlib ThreadingHTTPServer worker
    "(serve_forever)",           # stdlib test HTTP servers
)


def _unnamed_stray_threads():
    import re
    import threading

    out = []
    for t in threading.enumerate():
        if not t.is_alive() or not re.match(r"^Thread-\d+", t.name):
            continue
        if t.name.endswith(_THIRD_PARTY_THREAD_SUFFIXES):
            continue
        out.append(t.name)
    return out


@pytest.fixture
def thread_leak_check():
    """Multi-client/concurrency tests opt in: asserts every NEW
    tpusched worker thread spawned during the test has exited by the
    end (i.e. Engine.close / SchedulerService.close actually drained).
    Threads predating the test (module-scoped servers) are exempt.

    Matches "tpusched" ANYWHERE in the thread name (round 8): besides
    the fetch workers and bind pools this now covers the failure-
    domain machinery — fetch workers respawned after a watchdog trip
    or a deliberate kill (still "tpusched-fetch": abandoned ones must
    drain and exit, not accumulate) and the chaos harness's delayed
    restart timers ("tpusched-chaos-restart").

    Round 9 additionally pins the trace collector's THREADLESS design:
    tpusched.trace must never spawn a worker (span collection is a
    ring append on the caller's thread; export happens on demand), so
    after any traced test NO new thread may carry "trace" in its name
    — a regression here would put a leakable thread on every traced
    hot path."""
    import threading

    # Setup assertion (round 15): every thread alive when the leak
    # check arms must satisfy TPL005 — a default-named stray that
    # predates the test would be exempt from the leak match below AND
    # invisible to it if re-leaked, so it fails LOUDLY here instead.
    strays = _unnamed_stray_threads()
    assert strays == [], (
        f"unnamed (TPL005-violating) threads alive at leak-check "
        f"setup: {strays} — name them tpusched-* or exempt a known "
        f"third-party target in _THIRD_PARTY_THREAD_SUFFIXES"
    )

    # Keyed by Thread OBJECT, not ident: the OS recycles idents, and a
    # leaked worker created with a recycled ident would otherwise be
    # silently exempted.
    before = set(threading.enumerate())

    def leaked():
        return [
            t for t in threading.enumerate()
            if t not in before and t.is_alive()
            and "tpusched" in t.name
        ]

    yield
    deadline = time.monotonic() + 5.0
    while leaked() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert leaked() == [], f"leaked worker threads: {leaked()}"
    tracers = [
        t for t in threading.enumerate()
        if t not in before and t.is_alive() and "trace" in t.name.lower()
    ]
    assert tracers == [], (
        f"the trace collector must not add threads: {tracers}"
    )


@pytest.fixture(scope="session", autouse=True)
def lock_order_witness_gate():
    """Tier-1 acceptance (ISSUE 14): across the WHOLE run, no observed
    lock acquisition order may invert the static hierarchy — an
    inversion is the deadlock-shaped disagreement between model and
    reality the witness exists to catch. Unmodeled edges (orders the
    static graph has no opinion on) are printed for the hierarchy
    workflow but do not fail: dispatch-fallback gaps and third-party
    callback paths land there legitimately."""
    yield
    if not _WITNESS.installed:
        return
    rep = _WITNESS.report()
    if rep["unmodeled"]:
        print("\n[lock-witness] unmodeled observed edges "
              "(static analysis has no opinion; consider --graph):")
        for a, b in rep["unmodeled"]:
            print(f"  {a} -> {b}")
    assert rep["violations"] == [], (
        "observed lock acquisition orders INVERT the static hierarchy "
        "(tools/lock_hierarchy.json) — deadlock-shaped; fix the code "
        "or the analysis, do not re-point the artifact:\n"
        + "\n".join(f"  observed {a} -> {b}, hierarchy derives "
                    f"{b} -> {a}" for a, b in rep["violations"])
    )


def pytest_configure(config):
    # Tier-1 runs with -m 'not slow' (ROADMAP.md): the marker gates
    # compile-heavy multi-device tests that a 2-core CPU host cannot
    # afford inside the tier-1 wall budget; the full (unfiltered) suite
    # still runs everything.
    config.addinivalue_line(
        "markers", "slow: compile-heavy test excluded from tier-1"
    )
