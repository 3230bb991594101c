"""tpuschedlint suite tests (round 15, ISSUE 10).

Per-rule positive/negative fixture twins (each rule must fire on its
bad snippet and stay silent on the good one), the suppression grammar
(reason mandatory), the baseline round trip, and — the point of the
whole exercise — the tier-1 gate: the REAL tree lints clean with an
EMPTY baseline, so every invariant the repo has paid review passes for
is now enforced, not remembered.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from tpusched.lint import (
    Finding,
    LintContext,
    LintEngine,
    RULES,
    load_baseline,
    parse_suppressions,
    write_baseline,
)
from tpusched.lint.engine import BAD_SUPPRESSION, apply_baseline

REPO_ROOT = Path(__file__).resolve().parents[1]


def lint(src: str, relpath: str, **ctx_kw) -> "list[Finding]":
    ctx = LintContext(root=REPO_ROOT, **ctx_kw)
    return LintEngine(ctx=ctx).lint_text(src, relpath)


def rules_of(findings) -> "set[str]":
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# Fixture twins: (rule, relpath, bad snippet, good snippet). Each bad
# snippet must yield EXACTLY its rule (no collateral findings — keeps
# the fixtures honest about what fires), each good twin nothing.
# ---------------------------------------------------------------------------

FIXTURES = [
    (
        "TPL001", "tpusched/foo.py",
        "def f():\n    from tpusched import trace\n    return trace\n",
        "from tpusched import trace\n\n\ndef f():\n    return trace\n",
    ),
    (
        # allowlisted optional dep: function-level grpc is legal
        None, "tpusched/foo.py",
        None,
        "def f():\n    import grpc\n    return grpc\n",
    ),
    (
        "TPL002", "tpusched/sim/foo.py",
        "import random\n\n\ndef f():\n    return random.random()\n",
        "import random\n\n\ndef f():\n    return random.Random(0).random()\n",
    ),
    (
        "TPL002", "tpusched/kernels/foo.py",
        "import numpy as np\n\n\ndef f():\n    return np.random.rand(3)\n",
        "import numpy as np\n\n\ndef f():\n"
        "    return np.random.default_rng(7).random(3)\n",
    ),
    (
        "TPL002", "tpusched/faults.py",
        "import time\n\n\ndef f():\n    return time.time()\n",
        "import time\n\n\ndef f(clock):\n"
        "    return (clock.now(), time.monotonic())\n",
    ),
    (
        "TPL002", "tpusched/sim/foo.py",
        "import numpy as np\n\n\ndef f():\n    return np.random.default_rng()\n",
        "import numpy as np\n\n\ndef f(seed):\n"
        "    return np.random.default_rng(seed)\n",
    ),
    (
        # an explicit None seed is still OS entropy
        "TPL002", "tpusched/sim/foo.py",
        "import numpy as np\n\n\ndef f():\n"
        "    return np.random.default_rng(None)\n",
        "import numpy as np\n\n\ndef f():\n"
        "    return np.random.default_rng(seed=0)\n",
    ),
    (
        "TPL003", "tpusched/foo.py",
        "def f(self):\n    with self._lock:\n"
        "        return self._fut.result()\n",
        "def f(self):\n    with self._lock:\n        fut = self._fut\n"
        "    return fut.result()\n",
    ),
    (
        # defining a function under the lock is free
        None, "tpusched/foo.py",
        None,
        "def f(self):\n    with self._lock:\n"
        "        def g():\n            return self._fut.result()\n"
        "    return g\n",
    ),
    (
        "TPL004", "tpusched/foo.py",
        "def f(v):\n    return min(max(v, 0.0), 1.0)\n",
        "from tpusched.config import clamp01\n\n\ndef f(v):\n"
        "    return clamp01(v)\n",
    ),
    (
        # a non-unit range clamp is NOT the clamp01 bug class
        None, "tpusched/foo.py",
        None,
        "def f(k, n):\n    return max(1, min(k, n))\n",
    ),
    (
        "TPL005", "tpusched/foo.py",
        "import threading\n\n\ndef f():\n"
        "    return threading.Thread(target=f)\n",
        "import threading\n\n\ndef f():\n"
        "    return threading.Thread(target=f, name='tpusched-foo')\n",
    ),
    (
        "TPL005", "tools/foo.py",
        "import threading\n\n\ndef f(i):\n"
        "    return threading.Thread(target=f, name=f'worker-{i}')\n",
        "import threading\n\n\ndef f(i):\n"
        "    return threading.Thread(target=f, name=f'tpusched-w-{i}')\n",
    ),
    (
        "TPL007", "tpusched/foo.py",
        "def f(d):\n    return next(reversed(d), None)\n",
        "def f(d, newest):\n    return d.get(newest)\n",
    ),
    (
        "TPL008", "tools/foo.py",
        "def f(rounds):\n    return sorted(rounds)\n",
        "def f(rounds):\n    return sorted(rounds, key=int)\n",
    ),
    (
        # name without round/seq tokens: not this bug class
        None, "tools/foo.py",
        None,
        "def f(node_names):\n    node_names.sort()\n"
        "    return sorted(node_names)\n",
    ),
    (
        "TPL009", "tpusched/foo.py",
        "from tpusched import trace as tracing\n\n\ndef f():\n"
        "    tracing.DEFAULT.record('x')\n",
        "from tpusched import trace as tracing\n\n\ndef f(tracer):\n"
        "    (tracer or tracing.DEFAULT).record('x')\n",
    ),
    (
        "TPL011", "tools/foo.py",
        "def f(ds):\n    return ds.warm_state.tableau\n",
        "def f(ds):\n    return (ds.warm_solves, ds.last_warm_rows)\n",
    ),
    (
        # the engine warm path owns the tableau; reads there are the
        # design, not the hazard
        None, "tpusched/engine.py",
        None,
        "def f(warm):\n    return warm.tableau\n",
    ),
    (
        # TPL101 (ISSUE 14): inconsistent two-lock order in one class
        # is a deadlock-shaped cycle; a consistent global order is not.
        "TPL101", "tpusched/foo.py",
        "import threading\n\n\nclass A:\n"
        "    def __init__(self):\n"
        "        self._a_lock = threading.Lock()\n"
        "        self._b_lock = threading.Lock()\n\n"
        "    def one(self):\n"
        "        with self._a_lock:\n"
        "            with self._b_lock:\n"
        "                return 1\n\n"
        "    def two(self):\n"
        "        with self._b_lock:\n"
        "            with self._a_lock:\n"
        "                return 2\n",
        "import threading\n\n\nclass A:\n"
        "    def __init__(self):\n"
        "        self._a_lock = threading.Lock()\n"
        "        self._b_lock = threading.Lock()\n\n"
        "    def one(self):\n"
        "        with self._a_lock:\n"
        "            with self._b_lock:\n"
        "                return 1\n\n"
        "    def two(self):\n"
        "        with self._a_lock:\n"
        "            with self._b_lock:\n"
        "                return 2\n",
    ),
    (
        # TPL101 degenerate form: provably same-instance re-acquisition
        # of a non-reentrant Lock through a self-call chain.
        "TPL101", "tpusched/foo.py",
        "import threading\n\n\nclass A:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n\n"
        "    def outer(self):\n"
        "        with self._lock:\n"
        "            return self._helper()\n\n"
        "    def _helper(self):\n"
        "        with self._lock:\n"
        "            return 1\n",
        "import threading\n\n\nclass A:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n\n"
        "    def outer(self):\n"
        "        with self._lock:\n"
        "            return self._helper_locked()\n\n"
        "    def _helper_locked(self):\n"
        "        return 1\n",
    ),
    (
        # TPL102 (ISSUE 14): a fetch join reached THROUGH a call made
        # under the lock — invisible to the lexical TPL003.
        "TPL102", "tpusched/foo.py",
        "import threading\n\n\nclass A:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n\n"
        "    def f(self, fut):\n"
        "        with self._lock:\n"
        "            return self._join(fut)\n\n"
        "    def _join(self, fut):\n"
        "        return fut.result()\n",
        "import threading\n\n\nclass A:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n\n"
        "    def f(self, fut):\n"
        "        with self._lock:\n"
        "            pending = fut\n"
        "        return self._join(pending)\n\n"
        "    def _join(self, fut):\n"
        "        return fut.result()\n",
    ),
    (
        # TPL103 (ISSUE 14): a per-call jax.jit rebuilds the compile
        # cache every invocation; module-level construction is the fix.
        "TPL103", "tpusched/foo.py",
        "import jax\n\n\ndef f(x):\n"
        "    fn = jax.jit(lambda v: v + 1)\n"
        "    return fn(x)\n",
        "import jax\n\n_FN = jax.jit(lambda v: v + 1)\n\n\n"
        "def f(x):\n    return _FN(x)\n",
    ),
    (
        # TPL104 (ISSUE 14): a memo-dict jit family keyed by a raw
        # request value compiles per distinct key; a pow2/bucket helper
        # on the key bounds the family.
        "TPL104", "tpusched/foo.py",
        "import jax\n\n\nclass E:\n"
        "    def __init__(self):\n"
        "        self._jits = {}\n\n"
        "    def fn(self, k):\n"
        "        f = self._jits.get(k)\n"
        "        if f is None:\n"
        "            f = self._jits[k] = jax.jit(lambda v: v)\n"
        "        return f\n",
        "import jax\n\n\ndef pow2_bucket(k):\n"
        "    return 1 << (max(int(k), 1) - 1).bit_length()\n\n\n"
        "class E:\n"
        "    def __init__(self):\n"
        "        self._jits = {}\n\n"
        "    def fn(self, k):\n"
        "        kb = pow2_bucket(k)\n"
        "        f = self._jits.get(kb)\n"
        "        if f is None:\n"
        "            f = self._jits[kb] = jax.jit(lambda v: v)\n"
        "        return f\n",
    ),
    (
        # TPL105 (ISSUE 14): a jit-wrapped closure reading self state
        # bakes the value in at trace time; bind to a local first.
        "TPL105", "tpusched/foo.py",
        "import jax\n\n\nclass E:\n"
        "    def build(self):\n"
        "        def _fn(v):\n"
        "            return v * self.scale\n"
        "        self._jit = jax.jit(_fn)\n",
        "import jax\n\n\nclass E:\n"
        "    def build(self):\n"
        "        scale = self.scale\n\n"
        "        def _fn(v):\n"
        "            return v * scale\n"
        "        self._jit = jax.jit(_fn)\n",
    ),
    (
        # TPL201 (ISSUE 15): an f32 sum feeding a compare is stable
        # only at a fixed width/layout; the int32 fixed-point idiom
        # (clip bounds the sum provably) is exact in any tree.
        "TPL201", "tpusched/kernels/foo.py",
        "import jax.numpy as jnp\n\n\ndef f(scores, mask):\n"
        "    total = jnp.sum(jnp.where(mask, scores, 0.0), axis=0)\n"
        "    return total > 10.0\n",
        "import jax.numpy as jnp\n\n\ndef f(scores, mask):\n"
        "    iq = jnp.clip(jnp.round(scores * 16.0), -32767.0,\n"
        "                  32767.0).astype(jnp.int32)\n"
        "    total = jnp.sum(jnp.where(mask, iq, 0), axis=0)\n"
        "    return total > 160\n",
    ),
    (
        # TPL202 (ISSUE 15): a plain f32 cumsum on a compacted-view
        # path moves bitwise with the view width; the width-padded
        # rank-major layout (PR 12's idiom) is byte-stable.
        "TPL202", "tpusched/kernels/foo.py",
        "import jax.numpy as jnp\n\n\n"
        "def _pods_view(snap, static, sel):\n"
        "    return snap, static\n\n\n"
        "def f(snap, static, sel, requests, mask):\n"
        "    snap_v, static_v = _pods_view(snap, static, sel)\n"
        "    dem = jnp.where(mask[:, None], requests, 0.0)\n"
        "    return jnp.cumsum(dem, axis=0)\n",
        "import jax.numpy as jnp\n\n\n"
        "def _pods_view(snap, static, sel):\n"
        "    return snap, static\n\n\n"
        "def f(snap, static, sel, requests, mask, rank, width):\n"
        "    snap_v, static_v = _pods_view(snap, static, sel)\n"
        "    dem = jnp.where(mask[:, None], requests, 0.0)\n"
        "    rm = jnp.zeros((width, dem.shape[1]),"
        " dem.dtype).at[rank].set(dem)\n"
        "    return jnp.cumsum(rm, axis=0)\n",
    ),
    (
        # TPL203 (ISSUE 15): duplicate-capable f32 scatter-add applies
        # in unspecified order; an argsort perm index is duplicate-free.
        "TPL203", "tpusched/kernels/foo.py",
        "import jax.numpy as jnp\n\n\ndef f(used, node, requests):\n"
        "    return used.at[node].add(requests)\n",
        "import jax.numpy as jnp\n\n\ndef f(used, requests, keys):\n"
        "    perm = jnp.argsort(keys)\n"
        "    return used.at[perm].add(requests)\n",
    ),
    (
        # TPL204 (ISSUE 15): a fixed-point sum without a clip on the
        # quantized operand has no provable int32 bound.
        "TPL204", "tpusched/kernels/foo.py",
        "import jax.numpy as jnp\n\n\ndef f(scores):\n"
        "    iq = jnp.round(scores * 16.0).astype(jnp.int32)\n"
        "    return jnp.sum(iq, axis=0)\n",
        "import jax.numpy as jnp\n\n\ndef f(scores):\n"
        "    iq = jnp.clip(jnp.round(scores * 16.0), -32767.0,\n"
        "                  32767.0).astype(jnp.int32)\n"
        "    return jnp.sum(iq, axis=0)\n",
    ),
    (
        # TPL2xx scope: the identical hazard outside the kernel scope
        # is not this analysis's territory (engine/host orchestration
        # is not an array program).
        None, "tpusched/engine.py",
        None,
        "import jax.numpy as jnp\n\n\ndef f(used, node, requests):\n"
        "    return used.at[node].add(requests)\n",
    ),
]


@pytest.mark.parametrize(
    "rule,relpath,bad,good",
    FIXTURES,
    ids=[f"{r or 'neg'}-{i}" for i, (r, _, _, _) in enumerate(FIXTURES)],
)
def test_rule_fires_on_bad_and_not_on_good(rule, relpath, bad, good):
    if bad is not None:
        got = rules_of(lint(bad, relpath))
        assert got == {rule}, f"bad twin: expected {{{rule}}}, got {got}"
    assert lint(good, relpath) == [], "good twin must lint clean"


def test_tpl010_fires_and_clears_on_close():
    bad = (
        "def test_leaks():\n"
        "    eng = Engine(cfg)\n"
        "    assert eng.solve(snap)\n"
    )
    closed = (
        "def test_closes():\n"
        "    eng = Engine(cfg)\n"
        "    try:\n"
        "        assert eng.solve(snap)\n"
        "    finally:\n"
        "        eng.close()\n"
    )
    handed_off = (
        "def test_hands_off():\n"
        "    eng = Engine(cfg)\n"
        "    host = HostScheduler(api, cfg, engine=eng)\n"
        "    host.close()\n"
    )
    kw = dict(closeable_classes={"Engine", "HostScheduler"})
    assert rules_of(lint(bad, "tests/test_x.py", **kw)) == {"TPL010"}
    assert lint(closed, "tests/test_x.py", **kw) == []
    assert lint(handed_off, "tests/test_x.py", **kw) == []
    # rule is tests-only: the same code in product scope is silent
    assert lint(bad, "tpusched/foo.py", **kw) == []


def test_closeable_scan_finds_the_real_classes():
    ctx = LintContext(root=REPO_ROOT)
    assert {"Engine", "HostScheduler", "SchedulerClient",
            "SchedulerService", "ReplicaSet"} <= ctx.closeable_classes


# ---------------------------------------------------------------------------
# Suppressions.
# ---------------------------------------------------------------------------

BAD_TPL007 = "def f(d):\n    return next(reversed(d), None){}\n"


def test_suppression_with_reason_silences_the_rule():
    src = BAD_TPL007.format(
        "  # tpl: disable=TPL007(any element is acceptable here)"
    )
    assert lint(src, "tpusched/foo.py") == []


def test_suppression_without_reason_is_its_own_finding():
    for marker in ("  # tpl: disable=TPL007",
                   "  # tpl: disable=TPL007()"):
        got = lint(BAD_TPL007.format(marker), "tpusched/foo.py")
        assert rules_of(got) == {BAD_SUPPRESSION, "TPL007"}, (
            "a reasonless suppression must not suppress, and must "
            "flag itself"
        )


def test_suppression_only_covers_its_own_line_and_rule():
    src = (
        "def f(d):\n"
        "    x = next(reversed(d))  # tpl: disable=TPL001(wrong rule)\n"
        "    y = next(reversed(d))\n"
        "    return x, y\n"
    )
    got = lint(src, "tpusched/foo.py")
    assert [f.line for f in got] == [2, 3]
    assert rules_of(got) == {"TPL007"}


def test_suppression_marker_inside_string_literal_is_ignored():
    src = 'MSG = "write # tpl: disable=TPL007(reason) on the line"\n'
    assert lint(src, "tpusched/foo.py") == []


# ---------------------------------------------------------------------------
# Baseline round trip.
# ---------------------------------------------------------------------------

def test_baseline_round_trip(tmp_path):
    src = "def f(d):\n    return next(reversed(d), None)\n"
    findings = lint(src, "tpusched/foo.py")
    assert rules_of(findings) == {"TPL007"}
    bl_path = tmp_path / "baseline.json"
    write_baseline(bl_path, findings)
    baseline = load_baseline(bl_path)
    assert apply_baseline(findings, baseline) == []
    # a NEW finding (different line) is not covered
    moved = [Finding(f.path, f.line + 10, f.rule, f.message)
             for f in findings]
    assert apply_baseline(moved, baseline) == moved
    # the checked-in JSON stays list-shaped
    assert isinstance(json.loads(bl_path.read_text()), list)


def test_missing_baseline_is_empty(tmp_path):
    assert load_baseline(tmp_path / "nope.json") == set()


# ---------------------------------------------------------------------------
# The tier-1 gate.
# ---------------------------------------------------------------------------

def test_rule_table_is_complete():
    ids = [cls.rule_id for cls in RULES]
    assert len(ids) == len(set(ids)) == 19
    for cls in RULES:
        assert cls.incident, f"{cls.rule_id} must cite its incident"
        assert cls.title, f"{cls.rule_id} must carry a title"


def test_tree_is_clean():
    """THE gate (acceptance criterion): the full repo — product code
    AND tests — lints clean against the checked-in baseline, which is
    EMPTY. A finding here is a real invariant violation: fix it or
    suppress it on-line with a reason, do not baseline it."""
    baseline_path = REPO_ROOT / "tools" / "lint_baseline.json"
    baseline = load_baseline(baseline_path)
    assert baseline == set(), (
        "tools/lint_baseline.json must stay EMPTY at HEAD — baselines "
        "grandfather a new rule in, they are not a suppression pool"
    )
    engine = LintEngine(ctx=LintContext(root=REPO_ROOT))
    findings = engine.lint_paths([
        REPO_ROOT / "tpusched",
        REPO_ROOT / "tools",
        REPO_ROOT / "tests",
    ])
    findings = apply_baseline(findings, baseline)
    assert findings == [], (
        "tpuschedlint findings at HEAD:\n"
        + "\n".join(f.render() for f in findings)
    )


def test_suppressions_in_tree_all_carry_reasons():
    """Every suppression in the real tree parses with a reason (the
    gate would fail on TPL000 otherwise, but this pins the grammar
    end-to-end over the live files)."""
    n_suppressions = 0
    for path in sorted((REPO_ROOT / "tpusched").rglob("*.py")):
        by_line, errors = parse_suppressions(path.read_text())
        assert errors == [], f"{path}: {errors}"
        n_suppressions += sum(len(v) for v in by_line.values())
    assert n_suppressions >= 5, (
        "the tree documents its deliberate exceptions via reasoned "
        "suppressions; losing them all suggests the parser broke"
    )
