"""tpuschedlint: the repo's hard-won invariants as enforced AST analysis.

Every rule here descends from a defect class this codebase has already
paid review passes for (round 15, ISSUE 10; incident lineage in
tools/README.md "Static analysis"):

    TPL001  function-level imports in tpusched/ (hot-path import cost)
    TPL002  unseeded randomness / wall-clock in the hash-pinned sim
    TPL003  known-cost calls lexically under a lock
    TPL004  inline [0,1] clamps bypassing config.clamp01
    TPL005  threading.Thread without a tpusched- name
    TPL007  next(reversed(...)) dict-order-dependent selection
    TPL008  sorted() on round/seq-shaped keys without a numeric key
    TPL009  trace.DEFAULT/explain.DEFAULT outside the fallback idiom
    TPL010  closeable class never closed in a test function
    TPL011  carried warm-tableau read outside the engine warm path

Whole-program analyses (round 19, ISSUE 14; call graph + per-function
summaries in tpusched/lint/interproc.py, runtime cross-check in
tpusched/lint/witness.py):

    TPL101  lock-order cycle (potential deadlock)
    TPL102  transitive known-cost call under a lock (TPL003, deep)
    TPL103  per-call jax.jit construction (retrace hazard)
    TPL104  unbounded jit family (no bounding bucket on the memo key)
    TPL105  jit-wrapped closure reads mutable self state

Kernel dataflow analysis (round 20, ISSUE 15; abstract interpreter in
tpusched/lint/kernelflow.py, runtime refuter in tools/padcheck.py):

    TPL201  f32 order-sensitive reduction feeds a commit/compare
            decision (tree shape = width/layout/sharding dependence)
    TPL202  padding-hazardous reduction reachable from a compacted-view
            (_pods_view/frontier) path
    TPL203  scatter-add with non-unique indices and f32 values
            (duplicates apply in unspecified order)
    TPL204  int32 fixed-point sum without a provable overflow bound

Every cross-pod/cross-node reduction site is inventoried in
tools/reduction_ledger.json (exactness class, padding verdict,
sharding-safety note — the artifact ROADMAP item 1 consumes;
regenerate: ``python tools/lint.py --write-ledger``; staleness is a
``tools/check.py`` kernelflow failure, and tools/padcheck.py
differentially executes the sites' enclosing kernels at two bucket
widths to refute bad exactness claims at runtime).

The static lock order is checked in as tools/lock_hierarchy.json
(regenerate: ``python tools/lint.py --write-hierarchy``; staleness is a
``tools/check.py`` lockgraph failure) and validated at runtime by the
lock-order witness tier-1 installs via tests/conftest.py.

Run via ``python tools/lint.py tpusched tools tests`` (the
tier-1 gate, tests/test_lint.py::test_tree_is_clean) or through
``tools/check.py``. Per-line suppressions:

    expr  # tpl: disable=TPL003(reason is mandatory)

and a JSON baseline file (tools/lint_baseline.json) for grandfathered
findings — kept EMPTY at HEAD; the engine reports TPL000 for a
suppression without a reason so the escape hatch stays documented.
"""

from tpusched.lint.engine import (  # noqa: F401
    Finding,
    LintContext,
    LintEngine,
    load_baseline,
    parse_suppressions,
    write_baseline,
)
from tpusched.lint.rules import RULES, default_rules  # noqa: F401
