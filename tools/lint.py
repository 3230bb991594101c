#!/usr/bin/env python
"""tpuschedlint CLI: enforce the repo's review-pass invariants (round 15).

Runs the AST rule suite in tpusched/lint/ over the given paths and
fails on any finding not covered by the checked-in baseline. The
tier-1 gate (tests/test_lint.py::test_tree_is_clean) runs exactly:

  python tools/lint.py tpusched tools tests

Suppress a legitimate exception per line, reason mandatory:

  expr  # tpl: disable=TPL003(why this line is exempt)

Baseline workflow (for landing a NEW rule against an old tree):

  python tools/lint.py --write-baseline tpusched tools tests
  ... fix findings, shrinking tools/lint_baseline.json to [] ...

The baseline at HEAD is kept EMPTY; entries are grandfathered debt,
not a second suppression mechanism.

  python tools/lint.py --list-rules     # rule table + incident lineage

Whole-program surfaces (round 19, ISSUE 14):

  python tools/lint.py --graph            # call graph + held-lock sets (JSON)
  python tools/lint.py --write-hierarchy  # regenerate tools/lock_hierarchy.json
  python tools/lint.py --check-hierarchy  # fail if the artifact is stale/cyclic
  python tools/lint.py --jit-report       # every jit site, families + bounds

Kernel dataflow surfaces (round 20, ISSUE 15):

  python tools/lint.py --kernel-report    # per-site exactness/padding dump
  python tools/lint.py --write-ledger     # regenerate tools/reduction_ledger.json
  python tools/lint.py --check-ledger     # fail if the ledger is stale or a
                                          # hazard site lacks a reasoned
                                          # suppression (the kernelflow gate)
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT))

from tpusched.lint import (  # noqa: E402
    LintContext,
    LintEngine,
    RULES,
    load_baseline,
    write_baseline,
)
from tpusched.lint import interproc  # noqa: E402
from tpusched.lint import kernelflow  # noqa: E402
from tpusched.lint.engine import apply_baseline, parse_suppressions  # noqa: E402

DEFAULT_BASELINE = REPO_ROOT / "tools" / "lint_baseline.json"
DEFAULT_HIERARCHY = REPO_ROOT / "tools" / "lock_hierarchy.json"
DEFAULT_LEDGER = REPO_ROOT / "tools" / "reduction_ledger.json"
DEFAULT_PATHS = ("tpusched", "tools", "tests")


def _program() -> "interproc.Program":
    return interproc.Program(interproc.scan_product_sources(REPO_ROOT))


def _kernel_program() -> "kernelflow.KernelProgram":
    return kernelflow.KernelProgram(kernelflow.kernel_sources(
        interproc.scan_product_sources(REPO_ROOT)))


def _kernel_ledger_doc(prog: "kernelflow.KernelProgram") -> dict:
    """Fresh ledger doc with per-site suppression status read from the
    live tree's `# tpl: disable=` comments (a suppressed hazard is a
    REASONED entry in the ledger, not an absent one)."""
    suppressed: "dict[str, dict[int, set[str]]]" = {}
    for relpath, src in prog.sources.items():
        by_line, _errors = parse_suppressions(src)
        suppressed[relpath] = by_line
    return prog.ledger_doc(suppressed)


def cmd_graph() -> int:
    print(json.dumps(_program().graph_doc(), indent=2, sort_keys=True))
    return 0


def cmd_write_hierarchy() -> int:
    prog = _program()
    interproc.write_hierarchy(DEFAULT_HIERARCHY, prog)
    doc = prog.hierarchy_doc()
    print(f"lockgraph: wrote {len(doc['locks'])} locks / "
          f"{len(doc['edges'])} edges to {DEFAULT_HIERARCHY}")
    return 0


def cmd_check_hierarchy() -> int:
    """The lockgraph gate: the checked-in artifact must match a fresh
    regeneration byte-for-byte (line numbers drift with edits — a stale
    artifact blinds the runtime witness), and the order must be acyclic."""
    prog = _program()
    fresh = json.dumps(prog.hierarchy_doc(), indent=2, sort_keys=True) + "\n"
    ok = True
    if not DEFAULT_HIERARCHY.exists():
        print("lockgraph: tools/lock_hierarchy.json missing — run "
              "`python tools/lint.py --write-hierarchy`", file=sys.stderr)
        ok = False
    elif DEFAULT_HIERARCHY.read_text() != fresh:
        print("lockgraph: tools/lock_hierarchy.json is STALE — run "
              "`python tools/lint.py --write-hierarchy` and commit it",
              file=sys.stderr)
        ok = False
    cycles = prog.lock_cycles()
    if cycles:
        for c in cycles:
            print(f"lockgraph: CYCLE {' <-> '.join(c)}", file=sys.stderr)
        ok = False
    doc = prog.hierarchy_doc()
    print(f"lockgraph: {len(doc['locks'])} locks, {len(doc['edges'])} "
          f"edges, {len(cycles)} cycles"
          + ("" if not ok else " — in sync"))
    return 0 if ok else 1


def cmd_kernel_report() -> int:
    """Human-readable per-site dump of the kernel dataflow ledger."""
    prog = _kernel_program()
    for line in prog.report_lines():
        print(line)
    doc = _kernel_ledger_doc(prog)
    t = doc["totals"]
    print(f"kernelflow: {t['sites']} sites, {t['findings']} hazard "
          f"finding(s), {t['unsuppressed']} unsuppressed")
    return 0


def cmd_write_ledger() -> int:
    prog = _kernel_program()
    doc = _kernel_ledger_doc(prog)
    kernelflow.write_ledger(DEFAULT_LEDGER, doc)
    t = doc["totals"]
    print(f"kernelflow: wrote {t['sites']} sites "
          f"({t['findings']} hazards, {t['unsuppressed']} unsuppressed) "
          f"to {DEFAULT_LEDGER}")
    return 0


def cmd_check_ledger() -> int:
    """The kernelflow gate: the checked-in reduction ledger must match
    a fresh regeneration byte-for-byte (line numbers drift with edits —
    a stale ledger lies to ROADMAP item 1 about which reductions are
    sharding-safe), and every hazardous site must be fixed or carry a
    reasoned suppression."""
    prog = _kernel_program()
    doc = _kernel_ledger_doc(prog)
    fresh = json.dumps(doc, indent=2, sort_keys=True) + "\n"
    ok = True
    if not DEFAULT_LEDGER.exists():
        print("kernelflow: tools/reduction_ledger.json missing — run "
              "`python tools/lint.py --write-ledger`", file=sys.stderr)
        ok = False
    elif DEFAULT_LEDGER.read_text() != fresh:
        print("kernelflow: tools/reduction_ledger.json is STALE — run "
              "`python tools/lint.py --write-ledger` and commit it",
              file=sys.stderr)
        ok = False
    t = doc["totals"]
    if t["unsuppressed"]:
        for rec in doc["sites"]:
            if rec.get("rule") and not rec.get("suppressed"):
                print(f"kernelflow: UNSUPPRESSED {rec['rule']} at "
                      f"{rec['path']}:{rec['line']} ({rec['op']})",
                      file=sys.stderr)
        ok = False
    # Trend metric (lower is better: hazards shrink as conversions
    # land).
    print(json.dumps({"metric": "kernelflow_findings_total",
                      "value": float(t["findings"]), "unit": "count",
                      "direction": "lower"}))
    print(f"kernelflow: {t['sites']} sites, {t['findings']} hazards, "
          f"{t['unsuppressed']} unsuppressed"
          + (" — in sync" if ok else ""))
    return 0 if ok else 1


def cmd_jit_report() -> int:
    """The jitlint gate: enumerate every jax.jit/_traced_jit site with
    its caching classification; unbounded families fail (they are also
    TPL104 findings, but this surface reports the WHOLE inventory)."""
    prog = _program()
    for s in prog.jit_sites:
        fam = f" family={s.family}" if s.family else ""
        bound = ""
        if s.kind == "family":
            bound = (f" bounded={s.bounded}"
                     + (f" ({s.bound_via})" if s.bound_via else ""))
        print(f"{s.path}:{s.line}: {s.kind}{fam}{bound}")
    bad = prog.unbounded_families()
    print(f"jitlint: {len(prog.jit_sites)} jit sites, "
          f"{len(bad)} unbounded families")
    return 1 if bad else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", default=None,
                    help=f"files/dirs to lint (default: {' '.join(DEFAULT_PATHS)})")
    ap.add_argument("--baseline", default=str(DEFAULT_BASELINE),
                    help="baseline JSON (default tools/lint_baseline.json)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write current findings to the baseline and exit 0")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline (show every finding)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule table and exit")
    ap.add_argument("--graph", action="store_true",
                    help="dump the call graph + held-lock sets as JSON")
    ap.add_argument("--write-hierarchy", action="store_true",
                    help="regenerate tools/lock_hierarchy.json")
    ap.add_argument("--check-hierarchy", action="store_true",
                    help="fail when the hierarchy artifact is stale or cyclic")
    ap.add_argument("--jit-report", action="store_true",
                    help="enumerate jit sites; fail on unbounded families")
    ap.add_argument("--kernel-report", action="store_true",
                    help="dump the kernel dataflow ledger per site")
    ap.add_argument("--write-ledger", action="store_true",
                    help="regenerate tools/reduction_ledger.json")
    ap.add_argument("--check-ledger", action="store_true",
                    help="fail when the reduction ledger is stale or a "
                         "hazard site lacks a reasoned suppression")
    args = ap.parse_args(argv)

    if args.graph:
        return cmd_graph()
    if args.write_hierarchy:
        return cmd_write_hierarchy()
    if args.check_hierarchy:
        return cmd_check_hierarchy()
    if args.jit_report:
        return cmd_jit_report()
    if args.kernel_report:
        return cmd_kernel_report()
    if args.write_ledger:
        return cmd_write_ledger()
    if args.check_ledger:
        return cmd_check_ledger()
    if args.list_rules:
        for cls in RULES:
            print(f"{cls.rule_id}  {cls.title}")
            print(f"        descends from: {cls.incident}")
        return 0

    paths = [Path(p) for p in (args.paths or DEFAULT_PATHS)]
    missing = [p for p in paths if not p.exists()]
    if missing:
        print(f"lint: no such path(s): {missing}", file=sys.stderr)
        return 2

    engine = LintEngine(ctx=LintContext(root=REPO_ROOT))
    findings = engine.lint_paths(paths)

    if args.write_baseline:
        write_baseline(Path(args.baseline), findings)
        print(f"lint: wrote {len(findings)} finding(s) to {args.baseline}")
        return 0

    if not args.no_baseline:
        baseline = load_baseline(Path(args.baseline))
        if baseline:
            before = len(findings)
            findings = apply_baseline(findings, baseline)
            print(f"lint: {before - len(findings)} finding(s) covered "
                  f"by baseline {args.baseline}", file=sys.stderr)

    for f in findings:
        print(f.render())
    n = len(findings)
    print(f"lint: {n} finding(s) across {len(RULES)} rules"
          + ("" if n else " — clean"))
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
