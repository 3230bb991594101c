"""Lint engine: file walking, suppressions, baseline, rule dispatch.

Design constraints (round 15, ISSUE 10):

* Pure stdlib ``ast`` — this image must not grow dependencies.
* Findings are (path, line, rule, message) and deterministic: the
  tier-1 gate diffs them against an (empty) checked-in baseline, so
  ordering and paths must be stable across machines — paths are
  repo-root-relative POSIX strings.
* Suppressions are per-line and must carry a reason:
  ``# tpl: disable=TPL003(scrape is O(1) here)``. A reasonless
  suppression is itself a finding (TPL000) — the escape hatch is part
  of the documented invariant surface, not a way around it.
* The baseline exists for grandfathering a rule in; the repo keeps it
  empty (acceptance: ``tools/lint.py tpusched tools tests`` exits 0
  with ``tools/lint_baseline.json == []``).
"""

from __future__ import annotations

import ast
import dataclasses
import io
import json
import re
import tokenize
from pathlib import Path, PurePosixPath
from typing import TYPE_CHECKING, Any, Iterable, Sequence

if TYPE_CHECKING:
    from tpusched.lint.interproc import Program
    from tpusched.lint.rules import Rule

#: Engine-level pseudo-rule for malformed suppression comments.
BAD_SUPPRESSION = "TPL000"

_SUPPRESS_RE = re.compile(r"#\s*tpl:\s*disable=(?P<entries>.+)$")
_ENTRY_RE = re.compile(r"(TPL\d{3})\s*(?:\(([^)]*)\))?")


@dataclasses.dataclass(frozen=True, order=True)
class Finding:
    path: str   # repo-relative POSIX path
    line: int   # 1-indexed
    rule: str   # "TPL001"
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"

    def key(self) -> "tuple[str, int, str]":
        """Baseline identity (message excluded: wording may evolve
        without re-grandfathering a finding)."""
        return (self.path, self.line, self.rule)


class LintContext:
    """Cross-file project knowledge shared by all rules over one run.

    ``root`` anchors relative paths; ``closeable_classes`` (TPL010) is
    computed lazily so linting a single fixture snippet never scans the
    tree, and is injectable for rule unit tests.
    """

    def __init__(
        self,
        root: "Path | None" = None,
        closeable_classes: "set[str] | None" = None,
        program_sources: "dict[str, str] | None" = None,
    ) -> None:
        self.root = Path(root) if root is not None else _default_root()
        self._closeable = closeable_classes
        # Whole-program index (round 19, ISSUE 14): `program_sources`
        # injects an explicit {relpath: src} universe for multi-file
        # rule tests; None scans the real product tree lazily.
        self._program_sources = program_sources
        self._base_sources: "dict[str, str] | None" = None
        self._program: "Program | None" = None
        self._kernel_program: Any = None

    @property
    def closeable_classes(self) -> "set[str]":
        """Public tpusched classes defining close(): the TPL010 set."""
        if self._closeable is None:
            self._closeable = scan_closeable_classes(self.root / "tpusched")
        return self._closeable

    def program_view(self, relpath: str, src: str) -> "Program":
        """The interprocedural Program the TPL1xx rules run against
        when linting (relpath, src).

        Real-tree runs (the file on disk matches `src`) share ONE
        cached whole-program index, so the gate builds the call graph
        once. A fixture snippet (no such file, or content differs)
        gets an ISOLATED program over the injected `program_sources`
        plus the snippet — per-rule fixture twins stay hermetic instead
        of resolving against the live tree."""
        from tpusched.lint import interproc  # tpl: disable=TPL001(lazy: keeps engine.py importable standalone without the analysis layer — rules.py does load interproc at module top for the shared COSTLY sets, but engine alone must not)

        if self._program_sources is not None:
            base = self._program_sources
        else:
            if self._base_sources is None:
                self._base_sources = interproc.scan_product_sources(self.root)
            base = self._base_sources
        if base.get(relpath) == src:
            if self._program is None:
                self._program = interproc.Program(base)
            return self._program
        srcs = dict(self._program_sources or {})
        srcs[relpath] = src
        return interproc.Program(srcs)

    def kernel_view(self, relpath: str, src: str) -> Any:
        """The kernelflow KernelProgram the TPL2xx rules run against
        when linting (relpath, src) — same caching/isolation contract
        as program_view: real-tree runs share ONE cached index built
        over the kernel-scope sources; a fixture snippet gets an
        isolated program over the injected sources plus itself."""
        from tpusched.lint import kernelflow  # tpl: disable=TPL001(lazy: keeps engine.py importable standalone without the analysis layer — same contract as the interproc import above)

        if self._program_sources is not None:
            base = self._program_sources
        else:
            if self._base_sources is None:
                from tpusched.lint import interproc  # tpl: disable=TPL001(lazy: same engine-standalone contract as program_view)

                self._base_sources = interproc.scan_product_sources(
                    self.root)
            base = self._base_sources
        if base.get(relpath) == src:
            if self._kernel_program is None:
                self._kernel_program = kernelflow.KernelProgram(
                    kernelflow.kernel_sources(base))
            return self._kernel_program
        srcs = kernelflow.kernel_sources(dict(self._program_sources or {}))
        srcs[relpath] = src
        return kernelflow.KernelProgram(srcs)


def _default_root() -> Path:
    # tpusched/lint/engine.py -> tpusched/lint -> tpusched -> repo root
    return Path(__file__).resolve().parents[2]


def scan_closeable_classes(pkg_dir: Path) -> "set[str]":
    """Names of PUBLIC classes under ``pkg_dir`` that define close():
    the classes a test may construct but must not leak (TPL010)."""
    out: set[str] = set()
    if not pkg_dir.is_dir():
        return out
    for path in sorted(pkg_dir.rglob("*.py")):
        try:
            tree = ast.parse(path.read_text(), filename=str(path))
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.ClassDef) or node.name.startswith("_"):
                continue
            for item in node.body:
                if (isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef))
                        and item.name == "close"):
                    out.add(node.name)
                    break
    return out


def parse_suppressions(src: str) -> "tuple[dict[int, set[str]], list[tuple[int, str]]]":
    """``(line -> suppressed rule ids, [(line, error)])``.

    Grammar (one comment suppresses one PHYSICAL line — put it on the
    line the finding reports, i.e. the statement's first line):

        # tpl: disable=TPL001(reason),TPL009(another reason)

    The reason is mandatory; ``TPL001`` or ``TPL001()`` yields a
    TPL000 error instead of a suppression.
    """
    by_line: dict[int, set[str]] = {}
    errors: list[tuple[int, str]] = []
    # Real COMMENT tokens only: the suppression marker inside a string
    # literal (e.g. lint's own error messages) must not suppress.
    try:
        comments = [
            (tok.start[0], tok.string)
            for tok in tokenize.generate_tokens(io.StringIO(src).readline)
            if tok.type == tokenize.COMMENT
        ]
    except (tokenize.TokenError, IndentationError, SyntaxError):
        comments = []
    for lineno, line in comments:
        m = _SUPPRESS_RE.search(line)
        if not m:
            continue
        entries = m.group("entries").strip()
        matched_any = False
        for em in _ENTRY_RE.finditer(entries):
            matched_any = True
            rule, reason = em.group(1), em.group(2)
            if not reason or not reason.strip():
                errors.append((
                    lineno,
                    f"suppression of {rule} without a reason — write "
                    f"`# tpl: disable={rule}(why this line is exempt)`",
                ))
                continue
            by_line.setdefault(lineno, set()).add(rule)
        if not matched_any:
            errors.append((
                lineno,
                f"unparseable tpl suppression {entries!r} — expected "
                "`TPLnnn(reason)` entries",
            ))
    return by_line, errors


def load_baseline(path: "Path | str") -> "set[tuple[str, int, str]]":
    """Baseline file: JSON list of {path, line, rule}. Missing file ==
    empty baseline."""
    if not Path(path).exists():
        return set()
    doc = json.loads(Path(path).read_text())
    if not isinstance(doc, list):
        raise ValueError(f"{path}: baseline must be a JSON list")
    out: "set[tuple[str, int, str]]" = set()
    for rec in doc:
        out.add((str(rec["path"]), int(rec["line"]), str(rec["rule"])))
    return out


def write_baseline(path: "Path | str",
                   findings: "Sequence[Finding]") -> None:
    recs = [
        {"path": f.path, "line": f.line, "rule": f.rule}
        for f in sorted(findings)
    ]
    Path(path).write_text(json.dumps(recs, indent=2) + "\n")


def build_parent_map(tree: ast.AST) -> "dict[ast.AST, ast.AST]":
    parents: dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


class LintEngine:
    def __init__(self, rules: "Iterable[Rule] | None" = None,
                 ctx: "LintContext | None" = None) -> None:
        if rules is None:
            from tpusched.lint.rules import default_rules  # tpl: disable=TPL001(rules imports Finding from engine; importing rules at module top would be a cycle)

            rules = default_rules()
        self.rules = list(rules)
        self.ctx = ctx if ctx is not None else LintContext()

    # -- single-source entry (also the fixture-test entry) -----------

    def lint_text(self, src: str, relpath: str) -> "list[Finding]":
        """Lint one source blob as if it lived at ``relpath`` (POSIX,
        repo-relative — applicability predicates key off it)."""
        relpath = str(PurePosixPath(relpath))
        try:
            tree = ast.parse(src, filename=relpath)
        except SyntaxError as e:
            return [Finding(relpath, int(e.lineno or 1), BAD_SUPPRESSION,
                            f"file does not parse: {e.msg}")]
        suppressed, sup_errors = parse_suppressions(src)
        parents = build_parent_map(tree)
        findings = [
            Finding(relpath, line, BAD_SUPPRESSION, msg)
            for line, msg in sup_errors
        ]
        for rule in self.rules:
            if not rule.applies(relpath):
                continue
            for f in rule.check(tree, src, relpath, self.ctx, parents):
                if rule.rule_id in suppressed.get(f.line, ()):
                    continue
                findings.append(f)
        return sorted(findings)

    # -- filesystem entries ------------------------------------------

    def lint_file(self, path: "Path | str") -> "list[Finding]":
        path = Path(path).resolve()
        try:
            rel = path.relative_to(self.ctx.root).as_posix()
        except ValueError:
            # A basename fallback would fail every path-scoped
            # applies() predicate and report the file CLEAN — a
            # false-green gate for sibling checkouts / CI mounts.
            raise ValueError(
                f"{path} is outside the lint root {self.ctx.root}; "
                "pass a LintContext(root=...) covering it"
            ) from None
        return self.lint_text(path.read_text(), rel)

    def lint_paths(self, paths: "Iterable[Path | str]") -> "list[Finding]":
        findings: list[Finding] = []
        for path in paths:
            path = Path(path)
            if path.is_dir():
                for f in sorted(path.rglob("*.py")):
                    findings.extend(self.lint_file(f))
            else:
                findings.extend(self.lint_file(path))
        return sorted(findings)


def apply_baseline(
    findings: "Sequence[Finding]",
    baseline: "set[tuple[str, int, str]]",
) -> "list[Finding]":
    return [f for f in findings if f.key() not in baseline]
