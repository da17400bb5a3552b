"""Shared linting machinery: the ``Finding`` record, per-rule suppression
comments, file collection, and the per-file runner.

Layer 1 is pure stdlib ``ast`` — no JAX import happens on the analysis path,
so the AST rules run (and fail) fast in CI even when the accelerator stack is
broken. Layer 2 (``--trace``) lives in :mod:`jimm_tpu.lint.trace` and does
import JAX.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import os
import tokenize

#: severity levels; only "error" findings make the CLI exit non-zero
ERROR = "error"
WARNING = "warning"

#: directory names never walked when collecting files from a directory
#: argument (explicitly-listed files are always linted, which is how the
#: test suite points the linter at the deliberately-broken fixtures)
EXCLUDED_DIRS = frozenset({"__pycache__", "lint_fixtures", ".git",
                           ".venv", "build", "dist"})

SUPPRESS_TAG = "jaxlint:"


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""
    rule: str
    severity: str
    path: str
    line: int
    message: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.severity}: " \
               f"{self.message}"


@dataclasses.dataclass(frozen=True)
class Directive:
    """One ``# jaxlint: disable=...`` comment: where it sits, which line it
    applies to, which rules it waives, and the trailing justification text
    (empty string = a bare, unjustified disable — JL020)."""
    lineno: int
    col: int
    target: int
    rules: frozenset[str]
    justification: str


def parse_directives(source: str) -> list[Directive]:
    """Every suppression directive in ``source``, in file order.

    ``# jaxlint: disable=JL004`` (comma-separate for several rules) on a code
    line suppresses those rules on that line; on a standalone comment line it
    suppresses them on the next line. ``disable=all`` suppresses every rule.
    Comments are found with ``tokenize`` so strings containing the marker
    don't count. Text after the rule list is the human justification.
    """
    out: list[Directive] = []
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        comments = [(t.start[0], t.start[1], t.string, t.line)
                    for t in tokens if t.type == tokenize.COMMENT]
    except (tokenize.TokenError, SyntaxError, IndentationError):
        return []
    for lineno, col, text, line in comments:
        body = text.lstrip("#").strip()
        if not body.startswith(SUPPRESS_TAG):
            continue
        directive = body[len(SUPPRESS_TAG):].strip()
        if not directive.startswith("disable="):
            continue
        # everything after "disable=" up to the first space is the rule list;
        # the rest of the comment is the human justification
        parts = directive[len("disable="):].split(None, 1)
        rules = parts[0]
        justification = parts[1].strip() if len(parts) > 1 else ""
        ids = frozenset(r.strip() for r in rules.split(",") if r.strip())
        # a comment-only line (any indentation) targets the next line; a
        # trailing comment targets its own
        standalone = not line[:col].strip()
        target = lineno + 1 if standalone else lineno
        out.append(Directive(lineno, col, target, ids, justification))
    return out


def parse_suppressions(source: str) -> dict[int, frozenset[str]]:
    """Map line number -> rule IDs suppressed there (see
    :func:`parse_directives` for the comment grammar)."""
    suppressed: dict[int, set[str]] = {}
    for d in parse_directives(source):
        suppressed.setdefault(d.target, set()).update(d.rules)
    return {ln: frozenset(ids) for ln, ids in suppressed.items()}


def check_bare_suppressions(source: str, path: str) -> list[Finding]:
    """JL020: a ``# jaxlint: disable=...`` with no trailing justification.
    A suppression is a standing exception to a correctness rule; the
    reviewer three PRs later needs the *why* next to the waiver, not in
    the commit that introduced it."""
    findings = []
    for d in parse_directives(source):
        if d.justification:
            continue
        findings.append(Finding(
            "JL020", WARNING, path, d.lineno,
            f"bare suppression of {', '.join(sorted(d.rules))} with no "
            f"justification — append the reason to the comment "
            f"(# jaxlint: disable={','.join(sorted(d.rules))} <why>); "
            f"audit all waivers with `python -m jimm_tpu.lint "
            f"--suppressions`"))
    return findings


def is_suppressed(finding: Finding,
                  suppressions: dict[int, frozenset[str]]) -> bool:
    ids = suppressions.get(finding.line, frozenset())
    return finding.rule in ids or "all" in ids


def collect_files(paths: list[str]) -> list[str]:
    """Expand path arguments into a sorted list of ``.py`` files. Directories
    are walked (skipping :data:`EXCLUDED_DIRS`); explicit file arguments are
    taken verbatim, excluded or not."""
    out: set[str] = set()
    for p in paths:
        if os.path.isfile(p):
            out.add(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [d for d in dirnames if d not in EXCLUDED_DIRS]
            out.update(os.path.join(dirpath, f) for f in filenames
                       if f.endswith(".py"))
    return sorted(out)


def lint_file(path: str, *, vmem_budget: int | None = None) -> list[Finding]:
    """Run every AST rule over one file; returns unsuppressed findings."""
    from jimm_tpu.lint import rules_ast

    path = str(path)
    try:
        with open(path, encoding="utf-8") as f:
            source = f.read()
    except (OSError, UnicodeDecodeError) as e:
        return [Finding("JL000", ERROR, path, 0, f"unreadable file: {e}")]
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding("JL000", ERROR, path, e.lineno or 0,
                        f"syntax error: {e.msg}")]
    suppressions = parse_suppressions(source)
    findings = rules_ast.run_all(tree, path, vmem_budget=vmem_budget)
    findings += check_bare_suppressions(source, path)
    return [f for f in findings if not is_suppressed(f, suppressions)]


def suppression_audit(paths: list[str]) -> list[tuple[str, int, str, str]]:
    """Every suppression directive under ``paths``:
    (path, line, comma-joined rules, justification) in path order — the
    data behind ``--suppressions``."""
    rows: list[tuple[str, int, str, str]] = []
    for path in collect_files(paths):
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
        except (OSError, UnicodeDecodeError):
            continue
        for d in parse_directives(source):
            rows.append((path, d.lineno, ",".join(sorted(d.rules)),
                         d.justification))
    return rows


def lint_paths(paths: list[str], *,
               vmem_budget: int | None = None) -> list[Finding]:
    findings: list[Finding] = []
    for path in collect_files(paths):
        findings.extend(lint_file(path, vmem_budget=vmem_budget))
    return findings
