"""Shared plumbing for the repo-native static analyzers (DESIGN.md §13).

The engine's performance story rests on conventions nothing in pytest
can see: donated accumulators must never be read after dispatch, the
compiled round must stay free of host syncs outside the intentional
overlap barriers, every Pallas call site must satisfy its own aliasing
and arity contract, and every kernel must keep a pinned jnp twin.  This
module holds the pieces every analyzer shares:

- ``Finding``: one rule violation at one source line.
- ``SourceFile``: a parsed file plus its waiver table.  A waiver is the
  inline comment ``# staticcheck: allow(rule) — reason`` (also accepted:
  ``allow(rule1, rule2)``, ``--`` or ``:`` as the separator).  Placed on
  its own line it waives the next code line; a waiver without a reason
  is NOT honoured — every intentional violation must say why.
- ``Project``: the file set under the paths given on the CLI.
- small ``ast`` helpers (dotted-name rendering, keyword lookup, literal
  int decoding) used by every analyzer.

Everything here is stdlib-only and never imports jax — the suite must
run in the docs/CI lane where jax is absent (tests/test_staticcheck.py
proves it with a poisoned ``jax`` module on PYTHONPATH).
"""
from __future__ import annotations

import ast
import dataclasses
import pathlib
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set

# one bit per rule: the runner's exit status is the OR of the bits of
# every rule with an unwaived finding, so a CI log shows *which*
# invariant broke before anyone opens the JSON report
RULE_BITS = {
    "donation": 1,
    "hostsync": 2,
    "pallas": 4,
    "parity": 8,
    "determinism": 16,
    "docs": 32,
    "syntax": 64,        # unparseable file (every analyzer is blind to it)
}

# directories never scanned when a CLI path is expanded (explicitly
# listed files are always scanned — the fixture corpus relies on that)
SKIP_DIRS = {".git", "__pycache__", ".pytest_cache", "fixtures"}


def skip_dirs(root: pathlib.Path) -> Set[str]:
    """``SKIP_DIRS`` plus every directory the root ``.gitignore`` lists
    as ``name/``: what git never commits (caches, local checkouts of
    the repo) is not the repo's code or docs."""
    ignore = pathlib.Path(root) / ".gitignore"
    lines = ignore.read_text().splitlines() if ignore.is_file() else []
    return SKIP_DIRS | {ln.strip()[:-1] for ln in lines
                        if re.fullmatch(r"[\w.-]+/", ln.strip())}

WAIVER_RE = re.compile(
    r"#\s*staticcheck:\s*allow\(\s*([\w\s,-]+?)\s*\)\s*"
    r"(?:(?:[—–:]|--)\s*(\S.*))?$")


@dataclasses.dataclass
class Finding:
    """One rule violation, anchored to a repo-relative path and line."""
    rule: str
    path: str
    line: int
    message: str
    waived: bool = False
    reason: Optional[str] = None          # the waiver's reason when waived

    def render(self) -> str:
        tail = f"  [waived: {self.reason}]" if self.waived else ""
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}{tail}"


@dataclasses.dataclass
class Waiver:
    rules: Set[str]
    reason: Optional[str]
    line: int


class SourceFile:
    """One parsed ``.py`` file plus its per-line waiver table."""

    def __init__(self, path: pathlib.Path, root: pathlib.Path):
        self.path = path
        self.rel = path.relative_to(root).as_posix()
        self.text = path.read_text()
        self.lines = self.text.splitlines()
        self.error: Optional[SyntaxError] = None
        self.tree: Optional[ast.Module] = None
        try:
            self.tree = ast.parse(self.text)
        except SyntaxError as e:          # surfaced as a `syntax` finding
            self.error = e
        self.waivers = self._parse_waivers()

    def _parse_waivers(self) -> Dict[int, Waiver]:
        """line -> waiver.  An inline waiver covers its own line; a
        waiver on a comment-only line covers the next code line (blank
        and comment lines in between are skipped)."""
        table: Dict[int, Waiver] = {}
        pending: Optional[Waiver] = None
        for i, line in enumerate(self.lines, 1):
            m = WAIVER_RE.search(line)
            if m:
                rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
                w = Waiver(rules, m.group(2), i)
                if line[:m.start()].strip():
                    table[i] = w          # inline: waives this line
                else:
                    pending = w           # own line: waives the next one
                continue
            if pending and line.strip() and not line.strip().startswith("#"):
                table[i] = pending
                pending = None
        return table


class Project:
    """The file set one runner invocation analyzes.

    ``paths`` are repo-root-relative files or directories; directories
    expand to every ``*.py`` under them minus ``skip_dirs``.  Explicit
    file paths are never filtered, so the fixture corpus under
    ``tests/fixtures/`` can be analyzed one file at a time.
    """

    def __init__(self, root, paths: Optional[Sequence[str]] = None):
        self.root = pathlib.Path(root).resolve()
        targets = [self.root / p for p in paths] if paths else [self.root]
        skip = skip_dirs(self.root)
        ordered: List[pathlib.Path] = []
        seen: Set[pathlib.Path] = set()
        for t in targets:
            found = [t] if t.is_file() else sorted(t.rglob("*.py"))
            for p in found:
                rel = p.relative_to(self.root)
                if (p.is_file() is False
                        or (not t.is_file()
                            and skip.intersection(rel.parts))
                        or p in seen):
                    continue
                seen.add(p)
                ordered.append(p)
        self.files = [SourceFile(p, self.root) for p in ordered]
        self._by_rel = {sf.rel: sf for sf in self.files}

    def file(self, rel: str) -> Optional[SourceFile]:
        return self._by_rel.get(rel)


def apply_waivers(project: Project, findings: Iterable[Finding]) -> None:
    """Mark findings covered by a waiver on their line.  A matching
    waiver with no reason does NOT suppress — the finding stays live and
    says so, enforcing the every-waiver-carries-a-reason rule."""
    for f in findings:
        sf = project.file(f.path)
        if sf is None:
            continue                      # e.g. docs findings in .md files
        w = sf.waivers.get(f.line)
        if w is None or f.rule not in w.rules:
            continue
        if w.reason:
            f.waived, f.reason = True, w.reason
        else:
            f.message += (" [waiver present but carries no reason — "
                          "not honoured]")


def exit_code(findings: Iterable[Finding]) -> int:
    code = 0
    for f in findings:
        if not f.waived:
            code |= RULE_BITS.get(f.rule, 0)
    return code


# --------------------------------------------------------------------------
# ast helpers shared by the analyzers
# --------------------------------------------------------------------------

def dotted(node) -> Optional[str]:
    """Render a Name/Attribute chain as ``a.b.c`` (None if not a chain)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def last_segment(name: Optional[str]) -> Optional[str]:
    return name.rsplit(".", 1)[-1] if name else None


def keyword(call: ast.Call, name: str) -> Optional[ast.expr]:
    for k in call.keywords:
        if k.arg == name:
            return k.value
    return None


def int_literal(node) -> Optional[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    return None


def int_tuple(node) -> Optional[tuple]:
    """Decode an int or a literal tuple/list of ints (donate_argnums)."""
    one = int_literal(node)
    if one is not None:
        return (one,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            v = int_literal(elt)
            if v is None:
                return None
            out.append(v)
        return tuple(out)
    return None


def local_assignments(scope) -> Dict[str, ast.expr]:
    """name -> last assigned value among the scope's own statements
    (nested function bodies are not descended into)."""
    table: Dict[str, ast.expr] = {}

    def visit(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda, ast.ClassDef)):
                continue
            if isinstance(child, ast.Assign) and len(child.targets) == 1 \
                    and isinstance(child.targets[0], ast.Name):
                table[child.targets[0].id] = child.value
            visit(child)

    visit(scope)
    return table


def function_defs(tree) -> Dict[str, List[ast.FunctionDef]]:
    """Every (possibly nested) function definition in a module, by name."""
    defs: Dict[str, List[ast.FunctionDef]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)
    return defs
