"""docs: intra-repo documentation links, section cites, config coverage.

The former standalone ``tools/check_doc_links.py`` (CI docs job),
rehomed as a staticcheck analyzer so one CLI carries every repo
invariant with shared reporting and exit-code plumbing.  The standalone
script remains as a thin shim over this module, preserving its
``check(root) -> list[str]`` API and output format for the existing CI
step and tests/test_docs.py.

Three reference classes are validated (history: for two PRs
``core/simnet.py`` cited an ``EXPERIMENTS.md §Paper-validation`` that
did not exist):

1. **Markdown links** ``[text](path)`` in every ``*.md`` file must
   resolve to an existing file or directory (anchors stripped;
   http/https/mailto ignored).
2. **Doc-section citations**: any ``SOMEDOC.md`` occurrence in source
   or docs must name a repo-root file (or, written with its path, a
   file at that path from the root), and ``SOMEDOC.md §Section`` must
   match one of its ``## §...`` headings.
3. **EngineConfig coverage**: every field of the ``EngineConfig``
   dataclass (parsed from ``src/repro/core/server.py`` with ``ast``)
   must appear as `` `field` `` in README.md.

Unlike the legacy script this emits per-occurrence line numbers, so CI
annotations land on the offending line.  Cite scanning skips this
module and the shim (their docstrings quote dangling examples) and the
fixture corpus (whose files are deliberately broken).
"""
from __future__ import annotations

import ast
import functools
import pathlib
import re
from typing import List

from tools.staticcheck import core

RULE = "docs"

MD_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
# a cite is a bare root doc (``DESIGN.md``) or a path to one
# (``.claude/skills/verify/SKILL.md``), resolved from the repo root
DOC_CITE = re.compile(r"(?<![\w./-])((?:[\w.-]+/)*[A-Z][A-Z0-9_]*\.md)"
                      r"(?:\s+§([A-Za-z0-9][\w-]*))?")
HEADING = re.compile(r"^#{1,6}\s", re.M)

# docstrings here quote dangling references as examples — not cites
_EXCLUDE_CITES = {"tools/check_doc_links.py", "tools/staticcheck/docs.py"}


def _files(root: pathlib.Path, suffix: str):
    skip = core.skip_dirs(root)
    for p in sorted(root.rglob(f"*{suffix}")):
        if not skip.intersection(p.relative_to(root).parts):
            yield p


@functools.lru_cache(maxsize=None)   # each doc is cited many times
def _headings(md_path: pathlib.Path) -> str:
    return "\n".join(line for line in md_path.read_text().splitlines()
                     if HEADING.match(line))


def _engine_config_fields(root: pathlib.Path) -> list:
    """Field names of EngineConfig, read syntactically (no jax import)."""
    src = root / "src" / "repro" / "core" / "server.py"
    if not src.exists():
        return []
    tree = ast.parse(src.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "EngineConfig":
            return [stmt.target.id for stmt in node.body
                    if isinstance(stmt, ast.AnnAssign)
                    and isinstance(stmt.target, ast.Name)]
    return []


def _line_of(text: str, pos: int) -> int:
    return text.count("\n", 0, pos) + 1


def check_root(root) -> List[core.Finding]:
    root = pathlib.Path(root).resolve()
    findings: List[core.Finding] = []

    def emit(rel, line, msg):
        findings.append(core.Finding(RULE, str(rel), line, msg))

    readme = root / "README.md"
    if readme.exists():
        text = readme.read_text()
        for field in _engine_config_fields(root):
            if f"`{field}`" not in text:
                emit("README.md", 1, f"EngineConfig field `{field}` "
                                     f"is not documented")

    for md in _files(root, ".md"):
        rel = md.relative_to(root).as_posix()
        text = md.read_text()
        for m in MD_LINK.finditer(text):
            target = m.group(1).split("#")[0]
            if not target or "://" in target or target.startswith("mailto:"):
                continue
            if not (md.parent / target).exists():
                emit(rel, _line_of(text, m.start()),
                     f"broken link -> {m.group(1)}")

    for src in list(_files(root, ".py")) + list(_files(root, ".md")):
        rel = src.relative_to(root).as_posix()
        if rel in _EXCLUDE_CITES:
            continue
        text = src.read_text()
        for m in DOC_CITE.finditer(text):
            doc, section = m.groups()
            doc_path = root / doc
            line = _line_of(text, m.start())
            if not doc_path.exists():
                emit(rel, line, f"cites missing doc {doc}")
                continue
            if section is None:
                continue
            # (?![\w-]) so a prefix cite (`§Arch` vs `§Arch-applicability`)
            # is still flagged as dangling
            if not re.search(rf"§{re.escape(section)}(?![\w-])",
                             _headings(doc_path)):
                emit(rel, line, f"cites {doc} §{section} "
                                f"but no such heading exists")
    return findings


def check(root) -> list:
    """Legacy API: the flat ``path: message`` strings the old script and
    tests/test_docs.py consume, in the old ordering."""
    found = check_root(root)
    # legacy order: README coverage, md links, cites — check_root
    # already emits in that order
    return [f"{f.path}: {f.message}" for f in found]


def analyze(project: core.Project) -> List[core.Finding]:
    return check_root(project.root)
