"""parity-coverage: every public Pallas kernel has a jnp twin and a test.

The correctness story of the kernel layer is differential: each
``*_pallas`` entry point is pinned bitwise (or to stated tolerances)
against a pure-jnp twin (``*_jnp`` / ``*_ref``), and a test exercises
both.  A kernel without a twin has no oracle; a kernel no test names by
identifier is a kernel whose parity can silently rot.  Two findings per
kernel are possible:

- **missing twin**: no ``*_jnp``/``*_ref`` definition in the kernels
  package shares the kernel's name tokens.  Matching is by token set
  with the suffix vocabulary ``{pallas, jnp, ref, batch}`` dropped, so
  ``packet_scatter_accum_q8_pallas`` pairs with
  ``packet_scatter_accum_batch_q8_jnp``.
- **missing test**: no file under ``tests/`` references the kernel's
  name (as a bare identifier or attribute) anywhere in its AST.  String
  mentions don't count — the test must actually call or import it.

Scope: public (non-underscore) ``*_pallas`` defs in files under
``src/repro/kernels/`` among the analyzed paths.  Findings anchor at
the kernel's ``def`` line, so a waiver can sit beside a deliberately
twin-less kernel.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Set

from tools.staticcheck import core

RULE = "parity"

KERNELS_PREFIX = "src/repro/kernels/"
_DROP_TOKENS = {"pallas", "jnp", "ref", "batch"}
_TWIN_SUFFIXES = {"jnp", "ref"}


def _tokens(name: str) -> frozenset:
    return frozenset(t for t in name.split("_") if t and t
                     not in _DROP_TOKENS)


def _test_identifiers(root) -> Set[str]:
    """Every identifier referenced anywhere under ``tests/``."""
    names: Set[str] = set()
    tests = root / "tests"
    if not tests.is_dir():
        return names
    skip = core.skip_dirs(root)
    for path in sorted(tests.rglob("*.py")):
        if skip.intersection(path.relative_to(root).parts):
            continue
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Name):
                names.add(node.id)
            elif isinstance(node, ast.Attribute):
                names.add(node.attr)
            elif isinstance(node, ast.ImportFrom):
                names.update(a.name for a in node.names)
    return names


def analyze(project: core.Project) -> List[core.Finding]:
    kernel_files = [sf for sf in project.files
                    if sf.rel.startswith(KERNELS_PREFIX)
                    and sf.tree is not None]
    if not kernel_files:
        return []

    kernels: List[tuple] = []               # (SourceFile, FunctionDef)
    twin_tokens: Dict[frozenset, str] = {}  # token set -> twin name
    for sf in kernel_files:
        for node in ast.walk(sf.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    or node.name.startswith("_"):
                continue
            if node.name.endswith("_pallas"):
                kernels.append((sf, node))
            elif set(node.name.split("_")) & _TWIN_SUFFIXES:
                twin_tokens.setdefault(_tokens(node.name), node.name)

    findings: List[core.Finding] = []
    tested = _test_identifiers(project.root)
    for sf, fn in kernels:
        toks = _tokens(fn.name)
        if toks not in twin_tokens:
            findings.append(core.Finding(
                RULE, sf.rel, fn.lineno,
                f"kernel `{fn.name}` has no jnp twin: no `*_jnp`/`*_ref` "
                f"definition in {KERNELS_PREFIX} shares its name tokens "
                f"— every Pallas kernel needs a pure-jnp oracle"))
        if fn.name not in tested:
            findings.append(core.Finding(
                RULE, sf.rel, fn.lineno,
                f"kernel `{fn.name}` is referenced by no file under "
                f"tests/ — add a parity test pinning it against "
                f"`{twin_tokens.get(toks, 'its jnp twin')}`"))
    return findings
