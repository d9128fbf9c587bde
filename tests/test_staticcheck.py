"""The staticcheck analyzers are live, waivable, and jax-free.

Three layers (DESIGN.md §13):

1. **Fixture corpus** (tests/fixtures/staticcheck/): each rule fires
   exactly once on its minimal bad snippet, the reasoned-waiver twin
   silences it, and a waiver *without* a reason is not honoured.
2. **Rule mechanics** on tmp_path mini-repos for the root-scoped rules
   (parity, docs) and for the shared plumbing (waiver parsing, exit
   bits, JSON report).
3. **Hermeticity**: the full CLI runs the acceptance command in a
   subprocess with a poisoned ``jax`` module first on PYTHONPATH and
   still exits 0 — the analyzers never import jax.

Everything here is stdlib + the analyzers themselves: this file is
tier-1 and runs in the no-jax docs lane.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import tools.staticcheck as sc                      # noqa: E402
from tools.staticcheck import core, docs            # noqa: E402

FIX = "tests/fixtures/staticcheck"


def _run_on(relpath, rule):
    return sc.run(core.Project(REPO, [relpath]), [rule])


# -- each rule fires exactly once on its bad fixture -----------------------

@pytest.mark.parametrize("rule,fixture,needle", [
    ("donation", f"{FIX}/donation_bad.py", "read after being donated"),
    ("hostsync", f"{FIX}/hostsync_bad.py", "float() cast inside traced"),
    ("hostsync", f"{FIX}/hostsync_hot_bad.py", "device-hot module"),
    ("pallas", f"{FIX}/pallas_bad.py", "value 1 is out of range"),
    ("determinism", f"{FIX}/determinism_bad.py", "wall-clock"),
])
def test_rule_fires_exactly_once(rule, fixture, needle):
    found = _run_on(fixture, rule)
    assert len(found) == 1, [f.render() for f in found]
    f = found[0]
    assert f.rule == rule and not f.waived
    assert needle in f.message
    assert core.exit_code(found) == core.RULE_BITS[rule]


@pytest.mark.parametrize("rule,fixture", [
    ("donation", f"{FIX}/donation_waived.py"),
    ("hostsync", f"{FIX}/hostsync_waived.py"),
    ("pallas", f"{FIX}/pallas_waived.py"),
    ("determinism", f"{FIX}/determinism_waived.py"),
])
def test_reasoned_waiver_silences(rule, fixture):
    found = _run_on(fixture, rule)
    assert len(found) == 1
    f = found[0]
    assert f.waived and f.reason and "fixture" in f.reason
    assert core.exit_code(found) == 0


def test_waiver_without_reason_not_honoured():
    found = _run_on(f"{FIX}/hostsync_waiver_noreason.py", "hostsync")
    assert len(found) == 1
    f = found[0]
    assert not f.waived
    assert "carries no reason" in f.message
    assert core.exit_code(found) == core.RULE_BITS["hostsync"]


def test_donation_rebind_is_clean():
    assert _run_on(f"{FIX}/donation_rebound.py", "donation") == []


# -- parity rule on tmp mini-repos -----------------------------------------

_KERNEL = "def foo_accum_pallas(x):\n    return x\n"
_TWIN = "def foo_accum_jnp(x):\n    return x\n"


def _mini_repo(tmp_path, kernel_src, test_src=None):
    kdir = tmp_path / "src" / "repro" / "kernels"
    kdir.mkdir(parents=True)
    (kdir / "foo.py").write_text(kernel_src)
    (tmp_path / "tests").mkdir()
    if test_src is not None:
        (tmp_path / "tests" / "test_foo.py").write_text(test_src)
    return core.Project(tmp_path, ["src"])


def test_parity_missing_test_fires_once(tmp_path):
    found = sc.run(_mini_repo(tmp_path, _KERNEL + _TWIN), ["parity"])
    assert len(found) == 1
    assert "referenced by no file under tests/" in found[0].message
    assert "foo_accum_jnp" in found[0].message      # names the twin to pin


def test_parity_missing_twin_fires_once(tmp_path):
    found = sc.run(
        _mini_repo(tmp_path, _KERNEL, "from x import foo_accum_pallas\n"),
        ["parity"])
    assert len(found) == 1
    assert "has no jnp twin" in found[0].message


def test_parity_batch_token_normalization(tmp_path):
    # the repo's real naming: *_q8_pallas twins with *_batch_q8_jnp
    found = sc.run(_mini_repo(
        tmp_path,
        "def foo_q8_pallas(x):\n    return x\n"
        "def foo_batch_q8_jnp(x):\n    return x\n",
        "from x import foo_q8_pallas\n"), ["parity"])
    assert found == []


def test_parity_covered_kernel_is_clean(tmp_path):
    found = sc.run(
        _mini_repo(tmp_path, _KERNEL + _TWIN,
                   "from x import foo_accum_pallas\n"), ["parity"])
    assert found == []


def test_parity_waivable_at_def_line(tmp_path):
    src = ("# staticcheck: allow(parity) — fixture: twin-less by design\n"
           + _KERNEL)
    found = sc.run(_mini_repo(tmp_path, src), ["parity"])
    assert len(found) == 2                  # missing twin + missing test
    assert all(f.waived for f in found)
    assert core.exit_code(found) == 0


# -- docs rule on a tmp mini-repo ------------------------------------------

def test_docs_rule_line_numbers(tmp_path):
    # name assembled at runtime so the repo-wide cite scan (which reads
    # this very file) doesn't see a doc reference in the literal
    doc = "NOTES" + ".md"
    (tmp_path / doc).write_text(
        "# notes\n\nfine text\n\nsee [the missing file](nope.md)\n")
    found = docs.check_root(tmp_path)
    assert len(found) == 1
    assert found[0].rule == "docs" and found[0].line == 5
    assert "broken link -> nope.md" in found[0].message
    # legacy string API (tools/check_doc_links.py shim) is stable
    assert docs.check(tmp_path) == [f"{doc}: broken link -> nope.md"]


def test_gitignored_dirs_are_not_scanned(tmp_path):
    """A directory .gitignore lists (a cache, a local checkout of the
    repo) holds nothing git commits, so no rule reads it."""
    doc = "NOTES" + ".md"
    for d in ("kept", ".local"):
        (tmp_path / d).mkdir()
        (tmp_path / d / doc).write_text("[gone](nope.md)\n")
        (tmp_path / d / "bad.py").write_text("def f(:\n")
    (tmp_path / ".gitignore").write_text("# local\n.local/\n*.pyc\n")
    assert core.skip_dirs(tmp_path) == core.SKIP_DIRS | {".local"}
    assert [f.path for f in docs.check_root(tmp_path)] == [f"kept/{doc}"]
    assert [f.rel for f in core.Project(tmp_path).files] == ["kept/bad.py"]


def test_docs_cite_with_path_resolves_from_root(tmp_path):
    doc = "NOTES" + ".md"
    (tmp_path / "sub").mkdir()
    (tmp_path / "sub" / doc).write_text("# notes\n")
    (tmp_path / "a.py").write_text(f"# see sub/{doc}\n# see {doc}\n")
    found = docs.check_root(tmp_path)
    assert [(f.path, f.line) for f in found] == [("a.py", 2)]


# -- shared plumbing -------------------------------------------------------

def test_rule_bits_are_distinct_powers_of_two():
    bits = list(core.RULE_BITS.values())
    assert len(set(bits)) == len(bits)
    assert all(b & (b - 1) == 0 for b in bits)


def test_waiver_regex_forms():
    m = core.WAIVER_RE.search(
        "x()  # staticcheck: allow(hostsync) — final flush")
    assert m and m.group(1) == "hostsync" and m.group(2) == "final flush"
    m = core.WAIVER_RE.search("# staticcheck: allow(pallas, docs) -- why")
    assert m and set(m.group(1).replace(" ", "").split(",")) == \
        {"pallas", "docs"} and m.group(2) == "why"
    m = core.WAIVER_RE.search("# staticcheck: allow(donation)")
    assert m and m.group(2) is None


def test_syntax_error_surfaces_as_finding(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def oops(:\n")
    found = sc.run(core.Project(tmp_path, ["broken.py"]), [])
    assert len(found) == 1 and found[0].rule == "syntax"
    assert core.exit_code(found) == core.RULE_BITS["syntax"]


def test_render_format():
    f = core.Finding("donation", "a/b.py", 7, "msg")
    assert f.render() == "a/b.py:7: [donation] msg"


def test_cli_json_report(tmp_path, capsys):
    report = tmp_path / "report.json"
    code = sc.main(["--root", str(REPO), "--rules", "determinism",
                    "--json", str(report), f"{FIX}/determinism_bad.py"])
    assert code == core.RULE_BITS["determinism"]
    payload = json.loads(report.read_text())
    assert payload["exit_code"] == code
    assert payload["counts"] == {"total": 1, "waived": 0}
    (entry,) = payload["findings"]
    assert entry["rule"] == "determinism" and not entry["waived"]
    out = capsys.readouterr().out
    assert f"{FIX}/determinism_bad.py" in out and "staticcheck: 1" in out


def test_cli_rejects_unknown_rule():
    with pytest.raises(SystemExit):
        sc.main(["--rules", "nonsense"])


def test_cli_list_rules(capsys):
    assert sc.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in core.RULE_BITS:
        assert rule in out


# -- hermeticity: the acceptance command runs with jax poisoned ------------

def test_cli_clean_on_repo_without_importing_jax(tmp_path):
    poison = tmp_path / "poison"
    poison.mkdir()
    (poison / "jax.py").write_text(
        "raise ImportError('staticcheck must not import jax')\n")
    env = dict(os.environ, PYTHONPATH=str(poison))
    # the poison actually poisons
    probe = subprocess.run([sys.executable, "-c", "import jax"],
                           env=env, capture_output=True, text=True)
    assert probe.returncode != 0
    proc = subprocess.run(
        [sys.executable, "-m", "tools.staticcheck",
         "src", "tools", "benchmarks", "examples"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout
