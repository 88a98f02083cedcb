"""Framework linter tests: every rule's good/bad fixture pair, exact rule
IDs and line numbers, suppression syntax, and the CLI contract.

The EXPECT harness covers ALL THREE analyzers: per-file lint findings,
whole-program protocheck findings (a proto fixture names its companion
modules with `# protocheck-with: other.py`, so the two-module cases —
sender/handler arity drift — analyze as one program with findings
attributed per file), and lockgraph's interprocedural RTL6xx
verdicts over the same file set."""

import os
import re
import subprocess
import sys

from ray_tpu.devtools import lint, lockgraph, protocheck

FIXTURE_DIR = os.path.join(os.path.dirname(__file__), "lint_fixtures")
_EXPECT_RE = re.compile(r"#\s*EXPECT:\s*([A-Z0-9]+)")
_WITH_RE = re.compile(r"#\s*protocheck-with:\s*([\w.,\s]+)")


def _expected_findings(path):
    """{(line, rule)} declared by `# EXPECT: RTLxxx` markers in a file."""
    out = set()
    with open(path, "r", encoding="utf-8") as f:
        for lineno, line in enumerate(f, start=1):
            for rule in _EXPECT_RE.findall(line):
                out.add((lineno, rule))
    return out


def _companions(path):
    """Fixture files this one analyzes WITH (the whole-program cases)."""
    out = []
    with open(path, "r", encoding="utf-8") as f:
        for line in list(f)[:10]:
            m = _WITH_RE.search(line)
            if m:
                out.extend(
                    os.path.join(FIXTURE_DIR, c.strip())
                    for c in m.group(1).split(",") if c.strip())
    return out


def _fixture_findings(path):
    """{(line, rule)} from all three analyzers, attributed to this
    file."""
    companions = _companions(path)
    got = {(f.line, f.rule) for f in lint.lint_file(path)}
    got |= {(f.line, f.rule)
            for f in protocheck.check_paths([path] + companions)
            if f.path == path}
    got |= {(f.line, f.rule)
            for f in lockgraph.check_paths([path] + companions)
            if f.path == path}
    return got


def _fixture_files():
    return sorted(
        os.path.join(FIXTURE_DIR, name)
        for name in os.listdir(FIXTURE_DIR)
        if name.endswith(".py"))


def test_fixtures_exist_in_good_bad_pairs():
    names = {os.path.basename(p) for p in _fixture_files()}
    bad = {n[len("bad_"):] for n in names if n.startswith("bad_")}
    good = {n[len("good_"):] for n in names if n.startswith("good_")}
    assert bad and bad == good, (bad, good)


def test_every_rule_has_a_firing_fixture():
    covered = set()
    for path in _fixture_files():
        covered.update(rule for _, rule in _expected_findings(path))
    all_rules = (set(lint.RULES) | set(protocheck.RULES)
                 | set(lockgraph.RULES))
    assert covered == all_rules, (
        f"rules without a bad fixture: {all_rules - covered}")


def test_fixture_findings_match_exactly():
    """Findings == EXPECT markers, per file: bad lines fire with the right
    rule ID on the right line, and NOTHING else fires (good files pin the
    negative space)."""
    for path in _fixture_files():
        got = _fixture_findings(path)
        want = _expected_findings(path)
        assert got == want, (
            f"{os.path.basename(path)}: findings {sorted(got)} != "
            f"expected {sorted(want)}")


def test_good_fixtures_are_silent():
    for path in _fixture_files():
        if os.path.basename(path).startswith("good_"):
            assert _fixture_findings(path) == set(), path


def test_noqa_requires_rule_id():
    src = "def f(l):\n    l.my_lock.acquire()  # noqa\n"
    assert [f.rule for f in lint.lint_source(src)] == ["RTL401"]
    src = "def f(l):\n    l.my_lock.acquire()  # noqa: RTL401 -- handoff\n"
    assert lint.lint_source(src) == []
    # Suppressing a DIFFERENT rule does not silence this one.
    src = "def f(l):\n    l.my_lock.acquire()  # noqa: RTL301\n"
    assert [f.rule for f in lint.lint_source(src)] == ["RTL401"]
    # Rationale text without the '--' separator still suppresses.
    src = "def f(l):\n    l.my_lock.acquire()  # noqa: RTL401 handoff\n"
    assert lint.lint_source(src) == []


def test_syntax_error_reports_rtl000():
    findings = lint.lint_source("def broken(:\n", "x.py")
    assert [f.rule for f in findings] == ["RTL000"]


def test_cli_contract_via_python_dash_m():
    """The real `python -m ray_tpu.devtools.lint` entry: exit 1 with rule
    ID + file:line on a bad fixture (one subprocess keeps this cheap; the
    other CLI behaviors are covered in-process below)."""
    bad = os.path.join(FIXTURE_DIR, "bad_lock_acquire.py")
    proc = subprocess.run(
        [sys.executable, "-m", "ray_tpu.devtools.lint", bad],
        capture_output=True, text=True)
    assert proc.returncode == 1
    assert "RTL401" in proc.stdout
    assert re.search(r"bad_lock_acquire\.py:\d+:\d+", proc.stdout)


def test_main_exits_nonzero_with_rule_and_location(capsys):
    bad = os.path.join(FIXTURE_DIR, "bad_bare_except.py")
    assert lint.main([bad]) == 1
    out = capsys.readouterr().out
    assert "RTL301" in out
    assert re.search(r"bad_bare_except\.py:\d+:\d+", out)


def test_main_exits_zero_on_clean_input(capsys):
    good = os.path.join(FIXTURE_DIR, "good_lock_acquire.py")
    assert lint.main([good]) == 0
    assert capsys.readouterr().out.strip() == ""


def test_main_rejects_missing_paths(capsys):
    # A typo'd path must not pass green without linting anything.
    assert lint.main(["no_such_dir/"]) == 2
    assert "no such path" in capsys.readouterr().err


def test_directory_walk_skips_fixture_corpus():
    # The documented `lint tests/` invocation must not drown in the
    # linter's own bad-fixture corpus...
    walk = lint._iter_py_files([os.path.dirname(FIXTURE_DIR)])
    assert not any(os.sep + "lint_fixtures" + os.sep in p for p in walk)
    # ...but naming a fixture file explicitly still lints it.
    bad = os.path.join(FIXTURE_DIR, "bad_bare_except.py")
    assert lint._iter_py_files([bad]) == [bad]


def test_explicit_file_without_py_extension_is_linted(tmp_path):
    script = tmp_path / "extensionless_tool"
    script.write_text("try:\n    pass\nexcept:\n    pass\n")
    findings = lint.lint_paths([str(script)])
    assert [f.rule for f in findings] == ["RTL301"]


def test_main_list_rules(capsys):
    assert lint.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in lint.RULES:
        assert rule_id in out


def test_main_doc_renders_rule_table(capsys):
    assert lint.main(["--doc"]) == 0
    out = capsys.readouterr().out
    assert "| rule | what it catches |" in out
    for rule_id in lint.RULES:
        assert rule_id in out


def test_main_select_runs_rules_individually(capsys):
    bad = os.path.join(FIXTURE_DIR, "bad_lock_acquire.py")
    # The file fires RTL401; selecting it keeps the finding...
    assert lint.main(["--select=RTL401", bad]) == 1
    assert "RTL401" in capsys.readouterr().out
    # ...selecting a different rule silences the run (exit 0)...
    assert lint.main(["--select=RTL301", bad]) == 0
    assert capsys.readouterr().out.strip() == ""
    # ...and a family prefix selects the whole family.
    assert lint.main(["--select=RTL4", bad]) == 1
    assert "RTL401" in capsys.readouterr().out
    # A selector matching NO rule is an error, not a silent green run.
    assert lint.main(["--select=RTL9", bad]) == 2
    assert "matches no rule" in capsys.readouterr().err
