"""Whole-program conformance checker tests: the seeded-mutation battery
(each protocol-breaking edit to a COPY of the real tree produces exactly
the expected finding), the catalog's agreement with the shipped code and
the lockcheck-pinned leaf conventions, and the CLI contract.

The fixture-level EXPECT coverage for RTL500–505 lives in
test_devtools_lint.py (the shared harness); this file owns the
whole-tree properties."""

import os
import re
import shutil
import subprocess
import sys

import pytest

import ray_tpu
from ray_tpu._private import object_transfer, protocol
from ray_tpu.devtools import protocheck

PKG_DIR = os.path.dirname(os.path.abspath(ray_tpu.__file__))


# -- catalog sanity ---------------------------------------------------------

def test_catalog_shape():
    roles = set()
    for verb, spec in protocol.VERBS.items():
        assert re.match(r"^[a-z][a-z0-9_]*$", verb), verb
        assert spec.senders and spec.handlers, verb
        roles.update(spec.senders)
        roles.update(spec.handlers)
        if spec.arity is not None:
            lo, hi = spec.arity
            assert 1 <= lo <= hi, verb
        assert spec.doc, f"{verb}: every catalog verb carries a doc line"
    assert roles <= {"head", "worker", "client", "agent", "objsrv"}


def test_catalog_caps_match_advertised_caps():
    """The verbs the catalog marks object_caps-gated are EXACTLY the
    verbs the object server advertises out of band — a new advertised
    verb must enter the catalog as gated, and vice versa."""
    gated = {v for v, spec in protocol.VERBS.items()
             if spec.caps == "object_caps"}
    assert gated == set(object_transfer.CAPS)


def test_readme_verb_table_matches_generated_doc():
    """The README says its wire-protocol table 'cannot drift from the
    code' — make that true: the pasted table must equal
    `protocheck --doc` byte-for-byte (regenerate with
    `python -m ray_tpu.devtools.protocheck --doc` after editing
    protocol.VERBS)."""
    readme = os.path.join(os.path.dirname(PKG_DIR), "README.md")
    with open(readme, "r", encoding="utf-8") as f:
        content = f.read()
    assert protocheck.catalog_doc() in content, (
        "README.md's verb table is stale — regenerate it with "
        "`python -m ray_tpu.devtools.protocheck --doc`")


def test_lock_graph_agrees_with_lockcheck_leaf_conventions():
    """Every independent-leaf convention pinned dynamically in
    test_lockcheck.py is ALSO declared statically ('# lock-order: leaf')
    where the lock is created, so RTL505 enforces it on paths the
    runtime checker never executes."""
    analysis = protocheck.Analysis([PKG_DIR])
    leaves = set()
    for mod in analysis.modules:
        base = os.path.basename(mod.path)
        for cls in mod.classes:
            for attr, (_line, leaf) in cls.lock_attrs.items():
                if leaf:
                    leaves.add((base, cls.name, attr))
        for name, (_line, leaf) in mod.module_locks.items():
            if leaf:
                leaves.add((base, None, name))
    expected = {
        ("object_transfer.py", "PullRegistry", "_lock"),
        ("object_transfer.py", "PutRegistry", "_lock"),
        ("object_transfer.py", "_PoolHost", "_lock"),
        ("recovery.py", "LineageTable", "_lock"),
        ("runtime.py", "Runtime", "_dispatch_dirty_lock"),
        ("streaming_executor.py", "StreamingStats", "_lock"),
        ("batching.py", "_Batcher", "_lock"),
        ("continuous.py", "_ContinuousBatcher", "_lock"),
        ("shm_store.py", "ShmStore", "_lock"),
        ("shm_store.py", None, "_copy_pool_lock"),
        ("shuffle.py", None, "_STATS_LOCK"),
    }
    missing = expected - leaves
    assert not missing, (
        f"lockcheck-pinned leaves without a static '# lock-order: leaf' "
        f"annotation: {sorted(missing)}")


# -- seeded mutations -------------------------------------------------------

def _mutate(pkg: str, rel: str, old: str, new: str):
    path = os.path.join(pkg, rel)
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    assert old in src, f"mutation anchor vanished from {rel}: {old!r}"
    with open(path, "w", encoding="utf-8") as f:
        f.write(src.replace(old, new, 1))
    return path, src


def test_seeded_mutations_each_produce_the_expected_finding(tmp_path):
    """The acceptance battery: deleting one handler arm, widening one
    sender tuple, dropping one caps guard, and dropping one counter from
    the serve rollup each produce exactly the expected finding class on
    an otherwise-clean copy of the shipped tree.  (What a worker
    inherits from Config is derived, not checked: test_config_env.py.)"""
    pkg = str(tmp_path / "ray_tpu")
    shutil.copytree(PKG_DIR, pkg,
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert protocheck.check_paths([pkg]) == [], \
        "the copied tree must be clean before any mutation"

    def run():
        return protocheck.check_paths([pkg])

    # 1. Delete a handler arm: the lease_renew verb loses its only head
    #    handler -> RTL501 missing-handler anchored at a sender.
    path, orig = _mutate(
        pkg, "_private/runtime.py",
        'elif tag == "lease_renew":', 'elif tag == "lease_renew_gone":')
    findings = run()
    assert any(f.rule == "RTL501" and "lease_renew" in f.message
               and "handles it" in f.message for f in findings), findings
    # (The renamed arm itself is also flagged as an unknown verb.)
    assert any(f.rule == "RTL501" and "lease_renew_gone" in f.message
               for f in findings), findings
    with open(path, "w", encoding="utf-8") as f:
        f.write(orig)

    # 2. Widen a sender tuple beyond the catalog arity -> RTL502 at the
    #    send site.
    path, orig = _mutate(
        pkg, "_private/worker_main.py",
        '("actor_token_new", actor_id, token)',
        '("actor_token_new", actor_id, token, 0)')
    findings = run()
    assert any(f.rule == "RTL502" and "actor_token_new" in f.message
               and "arity 4" in f.message for f in findings), findings
    with open(path, "w", encoding="utf-8") as f:
        f.write(orig)

    # 3. Drop the caps guard off the striped-fetch path -> RTL503 on the
    #    fetch_range sends (PR 3's "never probe an old peer").
    path, orig = _mutate(
        pkg, "_private/object_transfer.py",
        'if "fetch_range" in caps and self._stripe > 0:',
        'if self._stripe > 0:')
    findings = run()
    assert any(f.rule == "RTL503" and "fetch_range" in f.message
               for f in findings), findings
    with open(path, "w", encoding="utf-8") as f:
        f.write(orig)

    # 4. Drop a serving-memory counter from the controller rollup ->
    #    RTL504 anchored at the batcher/engine stats dict that ships it
    #    (the serve-plane twin of the xfer-stats survival rule).
    # cow_copies, not prefix_hits: the rule is name-granular and
    # prefix_hits now legitimately appears at three rollup sites (the
    # sum, the per-pool breakdown, the _router sub-dict) — any one of
    # them keeps the name visible, so a single-site drop can't fire.
    path, orig = _mutate(
        pkg, "serve/api.py", '"cow_copies",', '')
    findings = run()
    assert any(f.rule == "RTL504" and "cow_copies" in f.message
               and "rollup" in f.message for f in findings), findings
    with open(path, "w", encoding="utf-8") as f:
        f.write(orig)

    assert run() == [], "restores must return the copy to clean"


# -- CLI contract -----------------------------------------------------------

def test_cli_exits_nonzero_on_bad_fixture_with_rule_and_line():
    """The real `python -m ray_tpu.devtools.protocheck` entry on a bad
    fixture: exit 1 with the pinned rule ID and file:line (one
    subprocess keeps this cheap; other CLI behaviors run in-process)."""
    bad = os.path.join(os.path.dirname(__file__), "lint_fixtures",
                       "bad_proto_caps.py")
    proc = subprocess.run(
        [sys.executable, "-m", "ray_tpu.devtools.protocheck", bad],
        capture_output=True, text=True)
    assert proc.returncode == 1
    assert "RTL503" in proc.stdout
    assert re.search(r"bad_proto_caps\.py:13:", proc.stdout)


def test_cli_doc_renders_catalog_table():
    proc = subprocess.run(
        [sys.executable, "-m", "ray_tpu.devtools.protocheck", "--doc"],
        capture_output=True, text=True)
    assert proc.returncode == 0
    assert "| verb | senders | handlers |" in proc.stdout
    for verb in ("exec", "fetch_range", "lease_req", "put_commit"):
        assert f"| `{verb}` |" in proc.stdout
    # Caps-gated verbs carry their family in the table.
    assert "object_caps" in proc.stdout


def test_main_select_filters_rules(tmp_path, capsys):
    bad = tmp_path / "bad_select.py"
    bad.write_text(
        "# protocheck: role=head\n"
        "from ray_tpu._private import protocol\n\n\n"
        "def f(conn, rid):\n"
        '    protocol.send(conn, ("repyl", rid))\n')
    assert protocheck.main([str(bad)]) == 1
    assert "RTL501" in capsys.readouterr().out
    # Selecting a different family silences this finding.
    assert protocheck.main([f"--select=RTL505", str(bad)]) == 0
    assert capsys.readouterr().out.strip() == ""


def test_main_rejects_unknown_select(capsys):
    # A typo'd selector must not filter every finding and exit green.
    assert protocheck.main(["--select=RTL55", PKG_DIR]) == 2
    assert "matches no rule" in capsys.readouterr().err


def test_main_exit_codes(capsys):
    assert protocheck.main([]) == 2
    capsys.readouterr()
    assert protocheck.main(["no_such_dir/"]) == 2
    assert "no such path" in capsys.readouterr().err
    assert protocheck.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in protocheck.RULES:
        assert rule_id in out


def test_reasonless_protocheck_suppression_is_flagged(tmp_path, capsys):
    bad = tmp_path / "bad_noqa.py"
    bad.write_text(
        "# protocheck: role=head\n"
        "from ray_tpu._private import protocol\n\n\n"
        "def f(conn, rid):\n"
        '    protocol.send(conn, ("repyl", rid))  # noqa: RTL501\n')
    findings = protocheck.check_paths([str(bad)])
    assert [f.rule for f in findings] == ["RTL500"]
    # With a reason, the suppression stands.
    bad.write_text(
        "# protocheck: role=head\n"
        "from ray_tpu._private import protocol\n\n\n"
        "def f(conn, rid):\n"
        '    protocol.send(conn, ("repyl", rid))  # noqa: RTL501 -- deliberate interop probe\n')
    assert protocheck.check_paths([str(bad)]) == []
