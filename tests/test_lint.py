"""sparklint suite: fixture-driven true-positive/true-negative pairs
per rule, the three shipped-regression reproductions, suppression,
CLI contract (exit codes, --json schema, unknown-rule refusal), and
the full-tree cleanliness + wall gate.

Named test_lint so it sorts before the tier-1 timeout cutoff.
"""

import json
import os
import subprocess
import sys
import time

import pytest

import sparktorch_tpu
from sparktorch_tpu.lint import ALL_RULES, rules_by_selector
from sparktorch_tpu.lint.core import (
    PARSE_RULE_ID,
    lint_file,
    package_rel,
    run_lint,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "lint")
PKG_DIR = os.path.dirname(os.path.abspath(sparktorch_tpu.__file__))


def fx(name):
    return os.path.join(FIXTURES, name)


def counts(findings):
    out = {}
    for f in findings:
        out[f.rule] = out.get(f.rule, 0) + 1
    return out


# Exact per-fixture expectations: the counter pins BOTH that the rule
# catches its bug class and that no other rule adds noise on the same
# file.
TRUE_POSITIVES = {
    "buslock_percentile_tp.py": {"SPK301": 1},
    "event_kind_tp.py": {"SPK106": 3},
    "stopped_coord_tp.py": {"SPK501": 1},
    "timing_tp.py": {"SPK201": 3},
    "retrace_tp.py": {"SPK401": 3},
    "collective_tp.py": {"SPK402": 2},
    "obs_misc_tp.py": {"SPK101": 1, "SPK102": 1, "SPK103": 1,
                       "SPK104": 1, "SPK105": 1},
    "profiler_api_tp.py": {"SPK107": 3},
    "async_fetch_tp.py": {"SPK108": 4},
    "skew_stamp_tp.py": {"SPK201": 2, "SPK108": 2},
}

TRUE_NEGATIVES = [
    "buslock_percentile_tn.py",
    "event_kind_tn.py",
    "stopped_coord_tn.py",
    "timing_tn.py",
    "retrace_tn.py",
    "collective_tn.py",
    "obs_misc_tn.py",
    "profiler_api_tn.py",
    "async_fetch_tn.py",
    "skew_stamp_tn.py",
    "suppressed_ok.py",
]


def test_registry_stable():
    ids = [r.id for r in ALL_RULES]
    slugs = [r.slug for r in ALL_RULES]
    assert len(set(ids)) == len(ids)
    assert len(set(slugs)) == len(slugs)
    assert ids == sorted(ids), "rule IDs are the stable public order"
    for r in ALL_RULES:
        assert r.summary and r.why, f"{r.id} must document its bug class"


@pytest.mark.parametrize("name", sorted(TRUE_POSITIVES))
def test_true_positive_fixture(name):
    findings = lint_file(fx(name), ALL_RULES)
    assert counts(findings) == TRUE_POSITIVES[name]


@pytest.mark.parametrize("name", TRUE_NEGATIVES)
def test_true_negative_fixture(name):
    findings = lint_file(fx(name), ALL_RULES)
    assert findings == []


def test_shipped_regressions_reproduced():
    """The analyzer's reason to exist: the three bugs this repo
    actually shipped, each caught by its rule on a minimal
    reproduction."""
    # PR 9/11: percentile roll-up while holding the bus lock.
    lock = lint_file(fx("buslock_percentile_tp.py"), ALL_RULES)
    assert [f.rule for f in lock] == ["SPK301"]
    assert "percentile" in lock[0].snippet
    assert "_lock" in lock[0].message
    # The Telemetry.event(kind=...) envelope collision (alerts WATCH).
    kind = lint_file(fx("event_kind_tp.py"), ALL_RULES)
    assert {f.snippet.split("=")[0].strip() for f in kind} == {
        "kind", "ts", "rank"}
    # PR 10: stopped-GangCoordinator use-after-free.
    uaf = lint_file(fx("stopped_coord_tp.py"), ALL_RULES)
    assert [f.rule for f in uaf] == ["SPK501"]
    assert "coord.generation" in uaf[0].message


def test_suppression_same_line_and_preceding(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nts = time.time()\n")
    assert counts(lint_file(str(bad), ALL_RULES)) == {"SPK201": 1}
    annotated = tmp_path / "annotated.py"
    annotated.write_text(
        "import time\n"
        "ts = time.time()  # lint-obs: ok (test)\n"
        "# lint-obs: ok (test, preceding line)\n"
        "t2 = time.time()\n")
    assert lint_file(str(annotated), ALL_RULES) == []


def test_aliased_imports_detected(tmp_path):
    """What the grep ban could never see: aliased clock imports."""
    p = tmp_path / "aliased.py"
    p.write_text("import time as t\n"
                 "from time import perf_counter as pc\n"
                 "a = t.time()\n"
                 "b = pc()\n")
    assert counts(lint_file(str(p), ALL_RULES)) == {"SPK201": 2}


def test_multiline_with_span_not_flagged(tmp_path):
    """The historical `grep -v 'with '` hole: a with-block split
    across lines is still a with-block to the AST."""
    p = tmp_path / "wrapped.py"
    p.write_text("def f(tele):\n"
                 "    with tele.gauge_scope(), \\\n"
                 "            tele.span('train/chunk'):\n"
                 "        pass\n")
    assert lint_file(str(p), ALL_RULES) == []


def test_parse_error_is_a_finding(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    findings = lint_file(str(p), ALL_RULES)
    assert [f.rule for f in findings] == [PARSE_RULE_ID]


def test_unreadable_file_is_a_finding(tmp_path):
    findings = lint_file(str(tmp_path / "missing.py"), ALL_RULES)
    assert [f.rule for f in findings] == [PARSE_RULE_ID]
    assert "could not read" in findings[0].message


def test_loop_index_scoped_to_its_binding_loop(tmp_path):
    """A parameter sharing a name with a loop variable elsewhere in
    the module is NOT a loop index: only a call lexically inside the
    binding `for` is flagged."""
    p = tmp_path / "scoped.py"
    p.write_text(
        "import jax\n"
        "f = jax.jit(lambda x, n: x)\n"
        "def a(xs):\n"
        "    for i in range(3):\n"
        "        f(xs, i)\n"
        "def b(i, xs):\n"
        "    return f(xs, i)\n")
    findings = lint_file(str(p), ALL_RULES)
    assert counts(findings) == {"SPK401": 1}
    assert findings[0].line == 5


def test_skew_stamp_scope_pins():
    """obs/skew.py is stamp-scope: BOTH clocks are banned there (the
    module only does arithmetic over ledger-captured stamps) and SPK108
    applies even though it is not under train/. Plain obs/ modules keep
    their historical scoping — time.time exempt, perf_counter free."""
    import ast as ast_mod

    from sparktorch_tpu.lint.core import FileContext, ModuleIndex
    from sparktorch_tpu.lint.rules_obs import AsyncFetchRule
    from sparktorch_tpu.lint.rules_timing import TimingLedgerRule

    src = ("import time\nimport jax\n"
           "a = time.time()\n"
           "b = time.perf_counter()\n"
           "c = jax.device_get(a)\n")
    tree = ast_mod.parse(src)

    def ctx(rel):
        return FileContext(path=rel, rel=rel, tree=tree,
                           lines=src.splitlines(),
                           index=ModuleIndex(tree))

    timing, fetch = TimingLedgerRule(), AsyncFetchRule()
    skew_findings = list(timing.run(ctx("obs/skew.py")))
    assert len(skew_findings) == 2
    assert all("span clock" in f.message for f in skew_findings)
    assert fetch.applies("obs/skew.py")
    assert len(list(fetch.run(ctx("obs/skew.py")))) == 1
    assert list(timing.run(ctx("obs/goodput.py"))) == []
    assert not fetch.applies("obs/goodput.py")


def test_package_rel_scoping():
    assert package_rel(os.path.join(PKG_DIR, "obs", "telemetry.py")) \
        == "obs/telemetry.py"
    assert package_rel(fx("timing_tp.py")) is None


def test_rule_selectors():
    assert [r.id for r in rules_by_selector(["SPK301"])] == ["SPK301"]
    assert [r.id for r in rules_by_selector(["lock-hold"])] == ["SPK301"]
    assert [r.id for r in rules_by_selector(["spk301", "TIMING-LEDGER"])
            ] == ["SPK301", "SPK201"]
    assert rules_by_selector([]) == ALL_RULES
    with pytest.raises(KeyError):
        rules_by_selector(["SPK999"])


def test_full_tree_clean_and_under_wall_gate():
    """The merge contract: zero unexplained findings over the whole
    package. The wall bound here is only a generous
    pathological-regression backstop (the CLI's --gate-wall is the
    caller's to set), so a load spike on a shared rig can't flake the
    unit suite."""
    t0 = time.perf_counter()
    findings, n_files = run_lint([PKG_DIR], ALL_RULES)
    wall = time.perf_counter() - t0
    assert findings == [], "\n".join(f.render() for f in findings)
    assert n_files > 80
    assert wall < 30.0, f"analyzer wall {wall:.2f}s is pathological"


# ---------------------------------------------------------------- CLI


def run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "sparktorch_tpu.lint", *args],
        capture_output=True, text=True)


def test_cli_clean_file_exits_zero():
    res = run_cli(fx("obs_misc_tn.py"))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "clean" in res.stdout


def test_cli_findings_exit_one_and_json_schema():
    res = run_cli(fx("obs_misc_tp.py"), "--json")
    assert res.returncode == 1
    doc = json.loads(res.stdout)
    assert set(doc) == {"version", "files_scanned", "wall_s", "rules",
                        "counts", "findings"}
    assert doc["version"] == 1
    assert doc["files_scanned"] == 1
    assert doc["counts"] == {"SPK101": 1, "SPK102": 1, "SPK103": 1,
                             "SPK104": 1, "SPK105": 1}
    for f in doc["findings"]:
        assert set(f) == {"rule", "slug", "path", "line", "col",
                          "message", "snippet"}


def test_cli_unknown_rule_refused():
    res = run_cli(fx("obs_misc_tp.py"), "--rule", "nonsense")
    assert res.returncode == 2
    assert "unknown rule: nonsense" in res.stderr


def test_cli_missing_or_empty_path_never_reads_clean(tmp_path):
    """A gate that scans nothing must not exit 0: a path typo in the
    Makefile would silently disarm the tier-1 prerequisite."""
    res = run_cli(str(tmp_path / "no_such_dir"))
    assert res.returncode == 2
    assert "no such path" in res.stderr
    empty = tmp_path / "empty"
    empty.mkdir()
    res = run_cli(str(empty))
    assert res.returncode == 2
    assert "no .py files" in res.stderr


def test_cli_rule_filter_and_list():
    res = run_cli(fx("obs_misc_tp.py"), "--rule", "obs-print", "--json")
    assert res.returncode == 1
    assert json.loads(res.stdout)["counts"] == {"SPK101": 1}


def test_cli_list_rules():
    res = run_cli("--list-rules")
    assert res.returncode == 0
    for rule in ALL_RULES:
        assert rule.id in res.stdout and rule.slug in res.stdout


def test_cli_gate_wall_breach_and_log(tmp_path):
    log = tmp_path / "lint.jsonl"
    res = run_cli(fx("obs_misc_tn.py"), "--gate-wall", "0.0000001",
                  "--log", str(log))
    assert res.returncode == 1
    assert "exceeds --gate-wall" in res.stderr
    rec = json.loads(log.read_text().splitlines()[-1])
    assert rec["config"] == "lint"
    assert rec["findings"] == 0
    assert rec["ok"] is False
    assert rec["gate_wall_s"] == pytest.approx(1e-7)
