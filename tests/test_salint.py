"""salint: every rule covered by a passing + failing fixture, suppression,
spans, CLI (``--explain`` / ``--list-rules`` / exit codes).

Fixtures live in ``tests/salint_fixtures/`` (excluded from repo-wide scans)
and are copied into ``tmp_path`` before checking: some rules key off path
segments (SAL007 skips files under a ``tests/`` directory), so checking
them in place would mask the violations they exist to trigger.
"""
import os
import shutil
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO_ROOT, "tests", "salint_fixtures")
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)  # tools/ is importable from the repo root

from tools.salint import engine  # noqa: E402
from tools.salint import rules as R  # noqa: E402
from tools.salint.__main__ import main as salint_main  # noqa: E402
from tools.salint.rules import DEFAULT_RULES  # noqa: E402


def _check(tmp_path, fixture, rule, dest_name=None):
    """Copy a fixture into tmp_path (outside any tests/ segment) and run
    one rule over it; returns the violation list."""
    dest = str(tmp_path / (dest_name or os.path.basename(fixture)))
    shutil.copy(os.path.join(FIXTURES, fixture), dest)
    return engine.check_file(dest, [rule])


# ---------------------------------------------------------------------------
# per-rule fixture pairs
# ---------------------------------------------------------------------------


def test_sal002_bad_fixture(tmp_path):
    vs = _check(tmp_path, "sal002_bad.py", R.Sal002BackendReads())
    assert [(v.rule_id, v.line) for v in vs] == [
        ("SAL002", 5), ("SAL002", 9), ("SAL002", 14)]
    assert "read_items" in vs[0].message
    assert vs[0].col > 0  # span points at the call, not the line start


def test_sal002_good_fixture(tmp_path):
    assert _check(tmp_path, "sal002_good.py", R.Sal002BackendReads()) == []


def test_sal002_skips_store_layer(tmp_path):
    """The same calls inside core/store.py are the store talking to its own
    backend — allowed."""
    d = tmp_path / "core"
    d.mkdir()
    vs = _check(d, "sal002_bad.py", R.Sal002BackendReads(),
                dest_name="store.py")
    assert vs == []


def test_sal003_bad_fixture(tmp_path):
    vs = _check(tmp_path, "sal003_bad/superblock.py",
                R.Sal003MergeMaterialization(), dest_name="superblock.py")
    assert sorted((v.rule_id, v.line) for v in vs) == [
        ("SAL003", 8), ("SAL003", 8), ("SAL003", 9), ("SAL003", 10)]


def test_sal003_good_fixture(tmp_path):
    vs = _check(tmp_path, "sal003_good/superblock.py",
                R.Sal003MergeMaterialization(), dest_name="superblock.py")
    assert vs == []


def test_sal004_bad_fixture(tmp_path):
    vs = _check(tmp_path, "sal004_bad.py", R.Sal004FrozenConfigMutation())
    assert [(v.rule_id, v.line) for v in vs] == [
        ("SAL004", 5), ("SAL004", 11)]


def test_sal004_good_fixture(tmp_path):
    assert _check(tmp_path, "sal004_good.py",
                  R.Sal004FrozenConfigMutation()) == []


def test_sal005_bad_fixture(tmp_path):
    vs = _check(tmp_path, "sal005_bad.py", R.Sal005UnownedHandles())
    assert [(v.rule_id, v.line) for v in vs] == [
        ("SAL005", 8), ("SAL005", 12), ("SAL005", 16)]


def test_sal005_good_fixture(tmp_path):
    assert _check(tmp_path, "sal005_good.py", R.Sal005UnownedHandles()) == []


def test_sal006_bad_fixture(tmp_path):
    vs = _check(tmp_path, "sal006_bad.py", R.Sal006LegacyJaxApi())
    assert [(v.rule_id, v.line) for v in vs] == [
        ("SAL006", 4), ("SAL006", 8), ("SAL006", 12), ("SAL006", 16)]
    assert "pcast" in vs[1].message and "check_vma" in vs[2].message


def test_sal006_good_fixture(tmp_path):
    assert _check(tmp_path, "sal006_good.py", R.Sal006LegacyJaxApi()) == []


def test_sal007_bad_fixture(tmp_path):
    vs = _check(tmp_path, "sal007_bad.py",
                R.Sal007DeprecatedWrapperCallers())
    assert [(v.rule_id, v.line) for v in vs] == [
        ("SAL007", 6), ("SAL007", 7)]


def test_sal007_good_fixture(tmp_path):
    assert _check(tmp_path, "sal007_good.py",
                  R.Sal007DeprecatedWrapperCallers()) == []


def test_sal007_exempts_tests_dirs(tmp_path):
    """The wrappers' own tests keep calling them without violations."""
    d = tmp_path / "tests"
    d.mkdir()
    assert _check(d, "sal007_bad.py", R.Sal007DeprecatedWrapperCallers()) == []


def test_sal008_bad_fixture(tmp_path):
    vs = _check(tmp_path, "sal008_bad.py", R.Sal008ThreadsOutsideExecutor())
    assert [(v.rule_id, v.line) for v in vs] == [
        ("SAL008", 2), ("SAL008", 3), ("SAL008", 7), ("SAL008", 13),
        ("SAL008", 18)]
    assert "PipelineExecutor" in vs[0].message


def test_sal008_good_fixture(tmp_path):
    assert _check(tmp_path, "sal008_good.py",
                  R.Sal008ThreadsOutsideExecutor()) == []


def test_sal008_skips_pipeline_exec(tmp_path):
    """The executor itself is the one sanctioned home of raw threads."""
    d = tmp_path / "core"
    d.mkdir()
    vs = _check(d, "sal008_bad.py", R.Sal008ThreadsOutsideExecutor(),
                dest_name="pipeline_exec.py")
    assert vs == []


# ---------------------------------------------------------------------------
# SAL009/SAL010: interprocedural thread-context rules
# ---------------------------------------------------------------------------


def test_sal009_bad_fixture(tmp_path):
    vs = _check(tmp_path, "sal009_bad.py", R.Sal009CrossContextState())
    assert [(v.rule_id, v.line) for v in vs] == [
        ("SAL009", 15), ("SAL009", 16), ("SAL009", 32)]
    assert "worker context" in vs[0].message
    assert "self.staged" in vs[0].message
    assert "global 'done_flag'" in vs[2].message


def test_sal009_good_fixture(tmp_path):
    """Lock on both sides / executor hand-off: same shape, no violations."""
    assert _check(tmp_path, "sal009_good.py",
                  R.Sal009CrossContextState()) == []


def test_sal009_exempts_store_layer(tmp_path):
    """core/store.py backend-cache mutation is audited dynamically by the
    schedule harness, not flagged statically."""
    d = tmp_path / "core"
    d.mkdir()
    vs = _check(d, "sal009_bad.py", R.Sal009CrossContextState(),
                dest_name="store.py")
    assert vs == []


def test_sal010_bad_fixture(tmp_path):
    vs = _check(tmp_path, "sal010_bad.py", R.Sal010WorkerDeviceAccounting())
    assert [(v.rule_id, v.line) for v in vs] == [
        ("SAL010", 12), ("SAL010", 13), ("SAL010", 14), ("SAL010", 24)]
    assert "stage_items" in vs[0].message  # accounting entry point
    assert "jnp.asarray" in vs[1].message  # device call
    assert "staged_bytes" in vs[2].message  # gated counter
    assert "fetch_keys" in vs[3].message  # accounting via submitted lambda


def test_sal010_good_fixture(tmp_path):
    """stage_read/gather_keys on the worker + note_* at collection: clean."""
    assert _check(tmp_path, "sal010_good.py",
                  R.Sal010WorkerDeviceAccounting()) == []


def test_sal012_bad_fixture(tmp_path):
    vs = _check(tmp_path, "sal012_bad.py", R.Sal012AtomicPublish())
    assert [(v.rule_id, v.line) for v in vs] == [
        ("SAL012", 9), ("SAL012", 13), ("SAL012", 17)]
    assert "os.replace" in vs[0].message
    assert "publish_file/publish_dir" in vs[0].message
    assert "os.rename" in vs[1].message
    assert "shutil.move" in vs[2].message


def test_sal012_good_fixture(tmp_path):
    assert _check(tmp_path, "sal012_good.py", R.Sal012AtomicPublish()) == []


def test_sal012_skips_integrity_helper(tmp_path):
    """The renames inside the sanctioned helper module itself are the one
    place the raw calls belong."""
    d = tmp_path / "core"
    d.mkdir()
    vs = _check(d, "sal012_bad.py", R.Sal012AtomicPublish(),
                dest_name="integrity.py")
    assert vs == []


def test_sal012_skips_tests_dirs(tmp_path):
    """Tests simulate torn publishes with raw renames on purpose."""
    d = tmp_path / "tests"
    d.mkdir()
    vs = _check(d, "sal012_bad.py", R.Sal012AtomicPublish())
    assert vs == []


# ---------------------------------------------------------------------------
# SAL011: kernel contract (fixture trees, scanned as a project)
# ---------------------------------------------------------------------------


def test_sal011_bad_tree():
    vs = engine.run([os.path.join(FIXTURES, "sal011_bad")],
                    [R.Sal011KernelContract()])
    spans = [(os.path.basename(v.path), v.line) for v in vs]
    assert spans == [("__init__.py", 14), ("__init__.py", 14),
                     ("ops.py", 1), ("ref.py", 1), ("use.py", 7)]
    msgs = "\n".join(v.message for v in vs)
    assert "bar_op" in msgs and "bar_ref" in msgs  # missing op + ref defs
    assert "block=256" in msgs and "block=512" in msgs  # tuning fork
    assert "does not match op" in msgs  # ref signature drift
    assert "int64" in msgs  # bad call-site cast


def test_sal011_good_tree():
    assert engine.run([os.path.join(FIXTURES, "sal011_good")],
                      [R.Sal011KernelContract()]) == []


# ---------------------------------------------------------------------------
# SAL001: repo-level kernel registry pairing (fixture trees)
# ---------------------------------------------------------------------------


def _sal001_rule(tree):
    base = os.path.join(FIXTURES, tree)
    return R.Sal001KernelRegistry(
        kernels_dir=os.path.join(base, "kernels"),
        ref_file=os.path.join(base, "kernels", "ref.py"),
        test_file=os.path.join(base, "tests", "test_kernels.py"),
    )


def test_sal001_good_tree():
    assert list(_sal001_rule("sal001_good").check_repo(FIXTURES)) == []


def test_sal001_bad_tree():
    vs = list(_sal001_rule("sal001_bad").check_repo(FIXTURES))
    msgs = sorted(v.message for v in vs)
    assert len(vs) == 3 and all(v.rule_id == "SAL001" for v in vs)
    assert "rotten" in msgs[0] and "not registered" in msgs[0]
    assert "missing_ref" in msgs[1]
    assert "KERNEL_REGISTRY" in msgs[2] and "test_kernels" in msgs[2]


def test_sal001_real_repo_is_clean():
    assert list(R.Sal001KernelRegistry().check_repo(REPO_ROOT)) == []


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------


def test_line_and_next_line_suppression(tmp_path):
    vs = _check(tmp_path, "sal002_suppressed.py", R.Sal002BackendReads())
    assert vs == []


def test_file_level_suppression(tmp_path):
    src = (FIXTURES + "/sal002_bad.py")
    with open(src) as f:
        body = "# salint: disable-file=SAL002\n" + f.read()
    p = tmp_path / "suppressed_all.py"
    p.write_text(body)
    assert engine.check_file(str(p), [R.Sal002BackendReads()]) == []


def test_unrelated_suppression_does_not_mask(tmp_path):
    with open(os.path.join(FIXTURES, "sal002_bad.py")) as f:
        body = f.read().replace(
            "backend.read_items(lo, hi)  # line 5: SAL002",
            "backend.read_items(lo, hi)  # salint: disable=SAL005")
    p = tmp_path / "wrong_id.py"
    p.write_text(body)
    vs = engine.check_file(str(p), [R.Sal002BackendReads()])
    assert len(vs) == 3  # SAL005 comment does not suppress SAL002


def test_syntax_error_reports_sal000(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def broken(:\n")
    vs = engine.check_file(str(p), DEFAULT_RULES)
    assert [v.rule_id for v in vs] == ["SAL000"]


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    shutil.copy(os.path.join(FIXTURES, "sal002_bad.py"), str(bad))
    assert salint_main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert f"{bad}:5:" in out and "SAL002" in out

    good = tmp_path / "good.py"
    shutil.copy(os.path.join(FIXTURES, "sal002_good.py"), str(good))
    assert salint_main([str(good)]) == 0


def test_cli_explain(capsys):
    assert salint_main(["--explain", "SAL003"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("SAL003:") and "add_frontier" in out
    assert salint_main(["--explain", "SAL999"]) == 2


def test_cli_list_rules(capsys):
    assert salint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("SAL001", "SAL002", "SAL003", "SAL004", "SAL005", "SAL006",
                "SAL007", "SAL008", "SAL009", "SAL010", "SAL011"):
        assert rid in out


def test_cli_explain_new_rules(capsys):
    for rid, needle in (("SAL009", "hand-off"),
                        ("SAL010", "traffic"),
                        ("SAL011", "tuning")):
        assert salint_main(["--explain", rid]) == 0
        out = capsys.readouterr().out
        assert out.startswith(f"{rid}:") and needle in out


def test_cli_json_format(tmp_path, capsys):
    import json

    bad = tmp_path / "bad.py"
    shutil.copy(os.path.join(FIXTURES, "sal002_bad.py"), str(bad))
    assert salint_main([str(bad), "--format", "json"]) == 1
    data = json.loads(capsys.readouterr().out)
    assert [v["rule_id"] for v in data["violations"]] == ["SAL002"] * 3
    assert data["violations"][0]["line"] == 5


def test_cli_sarif_format(tmp_path, capsys):
    import json

    bad = tmp_path / "bad.py"
    shutil.copy(os.path.join(FIXTURES, "sal002_bad.py"), str(bad))
    out_file = tmp_path / "report.sarif"
    assert salint_main(
        [str(bad), "--format", "sarif", "--output", str(out_file)]) == 1
    with open(out_file) as f:
        sarif = json.load(f)
    assert sarif["version"] == "2.1.0"
    run0 = sarif["runs"][0]
    rule_ids = [r["id"] for r in run0["tool"]["driver"]["rules"]]
    assert "SAL009" in rule_ids and "SAL011" in rule_ids
    results = run0["results"]
    assert len(results) == 3 and results[0]["ruleId"] == "SAL002"
    region = results[0]["locations"][0]["physicalLocation"]["region"]
    assert region["startLine"] == 5 and region["startColumn"] >= 1


def test_cache_incremental(tmp_path):
    """Second run over unchanged files hits the cache; an edit misses."""
    from tools.salint.cache import ResultCache

    bad = tmp_path / "bad.py"
    shutil.copy(os.path.join(FIXTURES, "sal002_bad.py"), str(bad))
    rules = [R.Sal002BackendReads()]

    c1 = ResultCache(str(tmp_path / "cache"), rules)
    first = engine.run([str(bad)], rules, cache=c1)
    c1.save()
    assert c1.hits == 0 and c1.misses == 1 and len(first) == 3

    c2 = ResultCache(str(tmp_path / "cache"), rules)
    second = engine.run([str(bad)], rules, cache=c2)
    assert c2.hits == 1 and c2.misses == 0
    assert [(v.rule_id, v.line) for v in second] == [
        (v.rule_id, v.line) for v in first]

    bad.write_text(bad.read_text() + "\n# touched\n")
    c3 = ResultCache(str(tmp_path / "cache"), rules)
    engine.run([str(bad)], rules, cache=c3)
    assert c3.misses == 1


def test_cache_invalidated_by_ruleset(tmp_path):
    """A different rule set (id/summary) discards the whole cache."""
    from tools.salint.cache import ResultCache

    bad = tmp_path / "bad.py"
    shutil.copy(os.path.join(FIXTURES, "sal002_bad.py"), str(bad))
    c1 = ResultCache(str(tmp_path / "cache"), [R.Sal002BackendReads()])
    engine.run([str(bad)], [R.Sal002BackendReads()], cache=c1)
    c1.save()
    c2 = ResultCache(str(tmp_path / "cache"),
                     [R.Sal002BackendReads(), R.Sal005UnownedHandles()])
    engine.run([str(bad)], [R.Sal002BackendReads()], cache=c2)
    assert c2.hits == 0 and c2.misses == 1


def test_cli_cache_roundtrip(tmp_path, capsys):
    good = tmp_path / "good.py"
    shutil.copy(os.path.join(FIXTURES, "sal002_good.py"), str(good))
    cache_dir = str(tmp_path / "cache")
    assert salint_main([str(good), "--cache", cache_dir]) == 0
    assert os.path.exists(os.path.join(cache_dir, "salint-cache.json"))
    capsys.readouterr()
    assert salint_main([str(good), "--cache", cache_dir]) == 0


def test_repo_is_lint_clean():
    """The acceptance gate itself: the live tree scans clean — including
    the project-level thread-context and kernel-contract rules."""
    paths = [os.path.join(REPO_ROOT, p)
             for p in ("src", "tests", "tools")]
    vs = engine.run(paths, DEFAULT_RULES, root=REPO_ROOT)
    assert vs == [], "\n".join(v.format() for v in vs)


def test_rules_have_metadata():
    assert len(DEFAULT_RULES) >= 11
    seen = set()
    for r in DEFAULT_RULES:
        assert r.rule_id.startswith("SAL") and r.rule_id not in seen
        assert r.summary and r.rationale
        seen.add(r.rule_id)


def test_thread_context_inference():
    """The graph layer itself: submit targets are worker roots, their
    callees are worker context, untouched functions stay main-only."""
    from tools.salint.graph import ProjectGraph

    src = '''
class Driver:
    def __init__(self, executor):
        self._exec = executor

    def _work(self):
        return helper()

    def go(self):
        return self._exec.submit(self._work)


def helper():
    return 1


def main_only():
    return helper()
'''
    ctx, _sup, _err = engine._parse_file("driver.py", src)
    g = ProjectGraph([ctx])
    by_qual = {fi.qualname: fi for fi in g.functions}
    assert g.context_of(by_qual["Driver._work"]) == "worker"
    assert g.context_of(by_qual["helper"]) == "both"  # called from main too
    assert g.context_of(by_qual["main_only"]) == "main"
    assert g.context_of(by_qual["Driver.go"]) == "main"
