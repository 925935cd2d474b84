"""Veleslint (veles_tpu/analysis): every rule catches its seeded
fixture violations and passes its clean twin, waivers and the
baseline behave, the knob/event registries are wired, the generated
docs table is in sync — and the FULL-REPO scan reports zero
non-baselined findings, which is the tier-1 gate that makes the
PR 6-8 hardening invariants bite on every future change."""

import json
import os

import pytest

from veles_tpu import events, knobs
from veles_tpu.analysis import (
    Config,
    check_knob_table,
    load_baseline,
    load_config,
    new_findings,
    repo_root,
    repo_scan,
    rule_names,
    run_lint,
    scan_source,
    write_baseline,
)
from veles_tpu.analysis.engine import _mini_toml_table

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "veleslint")


def fixture(name: str) -> str:
    with open(os.path.join(FIXTURES, name), encoding="utf-8") as f:
        return f.read()


def scan_fixture(name: str, rule: str, path: str = None,
                 config: Config = None):
    """Scan one fixture under a fake in-scope path, returning only
    the findings of the rule under test."""
    path = path or f"veles_tpu/_fixture_{name}"
    found = scan_source(path, fixture(name), config or Config())
    assert not any(f.rule == "parse-error" for f in found), found
    return [f for f in found if f.rule == rule]


# -- one positive + one clean fixture per rule -------------------------

def test_atomic_write_catches_seeded():
    got = scan_fixture("atomic_bad.py", "atomic-write")
    assert len(got) == 3, got
    assert {f.detail for f in got} == {"open-w", "open-wb", "open-w+"}


def test_atomic_write_clean():
    assert scan_fixture("atomic_clean.py", "atomic-write") == []


def test_atomic_write_out_of_scope():
    # the rule bites package code only; scripts write scratch freely
    found = scan_source("scripts/_fixture.py",
                        fixture("atomic_bad.py"), Config())
    assert [f for f in found if f.rule == "atomic-write"] == []


def test_env_registry_catches_seeded():
    got = scan_fixture("env_bad.py", "env-registry")
    assert {f.detail for f in got} == {
        "VELES_NOT_A_KNOB", "VELES_PREEMPT_GRAEC",
        "VELES_ALSO_UNDECLARED", "VELES_MYSTERY_FLAG"}, got


def test_env_registry_clean():
    # declared literals, module consts, class consts, non-VELES
    # names, and unresolvable dynamics all pass
    assert scan_fixture("env_clean.py", "env-registry") == []


def test_event_registry_catches_seeded():
    got = scan_fixture("event_bad.py", "event-registry")
    assert {f.detail for f in got} == {
        "ga.hang_detected", "ga.hangs_detcted", "ga.last_hang_wait",
        "ga.genome_seconds", "ga.cohort_train"}, got
    typo = [f for f in got if f.detail == "ga.hangs_detcted"]
    assert "NOT declared" in typo[0].message


def test_event_registry_clean():
    assert scan_fixture("event_clean.py", "event-registry") == []


def test_tracer_hygiene_catches_seeded():
    got = scan_fixture("tracer_bad.py", "tracer-hygiene")
    whats = {f.detail.split(":", 1)[1] for f in got}
    assert ".item()" in whats
    assert "print()" in whats
    assert "np.asarray()" in whats
    assert "float(lr)" in whats
    assert ".block_until_ready()" in whats
    assert "python branch on jnp value" in whats
    # every seeded traced function was detected, decorator and
    # passed-to-jit/vmap forms alike
    fns = {f.detail.split(":", 1)[0] for f in got}
    assert fns == {"decorated_sync", "partial_decorated",
                   "passed_to_jit", "vmapped"}, fns


def test_tracer_hygiene_clean():
    assert scan_fixture("tracer_clean.py", "tracer-hygiene") == []


def test_exit_code_catches_seeded():
    cfg = Config(exit_code_modules=["fx/exit_bad.py"])
    got = scan_fixture("exit_bad.py", "exit-code-literals",
                       path="fx/exit_bad.py", config=cfg)
    # os._exit(13), sys.exit(14), rc == 14, rc in (13, 14)
    assert len(got) == 5, got
    assert {f.detail for f in got} == {
        "exit-call-13", "exit-call-14", "comparison-13",
        "comparison-14"}


def test_exit_code_clean_and_scoped():
    cfg = Config(exit_code_modules=["fx/exit_clean.py"])
    assert scan_fixture("exit_clean.py", "exit-code-literals",
                        path="fx/exit_clean.py", config=cfg) == []
    # out of the configured module list, nothing fires at all
    found = scan_source("fx/elsewhere.py", fixture("exit_bad.py"),
                        Config())
    assert [f for f in found if f.rule == "exit-code-literals"] == []


def test_lock_discipline_catches_seeded():
    cfg = Config(lock_modules=["fx/lock_bad.py"])
    got = scan_fixture("lock_bad.py", "lock-discipline",
                       path="fx/lock_bad.py", config=cfg)
    assert {f.detail for f in got} == {
        "_jobs.setitem", "_jobs.clear", "_queue.append",
        "_queue.popleft", "_seen.append"}, got
    # the import-time mutation stayed exempt
    assert all(f.line > 11 for f in got)


def test_lock_discipline_clean():
    cfg = Config(lock_modules=["fx/lock_clean.py"])
    assert scan_fixture("lock_clean.py", "lock-discipline",
                        path="fx/lock_clean.py", config=cfg) == []


def test_traced_env_read_catches_seeded():
    got = scan_fixture("traced_env_bad.py", "traced-env-read",
                       path="veles_tpu/ops/_fixture_kernel.py")
    assert sorted(f.detail for f in got) == [
        "os.environ", "os.environ", "os.getenv"], got


def test_traced_env_read_clean_and_scoped():
    assert scan_fixture("traced_env_clean.py", "traced-env-read",
                        path="veles_tpu/engine/_fixture.py") == []
    # the rule bites the code that builds traced programs only
    assert scan_fixture("traced_env_bad.py", "traced-env-read",
                        path="veles_tpu/serve/_fixture.py") == []


def test_waivers_suppress_findings():
    found = scan_source("veles_tpu/_fixture_waiver.py",
                        fixture("waiver.py"), Config())
    assert found == [], found


# -- engine mechanics --------------------------------------------------

def test_finding_key_is_line_stable():
    a = scan_fixture("env_bad.py", "env-registry")
    # shift the whole module down: lines move, keys must not
    b = scan_source("veles_tpu/_fixture_env_bad.py",
                    "# pad\n# pad\n" + fixture("env_bad.py"),
                    Config())
    b = [f for f in b if f.rule == "env-registry"]
    assert {f.key for f in a} == {f.key for f in b}
    assert {f.line for f in a} != {f.line for f in b}


def test_baseline_roundtrip(tmp_path):
    findings = scan_fixture("env_bad.py", "env-registry")
    path = str(tmp_path / "baseline.json")
    write_baseline(path, findings)
    # a freshly grandfathered baseline carries TODO justifications —
    # the loader must refuse it until a human writes the reasons
    with pytest.raises(ValueError, match="justification"):
        load_baseline(path)
    with open(path) as f:
        data = json.load(f)
    for entry in data["findings"]:
        entry["justification"] = "fixture: deliberately seeded"
    with open(path, "w") as f:
        json.dump(data, f)
    baseline = load_baseline(path)
    assert len(baseline) == len({f.key for f in findings})
    assert new_findings(findings, baseline) == []


def test_mini_toml_fallback_parses_pyproject():
    # python 3.10 has no tomllib; the fallback must read the real
    # [tool.veleslint] section (multi-line string arrays included)
    with open(os.path.join(repo_root(), "pyproject.toml")) as f:
        table = _mini_toml_table(f.read(), "tool.veleslint")
    assert table["baseline"] == "veles_tpu/analysis/baseline.json"
    assert "veles_tpu" in table["paths"]
    assert "veles_tpu/telemetry.py" in table["lock_modules"]
    assert "scripts/chaos_drill.py" in table["exit_code_modules"]


def test_config_rejects_unknown_keys():
    with pytest.raises(ValueError, match="unknown key"):
        Config(not_a_real_option=True)


# -- registry wiring ---------------------------------------------------

def test_knob_registry_defaults():
    from veles_tpu import supervisor
    assert knobs.get("VELES_SUPERVISE_MAX_CRASHES") == 5
    assert knobs.get("VELES_PREEMPT_GRACE") == 25.0
    assert knobs.get("VELES_FAULTS") == ""
    # the supervisor's env-default strings agree with the registry
    assert int(os.environ.get(supervisor.MAX_CRASHES_ENV, "5")) == \
        knobs.get(supervisor.MAX_CRASHES_ENV)
    # parsing: flags are set-and-not-"0"; malformed values fall back
    assert knobs.get("VELES_PREEMPT_DISABLE",
                     {"VELES_PREEMPT_DISABLE": "1"}) is True
    assert knobs.get("VELES_PREEMPT_DISABLE",
                     {"VELES_PREEMPT_DISABLE": "0"}) is False
    assert knobs.get("VELES_PREEMPT_GRACE",
                     {"VELES_PREEMPT_GRACE": "banana"}) == 25.0
    with pytest.raises(KeyError):
        knobs.get("VELES_NOT_A_KNOB")


def test_event_registry_covers_drill_names():
    # the names chaos_drill asserts on must stay declared — renaming
    # an event now breaks HERE, not mid-drill
    for name in ("ga.hang_detected", "ga.evaluator_restart",
                 "snapshot.fallback", "ga.checkpoint_fallback",
                 "loader.corrupt_file", "device.oom_retry",
                 "device.oom_degraded", "multihost.emergency_snapshot",
                 "preempt.requested", "preempt.final_snapshot",
                 "supervisor.resumed", "supervisor.done"):
        assert events.known(name), name
    assert not events.known("ga.hangs_detcted")
    assert events.all_names()


def test_rule_catalog_is_stable():
    assert rule_names() == [
        "atomic-write", "env-registry", "event-registry",
        "tracer-hygiene", "exit-code-literals", "lock-discipline",
        "engine-residency-seam", "traced-env-read",
        "thread-lifecycle", "wire-protocol",
        "trace-wire-key", "lock-order", "blocking-under-lock",
        "waiter-discipline"]


# -- docs + full-repo gate ---------------------------------------------

def test_guide_knob_table_in_sync():
    root = repo_root()
    finding = check_knob_table(root, load_config(root))
    assert finding is None, finding and finding.message


def test_full_repo_scan_zero_new_findings():
    """THE gate: the whole repo, scanned with the checked-in config
    and baseline, reports nothing new.  If this fails you either fix
    the finding, waive it inline with a reason, or baseline it with a
    written justification (docs/guide.md section 10)."""
    new, baseline = repo_scan()
    assert baseline, "baseline.json should load non-empty"
    msg = "\n".join(f.format() for f in new)
    assert not new, f"new veleslint findings:\n{msg}"


def test_cli_json_smoke(capsys):
    from veles_tpu.analysis import cli
    rc = cli.main(["--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["new"] == []
    assert out["baseline_total"] == 2


def test_cli_single_rule_and_exit_code(tmp_path, capsys):
    # a scratch repo with one seeded violation: rc must be 1 and the
    # finding printed — the CI contract
    from veles_tpu.analysis import cli
    root = tmp_path / "repo"
    (root / "veles_tpu").mkdir(parents=True)
    (root / "veles_tpu" / "bad.py").write_text(
        'def w(p):\n    with open(p, "w") as f:\n        f.write("x")\n')
    (root / "docs").mkdir()
    (root / "docs" / "guide.md").write_text("stub\n")
    rc = cli.main(["--root", str(root), "--rule", "atomic-write",
                   "--no-docs-check"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "atomic-write" in out and "bad.py" in out


def test_run_lint_changed_only_scoping(tmp_path):
    """--changed-only semantics: per-file findings report only for
    the given paths, while law-level findings (the lock-order json)
    always report — the graph is meaningless piecemeal."""
    root = tmp_path / "repo"
    (root / "veles_tpu").mkdir(parents=True)
    bad = 'def w(p):\n    with open(p, "w") as f:\n        f.write("x")\n'
    (root / "veles_tpu" / "a.py").write_text(bad)
    (root / "veles_tpu" / "b.py").write_text(bad)
    found = run_lint(str(root), Config(), check_docs=False,
                     only_paths=["veles_tpu/a.py"])
    per_file = [f for f in found if f.path.endswith(".py")]
    assert {f.path for f in per_file} == {"veles_tpu/a.py"}
    assert any(f.rule == "lock-order" and f.detail == "missing"
               for f in found)
    full = run_lint(str(root), Config(), check_docs=False)
    assert {f.path for f in full if f.path.endswith(".py")} == \
        {"veles_tpu/a.py", "veles_tpu/b.py"}


def test_scan_is_fast_enough_for_tier1():
    import time
    t0 = time.perf_counter()
    run_lint(repo_root())
    assert time.perf_counter() - t0 < 10.0
