"""dtm-lint: engine semantics, per-rule fixtures, tree cleanliness.

Three layers:

- **Fixtures** (``tests/lint_fixtures/``): each rule has a minimal
  known-bad snippet asserting exact rule id + line, and a known-good
  twin asserting silence — the rule's contract, pinned.
- **Engine**: suppression use/unuse, baseline well-formedness and
  staleness, rule selection, error handling.
- **Tree**: the whole package lints clean modulo ``analysis/
  baseline.json`` (which starts — and must stay — empty), both through
  the library API and the ``scripts/dtm_lint.py`` CLI with ``--json``.

Everything here is pure AST work — no jax, no device, fast.
"""

import json
import os
import subprocess
import sys

import pytest

from analysis.dtmlint import (
    LintError,
    apply_baseline,
    Finding,
    load_baseline,
    repo_config,
    run,
    strict_config,
    write_baseline,
)
from analysis.dtmlint.config import DEFAULT_BASELINE, JAX_FREE_ROOTS

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO_ROOT, "tests", "lint_fixtures")
DTM_LINT = os.path.join(REPO_ROOT, "scripts", "dtm_lint.py")


def lint_files(*names):
    paths = [os.path.join(FIXTURES, n) for n in names]
    return run(strict_config(paths, REPO_ROOT))


# --------------------------------------------------------------------------
# Per-rule fixtures: exact rule id + line on bad, silence on good
# --------------------------------------------------------------------------

BAD_EXPECT = {
    "bad_lockstep.py": {("collective-lockstep", 6),
                        ("collective-lockstep", 11)},
    "bad_int64_wire.py": {("int32-wire", 8), ("int32-wire", 9)},
    "bad_thread.py": {("thread-discipline", 7), ("thread-discipline", 13)},
    "bad_wallclock_cursor.py": {("determinism-hazard", 7),
                                ("determinism-hazard", 8)},
    "bad_metric_key.py": {("metric-key-registry", 5)},
    "bad_recompile.py": {("recompile-hazard", 10),
                         ("recompile-hazard", 11),
                         ("recompile-hazard", 12),
                         ("recompile-hazard", 14),
                         ("recompile-hazard", 19),
                         ("recompile-hazard", 23)},
    "bad_donation.py": {("donation-safety", 10),
                        ("donation-safety", 16)},
    "bad_paged_arena.py": {("recompile-hazard", 12),
                           ("donation-safety", 22),
                           ("donation-safety", 28)},
    "bad_specdec.py": {("recompile-hazard", 13),
                       ("donation-safety", 23),
                       ("donation-safety", 29)},
    "bad_lockdisc.py": {("lock-discipline", 13),
                        ("lock-discipline", 20),
                        ("lock-discipline", 24)},
    "bad_race.py": {("shared-state-race", 16)},
    "bad_collective_order.py": {("collective-order", 6),
                                ("collective-order", 9),
                                ("collective-order", 20)},
    "meshaxes_bad.py": {("collective-order", 10),
                        ("collective-order", 11)},
    "bad_resize.py": {("collective-lockstep", 6),
                      ("collective-order", 12)},
    "bad_lifecycle.py": {("resource-lifecycle", 9),
                         ("resource-lifecycle", 15),
                         ("resource-lifecycle", 24),
                         ("resource-lifecycle", 30)},
    "bad_serving_obs.py": {("determinism-hazard", 6),
                           ("metric-key-registry", 7)},
    "bad_shipping.py": {("int32-wire", 8),
                        ("int32-wire", 9),
                        ("resource-lifecycle", 13)},
    "bad_autoscale.py": {("determinism-hazard", 7),
                         ("thread-discipline", 11)},
    "bad_deploy.py": {("donation-safety", 12),
                      ("determinism-hazard", 16)},
}

GOOD_FILES = [
    "good_lockstep.py",
    "good_int64_wire.py",
    "good_thread.py",
    "good_wallclock_cursor.py",
    "good_metric_key.py",
    "good_recompile.py",
    "good_donation.py",
    "good_lockdisc.py",
    "good_paged_arena.py",
    "good_specdec.py",
    "good_race.py",
    "good_collective_order.py",
    "good_resize.py",
    "meshaxes_good.py",
    "good_lifecycle.py",
    "good_serving_obs.py",
    "good_shipping.py",
    "good_autoscale.py",
    "good_deploy.py",
]


@pytest.mark.parametrize("name", sorted(BAD_EXPECT))
def test_bad_fixture_trips_its_rule(name):
    result = lint_files(name)
    got = {(f.rule, f.line) for f in result.new}
    assert BAD_EXPECT[name] <= got, result.new
    # ...and nothing from unrelated rules leaks in.
    expected_rules = {r for r, _ in BAD_EXPECT[name]}
    assert {f.rule for f in result.new} == expected_rules, result.new


def test_bad_thread_flags_both_problems_on_ctor_line():
    # Line 7 carries two distinct findings: implicit daemonhood and a
    # handle that is never joined.
    result = lint_files("bad_thread.py")
    msgs = [f.message for f in result.new if f.line == 7]
    assert len(msgs) == 2
    assert any("daemon=" in m for m in msgs)
    assert any("never joined" in m for m in msgs)


@pytest.mark.parametrize("name", GOOD_FILES)
def test_good_twin_is_silent(name):
    result = lint_files(name)
    assert result.new == [], result.new


def test_jaxzone_bad_reports_transitive_chain():
    result = lint_files("jaxzone_bad/supervisor.py", "jaxzone_bad/helper.py")
    assert len(result.new) == 1, result.new
    f = result.new[0]
    assert f.rule == "jax-free-zone"
    assert f.path.endswith("jaxzone_bad/helper.py")
    assert f.line == 3
    assert "supervisor.py" in f.message  # the chain names the root


def test_jaxzone_good_lazy_and_type_only_imports_pass():
    result = lint_files("jaxzone_good/supervisor.py")
    assert result.new == [], result.new


# --------------------------------------------------------------------------
# Interprocedural pairs: the finding is at the *call site*, the evidence
# lives in another file — the call-graph layer has to connect them.
# --------------------------------------------------------------------------


def test_helper_blocks_under_lock_cross_file():
    result = lint_files(
        "lockhelper_bad/helper.py", "lockhelper_bad/pump.py"
    )
    assert len(result.new) == 1, result.new
    f = result.new[0]
    assert (f.rule, f.line) == ("lock-discipline", 11)
    assert f.path.endswith("lockhelper_bad/pump.py")
    # The message names the helper and the blocking op it hides.
    assert "drain_one" in f.message and "queue.get" in f.message


def test_helper_nonblocking_under_lock_is_silent():
    result = lint_files(
        "lockhelper_good/helper.py", "lockhelper_good/pump.py"
    )
    assert result.new == [], result.new


def test_helper_collective_under_chief_branch_cross_file():
    result = lint_files(
        "chiefhelper_bad/helper.py", "chiefhelper_bad/caller.py"
    )
    assert len(result.new) == 1, result.new
    f = result.new[0]
    assert (f.rule, f.line) == ("collective-lockstep", 7)
    assert f.path.endswith("chiefhelper_bad/caller.py")
    assert "announce" in f.message and "broadcast_int" in f.message


def test_helper_collective_matched_on_both_paths_is_silent():
    result = lint_files(
        "chiefhelper_good/helper.py", "chiefhelper_good/caller.py"
    )
    assert result.new == [], result.new


def test_racing_write_hidden_in_cross_file_helper():
    # The thread target calls `helper.bump(self)`; the racing write is
    # one file away, on a parameter the object was passed through.
    result = lint_files(
        "racehelper_bad/worker.py", "racehelper_bad/helper.py"
    )
    assert len(result.new) == 1, result.new
    f = result.new[0]
    assert (f.rule, f.line) == ("shared-state-race", 18)
    assert f.path.endswith("racehelper_bad/worker.py")
    # The message names the helper function and the file it hides in.
    assert "bump" in f.message and "racehelper_bad/helper.py" in f.message


def test_event_mediated_cross_file_helper_is_silent():
    result = lint_files(
        "racehelper_good/worker.py", "racehelper_good/helper.py"
    )
    assert result.new == [], result.new


def test_interprocedural_donation_read_via_method():
    # Donate self.arena, then call a method whose summary reads it —
    # the read is a whole method away from the donate site.
    import textwrap

    src = textwrap.dedent(
        '''
        class Eng:
            def __init__(self, fn):
                self._step = jax.jit(fn, donate_argnums=(0,))

            def peek(self):
                return self.arena.sum()

            def go(self):
                out = self._step(self.arena)
                return out, self.peek()
        '''
    ).strip() + "\n"
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, "eng.py")
        with open(p, "w") as fh:
            fh.write(src)
        result = run(strict_config([p], td))
    assert [(f.rule, f.line) for f in result.new] == [
        ("donation-safety", 10)
    ], result.new
    assert "peek" in result.new[0].message


# --------------------------------------------------------------------------
# Suppressions
# --------------------------------------------------------------------------


def test_used_suppression_silences_unused_suppression_reports():
    result = lint_files("suppressed_ok.py")
    assert [(f.rule, f.line) for f in result.new] == [
        ("unused-suppression", 10)
    ], result.new


def test_unused_suppressions_of_v3_rules_are_reported():
    result = lint_files("suppressed_new_rules.py")
    assert [(f.rule, f.line) for f in result.new] == [
        ("unused-suppression", 3),
        ("unused-suppression", 4),
        ("unused-suppression", 5),
    ], result.new


def test_used_suppression_of_race_rule_silences_it():
    result = lint_files("suppressed_race_ok.py")
    assert result.new == [], result.new


def test_disabling_a_rule_does_not_flip_its_suppressions_to_unused():
    paths = [os.path.join(FIXTURES, "suppressed_ok.py")]
    result = run(
        strict_config(paths, REPO_ROOT),
        disable=("determinism-hazard", "int32-wire"),
    )
    assert result.new == [], result.new


# --------------------------------------------------------------------------
# Rule selection and error handling
# --------------------------------------------------------------------------


def test_only_restricts_to_named_rules():
    paths = [os.path.join(FIXTURES, "bad_thread.py")]
    result = run(strict_config(paths, REPO_ROOT), only=["int32-wire"])
    assert result.new == []
    assert result.enabled == ("int32-wire",)


def test_unknown_rule_is_a_config_error():
    paths = [os.path.join(FIXTURES, "good_thread.py")]
    with pytest.raises(LintError, match="unknown rule"):
        run(strict_config(paths, REPO_ROOT), only=["no-such-rule"])


def test_unparseable_file_is_a_finding_not_a_crash(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def oops(:\n")
    result = run(strict_config([str(p)], str(tmp_path)))
    assert [f.rule for f in result.new] == ["parse-error"]


# --------------------------------------------------------------------------
# Baseline
# --------------------------------------------------------------------------


def test_committed_baseline_is_well_formed_and_empty():
    entries = load_baseline(os.path.join(REPO_ROOT, DEFAULT_BASELINE))
    # The tree was fixed rather than grandfathered in the PR that
    # introduced dtm-lint; new findings must be fixed, not baselined.
    assert entries == []


@pytest.mark.parametrize(
    "payload",
    [
        "not json{",
        '{"findings": []}',  # missing version
        '{"version": 99, "findings": []}',
        '{"version": 1, "findings": {}}',
        '{"version": 1, "findings": [{"rule": "x"}]}',  # missing keys
        '{"version": 1, "findings": [{"rule": "x", "path": "p", '
        '"line": "7"}]}',  # line not an int
    ],
)
def test_malformed_baseline_fails_loudly(tmp_path, payload):
    p = tmp_path / "baseline.json"
    p.write_text(payload)
    with pytest.raises(LintError):
        load_baseline(str(p))


def test_baseline_roundtrip_grandfathers_and_reports_stale(tmp_path):
    live = Finding("a.py", 3, "int32-wire", "m")
    gone = Finding("b.py", 9, "int32-wire", "m")
    p = tmp_path / "baseline.json"
    write_baseline(str(p), [live, gone])
    loaded = load_baseline(str(p))
    new, old, stale = apply_baseline([live], loaded)
    assert new == [] and old == [live] and stale == [gone]


# --------------------------------------------------------------------------
# The tree itself
# --------------------------------------------------------------------------


def test_tree_is_clean_modulo_baseline():
    baseline = load_baseline(os.path.join(REPO_ROOT, DEFAULT_BASELINE))
    result = run(repo_config(REPO_ROOT), baseline=baseline)
    assert result.ok, "\n".join(f.render() for f in result.new)
    assert result.stale_baseline == [], result.stale_baseline
    # The v3 packs are default-on: the clean sweep above must include
    # them, or "clean" is vacuous for the new invariants.
    for rule in (
        "shared-state-race", "collective-order", "resource-lifecycle"
    ):
        assert rule in result.enabled, result.enabled


def test_jax_free_roots_exist():
    # The zone list in config.py (cross-referenced from KNOBS.md) must
    # track the tree — a renamed module silently dropping out of the
    # walk would gut the rule.
    for rel in JAX_FREE_ROOTS:
        assert os.path.exists(os.path.join(REPO_ROOT, rel)), rel


def test_cli_json_clean_on_tree():
    proc = subprocess.run(
        [sys.executable, DTM_LINT, "--json"],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is True
    assert payload["findings"] == []
    assert "collective-lockstep" in payload["rules"]


def test_cli_nonzero_with_rule_and_location_on_bad_fixture():
    bad = os.path.join(FIXTURES, "bad_lockstep.py")
    proc = subprocess.run(
        [sys.executable, DTM_LINT, bad, "--json"],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    found = {(f["rule"], f["line"]) for f in payload["findings"]}
    assert ("collective-lockstep", 6) in found
    # Text mode renders path:line: [rule] for operators and editors.
    proc = subprocess.run(
        [sys.executable, DTM_LINT, bad],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 1
    assert "[collective-lockstep]" in proc.stdout
    assert "bad_lockstep.py:6" in proc.stdout


# --------------------------------------------------------------------------
# --changed-only: findings restricted to files changed vs a git ref
# --------------------------------------------------------------------------

BAD_SNIPPET = (
    '"""scratch."""\n\n\n'
    "def chief_only(consensus, is_chief, value):\n"
    "    if is_chief:\n"
    "        return consensus.broadcast_int(value)\n"
    "    return None\n"
)


def _scratch_repo(tmp_path, *, git=True):
    pkg = tmp_path / "distributed_tensorflow_models_tpu"
    pkg.mkdir()
    (pkg / "clean.py").write_text('"""clean."""\n\nX = 1\n')
    if git:
        env = dict(
            os.environ,
            GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
            GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t",
        )
        for cmd in (
            ["git", "init", "-q"],
            ["git", "add", "-A"],
            ["git", "commit", "-qm", "seed"],
        ):
            subprocess.run(cmd, cwd=tmp_path, env=env, check=True)
    return pkg


def _lint_cli(root, *flags):
    return subprocess.run(
        [sys.executable, DTM_LINT, "--root", str(root), "--json", *flags],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )


def test_changed_only_reports_new_file_and_agrees_with_full_run(tmp_path):
    pkg = _scratch_repo(tmp_path)
    (pkg / "gated.py").write_text(BAD_SNIPPET)  # untracked = changed
    changed = _lint_cli(tmp_path, "--changed-only")
    full = _lint_cli(tmp_path)
    assert changed.returncode == 1, changed.stdout + changed.stderr
    got = json.loads(changed.stdout)["findings"]
    want = json.loads(full.stdout)["findings"]
    # One file changed: the changed-only run agrees with the full run
    # for that file exactly (here: the full run has nothing else).
    assert got == want and len(got) == 1
    assert got[0]["rule"] == "collective-lockstep"
    assert got[0]["path"].endswith("gated.py")


def test_changed_only_skips_committed_violations(tmp_path):
    pkg = _scratch_repo(tmp_path)
    (pkg / "gated.py").write_text(BAD_SNIPPET)
    env = dict(
        os.environ,
        GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
        GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t",
    )
    subprocess.run(["git", "add", "-A"], cwd=tmp_path, env=env, check=True)
    subprocess.run(
        ["git", "commit", "-qm", "grandfather"],
        cwd=tmp_path, env=env, check=True,
    )
    (pkg / "touched.py").write_text('"""touched."""\n\nY = 2\n')
    changed = _lint_cli(tmp_path, "--changed-only")
    # gated.py is dirty in the *tree* but unchanged vs HEAD, so its
    # finding is out of scope; the touched file is clean.
    assert changed.returncode == 0, changed.stdout + changed.stderr
    assert json.loads(changed.stdout)["findings"] == []
    # The full run still fails: --changed-only narrows scope, it does
    # not bless the tree.
    assert _lint_cli(tmp_path).returncode == 1


def test_changed_only_falls_back_to_full_tree_without_git(tmp_path):
    pkg = _scratch_repo(tmp_path, git=False)
    (pkg / "gated.py").write_text(BAD_SNIPPET)
    proc = _lint_cli(tmp_path, "--changed-only")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "falling back to full-tree" in proc.stderr
    assert len(json.loads(proc.stdout)["findings"]) == 1


def test_changed_only_rejects_explicit_paths():
    proc = subprocess.run(
        [sys.executable, DTM_LINT,
         os.path.join(FIXTURES, "good_thread.py"), "--changed-only"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert proc.returncode == 2
    assert "whole-tree" in proc.stderr


# --------------------------------------------------------------------------
# Incremental cache (.dtmlint_cache/): content-hash keyed, per-file
# invalidation closed over the stored dependency graph, engine/config
# fingerprints discarding stale stores wholesale.
# --------------------------------------------------------------------------


def _stats(proc):
    return json.loads(proc.stdout)["stats"]


def _seed_cached_repo(tmp_path):
    """Scratch tree with a dependency edge: b.py calls a helper it
    imports from a.py; c.py and clean.py stand alone."""
    pkg = _scratch_repo(tmp_path, git=False)
    (pkg / "a.py").write_text(
        '"""a."""\n\n\ndef helper():\n    return 1\n'
    )
    (pkg / "b.py").write_text(
        '"""b."""\n\n'
        "from distributed_tensorflow_models_tpu.a import helper\n\n\n"
        "def use():\n    return helper()\n"
    )
    (pkg / "c.py").write_text(
        '"""c."""\n\n\n'
        "def chief_only(consensus, is_chief, value):\n"
        "    del is_chief\n"
        "    return consensus.broadcast_int(value)\n"
    )
    return pkg


def test_cache_cold_then_fast_path_with_identical_findings(tmp_path):
    _seed_cached_repo(tmp_path)
    first = _lint_cli(tmp_path, "--stats")
    second = _lint_cli(tmp_path, "--stats")
    assert first.returncode == 0, first.stdout + first.stderr
    s1, s2 = _stats(first), _stats(second)
    assert s1["cache"] == "cold" and s1["analyzed"] == s1["files"] == 4
    assert s2["fast_path"] is True and s2["analyzed"] == 0
    assert s2["reused"] == 4
    assert os.path.exists(
        os.path.join(str(tmp_path), ".dtmlint_cache", "cache.json")
    )
    p1, p2 = json.loads(first.stdout), json.loads(second.stdout)
    for key in ("ok", "findings", "baselined", "rules"):
        assert p1[key] == p2[key]


def test_cache_reanalyzes_only_changed_file_and_dependents(tmp_path):
    pkg = _seed_cached_repo(tmp_path)
    _lint_cli(tmp_path)  # warm
    # Same symbol set, new body: a per-file event, not a global one.
    (pkg / "a.py").write_text(
        '"""a."""\n\n\ndef helper():\n    return 2\n'
    )
    proc = _lint_cli(tmp_path, "--stats")
    s = _stats(proc)
    assert s["cache"] == "warm" and s["fast_path"] is False
    assert s["analyzed_files"] == [
        "distributed_tensorflow_models_tpu/a.py",
        "distributed_tensorflow_models_tpu/b.py",
    ], s
    assert s["reused"] == 2


def test_cache_detects_content_change_with_unchanged_mtime(tmp_path):
    pkg = _seed_cached_repo(tmp_path)
    _lint_cli(tmp_path)  # warm
    target = pkg / "c.py"
    st = os.stat(str(target))
    target.write_text(BAD_SNIPPET)  # same symbols, now chief-gated
    os.utime(str(target), (st.st_atime, st.st_mtime))
    proc = _lint_cli(tmp_path, "--stats")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    findings = json.loads(proc.stdout)["findings"]
    assert any(
        f["rule"] == "collective-lockstep" and f["path"].endswith("c.py")
        for f in findings
    ), findings
    s = _stats(proc)
    assert s["cache"] == "warm"
    assert "distributed_tensorflow_models_tpu/c.py" in s["analyzed_files"]


def test_cache_from_older_engine_version_is_discarded(tmp_path):
    _seed_cached_repo(tmp_path)
    _lint_cli(tmp_path)  # warm
    cache_file = os.path.join(str(tmp_path), ".dtmlint_cache", "cache.json")
    with open(cache_file) as f:
        data = json.load(f)
    data["engine"] = "0" * 64  # a checker from another era
    with open(cache_file, "w") as f:
        json.dump(data, f)
    proc = _lint_cli(tmp_path, "--stats")
    s = _stats(proc)
    assert s["cache"] == "cold" and s["analyzed"] == s["files"]
    # ...and the rewritten store is trusted again on the next run.
    assert _stats(_lint_cli(tmp_path, "--stats"))["fast_path"] is True


def test_changed_only_composes_with_warm_cache(tmp_path):
    pkg = _scratch_repo(tmp_path)
    (pkg / "gated.py").write_text(BAD_SNIPPET)
    env = dict(
        os.environ,
        GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
        GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t",
    )
    subprocess.run(["git", "add", "-A"], cwd=tmp_path, env=env, check=True)
    subprocess.run(
        ["git", "commit", "-qm", "grandfather"],
        cwd=tmp_path, env=env, check=True,
    )
    assert _lint_cli(tmp_path).returncode == 1  # warm the cache
    # Nothing changed vs HEAD: the restriction (applied after the
    # cache merge) empties the report without disturbing the store.
    proc = _lint_cli(tmp_path, "--changed-only", "--stats")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["findings"] == []
    assert _stats(proc)["fast_path"] is True
    # The cached full view still fails — restriction never leaked in.
    assert _lint_cli(tmp_path).returncode == 1


def test_no_cache_flag_bypasses_and_writes_nothing(tmp_path):
    _seed_cached_repo(tmp_path)
    proc = _lint_cli(tmp_path, "--no-cache", "--stats")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert _stats(proc)["cache"] == "disabled"
    assert not os.path.exists(
        os.path.join(str(tmp_path), ".dtmlint_cache")
    )


def test_cached_and_uncached_runs_agree_on_the_real_tree():
    cached = subprocess.run(
        [sys.executable, DTM_LINT, "--json"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    uncached = subprocess.run(
        [sys.executable, DTM_LINT, "--json", "--no-cache"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert cached.returncode == uncached.returncode == 0, (
        cached.stdout + uncached.stdout
    )
    pc, pu = json.loads(cached.stdout), json.loads(uncached.stdout)
    for key in ("ok", "findings", "baselined", "stale_baseline", "rules"):
        assert pc[key] == pu[key], key


def test_warm_cache_full_tree_meets_runtime_budget():
    # The drill pre-gates run dtm-lint on every invocation; the warm
    # path has to stay effectively free.  ~3s is the budget from
    # ISSUE 13 — the observed fast path is under 0.1s, so this bounds
    # regressions without flaking on slow CI.
    subprocess.run(
        [sys.executable, DTM_LINT, "--json"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )  # seed/refresh the store
    proc = subprocess.run(
        [sys.executable, DTM_LINT, "--json", "--stats"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    s = _stats(proc)
    assert s["fast_path"] is True, s
    assert s["total_s"] < 3.0, s


def test_json_schema_version_and_timings_present():
    proc = subprocess.run(
        [sys.executable, DTM_LINT, "--json", "--no-cache", "--stats"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    payload = json.loads(proc.stdout)
    assert payload["schema_version"] == 2
    # Per-rule wall-clock: one entry per checker pass, all floats
    # (unused-suppression is engine bookkeeping, not a timed pass).
    assert set(payload["timings"]) == (
        set(payload["rules"]) - {"unused-suppression"}
    )
    assert all(
        isinstance(v, float) and v >= 0.0
        for v in payload["timings"].values()
    )


# --------------------------------------------------------------------------
# Injection probes: copy a *real* source file, break a real invariant,
# and require the v3 packs to catch it — proof the rules bite on
# production-shaped code, not only on minimal fixtures.
#
#   1. serving/server.py   + unguarded worker-thread counter → race
#   2. resilience/heartbeat.py − the beat() lock             → race
#   3. parallel/ring.py    + hard-coded bogus axis literal   → order
# --------------------------------------------------------------------------

PKG_ROOT = os.path.join(REPO_ROOT, "distributed_tensorflow_models_tpu")

_PROBE_RACE_CLASS = '''

class _ProbePump:
    def __init__(self):
        self._inflight = 0
        self._worker = threading.Thread(target=self._pump, daemon=True)
        self._worker.start()

    def _pump(self):
        while True:
            self._inflight += 1

    def backlog(self):
        return self._inflight

    def stop(self):
        self._worker.join()
'''

_PROBE_AXIS_FN = '''

def _probe_reduce(x):
    return jax.lax.psum(x, axis_name="bogus_axis")
'''


def _probe_lint(tmp_path, sources, rule):
    paths = []
    for name, text in sources.items():
        p = tmp_path / name
        p.write_text(text)
        paths.append(str(p))
    result = run(strict_config(paths, str(tmp_path)), only=[rule])
    return [f for f in result.new if f.rule == rule]


def test_probe_server_unguarded_thread_counter(tmp_path):
    src = open(os.path.join(PKG_ROOT, "serving", "server.py")).read()
    clean = _probe_lint(
        tmp_path, {"server.py": src}, "shared-state-race"
    )
    assert clean == [], clean  # non-vacuous: the real file passes
    hits = _probe_lint(
        tmp_path, {"server_bad.py": src + _PROBE_RACE_CLASS},
        "shared-state-race",
    )
    assert len(hits) == 1, hits
    assert "_ProbePump._inflight" in hits[0].message


def test_probe_heartbeat_without_beat_lock(tmp_path):
    src = open(
        os.path.join(PKG_ROOT, "resilience", "heartbeat.py")
    ).read()
    guarded = (
        "    def beat(self, step: int) -> None:\n"
        "        with self._lock:\n"
        "            self._step = int(step)\n"
    )
    unguarded = (
        "    def beat(self, step: int) -> None:\n"
        "        self._step = int(step)\n"
    )
    assert guarded in src  # the real fix this probe guards
    clean = _probe_lint(
        tmp_path, {"heartbeat.py": src}, "shared-state-race"
    )
    assert clean == [], clean
    hits = _probe_lint(
        tmp_path,
        {"heartbeat_bad.py": src.replace(guarded, unguarded)},
        "shared-state-race",
    )
    assert hits, "dropping beat()'s lock must re-trip the race pack"
    assert any("_step" in f.message for f in hits)


def test_probe_ring_bogus_axis_literal(tmp_path):
    ring = open(os.path.join(PKG_ROOT, "parallel", "ring.py")).read()
    mesh = open(os.path.join(PKG_ROOT, "core", "mesh.py")).read()
    clean = _probe_lint(
        tmp_path, {"ring.py": ring, "mesh.py": mesh}, "collective-order"
    )
    assert clean == [], clean
    hits = _probe_lint(
        tmp_path,
        {"ring_bad.py": ring + _PROBE_AXIS_FN, "mesh.py": mesh},
        "collective-order",
    )
    assert len(hits) == 1, hits
    assert "bogus_axis" in hits[0].message


# --------------------------------------------------------------------------
# Declared-vs-emitted coverage (check_metrics_schema --declared-coverage)
# --------------------------------------------------------------------------


def _load_schema_script():
    from importlib import util as importutil

    path = os.path.join(REPO_ROOT, "scripts", "check_metrics_schema.py")
    spec = importutil.spec_from_file_location("check_metrics_schema", path)
    mod = importutil.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_declared_coverage_flags_never_emitted_keys(tmp_path):
    mod = _load_schema_script()
    registry_py = tmp_path / "registry.py"
    registry_py.write_text(
        'STEP = "train/step"\nDEAD = "train/dead"\n'
        'WAIT = "pipeline/wait"\n'
    )
    declared = mod.declared_metric_keys(str(registry_py))
    assert declared == {
        "train/step": "STEP",
        "train/dead": "DEAD",
        "pipeline/wait": "WAIT",
    }
    report = {"metrics": {"train/step": 1.0, "pipeline/wait/total_s": 0.2}}
    errors = mod.check_declared_coverage(report, declared)
    assert len(errors) == 1 and "train/dead" in errors[0]
    # Timer/family expansion counts as emitted; allow-missing excuses.
    assert mod.check_declared_coverage(
        report, declared, allow_missing=["train/dead"]
    ) == []
    assert mod.check_declared_coverage({}, declared) == [
        "report carries no 'metrics' snapshot object"
    ]
    # only_prefix scopes the declared set: a report owning one
    # subsystem's keys is checked against that slice alone.
    assert mod.check_declared_coverage(
        report, declared, only_prefix=["pipeline/"]
    ) == []
    errors = mod.check_declared_coverage(
        report, declared, only_prefix=["train/"]
    )
    assert len(errors) == 1 and "train/dead" in errors[0]
