"""Framework-level tests for tosa: suppressions, baseline workflow, the
CLI contract, and the self-run gate asserting this repo is clean."""

import json
import os
import subprocess
import sys
import textwrap

from tosa_testutil import REPO_ROOT, run_rule
from tosa import ALL_CHECKERS, analyze_source, core, make_checkers


def _src(s):
    return textwrap.dedent(s).lstrip()


BAD_SLEEP = _src("""
    import time

    def wait(q):
        while q.empty():
            time.sleep(0.1)
""")


class TestSuppressions:
    def test_inline_disable_silences_with_reason(self):
        src = BAD_SLEEP.replace(
            "time.sleep(0.1)",
            "time.sleep(0.1)  # tosa: disable=retry-discipline -- fixture needs a raw sleep",
        )
        findings = analyze_source(src, "mod.py", make_checkers(["retry-discipline"]))
        assert len(findings) == 1
        assert findings[0].suppressed == "fixture needs a raw sleep"
        assert core.gating(findings) == []

    def test_disable_of_other_rule_does_not_silence(self):
        src = BAD_SLEEP.replace(
            "time.sleep(0.1)",
            "time.sleep(0.1)  # tosa: disable=jit-purity -- wrong rule",
        )
        findings = analyze_source(src, "mod.py", make_checkers(["retry-discipline"]))
        assert len(core.gating(findings)) == 1

    def test_disable_all_silences_everything(self):
        src = BAD_SLEEP.replace(
            "time.sleep(0.1)",
            "time.sleep(0.1)  # tosa: disable=all -- kitchen sink",
        )
        findings = analyze_source(src, "mod.py", make_checkers(["retry-discipline"]))
        assert core.gating(findings) == []


class TestBaseline:
    def test_baselined_finding_does_not_gate(self, tmp_path):
        findings = analyze_source(BAD_SLEEP, "mod.py", make_checkers(["retry-discipline"]))
        assert len(core.gating(findings)) == 1
        bl = tmp_path / "baseline.json"
        core.write_baseline(str(bl), findings)
        fresh = analyze_source(BAD_SLEEP, "mod.py", make_checkers(["retry-discipline"]))
        fresh = core.apply_baseline(fresh, core.load_baseline(str(bl)))
        assert core.gating(fresh) == []
        assert all(f.baselined for f in fresh)

    def test_fingerprint_is_line_free(self):
        shifted = "# a leading comment\n# another\n" + BAD_SLEEP
        a = analyze_source(BAD_SLEEP, "mod.py", make_checkers(["retry-discipline"]))
        b = analyze_source(shifted, "mod.py", make_checkers(["retry-discipline"]))
        assert a[0].line != b[0].line
        assert a[0].fingerprint == b[0].fingerprint

    def test_baseline_allowance_is_counted(self):
        # one baseline entry grandfathers ONE occurrence; a second identical
        # finding still gates
        doubled = BAD_SLEEP.replace(
            "time.sleep(0.1)", "time.sleep(0.1)\n        time.sleep(0.1)"
        )
        findings = analyze_source(doubled, "mod.py", make_checkers(["retry-discipline"]))
        assert len(findings) == 2
        baseline = {findings[0].fingerprint: 1}
        findings = core.apply_baseline(findings, baseline)
        assert len(core.gating(findings)) == 1


class TestRegistry:
    def test_all_thirteen_rules_registered(self):
        assert set(ALL_CHECKERS) == {
            "jit-host-sync", "jit-purity", "retry-discipline",
            "lock-discipline", "lock-order", "chaos-obs-coverage",
            "import-hygiene", "donation-safety", "metrics-contract",
            "trace-discipline", "commit-discipline", "thread-lifecycle",
            "env-lane",
        }

    def test_unknown_rule_fails_loudly(self):
        try:
            make_checkers(["no-such-rule"])
        except KeyError as e:
            assert "no-such-rule" in e.args[0]
        else:
            raise AssertionError("expected KeyError")

    def test_parse_error_is_reported_not_raised(self):
        findings = analyze_source("def broken(:\n", "mod.py", make_checkers())
        assert len(findings) == 1
        assert findings[0].rule == "parse-error"


def _run_cli(args, cwd=REPO_ROOT):
    return subprocess.run(
        [sys.executable, "-m", "tosa"] + args,
        cwd=cwd,
        capture_output=True,
        text=True,
        timeout=120,
    )


class TestCLI:
    def test_json_report_and_exit_code(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(BAD_SLEEP)
        proc = _run_cli(
            ["--json", "--root", str(tmp_path), "--baseline", str(tmp_path / "bl.json"), str(bad)]
        )
        assert proc.returncode == 1, proc.stderr
        report = json.loads(proc.stdout)
        assert report["gating"] == 1
        assert report["files_analyzed"] == 1
        [finding] = report["findings"]
        assert finding["rule"] == "retry-discipline"
        assert finding["path"] == "bad.py"

    def test_write_baseline_then_clean(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(BAD_SLEEP)
        bl = tmp_path / "bl.json"
        args = ["--root", str(tmp_path), "--baseline", str(bl), str(bad)]
        proc = _run_cli(["--write-baseline"] + args)
        assert proc.returncode == 0, proc.stderr
        assert json.loads(bl.read_text())["findings"]
        proc = _run_cli(args)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "1 baselined" in proc.stdout

    def test_rules_filter_runs_only_selected(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(BAD_SLEEP)
        proc = _run_cli(
            ["--rules", "import-hygiene", "--root", str(tmp_path),
             "--baseline", str(tmp_path / "bl.json"), str(bad)]
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_unknown_rule_is_usage_error(self):
        proc = _run_cli(["--rules", "bogus"])
        assert proc.returncode == 2
        assert "unknown rule" in proc.stderr

    def test_list_rules_covers_catalog(self):
        proc = _run_cli(["--list-rules"])
        assert proc.returncode == 0
        for rule in ALL_CHECKERS:
            assert rule in proc.stdout

    def test_sarif_report_shape(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(BAD_SLEEP)
        out = tmp_path / "report.sarif"
        proc = _run_cli(
            ["--sarif", "--sarif-out", str(out), "--root", str(tmp_path),
             "--baseline", str(tmp_path / "bl.json"), str(bad)]
        )
        assert proc.returncode == 1, proc.stderr
        for payload in (proc.stdout, out.read_text()):
            sarif = json.loads(payload)
            assert sarif["version"] == "2.1.0"
            [run] = sarif["runs"]
            rule_ids = [r["id"] for r in run["tool"]["driver"]["rules"]]
            assert rule_ids == sorted(ALL_CHECKERS)
            [result] = run["results"]
            assert result["ruleId"] == "retry-discipline"
            assert rule_ids[result["ruleIndex"]] == "retry-discipline"
            loc = result["locations"][0]["physicalLocation"]
            assert loc["artifactLocation"]["uri"] == "bad.py"
            assert loc["region"]["startLine"] >= 1
            assert result["partialFingerprints"]["tosa/v1"]

    def test_changed_mode_requires_targets_and_scopes_report(self, tmp_path):
        proc = _run_cli(["--changed", "--root", str(tmp_path),
                         "--baseline", str(tmp_path / "bl.json")])
        assert proc.returncode == 2
        assert "--changed" in proc.stderr
        good = tmp_path / "good.py"
        good.write_text("def fine():\n    return 1\n")
        bad = tmp_path / "bad.py"
        bad.write_text(BAD_SLEEP)
        # only the changed file's findings are reported even though the
        # neighbor is also in the corpus being indexed
        proc = _run_cli(
            ["--changed", "--json", "--root", str(tmp_path),
             "--baseline", str(tmp_path / "bl.json"), str(good)]
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert json.loads(proc.stdout)["findings"] == []
        proc = _run_cli(
            ["--changed", "--json", "--root", str(tmp_path),
             "--baseline", str(tmp_path / "bl.json"), str(bad)]
        )
        assert proc.returncode == 1
        [finding] = json.loads(proc.stdout)["findings"]
        assert finding["path"] == "bad.py"

    def test_changed_mode_with_no_python_files_is_noop(self, tmp_path):
        doc = tmp_path / "notes.md"
        doc.write_text("prose only\n")
        proc = _run_cli(["--changed", "--root", str(tmp_path),
                         "--baseline", str(tmp_path / "bl.json"), str(doc)])
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "nothing to do" in proc.stdout


class TestIndexCache:
    def test_warm_run_skips_reparsing_and_is_faster(self, tmp_path):
        import time

        from tosa.index import build_index

        lib = os.path.join(REPO_ROOT, "tensorflowonspark_tpu")
        paths = sorted(
            os.path.join(dirpath, name)
            for dirpath, _, names in os.walk(lib)
            for name in names
            if name.endswith(".py")
        )
        assert len(paths) > 10
        cache_path = str(tmp_path / "cache.json")
        t0 = time.monotonic()
        cold = build_index(paths, root=REPO_ROOT, cache_path=cache_path)
        cold_s = time.monotonic() - t0
        assert os.path.exists(cache_path)
        t0 = time.monotonic()
        warm = build_index(paths, root=REPO_ROOT, cache_path=cache_path)
        warm_s = time.monotonic() - t0
        assert set(warm.modules) == set(cold.modules)
        assert warm.modules == cold.modules
        # the warm pass hashes file contents but never calls ast.parse;
        # generous margin so CI jitter doesn't flake the assertion
        assert warm_s < max(cold_s * 0.6, 0.05), (cold_s, warm_s)

    def test_cache_invalidated_by_content_change(self, tmp_path):
        from tosa.index import build_index

        mod = tmp_path / "mod.py"
        mod.write_text("import threading\n_lk = threading.Lock()\n")
        cache_path = str(tmp_path / "cache.json")
        first = build_index([str(mod)], root=str(tmp_path), cache_path=cache_path)
        assert first.modules["mod.py"]["module_locks"]
        mod.write_text("X = 1\n")
        second = build_index([str(mod)], root=str(tmp_path), cache_path=cache_path)
        assert not second.modules["mod.py"]["module_locks"]

    def test_stale_cache_version_is_ignored(self, tmp_path):
        from tosa import index as tosa_index

        mod = tmp_path / "mod.py"
        mod.write_text("X = 1\n")
        cache_path = str(tmp_path / "cache.json")
        tosa_index.build_index([str(mod)], root=str(tmp_path), cache_path=cache_path)
        with open(cache_path) as f:
            payload = json.load(f)
        payload["cache_version"] = -1
        with open(cache_path, "w") as f:
            json.dump(payload, f)
        cache = tosa_index.load_cache(cache_path, [])
        assert cache.files == {}


class TestSelfRun:
    def test_repo_is_clean_under_all_rules(self):
        """The hard gate: the analyzer over its default targets (library,
        scripts) finds nothing to report — every invariant the
        thirteen rules encode holds in this repo, with an empty baseline."""
        proc = _run_cli([])
        assert proc.returncode == 0, "\n" + proc.stdout + proc.stderr

    def test_committed_baseline_is_empty(self):
        with open(os.path.join(REPO_ROOT, "tools", "analyze", "baseline.json")) as f:
            assert json.load(f) == {"findings": []}
