"""graft-lint: fixture-corpus true-positive/true-negative runs per
checker, inline-suppression and baseline semantics, the ``--json``
schema, the subprocess exit-code contract, and the tier-1 gate run over
the real package.

Everything here is host-only and pure-AST: no test in this module may
pull jax through ``tools.lint`` (AST-pinned below, the same convention
GL01 itself enforces on the serving policy tier).
"""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "lint")

from tools.lint.core import (BaselineEntry, LintError,  # noqa: E402
                             load_baseline, render_json, render_markdown,
                             render_text, run)


def fixture_run(checker: str, kind: str, **kw):
    root = os.path.join(FIXTURES, checker, kind)
    return run(paths=[os.path.join(root, "deepspeed_tpu")], root=root, **kw)


def by_code(report, code):
    return [f for f in report.findings if f.code == code]


# ---------------------------------------------------------------------------
# the acceptance matrix: every checker fires on bad, stays silent on good


@pytest.mark.parametrize("code", ["GL01", "GL02", "GL03", "GL04", "GL05",
                                  "GL06", "GL07", "GL08"])
def test_checker_fires_on_bad_and_is_silent_on_good(code):
    name = code.lower()
    bad = fixture_run(name, "bad")
    assert by_code(bad, code), f"{code} missed its known-bad fixture"
    good = fixture_run(name, "good")
    assert not by_code(good, code), (
        f"{code} false-positives on its known-good fixture: "
        f"{by_code(good, code)}")


class TestGL01:
    def test_direct_and_transitive_legs(self):
        found = by_code(fixture_run("gl01", "bad"), "GL01")
        paths = {f.path for f in found}
        # direct: the registered module itself
        assert "deepspeed_tpu/telemetry/events.py" in paths
        # transitive: flagged AT the offending closure edge, naming the
        # chain from the registered module
        helper = [f for f in found
                  if f.path == "deepspeed_tpu/utils/devhelper.py"]
        assert helper and "scheduler" in helper[0].message \
            and "devhelper" in helper[0].message

    def test_shared_closure_edge_is_one_finding(self, tmp_path):
        """One bad import line reached from N registered modules is ONE
        finding (one fix), not N duplicates inflating the counts."""
        pkg = tmp_path / "deepspeed_tpu"
        (pkg / "serving").mkdir(parents=True)
        (pkg / "utils").mkdir()
        for name in ("scheduler.py", "router.py"):
            (pkg / "serving" / name).write_text(
                "from deepspeed_tpu.utils.shared_util import n\n")
        (pkg / "utils" / "shared_util.py").write_text("import jax\nn = 1\n")
        report = run(paths=[str(pkg)], root=str(tmp_path),
                     select=["GL01"])
        assert len(report.findings) == 1

    def test_registry_covers_the_serving_policy_tier(self):
        """The PR 6/7 ad-hoc pins migrated here: one registry."""
        from tools.lint.checkers.gl01_jax_free import JAX_FREE_MODULES

        assert {"deepspeed_tpu/serving/scheduler.py",
                "deepspeed_tpu/serving/router.py",
                "deepspeed_tpu/serving/health.py",
                "deepspeed_tpu/serving/blocks.py",
                "deepspeed_tpu/serving/prefix_cache.py",
                "deepspeed_tpu/serving/config.py",
                "deepspeed_tpu/serving/request.py",
                "deepspeed_tpu/telemetry/events.py",
                "deepspeed_tpu/autotuning/artifact.py"} \
            <= set(JAX_FREE_MODULES)


class TestGL02:
    def test_every_api_family_fires(self):
        msgs = " | ".join(f.message
                          for f in by_code(fixture_run("gl02", "bad"),
                                           "GL02"))
        for api in ("shard_map", "serialize_executable",
                    "CompilerParams", "force_tpu_interpret_mode",
                    "persistent-cache arming"):
            assert api in msgs, f"GL02 missed {api}"

    def test_compat_module_is_exempt(self):
        report = fixture_run("gl02", "good")
        assert not by_code(report, "GL02")
        # the exempt shim really was scanned (not just absent)
        assert report.files_scanned == 2


class TestGL03:
    def test_detection_modes_and_impurity_classes(self):
        found = by_code(fixture_run("gl03", "bad"), "GL03")
        msgs = " | ".join(f.message for f in found)
        # all four traced-function detection modes
        assert "decorated @jax.jit" in msgs
        assert "passed to jax.jit()" in msgs
        assert "passed to pl.pallas_call()" in msgs
        assert "@partial(jax.jit, ...)" in msgs
        # all impurity classes
        for impurity in ("time.time", "print()", "np.random.normal",
                         "random.random", ".item()",
                         "float() host sync on traced parameter"):
            assert impurity in msgs, f"GL03 missed {impurity}"

    def test_host_rng_feeding_decode_program_is_flagged(self):
        # the keyed-sampling regression shape: np.random noise baked
        # into a jitted decode program at trace time
        found = by_code(fixture_run("gl03", "bad"), "GL03")
        hits = [f for f in found if f.path.endswith("serving/sampler.py")]
        assert hits, "GL03 missed host rng feeding the decode program"
        assert any("np.random.gumbel" in f.message for f in hits)

    def test_host_wrapper_impurity_is_not_flagged(self):
        # the good fixture's host_wrapper calls time.time/print freely
        assert not by_code(fixture_run("gl03", "good"), "GL03")


class TestGL04:
    def test_sync_kinds_in_hot_bodies(self):
        found = by_code(fixture_run("gl04", "bad"), "GL04")
        msgs = " | ".join(f.message for f in found)
        for sync in ("np.asarray", ".block_until_ready()",
                     "jax.device_get"):
            assert sync in msgs, f"GL04 missed {sync}"

    def test_gates_and_suppression_hold(self):
        report = fixture_run("gl04", "good")
        assert not by_code(report, "GL04")
        # the designed-sync inline disable was counted, not silently ok
        assert report.suppressed == 1


class TestGL05:
    def test_unregistered_kinds_flagged_with_registry_listing(self):
        found = [f for f in by_code(fixture_run("gl05", "bad"), "GL05")
                 if "unregistered kind" in f.message]
        kinds = {f.message.split("'")[1] for f in found}
        assert kinds == {"servign", "decode_stats", "bogus", "gatway"}
        assert all("compile, serving, fault" in f.message for f in found)

    def test_unregistered_span_names_flagged(self):
        """Span-name registry leg: every literal span-name emit site
        (kind-\"span\" emits, tracer.record_span/begin, step_trace.mark,
        the one bracket's span= keyword) is pinned against
        telemetry/events.SPANS."""
        found = [f for f in by_code(fixture_run("gl05", "bad"), "GL05")
                 if "unregistered span name" in f.message]
        names = {f.message.split("'")[1] for f in found}
        assert names == {"prefil", "dequeue", "warmup", "fwdbwd",
                         "drafts", "commit", "migrat", "ingres",
                         # the start-up ledger's brackets (ISSUE 54)
                         "startup.pol", "pool", "startup.serving"}
        assert all("request, queue, decode, draft, verify, spec_commit"
                   in f.message for f in found)

    def test_dynamic_kind_not_flagged(self):
        # the good corpus includes registered span names, a DYNAMIC span
        # name, and a dynamic kind — none may fire
        assert not by_code(fixture_run("gl05", "good"), "GL05")


class TestGL06:
    def test_both_drift_directions(self):
        found = by_code(fixture_run("gl06", "bad"), "GL06")
        forward = [f for f in found
                   if f.path == "deepspeed_tpu/runtime/config.py"]
        reverse = [f for f in found if f.path == "docs/config.md"]
        assert len(forward) == 1 and "WidgetConfig.beta" \
            in forward[0].message
        assert len(reverse) == 1 and "widget.gamma" in reverse[0].message

    def test_alias_deprecated_and_freeform_exemptions(self):
        # good tree: alias documents `renamed`, deprecated exempt,
        # params payload never checked
        assert not by_code(fixture_run("gl06", "good"), "GL06")


class TestGL07:
    def test_every_clock_family_fires(self):
        found = by_code(fixture_run("gl07", "bad"), "GL07")
        msgs = " | ".join(f.message for f in found)
        for call in ("time.monotonic", "time.time", "time.perf_counter",
                     "time.sleep", "datetime.datetime.now", "dt.utcnow"):
            assert call in msgs, f"GL07 missed {call}"

    def test_seam_default_and_clock_reads_are_legal(self):
        """``clock=time.monotonic`` as a default argument is the seam
        itself; ``self.clock()`` reads are how the seam is consumed —
        neither may fire."""
        assert not by_code(fixture_run("gl07", "good"), "GL07")

    def test_unregistered_module_keeps_its_real_clock(self):
        """The good corpus' engine.py calls time.monotonic() directly —
        it is not in CLOCKED_MODULES (the device side keeps real time),
        so GL07 must stay scoped to the registry."""
        report = fixture_run("gl07", "good")
        assert report.files_scanned == 2      # engine.py really scanned
        assert not by_code(report, "GL07")

    def test_registry_covers_the_fleet_tier(self):
        from tools.lint.checkers.gl07_injectable_clock import \
            CLOCKED_MODULES

        assert {"deepspeed_tpu/serving/router.py",
                "deepspeed_tpu/serving/health.py",
                "deepspeed_tpu/serving/scheduler.py",
                "deepspeed_tpu/serving/autoscaler.py",
                "deepspeed_tpu/serving/replay.py",
                "deepspeed_tpu/serving/capacity.py",
                "deepspeed_tpu/serving/gateway.py",
                "deepspeed_tpu/serving/tenancy.py"} \
            <= set(CLOCKED_MODULES)


class TestGL08:
    def test_every_bad_shape_fires(self):
        """Typo names, near-misses and the keyword-argument form must
        all be caught."""
        found = by_code(fixture_run("gl08", "bad"), "GL08")
        msgs = " | ".join(f.message for f in found)
        for name in ("ds_step_total", "ds_fleet_overlod",
                     "ds_serving_ttft_millis", "ds_decode_stats_total",
                     "ds_slo_burnrate", "ds_migration_attempt_total",
                     "ds_gateway_request_total"):
            assert name in msgs, f"GL08 missed {name!r}"
        assert len(found) == 7

    def test_registered_dynamic_and_non_registry_shapes_are_legal(self):
        """Registered literals pass; dynamic names are the wrapper's
        responsibility; ``gauges()`` reads, ``collections.Counter`` and
        bare ``counter()`` calls carry no registry semantics."""
        assert not by_code(fixture_run("gl08", "good"), "GL08")

    def test_names_table_is_ast_readable_in_the_real_package(self):
        """The real registry's NAMES must stay a pure dict literal —
        the checker (and this test) read it without importing."""
        from tools.lint.checkers.gl08_metric_names import registry_names
        from tools.lint.core import LintContext

        names = registry_names(LintContext([], REPO))
        assert names is not None and len(names) >= 20
        assert "ds_serving_ttft_ms" in names
        assert "ds_slo_burn_rate" in names

    def test_real_call_sites_subset_of_names(self):
        """Belt-and-braces: the AST-read table agrees with the runtime
        NAMES dict (one definition, two readers)."""
        from deepspeed_tpu.telemetry.registry import NAMES
        from tools.lint.checkers.gl08_metric_names import registry_names
        from tools.lint.core import LintContext

        assert set(registry_names(LintContext([], REPO))) == set(NAMES)


# ---------------------------------------------------------------------------
# suppression semantics


class TestSuppressions:
    def _tree(self, tmp_path, body):
        pkg = tmp_path / "deepspeed_tpu" / "telemetry"
        pkg.mkdir(parents=True)
        (pkg / "events.py").write_text(body)
        return tmp_path

    def test_inline_disable_suppresses_matching_code_only(self, tmp_path):
        root = self._tree(tmp_path,
                          "import jax  # graft-lint: disable=GL01\n")
        report = run(paths=[str(tmp_path / "deepspeed_tpu")],
                     root=str(root))
        assert not report.findings and report.suppressed == 1

    def test_wrong_code_does_not_suppress(self, tmp_path):
        root = self._tree(tmp_path,
                          "import jax  # graft-lint: disable=GL02\n")
        report = run(paths=[str(tmp_path / "deepspeed_tpu")],
                     root=str(root))
        assert by_code(report, "GL01") and report.suppressed == 0

    def test_disable_is_line_scoped(self, tmp_path):
        root = self._tree(tmp_path,
                          "# graft-lint: disable=GL01\nimport jax\n")
        report = run(paths=[str(tmp_path / "deepspeed_tpu")],
                     root=str(root))
        assert by_code(report, "GL01"), \
            "a disable on line 1 must not cover line 2"

    def test_multi_code_disable(self, tmp_path):
        root = self._tree(
            tmp_path, "import jax  # graft-lint: disable=GL02, GL01\n")
        report = run(paths=[str(tmp_path / "deepspeed_tpu")],
                     root=str(root))
        assert not by_code(report, "GL01") and report.suppressed == 1

    def test_suppression_honored_outside_the_scan_set(self, tmp_path):
        """GL01 loads registry modules via the root even when the scan
        set is empty (the migrated router test does exactly this) — an
        inline disable must count identically, or the same tree lints
        clean or dirty depending on the caller's `paths`."""
        root = self._tree(tmp_path,
                          "import jax  # graft-lint: disable=GL01\n")
        report = run(paths=[], root=str(root), select=["GL01"])
        assert not report.findings and report.suppressed == 1


# ---------------------------------------------------------------------------
# baseline semantics


class TestBaseline:
    def test_matching_entry_moves_finding_to_baselined(self):
        entry = BaselineEntry(code="GL01",
                              path="deepspeed_tpu/telemetry/events.py",
                              justification="fixture: known-bad on purpose")
        report = fixture_run("gl01", "bad", baseline=[entry])
        assert not any(f.path == entry.path for f in report.findings)
        assert any(f.path == entry.path for f, _ in report.baselined)
        assert not report.stale_baseline

    def test_match_substring_narrows_the_entry(self):
        entry = BaselineEntry(code="GL01",
                              path="deepspeed_tpu/telemetry/events.py",
                              match="no finding says this",
                              justification="narrow")
        report = fixture_run("gl01", "bad", baseline=[entry])
        assert any(f.path == entry.path for f in report.findings)
        assert entry in report.stale_baseline

    def test_stale_entry_is_reported_in_text_and_markdown(self):
        entry = BaselineEntry(code="GL05", path="nowhere.py",
                              justification="stale on purpose")
        report = fixture_run("gl01", "good", baseline=[entry])
        assert report.stale_baseline == [entry]
        assert "stale baseline" in render_text(report)
        assert "stale baseline" in render_markdown(report)

    def test_baseline_without_justification_is_an_error(self, tmp_path):
        path = tmp_path / "baseline.json"
        path.write_text(json.dumps({"entries": [
            {"code": "GL01", "path": "x.py", "justification": "  "}]}))
        with pytest.raises(LintError, match="justification"):
            load_baseline(str(path))

    def test_baseline_wrong_top_level_shape_is_an_error(self, tmp_path):
        path = tmp_path / "baseline.json"
        path.write_text("[]")   # valid JSON, wrong shape
        with pytest.raises(LintError, match="JSON object"):
            load_baseline(str(path))

    def test_repo_baseline_file_loads_and_is_justified(self):
        entries = load_baseline(
            os.path.join(REPO, "tools", "lint_baseline.json"))
        assert all(e.justification for e in entries)


# ---------------------------------------------------------------------------
# output formats


class TestOutputs:
    def test_json_schema(self):
        payload = json.loads(render_json(fixture_run("gl01", "bad")))
        assert set(payload) == {"version", "clean", "files_scanned",
                                "codes_run", "counts", "suppressed",
                                "findings", "baselined", "stale_baseline"}
        assert payload["clean"] is False
        assert payload["counts"]["GL01"] == len(payload["findings"])
        f = payload["findings"][0]
        assert set(f) == {"code", "path", "line", "col", "message"}

    def test_json_is_deterministic(self):
        a = render_json(fixture_run("gl03", "bad"))
        b = render_json(fixture_run("gl03", "bad"))
        assert a == b

    def test_markdown_sections(self):
        entry = BaselineEntry(code="GL01",
                              path="deepspeed_tpu/telemetry/events.py",
                              justification="fixture baseline demo")
        md = render_markdown(fixture_run("gl01", "bad", baseline=[entry]))
        assert "### lint: machine-checked invariants" in md
        assert "| code | location | finding |" in md
        assert "#### baseline" in md and "fixture baseline demo" in md
        assert "#### checkers" in md and "GL06" in md

    def test_text_lists_findings_with_locations(self):
        text = render_text(fixture_run("gl02", "bad"))
        assert "deepspeed_tpu/ops/kernels.py:4:0: GL02" in text


# ---------------------------------------------------------------------------
# runner plumbing


class TestRunner:
    def test_select_and_ignore(self):
        only = fixture_run("gl02", "bad", select=["GL05"])
        assert not only.findings and only.codes_run == ["GL05"]
        skipped = fixture_run("gl02", "bad", ignore=["GL02"])
        assert not by_code(skipped, "GL02")

    def test_unknown_select_code_is_an_error(self):
        with pytest.raises(LintError, match="unknown checker"):
            fixture_run("gl01", "good", select=["GL99"])

    def test_explicit_non_py_file_is_an_error_not_clean(self, tmp_path):
        doc = tmp_path / "notes.md"
        doc.write_text("# notes\n")
        with pytest.raises(LintError, match="not a python file"):
            run(paths=[str(doc)], root=str(tmp_path))

    def test_syntax_error_file_is_tolerated_not_fatal(self, tmp_path):
        pkg = tmp_path / "deepspeed_tpu"
        pkg.mkdir()
        (pkg / "broken.py").write_text("def oops(:\n")
        (pkg / "fine.py").write_text("x = 1\n")
        report = run(paths=[str(pkg)], root=str(tmp_path))
        assert report.files_scanned == 2 and not report.findings


# ---------------------------------------------------------------------------
# the tier-1 gate: the real package lints clean, fast, without jax


@pytest.fixture(scope="module")
def repo_report():
    baseline = load_baseline(os.path.join(REPO, "tools",
                                          "lint_baseline.json"))
    # the CPU time of the THREAD that runs the pass: the wall clock waits
    # for a core beside five busy workers, and the process's CPU time counts
    # whatever the worker's other threads (a compile still being written to
    # the cache, the collector) burn in the same second
    t0 = time.thread_time()
    report = run(root=REPO, baseline=baseline)
    report.elapsed = time.thread_time() - t0
    return report


class TestRepoGate:
    def test_package_lints_clean(self, repo_report):
        assert repo_report.clean, (
            "graft-lint found new violations — fix them or baseline with "
            "a justification:\n" + render_text(repo_report))

    def test_no_stale_baseline_entries(self, repo_report):
        assert not repo_report.stale_baseline, (
            "baseline entries matched nothing — remove them: "
            f"{repo_report.stale_baseline}")

    def test_whole_package_was_scanned(self, repo_report):
        assert repo_report.files_scanned > 100
        assert repo_report.codes_run == ["GL01", "GL02", "GL03", "GL04",
                                         "GL05", "GL06", "GL07", "GL08"]

    def test_runs_inside_the_tier1_budget(self, repo_report):
        assert repo_report.elapsed < 2.0, (
            f"lint pass took {repo_report.elapsed:.2f}s of CPU time — it "
            f"must stay cheap enough to gate every tier-1 run")

    def test_lint_package_itself_is_jax_free(self):
        """AST pin, same convention as GL01: nothing under tools/lint
        (or the CLI script) may import jax/jaxlib/flax at module level —
        the linter must run on boxes with no accelerator stack."""
        import ast

        lint_dir = os.path.join(REPO, "tools", "lint")
        files = [os.path.join(REPO, "tools", "lint.py")]
        for dirpath, dirnames, filenames in os.walk(lint_dir):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            files += [os.path.join(dirpath, f) for f in filenames
                      if f.endswith(".py")]
        assert len(files) >= 9
        for path in files:
            tree = ast.parse(open(path).read(), path)
            for node in tree.body:
                names = []
                if isinstance(node, ast.Import):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    names = [node.module or ""]
                for name in names:
                    assert name.split(".")[0] not in \
                        ("jax", "jaxlib", "flax", "numpy"), (
                        f"{path} imports {name} at module level — "
                        f"graft-lint is pure-AST by contract")


# ---------------------------------------------------------------------------
# subprocess smoke: the CLI exit-code contract


class TestCLI:
    def _lint(self, *args):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "lint.py"),
             *args],
            capture_output=True, text=True, cwd=REPO, timeout=300)

    def test_exit_2_on_findings(self):
        root = os.path.join(FIXTURES, "gl01", "bad")
        res = self._lint(os.path.join(root, "deepspeed_tpu"),
                         "--root", root, "--no-baseline")
        assert res.returncode == 2
        assert "GL01" in res.stdout

    def test_exit_0_clean_with_json(self):
        root = os.path.join(FIXTURES, "gl01", "good")
        res = self._lint(os.path.join(root, "deepspeed_tpu"),
                         "--root", root, "--no-baseline", "--json")
        assert res.returncode == 0
        assert json.loads(res.stdout)["clean"] is True

    def test_exit_1_on_usage_error(self):
        res = self._lint("--baseline", "/nonexistent/baseline.json")
        assert res.returncode == 1
        assert "error" in res.stderr
