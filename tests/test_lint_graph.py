"""Tests for the whole-program layers: the flow graph (``lint.graph``),
the lock-discipline race detector (``lint.concurrency``), the jaxpr
invariant checks (``lint.jaxpr``), and the JL020 suppression meta-rule.

The concurrency fixtures live in ``tests/lint_fixtures/concurrency/`` —
each file pairs a seeded violation with a clean counterpart so every
assertion pins both the detection and the false-positive boundary.
"""

import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

from jimm_tpu.lint import ERROR, WARNING, lint_file
from jimm_tpu.lint.concurrency import (apply_jl014_waivers, jl014_waivers,
                                       run_concurrency_checks)
from jimm_tpu.lint.core import (check_bare_suppressions, collect_files,
                                parse_directives, suppression_audit)
from jimm_tpu.lint.graph import ProjectGraph

FIXTURES = Path(__file__).parent / "lint_fixtures"
CONC = FIXTURES / "concurrency"
REPO = Path(__file__).resolve().parent.parent


def fixture_files(*names):
    return [str(CONC / n) for n in names]


def rules_and_lines(findings):
    return {(f.rule, f.line) for f in findings}


@pytest.fixture(scope="module")
def fixture_graph():
    return ProjectGraph.build(collect_files([str(CONC)]))


@pytest.fixture(scope="module")
def live_graph():
    return ProjectGraph.build(collect_files(
        [str(REPO / "jimm_tpu"), str(REPO / "tests")]))


class TestGraphInference:
    def test_thread_roots_discovered(self, fixture_graph):
        assert fixture_graph.roots_of("RacyCounter._drain_a") \
            == {"thread:_drain_a"}

    def test_http_handler_root_seeded(self, fixture_graph):
        assert fixture_graph.roots_of("FixtureHandler.do_GET") \
            == {"http-handler"}

    def test_roots_propagate_through_calls(self, fixture_graph):
        # _make_fn is only reachable via do_GET -> _respond -> _make_fn,
        # so it inherits the handler root interprocedurally
        assert "http-handler" in fixture_graph.roots_of(
            "FixtureHandler._make_fn")

    def test_caller_guarded_helper_inherits_both_thread_roots(
            self, fixture_graph):
        assert fixture_graph.roots_of("CallerGuardedCounter._bump") \
            == {"thread:_loop_a", "thread:_loop_b"}

    def test_guard_sets_infer_lexical_locks(self, fixture_graph):
        guards = fixture_graph.guard_sets("LockedCounter")
        assert guards.get("hits"), "hits writes should be guarded"
        assert all("_lock" in g for g in guards["hits"])

    def test_entry_guard_inference_covers_callers(self, fixture_graph):
        # CallerGuardedCounter._bump holds no lock lexically, but every
        # caller acquires self._lock first -> entry guards make it safe
        fn = fixture_graph.function("CallerGuardedCounter._bump")
        assert fn is not None
        assert fn.entry_guards, "entry guards should be inferred"

    def test_write_sites_exclude_init(self, fixture_graph):
        sites = fixture_graph.write_sites()
        for (owner, _attr), ws in sites.items():
            assert all(not w.in_init for w in ws), owner


class TestConcurrencyRules:
    def test_jl017_racy_counter(self):
        findings = run_concurrency_checks(fixture_files("racy_counter.py"))
        assert rules_and_lines(findings) == {("JL017", 24)}
        f = findings[0]
        assert f.severity == ERROR
        assert "thread:_drain_a" in f.message
        assert "thread:_drain_b" in f.message

    def test_jl017_silent_on_guarded_and_caller_guarded(self):
        # LockedCounter and CallerGuardedCounter live in the same file as
        # the violation; the single finding above already proves silence,
        # but pin it explicitly on a graph-level query too
        g = ProjectGraph.build(fixture_files("racy_counter.py"))
        findings = run_concurrency_checks(
            fixture_files("racy_counter.py"), graph=g)
        assert not any("LockedCounter" in f.message or
                       "CallerGuarded" in f.message for f in findings)

    def test_jl018_lock_order_cycle(self):
        findings = run_concurrency_checks(fixture_files("lock_cycle.py"))
        assert rules_and_lines(findings) == {("JL018", 21)}
        f = findings[0]
        assert f.severity == ERROR
        assert "_plan_lock" in f.message and "_stats_lock" in f.message

    def test_jl019_blocking_under_lock(self):
        findings = run_concurrency_checks(
            fixture_files("sleep_under_lock.py"))
        assert rules_and_lines(findings) == {
            ("JL019", 18),  # time.sleep under lock
            ("JL019", 23),  # queue.get under lock
            ("JL019", 32),  # queue.get under caller-held (entry) guard
        }

    def test_jl006_interprocedural(self):
        findings = run_concurrency_checks(
            fixture_files("async_device_wait.py"))
        assert rules_and_lines(findings) == {("JL006", 7)}

    def test_jl008_interprocedural(self):
        findings = run_concurrency_checks(fixture_files("handler_jit.py"))
        assert rules_and_lines(findings) == {("JL008", 18)}

    def test_jl023_inline_tier_io_on_request_path(self):
        fx = str(FIXTURES / "retrieval" / "tier" / "streaming_fetch.py")
        findings = run_concurrency_checks([fx])
        assert rules_and_lines(findings) == {
            ("JL023", 29),  # ArtifactStore.get three hops below do_GET
            ("JL023", 33),  # np.load on the do_POST path
        }
        assert all(f.severity == ERROR for f in findings)
        assert "prefetch" in findings[0].message

    def test_jl023_worker_split_and_daemon_io_are_clean(self):
        fx = str(FIXTURES / "retrieval" / "tier" / "streaming_fetch.py")
        findings = run_concurrency_checks([fx])
        assert not any("WorkerFetchHandler" in f.message or
                       "_daemon_cycle" in f.message for f in findings)

    def test_jl014_waived_by_base_class_eviction(self):
        child = CONC / "serve" / "child_table.py"
        per_file = [f for f in lint_file(child) if f.rule == "JL014"]
        assert rules_and_lines(per_file) == {("JL014", 10)}

        g = ProjectGraph.build(collect_files([str(CONC / "serve")]))
        assert any(attr == "_table" for _path, attr in jl014_waivers(g))
        waived = apply_jl014_waivers(list(per_file), g)
        assert waived == []

    def test_zero_false_positives_on_live_tree(self, live_graph):
        files = collect_files([str(REPO / "jimm_tpu"), str(REPO / "tests")])
        findings = run_concurrency_checks(files, graph=live_graph)
        assert findings == [], "\n".join(f.render() for f in findings)

    @pytest.mark.slow
    def test_full_tree_build_within_budget(self):
        # the full tree takes a few seconds on a quiet machine; 20 s allows
        # for contention with the rest of the tests, so this asserts "same
        # order of magnitude", not luck
        t0 = time.perf_counter()
        files = collect_files([str(REPO / "jimm_tpu"), str(REPO / "tests")])
        g = ProjectGraph.build(files)
        run_concurrency_checks(files, graph=g)
        assert time.perf_counter() - t0 <= 20.0


class TestJl020Suppressions:
    def test_bare_suppression_warns(self, tmp_path):
        src = "import jax\nx = 1  # jaxlint: disable=JL008\n"
        findings = check_bare_suppressions(src, "foo.py")
        assert [(f.rule, f.line, f.severity) for f in findings] == [
            ("JL020", 2, WARNING)]
        assert "JL008" in findings[0].message

    def test_justified_suppression_is_silent(self):
        src = "x = 1  # jaxlint: disable=JL008 one compile per variant\n"
        assert check_bare_suppressions(src, "foo.py") == []

    def test_directive_parse_keeps_justification(self):
        src = ("a = 1  # jaxlint: disable=JL008,JL009 measured, on purpose\n"
               "# jaxlint: disable=JL013\n")
        directives = parse_directives(src)
        assert directives[0].rules == frozenset({"JL008", "JL009"})
        assert directives[0].justification == "measured, on purpose"
        assert directives[1].justification == ""
        # a full-line directive targets the NEXT line
        assert directives[1].target == 3

    def test_indented_standalone_directive_targets_next_line(self):
        # a comment-only line inside a block is still standalone, even
        # though its column is nonzero
        src = ("def f():\n"
               "    # jaxlint: disable=JL009 pinned probe config\n"
               "    g(block_q=128)\n")
        (d,) = parse_directives(src)
        assert d.target == 3
        assert d.justification == "pinned probe config"

    def test_audit_table_covers_tree(self):
        rows = suppression_audit([str(REPO / "jimm_tpu"),
                                  str(REPO / "scripts")])
        assert rows, "the tree has known, justified suppressions"
        bare = [r for r in rows if not r[3]]
        assert bare == [], f"bare suppressions in tree: {bare}"

    @pytest.mark.slow
    def test_shipped_tree_has_no_jl020(self):
        from jimm_tpu.lint import lint_paths
        findings = [f for f in lint_paths([str(REPO / "jimm_tpu")])
                    if f.rule == "JL020"]
        assert findings == [], "\n".join(f.render() for f in findings)


class TestJaxprLayer:
    def test_live_entries_match_goldens(self):
        from jimm_tpu.lint.jaxpr import run_jaxpr_checks
        findings = run_jaxpr_checks()
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_jlt104_promotion_drift(self):
        import jax.numpy as jnp

        from jimm_tpu.lint.jaxpr import run_jaxpr_checks

        def bad_promo():
            def f(x):
                return x.astype(jnp.float32) * 2
            return f, (jnp.zeros((4,), jnp.int8),)

        findings = run_jaxpr_checks(
            entry_points={"bad_promo": bad_promo},
            goldens={"bad_promo": {"f32_promotions": 0,
                                   "collectives": {}}})
        assert [f.rule for f in findings] == ["JLT104"]
        assert findings[0].severity == ERROR
        assert findings[0].path == "<jaxpr:bad_promo>"

    def test_jlt105_baked_constant(self):
        import numpy as np

        import jax.numpy as jnp

        from jimm_tpu.lint.jaxpr import run_jaxpr_checks

        def bad_const():
            baked = jnp.asarray(np.ones((64, 64), np.float32))

            def f(x):
                return x + baked
            return f, (jnp.zeros((64, 64), jnp.float32),)

        findings = run_jaxpr_checks(
            entry_points={"bad_const": bad_const},
            goldens={"bad_const": {"f32_promotions": 99,
                                   "collectives": {}}})
        assert [f.rule for f in findings] == ["JLT105"]
        assert "16384 bytes" in findings[0].message

    def test_jlt106_collective_drift_and_missing_golden(self):
        import jax
        import jax.numpy as jnp

        from jimm_tpu.lint.jaxpr import run_jaxpr_checks

        def with_sum():
            def f(x):
                # jnp.sum has no collective; drift comes from the golden
                return jnp.sum(x)
            return f, (jnp.zeros((4,), jnp.float32),)

        # golden expects one psum -> observing zero is ERROR drift
        drift = run_jaxpr_checks(
            entry_points={"e": with_sum},
            goldens={"e": {"f32_promotions": 9,
                           "collectives": {"psum_invariant": 1}}})
        assert [(f.rule, f.severity) for f in drift] == [("JLT106", ERROR)]

        # no golden at all -> WARNING nudging a goldens update
        missing = run_jaxpr_checks(entry_points={"e": with_sum}, goldens={})
        assert [(f.rule, f.severity) for f in missing] == [
            ("JLT106", WARNING)]
        assert "--update-goldens" in missing[0].message

    def test_broken_entry_becomes_jlt000(self):
        from jimm_tpu.lint.jaxpr import run_jaxpr_checks

        def broken():
            raise ValueError("fixture boom")

        findings = run_jaxpr_checks(entry_points={"broken": broken},
                                    goldens={})
        assert [(f.rule, f.severity) for f in findings] == [
            ("JLT000", ERROR)]
        assert "fixture boom" in findings[0].message

    def test_goldens_file_is_committed_and_complete(self):
        from jimm_tpu.lint.jaxpr import ENTRY_POINTS, GOLDENS_PATH
        goldens = json.loads(GOLDENS_PATH.read_text())
        assert set(goldens) == set(ENTRY_POINTS)
        assert goldens["data_parallel_psum"]["collectives"] == {"psum_invariant": 1}


class TestCliIntegration:
    def run_cli(self, *args):
        return subprocess.run(
            [sys.executable, "-m", "jimm_tpu.lint", *args],
            capture_output=True, text=True, cwd=REPO,
        )

    def test_concurrency_flag_finds_fixture_race(self):
        proc = self.run_cli(str(CONC / "racy_counter.py"),
                            "--concurrency", "--json")
        assert proc.returncode == 1
        report = json.loads(proc.stdout)
        assert [(f["rule"], f["line"]) for f in report] == [("JL017", 24)]

    def test_sarif_export(self, tmp_path):
        out = tmp_path / "lint.sarif"
        proc = self.run_cli(str(CONC / "lock_cycle.py"), "--concurrency",
                            "--sarif", str(out))
        assert proc.returncode == 1
        sarif = json.loads(out.read_text())
        assert sarif["version"] == "2.1.0"
        run = sarif["runs"][0]
        assert run["tool"]["driver"]["name"] == "jaxlint"
        results = run["results"]
        assert [r["ruleId"] for r in results] == ["JL018"]
        assert results[0]["level"] == "error"
        loc = results[0]["locations"][0]["physicalLocation"]
        assert loc["region"]["startLine"] == 21

    def test_suppressions_flag_exits_zero(self):
        proc = self.run_cli("jimm_tpu", "--suppressions")
        assert proc.returncode == 0
        assert "directive(s)" in proc.stdout


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
