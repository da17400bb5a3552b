"""Tests for the jimm_tpu.lint static analyzer (Layer 1 + CLI).

The fixtures under tests/lint_fixtures/ are excluded from normal lint walks
(see EXCLUDED_DIRS) and only linted when named explicitly, so the shipped
tree stays clean while each rule keeps a living positive example.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from jimm_tpu.lint import ERROR, lint_file, lint_paths
from jimm_tpu.lint.rules_ast import CANONICAL_MESH_AXES

FIXTURES = Path(__file__).parent / "lint_fixtures"
REPO = Path(__file__).resolve().parent.parent


def findings_for(name):
    return lint_file(FIXTURES / name)


def rules_and_lines(findings):
    return {(f.rule, f.line) for f in findings}


class TestRuleFixtures:
    def test_jl002_host_sync_in_jit(self):
        findings = findings_for("bad_host_sync.py")
        assert rules_and_lines(findings) == {
            ("JL002", 9),   # float() on traced value
            ("JL002", 10),  # np.asarray on traced value
            ("JL002", 11),  # Python `if` on traced value
            ("JL002", 13),  # .item()
        }

    def test_jl003_missing_donation(self):
        findings = findings_for("bad_donation.py")
        assert rules_and_lines(findings) == {
            ("JL003", 8),   # optimizer-carrying nnx.jit without donate_argnums
            ("JL003", 15),  # builder call without donate=
        }

    def test_jl004_non_canonical_partition_spec(self):
        findings = findings_for("bad_partition_spec.py")
        assert rules_and_lines(findings) == {("JL004", 9)}
        assert "'batch'" in findings[0].message

    def test_jl005_pallas_tiling_and_vmem(self):
        findings = findings_for("bad_pallas.py")
        assert rules_and_lines(findings) == {
            ("JL005", 11),  # lane dim 100 not %128
            ("JL005", 12),  # sublane dim 12 not %8
            ("JL005", 13),  # VMEM scratch over budget
        }

    def test_jl005_budget_is_configurable(self):
        findings = lint_file(FIXTURES / "bad_pallas.py",
                             vmem_budget=256 * 1024 * 1024)
        # with a 256 MiB budget the 64 MiB scratch is fine; tiling still fires
        assert rules_and_lines(findings) == {("JL005", 11), ("JL005", 12)}

    def test_jl006_async_host_sync_in_serve(self):
        findings = findings_for("serve/bad_async_sync.py")
        assert rules_and_lines(findings) == {
            ("JL006", 8),   # np.asarray on the event loop
            ("JL006", 10),  # .block_until_ready() on the event loop
            ("JL006", 11),  # .item() on the event loop
        }
        assert all(f.severity == ERROR for f in findings)
        # sync helpers and executor lambdas in the same file stay clean
        assert not any(f.line > 11 for f in findings)

    def test_jl006_scoped_to_serve_paths(self):
        # the identical source outside a serve/ path segment is not JL006's
        # business (general async code may sync freely)
        import ast

        from jimm_tpu.lint.rules_ast import check_async_host_sync
        src = (FIXTURES / "serve" / "bad_async_sync.py").read_text()
        tree = ast.parse(src)
        assert check_async_host_sync(tree, "jimm_tpu/train/loop.py") == []
        assert check_async_host_sync(tree, "jimm_tpu/serve/engine.py") != []

    def test_jl007_bare_print_in_library_code(self):
        findings = findings_for("jimm_tpu/bad_print.py")
        # line 10 fires; the suppressed print on 15 and the logger call on
        # 20 stay clean
        assert rules_and_lines(findings) == {("JL007", 10)}
        assert findings[0].severity == ERROR
        assert "obs" in findings[0].message

    def test_jl007_scoped_to_library_paths(self):
        import ast

        from jimm_tpu.lint.rules_ast import check_bare_print
        src = (FIXTURES / "jimm_tpu" / "bad_print.py").read_text()
        tree = ast.parse(src)
        # CLI entry points, scripts, and tests are print's legitimate home
        assert check_bare_print(tree, "jimm_tpu/cli.py") == []
        assert check_bare_print(tree, "jimm_tpu/obs/cli.py") == []
        assert check_bare_print(tree, "jimm_tpu/__main__.py") == []
        assert check_bare_print(tree, "jimm_tpu/launch.py") == []
        assert check_bare_print(tree, "scripts/obs_smoke.py") == []
        assert check_bare_print(tree, "tests/test_obs.py") == []
        # library modules are not
        assert check_bare_print(tree, "jimm_tpu/train/metrics.py") != []
        assert check_bare_print(tree, "jimm_tpu/serve/engine.py") != []

    def test_jl008_jit_in_loop(self):
        findings = findings_for("bad_jit_in_loop.py")
        assert rules_and_lines(findings) == {
            ("JL008", 10),  # jax.jit call in for body
            ("JL008", 13),  # nnx.jit call in while body
            ("JL008", 17),  # jit-decorated def in loop body
        }
        assert all(f.severity == ERROR for f in findings)
        assert any("AOT" in f.message for f in findings)
        # hoisted_ok (jit once, reuse) and the suppressed site stay clean

    def test_jl008_handler_and_test_scoping(self):
        import ast

        from jimm_tpu.lint.rules_ast import check_jit_in_loop
        from jimm_tpu.lint.rules_ast import _annotate_parents
        src = (
            "import jax\n"
            "class H:\n"
            "    def do_GET(self):\n"
            "        f = jax.jit(lambda x: x)\n"
            "async def handle(req):\n"
            "    g = jax.jit(lambda x: x)\n"
        )
        tree = ast.parse(src)
        _annotate_parents(tree)
        # do_GET fires anywhere; the async def only in serving code
        lib = check_jit_in_loop(tree, "jimm_tpu/train/loop.py")
        assert {(f.rule, f.line) for f in lib} == {("JL008", 4)}
        serve = check_jit_in_loop(tree, "jimm_tpu/serve/server.py")
        assert {(f.rule, f.line) for f in serve} == {("JL008", 4),
                                                    ("JL008", 6)}
        # tests construct jits per-case on purpose
        assert check_jit_in_loop(tree, "tests/test_serve.py") == []

    def test_jl009_block_size_literal(self):
        findings = findings_for("bad_block_literal.py")
        assert rules_and_lines(findings) == {
            ("JL009", 8),   # block_q=128
            ("JL009", 9),   # block_k=256
            ("JL009", 12),  # block_rows=64
            ("JL009", 27),  # flash_attention_masked block_q=128 — the rule
            ("JL009", 28),  # keys on kwarg names, so variants are covered
        }
        assert all(f.severity == ERROR for f in findings)
        assert any("best_config" in f.message for f in findings)
        # the suppressed pin, the named-constant kwarg, the def-site
        # default, and block_rows=None all stay clean

    def test_jl009_ops_tune_and_test_paths_exempt(self):
        import ast

        from jimm_tpu.lint.rules_ast import check_block_size_literal
        src = "flash_attention(q, k, v, block_q=128)\n"
        tree = ast.parse(src)
        assert check_block_size_literal(tree, "jimm_tpu/serve/engine.py")
        # ops defaults and the tuner's bench closures are the mechanism
        assert check_block_size_literal(
            tree, "jimm_tpu/ops/flash_attention.py") == []
        assert check_block_size_literal(tree, "jimm_tpu/tune/api.py") == []
        # tests pin blocks to exercise specific configs on purpose
        assert check_block_size_literal(tree, "tests/test_ops.py") == []

    def test_jl010_unplaced_device_put(self):
        findings = findings_for("serve/bad_device_put.py")
        assert rules_and_lines(findings) == {
            ("JL010", 7),   # jax.device_put(np.asarray(...)) — no placement
            ("JL010", 8),   # jax.device_put(padded) — no placement
        }
        assert all(f.severity == ERROR for f in findings)
        assert any("NamedSharding" in f.message for f in findings)
        # explicit positional/keyword placements and the suppressed put
        # (lines 10-14) stay clean

    def test_jl010_scoped_to_serve_and_parallel_paths(self):
        import ast

        from jimm_tpu.lint.rules_ast import check_device_put_placement
        src = "import jax\nx = jax.device_put(batch)\n"
        tree = ast.parse(src)
        assert check_device_put_placement(
            tree, "jimm_tpu/serve/topology.py") != []
        assert check_device_put_placement(
            tree, "jimm_tpu/parallel/sharding.py") != []
        # elsewhere the default device IS the contract (single-device code)
        assert check_device_put_placement(
            tree, "jimm_tpu/data/pipeline.py") == []
        assert check_device_put_placement(
            tree, "jimm_tpu/weights/loader.py") == []

    def test_jl011_host_sort(self):
        findings = findings_for("retrieval/host_sort.py")
        assert rules_and_lines(findings) == {
            ("JL011", 8),   # np.argsort over host copy of device scores
            ("JL011", 9),   # np.sort
            ("JL011", 10),  # jnp.argsort
            ("JL011", 11),  # sorted() over array-derived data
        }
        assert all(f.severity == ERROR for f in findings)
        assert any("lax.top_k" in f.message for f in findings)
        # np.lexsort over bounded candidates, sorted() on plain python
        # data, and the suppressed deliberate sort (lines 16-25) stay clean

    def test_jl011_ivf_merge_fixture(self):
        findings = findings_for("retrieval/ann_merge.py")
        assert rules_and_lines(findings) == {
            ("JL011", 9),   # np.argsort over probed candidate scores
            ("JL011", 10),  # sorted() over array-derived candidates
        }
        assert all(f.severity == ERROR for f in findings)
        # the lexsort-based bounded merge (merge_probed_candidates_ok)
        # stays clean — it is the idiom ivf.py actually uses

    def test_jl011_scoped_to_serve_and_retrieval_paths(self):
        import ast

        from jimm_tpu.lint.rules_ast import check_host_sort
        src = "import numpy as np\norder = np.argsort(-scores)\n"
        tree = ast.parse(src)
        assert check_host_sort(tree, "jimm_tpu/serve/server.py") != []
        assert check_host_sort(tree, "jimm_tpu/retrieval/topk.py") != []
        # retrieval/ann/ is covered by construction: the path test is
        # "retrieval" anywhere in the parts, so the new subpackage (and
        # any future one) inherits the rule without a lint change
        assert check_host_sort(
            tree, "jimm_tpu/retrieval/ann/ivf.py") != []
        assert check_host_sort(
            tree, "jimm_tpu/retrieval/ann/kmeans.py") != []
        # elsewhere a host sort is unexceptional (CLI display, training
        # eval), and test oracles *should* argsort
        assert check_host_sort(tree, "jimm_tpu/cli.py") == []
        assert check_host_sort(tree, "jimm_tpu/train/loop.py") == []
        assert check_host_sort(tree, "tests/test_retrieval.py") == []

    def test_jl012_quant_upcast(self):
        findings = findings_for("ops/int8_bad_upcast.py")
        assert rules_and_lines(findings) == {
            ("JL012", 7),   # bare .astype(jnp.float32) on the accumulator
            ("JL012", 8),   # jax.lax.convert_element_type(..., jnp.float32)
            ("JL012", 9),   # string dtype spelling .astype("float32")
        }
        assert all(f.severity == ERROR for f in findings)
        assert any("_dequant" in f.message for f in findings)
        # the _dequant/quantize_rows sanctioned sites, the bf16 epilogue,
        # and the suppressed deliberate upcast (lines 13-29) stay clean

    def test_jl012_scoped_to_quant_ops_paths(self):
        import ast

        from jimm_tpu.lint.rules_ast import check_quant_upcast
        src = "y = acc.astype(jnp.float32)\n"
        tree = ast.parse(src)
        assert check_quant_upcast(tree, "jimm_tpu/ops/int8_matmul.py") != []
        assert check_quant_upcast(
            tree, "jimm_tpu/ops/flash_attention_int8.py") != []
        assert check_quant_upcast(tree, "jimm_tpu/quant/__init__.py") != []
        # non-quantized ops and the rest of the tree upcast freely (f32 IS
        # their compute dtype), and tests compare against f32 on purpose
        assert check_quant_upcast(
            tree, "jimm_tpu/ops/flash_attention.py") == []
        assert check_quant_upcast(tree, "jimm_tpu/ops/layer_norm.py") == []
        assert check_quant_upcast(tree, "jimm_tpu/train/loop.py") == []
        assert check_quant_upcast(tree, "tests/test_int8_ops.py") == []

    def test_jl013_swallowed_exception(self):
        findings = findings_for("serve/bad_swallow.py")
        assert rules_and_lines(findings) == {
            ("JL013", 7),   # except Exception: pass
            ("JL013", 14),  # bare except: pass
        }
        assert all(f.severity == ERROR for f in findings)
        assert any("supervisor" in f.message for f in findings)
        # the narrow OSError swallow, the justified suppression, and the
        # handler that acts on the failure (lines 18-39) stay clean

    def test_jl013_scoped_to_resilience_critical_paths(self):
        import ast

        from jimm_tpu.lint.rules_ast import check_swallowed_exception
        src = "try:\n    f()\nexcept Exception:\n    pass\n"
        tree = ast.parse(src)
        assert check_swallowed_exception(
            tree, "jimm_tpu/serve/engine.py") != []
        assert check_swallowed_exception(
            tree, "jimm_tpu/train/checkpoint.py") != []
        assert check_swallowed_exception(
            tree, "jimm_tpu/resilience/supervisor.py") != []
        # the rest of the tree (and all tests) may use best-effort
        # swallows without a justification comment
        assert check_swallowed_exception(
            tree, "jimm_tpu/weights/resolve.py") == []
        assert check_swallowed_exception(
            tree, "jimm_tpu/obs/registry.py") == []
        assert check_swallowed_exception(
            tree, "tests/test_serve.py") == []
        assert check_swallowed_exception(
            tree, "jimm_tpu/serve/test_helpers.py") == []

    def test_jl014_unbounded_tenant_table(self):
        findings = findings_for("serve/bad_tenant_growth.py")
        assert rules_and_lines(findings) == {
            ("JL014", 12),  # self.per_tenant[tenant_id] = ..., no eviction
            ("JL014", 16),  # .setdefault(tenant_id, ...), same hole
        }
        assert all(f.severity == ERROR for f in findings)
        assert any("adversary" in f.message for f in findings)
        # the evicting router, the config-keyed ledger, the bounded LRU,
        # and the justified suppression (lines 20-59) stay clean

    def test_jl014_scoped_to_serve_library_paths(self):
        import ast

        from jimm_tpu.lint.rules_ast import (_annotate_parents,
                                             check_unbounded_tenant_table)
        src = ("class T:\n"
               "    def on_request(self, tenant):\n"
               "        self.seen[tenant] = 1\n")
        tree = ast.parse(src)
        _annotate_parents(tree)
        assert check_unbounded_tenant_table(
            tree, "jimm_tpu/serve/qos/scheduler.py") != []
        assert check_unbounded_tenant_table(
            tree, "jimm_tpu/serve/server.py") != []
        # non-serving code tracks what it likes, and tests build ad-hoc
        # tables on purpose
        assert check_unbounded_tenant_table(
            tree, "jimm_tpu/train/loop.py") == []
        assert check_unbounded_tenant_table(
            tree, "jimm_tpu/obs/registry.py") == []
        assert check_unbounded_tenant_table(
            tree, "tests/test_serve.py") == []
        assert check_unbounded_tenant_table(
            tree, "jimm_tpu/serve/test_helpers.py") == []

    def test_jl015_journal_bypass(self):
        findings = findings_for("resilience/bad_event_print.py")
        assert rules_and_lines(findings) == {
            ("JL015", 8),   # print(json.dumps(...))
            ("JL015", 12),  # "..." + json.dumps(...) concat
            ("JL015", 16),  # f-string interpolating json.dumps(...)
        }
        assert all(f.severity == ERROR for f in findings)
        assert any("flight-recorder" in f.message for f in findings)
        # the justified ready-line and the journal emitter (lines 19-28)
        # stay clean

    def test_jl015_scoped_to_resilience_paths_not_cli(self):
        import ast

        from jimm_tpu.lint.rules_ast import check_journal_bypass
        src = "import json\nprint(json.dumps({'a': 1}))\n"
        tree = ast.parse(src)
        assert check_journal_bypass(
            tree, "jimm_tpu/resilience/supervisor.py") != []
        assert check_journal_bypass(
            tree, "jimm_tpu/serve/engine.py") != []
        assert check_journal_bypass(
            tree, "jimm_tpu/train/loop.py") != []
        # CLI entry points keep their sanctioned parseable ready-lines,
        # tests print what they like, and the rest of the tree is JL007's
        # jurisdiction
        assert check_journal_bypass(tree, "jimm_tpu/cli.py") == []
        assert check_journal_bypass(tree, "jimm_tpu/launch.py") == []
        assert check_journal_bypass(tree, "tests/test_serve.py") == []
        assert check_journal_bypass(
            tree, "jimm_tpu/obs/registry.py") == []

    def test_jl016_bare_lowp_cast(self):
        findings = findings_for("ops/lowp_bad_cast.py")
        assert rules_and_lines(findings) == {
            ("JL016", 7),   # bare .astype(jnp.float8_e4m3fn)
            ("JL016", 8),   # jax.lax.convert_element_type(..., e5m2)
            ("JL016", 9),   # string dtype spelling .astype("int8")
        }
        assert all(f.severity == ERROR for f in findings)
        assert any("quantize_tensor" in f.message for f in findings)
        # the quantize/scale sanctioned sites, the expression-derived
        # dtype, and the suppressed deliberate cast (lines 13-28) stay
        # clean

    def test_jl016_scoped_to_ops_and_train_paths(self):
        import ast

        from jimm_tpu.lint.rules_ast import check_bare_lowp_cast
        src = "y = x.astype(jnp.float8_e4m3fn)\n"
        tree = ast.parse(src)
        assert check_bare_lowp_cast(
            tree, "jimm_tpu/ops/fp8_matmul.py") != []
        assert check_bare_lowp_cast(
            tree, "jimm_tpu/train/trainer.py") != []
        # checkpoint rewrite code stores int8 as a format, not a numerics
        # decision; tests compare against raw casts on purpose
        assert check_bare_lowp_cast(
            tree, "jimm_tpu/weights/quantize.py") == []
        assert check_bare_lowp_cast(tree, "tests/test_fp8_ops.py") == []
        # the quantizer's own cast is sanctioned by its enclosing name
        from jimm_tpu.lint.rules_ast import _annotate_parents
        src_ok = ("def quantize_rows(x, s):\n"
                  "    return (x / s).astype(jnp.int8)\n")
        tree_ok = ast.parse(src_ok)
        _annotate_parents(tree_ok)
        assert check_bare_lowp_cast(
            tree_ok, "jimm_tpu/ops/int8_matmul.py") == []

    def test_jl021_cascade_threshold_literals(self):
        findings = findings_for("serve/cascade/bad_threshold.py")
        assert rules_and_lines(findings) == {
            ("JL021", 4),   # def route(..., escalation_threshold=0.95)
            ("JL021", 6),   # confidence >= 0.92
            ("JL021", 14),  # self.confidence_floor = 0.9
            ("JL021", 15),  # self.margin_threshold: float = -0.05
            ("JL021", 18),  # make_router(..., threshold=0.88)
        }
        assert all(f.severity == ERROR for f in findings)
        assert any("cascade calibrate" in f.message for f in findings)
        # loading calibration.threshold, round(confidence, 6), and the
        # variable-vs-variable comparison (lines 24-31) stay clean

    def test_jl021_scoped_to_cascade_outside_calibrate(self):
        import ast

        from jimm_tpu.lint.rules_ast import check_cascade_thresholds
        src = "threshold = 0.92\n"
        tree = ast.parse(src)
        assert check_cascade_thresholds(
            tree, "jimm_tpu/serve/cascade/router.py") != []
        assert check_cascade_thresholds(
            tree, "jimm_tpu/serve/cascade/autoscale.py") != []
        # the fitter is the one place thresholds legitimately live
        assert check_cascade_thresholds(
            tree, "jimm_tpu/serve/cascade/calibrate.py") == []
        # outside the cascade package the marks mean nothing
        assert check_cascade_thresholds(
            tree, "jimm_tpu/serve/engine.py") == []
        assert check_cascade_thresholds(
            tree, "jimm_tpu/retrieval/cascade.py") == []
        assert check_cascade_thresholds(
            tree, "tests/test_cascade.py") == []

    def test_jl022_profiler_bypass(self):
        findings = findings_for("train/bad_profiler.py")
        assert rules_and_lines(findings) == {
            ("JL022", 9),   # jax.profiler.start_trace(log_dir)
            ("JL022", 12),  # jax.profiler.stop_trace()
            ("JL022", 16),  # start_trace(log_dir) — from-import spelling
            ("JL022", 18),  # stop_trace()
        }
        assert all(f.severity == ERROR for f in findings)
        assert any("profiler_session" in f.message for f in findings)
        # the disabled direct call, the profiler_session route, and the
        # session-agnostic TraceAnnotation all stay clean

    def test_jl022_scoped_to_outside_obs_prof(self):
        import ast

        from jimm_tpu.lint.rules_ast import check_profiler_bypass
        src = "import jax\njax.profiler.start_trace('/tmp/x')\n"
        tree = ast.parse(src)
        assert check_profiler_bypass(
            tree, "jimm_tpu/train/profile.py") != []
        assert check_profiler_bypass(
            tree, "jimm_tpu/serve/engine.py") != []
        # the sanctioned session owner and tests are exempt
        assert check_profiler_bypass(
            tree, "jimm_tpu/obs/prof/capture.py") == []
        assert check_profiler_bypass(
            tree, "tests/test_profile.py") == []

    def test_jl024_seqpar_discipline(self):
        findings = findings_for("parallel/seqpar_bad.py")
        assert rules_and_lines(findings) == {
            ("JL024", 15),  # all_gather — from-import spelling
            ("JL024", 19),  # jax.lax.all_gather on the KV chunk
            ("JL024", 24),  # dense (S, S) score einsum outside a hop fn
        }
        assert all(f.severity == ERROR for f in findings)
        assert any("ppermute" in f.message for f in findings)
        # the per-hop tile (_hop_scores_ok), the sanctioned ppermute, the
        # projection einsum, and the justified mask gather all stay clean

    def test_jl024_scoped_to_seqpar_modules(self):
        import ast

        from jimm_tpu.lint.rules_ast import check_seqpar_discipline
        src = "import jax\nx = jax.lax.all_gather(k, 'seq')\n"
        tree = ast.parse(src)
        assert check_seqpar_discipline(
            tree, "jimm_tpu/parallel/seqpar.py") != []
        # the zigzag ring module and the ring losses gather on purpose
        # (loss terms, not KV) — only seqpar* carries the contract
        assert check_seqpar_discipline(
            tree, "jimm_tpu/parallel/ring_attention.py") == []
        assert check_seqpar_discipline(
            tree, "jimm_tpu/train/losses.py") == []
        assert check_seqpar_discipline(
            tree, "tests/test_seqpar.py") == []

    def test_jl024_pv_contraction_not_score_shaped(self):
        from jimm_tpu.lint.rules_ast import _einsum_is_dense_scores
        assert _einsum_is_dense_scores("bqnd,bknd->bnqk")
        assert _einsum_is_dense_scores("bqd,bkd->bqk")
        # p @ V, grad contractions, and projections are contractions over
        # one of the two sequence axes — not materialized scores
        assert not _einsum_is_dense_scores("bnqk,bknd->bqnd")
        assert not _einsum_is_dense_scores("bnqk,bqnd->bknd")
        assert not _einsum_is_dense_scores("bsnd,ndh->bsh")

    def test_clean_counterexamples_and_suppression(self):
        # guarded config, canonical specs, static branches, and both
        # same-line and next-line `# jaxlint: disable=` forms: no findings
        assert findings_for("clean.py") == []

    def test_jl002_alias_of_static_metadata_not_tainted(self, tmp_path):
        # regression: `dtype = x.dtype` then branching on `dtype` used to
        # taint the alias and flag a perfectly static branch
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    dtype = x.dtype\n"
            "    if dtype == 'int8':\n"
            "        x = x + 1\n"
            "    y = x * 2\n"
            "    if y > 0:\n"          # line 8: genuinely traced branch
            "        x = x - 1\n"
            "    return x\n"
        )
        p = tmp_path / "alias.py"
        p.write_text(src)
        assert rules_and_lines(lint_file(p)) == {("JL002", 8)}


class TestTreeInvariants:
    def test_canonical_axes_match_mesh_module(self):
        from jimm_tpu.parallel.mesh import MESH_AXES
        assert CANONICAL_MESH_AXES == frozenset(MESH_AXES)

    def test_fixtures_excluded_from_directory_walks(self):
        findings = lint_paths([str(FIXTURES.parent)])
        assert not any("lint_fixtures" in f.path for f in findings)

    def test_shipped_tree_is_clean(self):
        findings = [f for f in lint_paths([str(REPO / "jimm_tpu")])
                    if f.severity == ERROR]
        assert findings == [], "\n".join(f.render() for f in findings)


class TestCli:
    def run_cli(self, *args):
        return subprocess.run(
            [sys.executable, "-m", "jimm_tpu.lint", *args],
            capture_output=True, text=True, cwd=REPO,
        )

    def test_broken_fixture_fails_with_json_report(self):
        proc = self.run_cli(str(FIXTURES / "bad_partition_spec.py"), "--json")
        assert proc.returncode == 1
        report = json.loads(proc.stdout)
        assert [(f["rule"], f["line"]) for f in report] == [("JL004", 9)]
        assert report[0]["path"].endswith("bad_partition_spec.py")
        assert report[0]["severity"] == "error"

    def test_clean_fixture_exits_zero(self):
        proc = self.run_cli(str(FIXTURES / "clean.py"), "--json")
        assert proc.returncode == 0
        assert json.loads(proc.stdout) == []


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
