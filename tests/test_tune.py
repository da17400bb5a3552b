"""jimm_tpu.tune: key stability, cache hit/miss/fallback, space pruning,
measurement discipline, and the ops integration (block sizes resolved from
the persistent cache at trace time)."""

import json
import subprocess
import sys

import numpy as np
import pytest

from jimm_tpu import obs
from jimm_tpu.tune import (KERNELS, TuneCache, best_config, kernel_space,
                           trimmed_median, tune_kernel, tune_key)

FLASH_SHAPES = ((2, 128, 4, 64), (2, 128, 4, 64), (2, 128, 4, 64))
LN_SHAPES = ((64, 256),)


def flash_key(**over):
    kw = dict(kernel="flash_attention", shapes=FLASH_SHAPES,
              dtypes=("float32",) * 3,
              kernel_version=KERNELS["flash_attention"].version,
              backend="cpu", jax_version="0.4.37")
    kw.update(over)
    kernel = kw.pop("kernel")
    return tune_key(kernel, **kw)


def counters():
    return obs.get_registry("jimm_tune").snapshot()


def delta(before, after, name):
    return after.get(name, 0) - before.get(name, 0)


class TestKeys:
    def test_fingerprint_deterministic(self):
        assert flash_key().fingerprint() == flash_key().fingerprint()

    def test_fingerprint_sensitivity(self):
        base = flash_key().fingerprint()
        assert flash_key(shapes=((2, 256, 4, 64),) * 3).fingerprint() != base
        assert flash_key(dtypes=("bfloat16",) * 3).fingerprint() != base
        assert flash_key(kernel_version=99).fingerprint() != base
        assert flash_key(backend="tpu").fingerprint() != base
        assert flash_key(jax_version="0.5.0").fingerprint() != base

    def test_dtype_spellings_canonicalize(self):
        # np dtype objects, type objects, and names all mean the same key
        a = flash_key(dtypes=(np.float32, np.dtype("float32"), "float32"))
        assert a.fingerprint() == flash_key().fingerprint()

    def test_fingerprint_stable_across_processes(self):
        # the persistence contract: a fresh interpreter maps the same
        # logical key to the same fingerprint (no per-process hash seeds,
        # dict ordering, or repr details leak in)
        code = (
            "from jimm_tpu.tune import tune_key\n"
            "k = tune_key('flash_attention',"
            " shapes=((2, 128, 4, 64),) * 3, dtypes=('float32',) * 3,"
            " kernel_version=%d, backend='cpu', jax_version='0.4.37')\n"
            "print(k.fingerprint())\n" % KERNELS["flash_attention"].version)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, check=True)
        assert out.stdout.strip() == flash_key().fingerprint()

    def test_cli_preset_points_key_like_the_ops_hot_path(self):
        # the CLI writes one dtype PER OPERAND because ops key on
        # (q.dtype, k.dtype, v.dtype); a drift here makes offline tuning
        # silently useless (configs that best_config never finds)
        from jimm_tpu.tune.cli import _preset_points
        pts = {p["kernel"]: p for p in
               _preset_points("clip-vit-base-patch16", 2, "float32")}
        flash = pts["flash_attention"]
        assert len(flash["dtypes"]) == len(flash["shapes"]) == 3
        cli_key = tune_key("flash_attention", shapes=flash["shapes"],
                           dtypes=flash["dtypes"], kernel_version=1,
                           backend="cpu", jax_version="x")
        ops_key = tune_key(
            "flash_attention",
            shapes=tuple(tuple(s) for s in flash["shapes"]),
            dtypes=tuple(np.dtype("float32") for _ in range(3)),
            kernel_version=1, backend="cpu", jax_version="x")
        assert cli_key.fingerprint() == ops_key.fingerprint()
        assert len(pts["layer_norm"]["dtypes"]) == 1

    def test_describe_is_json_round_trippable(self):
        d = flash_key().describe()
        assert json.loads(json.dumps(d)) == d
        assert d["kernel"] == "flash_attention"


class TestJaxFreeImport:
    @pytest.mark.parametrize("module", [
        "jimm_tpu.tune", "jimm_tpu.tune.cache", "jimm_tpu.tune.space",
        "jimm_tpu.tune.cli"])
    def test_import_does_not_pull_jax(self, module):
        code = (f"import {module}, sys; "
                f"assert 'jax' not in sys.modules, 'jax leaked'")
        subprocess.run([sys.executable, "-c", code], check=True,
                       capture_output=True)


class TestCache:
    def test_put_get_round_trip(self, tmp_path):
        cache = TuneCache(tmp_path / "c")
        key = flash_key()
        fp = cache.put(key, {"block_q": 128, "block_k": 256},
                       metrics={"time_s": 0.5})
        assert fp == key.fingerprint()
        rec = cache.get(key)
        assert rec["config"] == {"block_q": 128, "block_k": 256}
        assert rec["metrics"]["time_s"] == 0.5

    def test_second_instance_sees_persisted_config(self, tmp_path):
        TuneCache(tmp_path / "c").put(flash_key(), {"block_q": 512,
                                                    "block_k": 128})
        rec = TuneCache(tmp_path / "c").get(flash_key())
        assert rec["config"]["block_q"] == 512

    def test_miss_returns_none_and_is_not_memoized(self, tmp_path):
        cache = TuneCache(tmp_path / "c")
        assert cache.get(flash_key()) is None
        # an offline tune between lookups must become visible
        cache.put(flash_key(), {"block_q": 256, "block_k": 256})
        assert cache.get(flash_key())["config"]["block_q"] == 256

    def test_corrupt_record_quarantined_as_miss(self, tmp_path):
        cache = TuneCache(tmp_path / "c")
        key = flash_key()
        cache.put(key, {"block_q": 128, "block_k": 128})
        (cache.entries()[0].path / "artifact.bin").write_bytes(b"not json")
        fresh = TuneCache(tmp_path / "c")  # bypass the in-process memo
        assert fresh.get(key) is None

    def test_entries_meta_labels(self, tmp_path):
        cache = TuneCache(tmp_path / "c")
        cache.put(flash_key(), {"block_q": 128, "block_k": 128})
        (entry,) = cache.entries()
        assert entry.meta["label"] == "tune:flash_attention"
        assert entry.meta["kernel"] == "flash_attention"


class TestBestConfig:
    def test_hit_path(self, tmp_path):
        cache = TuneCache(tmp_path / "c")
        cache.put(tune_key("layer_norm", shapes=LN_SHAPES,
                           dtypes=("float32",),
                           kernel_version=KERNELS["layer_norm"].version),
                  {"block_rows": 32})
        before = counters()
        cfg = best_config("layer_norm", LN_SHAPES, ("float32",), cache=cache)
        after = counters()
        assert cfg == {"block_rows": 32}
        assert delta(before, after, "hit_total") == 1
        assert delta(before, after, "measure_total") == 0

    def test_fallback_path_uses_default_and_never_measures(self, tmp_path):
        cache = TuneCache(tmp_path / "c")
        before = counters()
        cfg = best_config("layer_norm", ((999, 333),), ("float32",),
                          default={"block_rows": 64}, cache=cache)
        after = counters()
        assert cfg == {"block_rows": 64}
        assert delta(before, after, "miss_total") == 1
        assert delta(before, after, "fallback_total") == 1
        assert delta(before, after, "measure_total") == 0

    def test_fallback_without_explicit_default_uses_kernel_default(
            self, tmp_path):
        from jimm_tpu.ops.layer_norm import DEFAULT_BLOCK_ROWS
        cfg = best_config("layer_norm", ((7, 48),), ("float32",),
                          cache=TuneCache(tmp_path / "c"))
        assert cfg == {"block_rows": DEFAULT_BLOCK_ROWS}

    def test_jimm_tune_env_tunes_on_miss(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JIMM_TUNE", "1")
        cache = TuneCache(tmp_path / "c")
        before = counters()
        cfg = best_config("layer_norm", ((16, 128),), ("float32",),
                          cache=cache)
        after = counters()
        assert "block_rows" in cfg
        assert delta(before, after, "measure_total") >= 1
        # and the result persisted: the next lookup is a pure hit
        assert cache.get(tune_key(
            "layer_norm", shapes=((16, 128),), dtypes=("float32",),
            kernel_version=KERNELS["layer_norm"].version)) is not None


class TestTuneKernel:
    def test_persists_winner_and_second_lookup_is_pure_hit(self, tmp_path):
        cache = TuneCache(tmp_path / "c")
        report = tune_kernel("layer_norm", ((32, 128),), ("float32",),
                             cache=cache)
        assert report["candidates"] == len(report["trials"]) >= 1
        assert report["config"] in [t["config"] for t in report["trials"]]
        before = counters()
        cfg = best_config("layer_norm", ((32, 128),), ("float32",),
                          cache=TuneCache(tmp_path / "c"))
        after = counters()
        assert cfg == report["config"]
        assert delta(before, after, "hit_total") == 1
        assert delta(before, after, "measure_total") == 0

    def test_explicit_candidates_override_space(self, tmp_path):
        report = tune_kernel("layer_norm", ((16, 128),), ("float32",),
                             cache=TuneCache(tmp_path / "c"),
                             candidates=[{"block_rows": 8}])
        assert report["config"] == {"block_rows": 8}
        assert report["candidates"] == 1


class TestSpace:
    def test_flash_space_prunes_oversized_blocks(self):
        cands = kernel_space("flash_attention", FLASH_SHAPES,
                             ("float32",) * 3)
        assert cands
        for c in cands:
            # seq len 128 -> no point in blocks beyond its 128-multiple
            assert c["block_q"] <= 128 and c["block_k"] <= 128

    def test_flash_space_vmem_formula_matches_ops(self):
        # the pruner's VMEM model must BE the ops guard's model — if the
        # kernel's working-set formula changes, this fails and space.py
        # follows
        from jimm_tpu.ops import flash_attention as fa
        from jimm_tpu.tune.space import (FLASH_BLOCKS, FLASH_VMEM_BUDGET,
                                         flash_vmem_bytes)
        assert FLASH_VMEM_BUDGET == fa._VMEM_BUDGET
        # every block `_pick_block` can hand out is a candidate, and no other
        assert {fa._pick_block(1 << 20, b) for b in FLASH_BLOCKS} \
            == set(FLASH_BLOCKS)
        assert fa._pick_block(1 << 20, 1 << 20) == max(FLASH_BLOCKS)
        assert fa.DEFAULT_BLOCK_Q in FLASH_BLOCKS
        for bq in FLASH_BLOCKS:
            for bk in FLASH_BLOCKS:
                for d in (64, 128, 256):
                    assert flash_vmem_bytes(bq, bk, d) == \
                        fa._per_head_vmem_bytes(bq, bk, d)
                    # the fused backward's resident dq: S_q x D x 4 a head
                    for rows in (1536, 8192):
                        assert flash_vmem_bytes(bq, bk, d, rows) == \
                            fa._per_head_vmem_bytes(bq, bk, d, dq_rows=rows)
        assert flash_vmem_bytes(512, 512, 256, 8192) \
            - flash_vmem_bytes(512, 512, 256) == 8 * 1024 * 1024
        # the backward's four fp32 score tiles and two MXU copies: 5 MB
        assert flash_vmem_bytes(512, 512, 128) \
            - flash_vmem_bytes(512, 0, 128) - 3 * 512 * 128 * 2 \
            == 20 * 512 * 512

    def test_flash_space_offers_what_the_kernel_resolves(self):
        """The blocks an untuned call runs at are a point of the tuner's
        space for the same shapes; nothing over 512 is offered, and a
        request over it is fitted down as it always was."""
        from jimm_tpu.ops import flash_attention as fa
        for shape, d in (((2, 8192, 32, 192), 256), ((1, 4096, 16, 128), 128),
                         ((2, 2048, 16, 64), 64), ((1, 1280, 12, 64), 64)):
            space = kernel_space("flash_attention", (shape[:3] + (d,),) * 3,
                                 ("bfloat16",) * 3)
            bq, bk = fa._fit_blocks(shape[1], shape[1], d, 2, fa._SOFTMAX,
                                    fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)
            assert {"block_q": bq, "block_k": bk} in space
            assert max(c["block_q"] for c in space) == 512
            assert len(space) == 9
        assert fa._fit_blocks(8192, 8192, 128, 2, fa._SOFTMAX, 2048, 1024,
                              requested=True) == (512, 512)

    def test_ln_space_clamps_to_row_count(self):
        cands = kernel_space("layer_norm", ((16, 128),), ("float32",))
        assert cands
        assert all(c["block_rows"] <= 16 for c in cands)

    def test_spaces_never_empty(self):
        # even absurd shapes yield the safe-default singleton
        assert kernel_space("layer_norm", ((1, 100000),), ("float32",))
        assert kernel_space("flash_attention",
                            ((1, 8, 1, 4096),) * 3, ("float32",) * 3)
        assert kernel_space("int8_matmul", ((1, 100000), (100000, 1)),
                            ("int8", "int8"))
        assert kernel_space("flash_attention_int8",
                            ((1, 16384, 1, 128),) * 3, ("float32",) * 3)

    def test_int8_matmul_space_prunes_to_shape(self):
        cands = kernel_space("int8_matmul", ((40, 64), (64, 40)),
                             ("int8", "int8"))
        assert cands
        for c in cands:
            # m=40 -> 64-row ceiling; n=40 -> one 128-lane tile
            assert c["block_m"] <= 64 and c["block_n"] <= 128

    def test_int8_matmul_vmem_formula_matches_ops(self):
        from jimm_tpu.ops import int8_matmul as im
        from jimm_tpu.tune.space import VMEM_BUDGET, int8_matmul_vmem_bytes
        assert VMEM_BUDGET == im._VMEM_BUDGET
        for bm in (32, 64, 256):
            for bn in (128, 512):
                for k in (64, 768):
                    assert int8_matmul_vmem_bytes(bm, bn, k) == \
                        im._per_cell_vmem_bytes(bm, bn, k)

    def test_int8_flash_vmem_formula_matches_ops(self):
        from jimm_tpu.ops import flash_attention_int8 as fi
        from jimm_tpu.tune.space import int8_flash_vmem_bytes
        for bq in (128, 512):
            for bk in (128, 512):
                for d in (64, 128):
                    assert int8_flash_vmem_bytes(bq, bk, d) == \
                        fi._per_head_vmem_bytes(bq, bk, d)

    def test_int8_flash_bwd_vmem_formula_matches_ops(self):
        # blocks are shared between the fwd and bwd kernels, so the pruner
        # must model BOTH working sets — this pins the bwd one
        from jimm_tpu.ops import flash_attention_int8 as fi
        from jimm_tpu.tune.space import int8_flash_bwd_vmem_bytes
        for bq in (128, 512):
            for bk in (128, 512):
                for d in (64, 128):
                    assert int8_flash_bwd_vmem_bytes(bq, bk, d) == \
                        fi._per_head_bwd_vmem_bytes(bq, bk, d)

    def test_fp8_matmul_vmem_formula_matches_ops(self):
        from jimm_tpu.ops import fp8_matmul as fm
        from jimm_tpu.tune.space import VMEM_BUDGET, fp8_matmul_vmem_bytes
        assert VMEM_BUDGET == fm._VMEM_BUDGET
        for bm in (32, 64, 256):
            for bn in (128, 512):
                for k in (64, 768):
                    assert fp8_matmul_vmem_bytes(bm, bn, k) == \
                        fm._per_cell_vmem_bytes(bm, bn, k)

    def test_fp8_matmul_space_prunes_to_shape(self):
        cands = kernel_space("fp8_matmul", ((40, 64), (64, 40)),
                             ("float8_e4m3fn", "float8_e4m3fn"))
        assert cands
        for c in cands:
            # m=40 -> 64-row ceiling; n=40 -> one 128-lane tile
            assert c["block_m"] <= 64 and c["block_n"] <= 128

    def test_int8_kernels_registered(self):
        for name in ("int8_matmul", "flash_attention_int8", "fp8_matmul"):
            assert name in KERNELS
            assert KERNELS[name].version >= 1
            assert callable(KERNELS[name].bench)

    def test_int8_flash_version_bumped_for_backward(self):
        # the lse output changed the fwd working set and the bwd added new
        # feasibility constraints — configs tuned for version 1 must miss
        assert KERNELS["flash_attention_int8"].version >= 2

    def test_attention_variant_vmem_formulas_match_ops(self):
        # one formula per family member: the pruner's model must BE the
        # kernel guard's model with that variant's spec flags
        from jimm_tpu.ops import flash_attention as fa
        from jimm_tpu.tune.space import (bias_flash_vmem_bytes,
                                         masked_flash_vmem_bytes,
                                         sigmoid_vmem_bytes)
        for bq in (128, 256, 512):
            for bk in (128, 256, 512):
                for d in (64, 128):
                    assert masked_flash_vmem_bytes(bq, bk, d) == \
                        fa._per_head_vmem_bytes(bq, bk, d, has_mask=True)
                    assert bias_flash_vmem_bytes(bq, bk, d) == \
                        fa._per_head_vmem_bytes(bq, bk, d, has_bias=True)
                    assert sigmoid_vmem_bytes(bq, bk, d) == \
                        fa._per_head_vmem_bytes(bq, bk, d, kind="sigmoid",
                                                has_mask=True)

    def test_attention_variant_spaces_and_kernels_registered(self):
        for name in ("flash_attention_masked", "flash_attention_bias",
                     "sigmoid_attention"):
            assert name in KERNELS
            assert KERNELS[name].version >= 1
            assert callable(KERNELS[name].bench)
            cands = kernel_space(name, FLASH_SHAPES, ("float32",) * 3)
            assert cands
            # seq len 128 -> no point in blocks beyond its 128-multiple
            assert all(c["block_q"] <= 128 and c["block_k"] <= 128
                       for c in cands)

    def test_bias_space_is_subset_of_flash_space(self):
        # the bias variant's extra (bq, bk) f32 tiles can only shrink the
        # feasible set, never grow it
        shapes = ((2, 1024, 4, 128),) * 3
        flash = {tuple(sorted(c.items()))
                 for c in kernel_space("flash_attention", shapes)}
        bias = {tuple(sorted(c.items()))
                for c in kernel_space("flash_attention_bias", shapes)}
        assert bias <= flash


class TestMeasure:
    def test_trimmed_median_drops_extremes(self):
        assert trimmed_median([100.0, 1.0, 2.0, 3.0, 0.1]) == 2.0

    def test_trimmed_median_small_samples(self):
        assert trimmed_median([3.0]) == 3.0
        assert trimmed_median([1.0, 3.0]) == 2.0

    def test_measure_counts_and_returns_positive(self):
        from jimm_tpu.tune.measure import measure
        before = counters()
        t = measure(lambda: sum(range(100)), reps=3, warmup=1)
        after = counters()
        assert t > 0
        assert delta(before, after, "measure_total") == 1


class TestOpsIntegration:
    def test_layer_norm_resolves_tuned_block(self, tmp_path):
        import jax.numpy as jnp

        from jimm_tpu.ops.layer_norm import layer_norm
        from jimm_tpu.tune import api as tune_api
        cache = tune_api.configure(tmp_path / "c")
        cache.put(tune_key("layer_norm", shapes=((24, 128),),
                           dtypes=("float32",),
                           kernel_version=KERNELS["layer_norm"].version),
                  {"block_rows": 8})
        x = jnp.arange(24 * 128, dtype=jnp.float32).reshape(24, 128) / 100
        before = counters()
        out = layer_norm(x, jnp.ones((128,)), jnp.zeros((128,)))
        after = counters()
        assert delta(before, after, "hit_total") >= 1
        assert delta(before, after, "measure_total") == 0
        ref = (x - x.mean(-1, keepdims=True)) / np.sqrt(
            x.var(-1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_masked_flash_resolves_tuned_block(self, tmp_path):
        """The variant looks up under its OWN kernel name — a tuned masked
        config must be honored by flash_attention_masked (and produce the
        oracle's numbers at the tuned blocks)."""
        import jax.numpy as jnp

        from jimm_tpu.ops.attention import reference_attention
        from jimm_tpu.ops.flash_attention import flash_attention_masked
        from jimm_tpu.tune import api as tune_api
        shapes = ((1, 128, 2, 64),) * 3
        cache = tune_api.configure(tmp_path / "c")
        cache.put(tune_key("flash_attention_masked", shapes=shapes,
                           dtypes=("float32",) * 3,
                           kernel_version=KERNELS[
                               "flash_attention_masked"].version),
                  {"block_q": 128, "block_k": 128})
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(1, 128, 2, 64).astype(np.float32))
                   for _ in range(3))
        mask = jnp.asarray(rng.rand(1, 128) > 0.3).at[:, 0].set(True)
        before = counters()
        out = flash_attention_masked(q, k, v, mask)
        after = counters()
        assert delta(before, after, "hit_total") >= 1
        assert delta(before, after, "measure_total") == 0
        ref = reference_attention(q, k, v, mask=mask[:, None, None, :])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)

    def test_flash_explicit_blocks_skip_cache(self):
        import jax
        import jax.numpy as jnp

        from jimm_tpu.ops.flash_attention import flash_attention
        k = jax.random.split(jax.random.PRNGKey(0), 3)
        q, kk, v = (jax.random.normal(ki, (1, 128, 2, 64)) for ki in k)
        before = counters()
        flash_attention(q, kk, v, block_q=128, block_k=128)
        after = counters()
        for name in ("hit_total", "miss_total", "fallback_total"):
            assert delta(before, after, name) == 0
