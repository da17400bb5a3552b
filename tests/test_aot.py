"""jimm_tpu.aot: keys, store, export round-trip, and serve warm-start.

The e2e class asserts the subsystem's two acceptance properties on CPU:
a fresh engine over a populated store reaches readiness with **zero**
fresh jit compilations (the serve `compile_count` gauge), and a corrupt
or version-mismatched store degrades to fresh compiles — incrementing
``jimm_aot_fallback_total`` — while still serving correct results.
"""

import asyncio
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from jimm_tpu.aot import (AOT_FORMAT_VERSION, ArtifactStore, canonical_json,
                          config_hash, donation_signature, serve_forward_key)

# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------

#: one fully-pinned key tuple, shared by the golden + subprocess tests
GOLDEN_KEY_KWARGS = dict(
    method="encode_image", bucket=4, item_shape=(32, 32, 3),
    in_dtype="float32", param_dtype="float32", mesh={"data": 8},
    backend="cpu", jax_version="0.0-test", jaxlib_version="0.0-test")
GOLDEN_CONFIG = {"family": "clip",
                 "vision": {"width": 64, "depth": 2, "image_size": 32}}
GOLDEN_FP = "e9ae5ee4081cf8d1a67403e413530de3bac7f25931ddfc98c4c02472229b0de1"


def golden_key():
    return serve_forward_key(GOLDEN_CONFIG, donation=donation_signature(),
                             **GOLDEN_KEY_KWARGS)


class TestKeys:
    def test_canonical_json_is_order_insensitive(self):
        a = canonical_json({"b": 1, "a": {"y": 2, "x": (3, 4)}})
        b = canonical_json({"a": {"x": [3, 4], "y": 2}, "b": 1})
        assert a == b == '{"a":{"x":[3,4],"y":2},"b":1}'

    def test_config_hash_ignores_key_order_not_values(self):
        assert config_hash({"w": 64, "d": 2}) == config_hash({"d": 2, "w": 64})
        assert config_hash({"w": 64}) != config_hash({"w": 65})

    def test_golden_fingerprint(self):
        # byte-stability contract: this digest may only change with a
        # deliberate AOT_FORMAT_VERSION bump (which invalidates stores)
        assert golden_key().fingerprint() == GOLDEN_FP

    def test_fingerprint_stable_across_processes(self):
        code = (
            "from jimm_tpu.aot import serve_forward_key, donation_signature\n"
            f"key = serve_forward_key({GOLDEN_CONFIG!r}, "
            f"donation=donation_signature(), **{GOLDEN_KEY_KWARGS!r})\n"
            "print(key.fingerprint())\n")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, check=True)
        assert out.stdout.strip() == GOLDEN_FP

    def test_every_field_changes_the_fingerprint(self):
        base = golden_key().fingerprint()
        for change in (dict(bucket=8), dict(method="__call__"),
                       dict(item_shape=(64, 64, 3)), dict(in_dtype="bfloat16"),
                       dict(param_dtype="bfloat16"), dict(mesh={"data": 4}),
                       dict(backend="tpu"), dict(jax_version="9.9"),
                       dict(jaxlib_version="9.9")):
            kw = {**GOLDEN_KEY_KWARGS, **change}
            other = serve_forward_key(GOLDEN_CONFIG,
                                      donation=donation_signature(), **kw)
            assert other.fingerprint() != base, change
        assert serve_forward_key(
            GOLDEN_CONFIG, donation=donation_signature(
                donate_argnums=(0,)),
            **GOLDEN_KEY_KWARGS).fingerprint() != base

    def test_mesh_object_and_dict_agree(self):
        class FakeMesh:
            shape = {"data": 8}
        a = serve_forward_key(GOLDEN_CONFIG, mesh=FakeMesh(),
                              donation=donation_signature(),
                              **{k: v for k, v in GOLDEN_KEY_KWARGS.items()
                                 if k != "mesh"})
        assert a.fingerprint() == GOLDEN_FP


# ---------------------------------------------------------------------------
# store
# ---------------------------------------------------------------------------

class TestStore:
    def test_put_get_roundtrip(self, tmp_path):
        store = ArtifactStore(tmp_path / "s")
        fp = "ab" + "0" * 62
        store.put(fp, b"payload-bytes", meta={"label": "t", "bucket": 1})
        assert store.contains(fp)
        assert store.get(fp) == b"payload-bytes"
        [entry] = store.entries()
        assert entry.fingerprint == fp
        assert entry.meta["label"] == "t"
        assert entry.meta["format_version"] == AOT_FORMAT_VERSION

    def test_get_miss_returns_none(self, tmp_path):
        store = ArtifactStore(tmp_path / "s")
        assert store.get("cd" + "0" * 62) is None

    def test_corrupt_payload_quarantined(self, tmp_path):
        store = ArtifactStore(tmp_path / "s")
        fp = "ab" + "1" * 62
        store.put(fp, b"good-bytes")
        (store.entry_dir(fp) / "artifact.bin").write_bytes(b"bit-rotted!")
        assert store.get(fp) is None          # never a corrupt executable
        assert not store.contains(fp)          # next lookup is a clean miss
        [q] = list(store.quarantine_dir.iterdir())
        assert "sha256 mismatch" in (q / "reason.txt").read_text()

    def test_format_version_mismatch_quarantined(self, tmp_path):
        store = ArtifactStore(tmp_path / "s")
        fp = "ab" + "2" * 62
        store.put(fp, b"old-format")
        meta_path = store.entry_dir(fp) / "meta.json"
        meta = json.loads(meta_path.read_text())
        meta["format_version"] = AOT_FORMAT_VERSION + 1
        meta_path.write_text(json.dumps(meta))
        assert store.get(fp) is None
        assert not store.contains(fp)

    def test_jax_version_mismatch_quarantined(self, tmp_path):
        # an entry recorded under another jax must never deserialize; the
        # caller sees a miss and compiles fresh, without error
        store = ArtifactStore(tmp_path / "s")
        fp = "ab" + "3" * 62
        store.put(fp, b"other-jax", meta={"jax": "0.1-old"})
        assert store.get(fp, expect_versions={"jax": "0.4-new"}) is None
        assert not store.contains(fp)
        [q] = list(store.quarantine_dir.iterdir())
        assert "jax mismatch" in (q / "reason.txt").read_text()
        # same fingerprint can be re-put afterwards (fresh write-through)
        store.put(fp, b"recompiled", meta={"jax": "0.4-new"})
        assert store.get(fp, expect_versions={"jax": "0.4-new"}) \
            == b"recompiled"

    def test_lru_eviction_by_size_cap(self, tmp_path):
        import os
        import time
        store = ArtifactStore(tmp_path / "s", max_bytes=250)
        fps = [f"{i:02x}" + str(i) * 62 for i in range(3)]
        now = time.time()
        for i, fp in enumerate(fps):
            store.put(fp, bytes(100))
            # deterministic LRU order without sleeping: backdate mtimes
            os.utime(store.entry_dir(fp) / "artifact.bin",
                     (now - 100 + i, now - 100 + i))
        # 300 bytes > 250 cap: the least-recently-used entry is gone
        assert not store.contains(fps[0])
        assert store.contains(fps[1]) and store.contains(fps[2])
        # a hit refreshes recency: touch fps[1], add a fourth entry
        store.get(fps[1])
        fp3 = "ff" + "9" * 62
        store.put(fp3, bytes(100))
        assert store.contains(fps[1])
        assert not store.contains(fps[2])

    def test_verify_quarantines_bad_entries(self, tmp_path):
        store = ArtifactStore(tmp_path / "s")
        good, bad = "aa" + "0" * 62, "bb" + "0" * 62
        store.put(good, b"fine")
        store.put(bad, b"fine-too")
        (store.entry_dir(bad) / "artifact.bin").write_bytes(b"flipped")
        problems = store.verify()
        assert [p["fingerprint"] for p in problems] == [bad]
        assert store.contains(good) and not store.contains(bad)
        assert store.verify() == []  # quarantine is not re-reported


# ---------------------------------------------------------------------------
# export round-trip + serve warm-start e2e (tiny CLIP, CPU)
# ---------------------------------------------------------------------------

BUCKETS = (1, 2)


@pytest.fixture(scope="module")
def tiny_clip():
    from flax import nnx

    from jimm_tpu import CLIP, preset
    from jimm_tpu.cli import _tiny_override
    cfg = _tiny_override(preset("clip-vit-base-patch16"))
    return CLIP(cfg, rngs=nnx.Rngs(0))


@pytest.fixture(scope="module")
def warm_store(tiny_clip, tmp_path_factory):
    from jimm_tpu.aot.warmup import warmup_store
    store = ArtifactStore(tmp_path_factory.mktemp("aot"))
    report = warmup_store(tiny_clip, method="encode_image", buckets=BUCKETS,
                          item_shape=(32, 32, 3), store=store, label="test")
    assert {b: r["action"] for b, r in report.items()} \
        == {1: "compiled", 2: "compiled"}
    return store


def make_forward(model, store):
    from jimm_tpu.aot.warmup import AotForward
    return AotForward(model, method="encode_image", item_shape=(32, 32, 3),
                      store=store, label="test")


def counter_values():
    from jimm_tpu import obs
    snap = obs.get_registry("jimm_aot").snapshot()
    return {k: snap.get(k, 0.0)
            for k in ("hit_total", "miss_total", "fallback_total")}


class TestWarmStartE2E:
    def test_populated_store_zero_fresh_compiles(self, tiny_clip, warm_store):
        from jimm_tpu.serve import BucketTable, InferenceEngine
        before = counter_values()
        forward = make_forward(tiny_clip, warm_store)
        engine = InferenceEngine(forward, item_shape=(32, 32, 3),
                                 buckets=BucketTable(BUCKETS),
                                 trace_count=forward.trace_count)
        engine.warmup_blocking()
        # THE acceptance property: readiness without one fresh jit trace
        assert forward.trace_count() == 0
        assert engine.metrics.snapshot()["compile_count"] == 0
        assert engine.warmup_report == {
            1: {"seconds": engine.warmup_report[1]["seconds"],
                "source": "aot"},
            2: {"seconds": engine.warmup_report[2]["seconds"],
                "source": "aot"}}
        after = counter_values()
        assert after["hit_total"] - before["hit_total"] == len(BUCKETS)
        assert after["fallback_total"] == before["fallback_total"]

    def test_aot_forward_matches_fresh_model(self, tiny_clip, warm_store):
        forward = make_forward(tiny_clip, warm_store)
        for b in BUCKETS:
            forward.prepare_bucket(b)
        x = np.random.RandomState(0).randn(2, 32, 32, 3).astype(np.float32)
        got = np.asarray(forward(x))
        want = np.asarray(tiny_clip.encode_image(x))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        assert forward.trace_count() == 0

    def test_corrupt_store_falls_back_and_still_serves(self, tiny_clip,
                                                       warm_store, tmp_path):
        import shutil
        store = ArtifactStore(tmp_path / "corrupt")
        shutil.copytree(warm_store.root / "objects", store.root / "objects",
                        dirs_exist_ok=True)
        for entry in store.entries():
            (entry.path / "artifact.bin").write_bytes(b"garbage")
        before = counter_values()
        forward = make_forward(tiny_clip, store)
        from jimm_tpu.serve import BucketTable, InferenceEngine
        engine = InferenceEngine(forward, item_shape=(32, 32, 3),
                                 buckets=BucketTable(BUCKETS),
                                 trace_count=forward.trace_count)
        engine.warmup_blocking()  # degrades, never raises
        assert {v["source"] for v in engine.warmup_report.values()} \
            == {"fallback"}
        after = counter_values()
        assert after["fallback_total"] - before["fallback_total"] \
            == len(BUCKETS)
        assert forward.trace_count() > 0  # fresh compiles did the work
        # ...and it still serves correct numbers end-to-end
        async def roundtrip():
            await engine.start()
            try:
                x = np.ones((32, 32, 3), np.float32)
                out = await engine.submit(x)
                return np.asarray(out)
            finally:
                await engine.stop()
        got = asyncio.run(roundtrip())
        want = np.asarray(tiny_clip.encode_image(
            np.ones((1, 32, 32, 3), np.float32)))[0]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_version_mismatch_falls_back_without_error(self, tiny_clip,
                                                       warm_store, tmp_path):
        import shutil
        store = ArtifactStore(tmp_path / "verdrift")
        shutil.copytree(warm_store.root / "objects", store.root / "objects",
                        dirs_exist_ok=True)
        for entry in store.entries():
            meta = dict(entry.meta)
            meta["jax"] = "0.0.1-ancient"
            (entry.path / "meta.json").write_text(json.dumps(meta))
        before = counter_values()
        forward = make_forward(tiny_clip, store)
        # never raises: the mismatched entry is quarantined, the bucket
        # falls back to a fresh compile, and serving proceeds
        assert forward.prepare_bucket(1) == "fallback"
        after = counter_values()
        assert after["fallback_total"] - before["fallback_total"] == 1
        fp = forward.key_for(1).fingerprint()
        assert not store.contains(fp)  # quarantined, not deleted
        assert any(store.quarantine_dir.iterdir())
        x = np.ones((1, 32, 32, 3), np.float32)
        want = np.asarray(tiny_clip.encode_image(x))
        np.testing.assert_allclose(np.asarray(forward(x)), want,
                                   rtol=1e-5, atol=1e-5)
        assert forward.trace_count() > 0  # the fresh compile did the work

    def test_write_through_populates_empty_store(self, tiny_clip, tmp_path):
        store = ArtifactStore(tmp_path / "wt")
        forward = make_forward(tiny_clip, store)
        assert forward.prepare_bucket(1) == "miss"
        assert len(store.entries()) == 1  # write-through happened
        # a second process (fresh forward) now starts warm
        forward2 = make_forward(tiny_clip, store)
        assert forward2.prepare_bucket(1) == "aot"
        assert forward2.trace_count() == 0

    def test_warmup_naflex_compiles_one_program_per_bucket_pair(self):
        """NaFlex serve warmup: one compile per (batch, seq) bucket pair,
        and a padded batch with different mask CONTENTS reuses the warm
        executable (the mask is runtime data, not a compile shape)."""
        from flax import nnx

        from jimm_tpu import SigLIP
        from jimm_tpu.aot.warmup import warmup_naflex
        from jimm_tpu.configs import SigLIPConfig, TextConfig, VisionConfig
        cfg = SigLIPConfig(
            vision=VisionConfig(image_size=16, patch_size=8, width=32,
                                depth=2, num_heads=2, mlp_dim=64,
                                act="gelu_tanh", pooling="map"),
            text=TextConfig(vocab_size=64, context_length=8, width=32,
                            depth=2, num_heads=2, mlp_dim=64,
                            act="gelu_tanh", causal=False, pooling="last",
                            proj_bias=True),
            projection_dim=32)
        model = SigLIP(cfg, rngs=nnx.Rngs(0))
        report = warmup_naflex(model, batch_buckets=(1, 2),
                               seq_buckets=(8,))
        assert set(report) == {(1, 8), (2, 8)}
        assert all(r["traces"] == 1 for r in report.values())
        assert all(r["seconds"] >= 0 for r in report.values())

    @pytest.mark.parametrize("env_dir, arg_dir, expected", [
        (None, "xla", "xla"),        # explicit directory
        (None, None, "default"),     # fixed in-checkout path
        ("from-env", "xla", "env"),  # the environment wins: nothing is set
    ])
    def test_enable_persistent_cache(self, tmp_path, monkeypatch, env_dir,
                                     arg_dir, expected):
        import jax

        from jimm_tpu.aot import export
        old = {k: getattr(jax.config, k) for k in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")}
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                               str(tmp_path / env_dir))
        try:
            jax.config.update("jax_compilation_cache_dir", "untouched")
            got = export.enable_persistent_cache(
                None if arg_dir is None else tmp_path / arg_dir)
            in_config = jax.config.jax_compilation_cache_dir
            if expected == "env":
                assert got == str(tmp_path / env_dir)
                assert in_config == "untouched"
            elif expected == "default":
                repo = pathlib.Path(export.__file__).resolve().parents[2]
                assert got == in_config == str(repo / ".jax_cache")
            else:
                assert got == in_config == str(tmp_path / arg_dir)
        finally:
            for k, v in old.items():
                jax.config.update(k, v)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
