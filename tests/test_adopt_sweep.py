"""scripts/adopt_sweep.py: ranking, fidelity filters, flag spelling."""

import json

import scripts.adopt_sweep as adopt


def _write(tmp_path, recs):
    p = tmp_path / "sweep.log"
    p.write_text("\n".join(json.dumps(r) for r in recs) + "\nnot json\n")
    return p


def test_ranking_filters_low_fidelity_records(tmp_path):
    path = _write(tmp_path, [
        {"variant": {"remat": "dots"}, "mfu": 0.45, "device": "TPU v5 lite"},
        # tiny/CPU validation lines must never outrank real measurements
        {"variant": {"remat": "dots"}, "mfu": 0.93, "device": "cpu"},
        {"variant": {"remat": "dots", "ln": "fused"}, "mfu": 0.91,
         "tiny": True, "device": "TPU v5 lite"},
        {"variant": {"remat": "dots", "ln": "fused"}, "mfu": 0.47,
         "device": "TPU v5 lite"},
        {"variant": {"remat": "dots"}, "error": "boom"},
    ])
    recs = adopt.load_records(path, phase_filter=False)
    assert all(isinstance(r["mfu"], float) for r in recs)
    assert sorted(r["mfu"] for r in recs) == [0.45, 0.47]


def test_last_record_per_variant_wins(tmp_path):
    path = _write(tmp_path, [
        {"variant": {"remat": "dots"}, "mfu": 0.40, "device": "TPU"},
        # key order must not split the variant into two entries
        {"variant": {"ln": "fused", "remat": "dots"}, "mfu": 0.30,
         "device": "TPU"},
        {"variant": {"remat": "dots", "ln": "fused"}, "mfu": 0.42,
         "device": "TPU"},
        {"variant": {"remat": "dots"}, "mfu": 0.46, "device": "TPU"},
    ])
    ranked = adopt.rank_records(adopt.load_records(path, phase_filter=False))
    assert [r["mfu"] for r in ranked] == [0.46, 0.42]


def test_flags_for_reproduces_measured_config():
    v = {"remat": "dots+ln", "ln": "fused", "fused_qkv": "1",
         "moment": "bf16", "unroll": "6", "batch": "256", "donate": "0",
         "attn": "saveable"}
    flags = adopt.flags_for(v)
    for expect in ("--remat dots+ln", "--ln fused", "--fused-qkv",
                   "--moment-dtype bf16", "--unroll 6", "--batch-size 256",
                   "--no-donate", "--attn saveable"):
        assert expect in flags, flags


def test_missing_device_field_is_low_fidelity(tmp_path):
    # pre-r4 sweep logs carry no device tag; they must not outrank (or even
    # enter) the ranking vs provenance-tagged TPU records (ADVICE r4)
    path = _write(tmp_path, [
        {"variant": {"remat": "dots"}, "mfu": 0.45, "device": "TPU v5 lite"},
        {"variant": {"remat": "full"}, "mfu": 0.93},
    ])
    recs = adopt.load_records(path, phase_filter=False)
    assert [r["mfu"] for r in recs] == [0.45]


def test_runtime_for_maps_variant_to_with_runtime_kwargs():
    rt = adopt.runtime_for({"remat": "dots+ln", "attn": "flash",
                            "ln": "fused", "fused_qkv": "1", "unroll": "6",
                            "moment": "bf16", "batch": "256"})
    assert rt == {"remat": True, "remat_policy": "dots+ln",
                  "attn_impl": "flash", "ln_impl": "fused",
                  "fused_qkv": True, "scan_unroll": 6}


def test_apply_adoption_round_trips_through_configs(tmp_path, monkeypatch):
    import jimm_tpu.configs as configs
    monkeypatch.setattr(configs, "ADOPTED_RUNTIME_PATH",
                        tmp_path / "adopted.json")
    best = {"variant": {"remat": "dots+ln", "attn": "flash", "unroll": "12"},
            "mfu": 0.47, "step_time_ms": 240.0, "device": "TPU v5 lite",
            "ts": "2026-07-30T00:00:00Z"}
    path = adopt.apply_adoption(best, "siglip-base-patch16-256")
    data = json.loads(path.read_text())
    entry = data["presets"]["siglip-base-patch16-256"]
    assert entry["provenance"]["mfu"] == 0.47
    assert entry["provenance"]["device"] == "TPU v5 lite"
    assert entry["variant"]["attn"] == "flash"
    # the configs-side loader returns exactly the runtime fields
    assert configs.adopted_runtime("siglip-base-patch16-256") == {
        "remat": True, "remat_policy": "dots+ln", "attn_impl": "flash",
        "scan_unroll": 12}
    # unknown preset -> {}
    assert configs.adopted_runtime("vit-large-patch16-384") == {}
    # a second adoption for another preset preserves the first entry
    adopt.apply_adoption({"variant": {"remat": "dots"}, "mfu": 0.5,
                          "device": "TPU v5 lite"}, "vit-large-patch16-384")
    data = json.loads(path.read_text())
    assert set(data["presets"]) == {"siglip-base-patch16-256",
                                    "vit-large-patch16-384"}


def test_adopted_runtime_rejects_bad_fields_with_warning(tmp_path,
                                                         monkeypatch):
    # a corrupted file must DEGRADE (warning + {}), never crash the CLI or
    # fail minutes into a jit trace with an invalid baked-in value
    import pytest

    import jimm_tpu.configs as configs
    p = tmp_path / "adopted.json"
    monkeypatch.setattr(configs, "ADOPTED_RUNTIME_PATH", p)
    for runtime in ({"width": 4096},              # architecture smuggling
                    {"attn_impl": "flsh"},        # typo'd enum value
                    {"scan_unroll": "12"},        # string where int needed
                    {"remat_policy": "dotz"},     # malformed remat spec
                    ["not", "a", "dict"]):        # wrong container type
        p.write_text(json.dumps({"presets": {"x": {"runtime": runtime}}}))
        with pytest.warns(UserWarning, match="ignoring adopted runtime"):
            assert configs.adopted_runtime("x") == {}
    # valid entries still load
    p.write_text(json.dumps({"presets": {"x": {"runtime": {
        "attn_impl": "flash", "scan_unroll": 12, "remat": True,
        "remat_policy": "dots+ln"}}}}))
    assert configs.adopted_runtime("x")["attn_impl"] == "flash"


def test_bench_resolve_adopted_defaults(tmp_path, monkeypatch):
    import importlib.util
    import pathlib

    import jimm_tpu.configs as configs
    spec = importlib.util.spec_from_file_location(
        "bench_for_adopt_test",
        pathlib.Path(__file__).resolve().parent.parent / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    p = tmp_path / "adopted.json"
    p.write_text(json.dumps({"presets": {"siglip-base-patch16-256": {
        "variant": {"remat": "dots+ln", "attn": "flash", "moment": "bf16",
                    "unroll": "12", "fused_qkv": "1"}}}}))
    monkeypatch.setattr(configs, "ADOPTED_RUNTIME_PATH", p)

    a = bench.parse_args(["--model", "siglip_b16_256"])
    assert bench.resolve_adopted_defaults(a, on_tpu=True)
    assert (a.remat, a.attn, a.moment_dtype, a.unroll, a.fused_qkv) == \
        ("dots+ln", "flash", "bf16", 12, True)

    # explicit flags always beat adopted values
    a = bench.parse_args(["--remat", "dots", "--attn", "xla", "--unroll", "6"])
    bench.resolve_adopted_defaults(a, on_tpu=True)
    assert (a.remat, a.attn, a.unroll) == ("dots", "xla", 6)

    # off-TPU: builtin fallbacks, adopted file untouched
    a = bench.parse_args([])
    assert not bench.resolve_adopted_defaults(a, on_tpu=False)
    assert (a.remat, a.attn, a.ln, a.moment_dtype) == \
        ("dots", "auto", "xla", "f32")

    # no adopted entry for the model's preset -> fallbacks only
    a = bench.parse_args(["--model", "vit_l16_384"])
    assert not bench.resolve_adopted_defaults(a, on_tpu=True)
    assert a.remat == "dots"


def test_sweep_skips_already_measured_tpu_variants(tmp_path, monkeypatch):
    """bench_sweep's retry-resume: only same-model, real-TPU, non-tiny,
    successful records mark a grid variant as already measured."""
    import scripts.bench_sweep as bs
    recs = [
        {"model": "siglip_b16_256", "variant": {"remat": "dots"},
         "mfu": 0.446, "device": "TPU v5 lite"},
        # errored attempt: must be retried
        {"model": "siglip_b16_256", "variant": {"remat": "dots",
                                                "ln": "fused"}, "error": "x"},
        # CPU validation record: never marks a TPU variant done
        {"model": "siglip_b16_256", "variant": {"remat": "dots",
                                                "batch": "192"},
         "mfu": 0.4, "device": "cpu"},
        # other bench model: independent
        {"model": "vit_l16_384", "variant": {"remat": "dots"},
         "mfu": 0.3, "device": "TPU v5 lite"},
        # tiny smoke: low fidelity
        {"model": "siglip_b16_256", "variant": {"remat": "dots+ln"},
         "mfu": 0.4, "device": "TPU v5 lite", "tiny": True},
    ]
    p = _write(tmp_path, recs)
    monkeypatch.setattr(bs, "MEASUREMENTS", p)
    assert bs.measured_variants("siglip_b16_256") == [{"remat": "dots"}]
    assert bs.measured_variants("vit_l16_384") == [{"remat": "dots"}]
    monkeypatch.setattr(bs, "MEASUREMENTS", tmp_path / "absent.jsonl")
    assert bs.measured_variants("siglip_b16_256") == []
