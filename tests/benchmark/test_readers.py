"""The readers of device numbers, which no rehearsal reaches (off the TPU
they return nothing): fed what the chip showed, they give the chip's numbers
(my chip runs, PR 22: PERF.md section 5)."""

import json

import pytest

from benchmarks import flops, harness

DEVICE = {"platform": "tpu", "device_kind": "TPU v5 lite", "chips": 1}


def _config(name):
    return json.loads((harness.BENCH / "configs" / f"{name}.json").read_text())


def _observed(config_name, batch, steps, period_s, trace):
    config = _config(config_name)
    return {**DEVICE, "config": config, "global_batch": batch,
            "window_rows": [{"step_time_s": period_s}] * steps,
            "window_s": steps * period_s, "trace": trace,
            "flops_per_step": flops.train_step_flops(config, batch)}


@pytest.fixture(scope="module")
def readers():
    return harness.load_readers("layer_metrics")


def test_siglip_b_step_is_22_teraflops_and_42_percent_of_the_peak(readers):
    o = _observed("siglip_b16_256", 128, 36, 0.2696,
                  {"idle_pct": 8.4, "scoped_ops": 900,
                   "scope_ms": {"fwd_bwd": 221.9, "optimizer_update": 3.75}})
    assert o["flops_per_step"] == pytest.approx(22.2e12, rel=0.01)
    assert readers["mfu_pct"](o) == pytest.approx(41.9, abs=0.2)
    assert readers["device_idle_pct"](o) == 8.4
    assert readers["fwd_bwd_ms"](o) == 221.9
    assert readers["optimizer_ms"](o) == 3.75
    assert readers["flash_ms"](o) is None, "no Pallas call on this path"


def test_vit_l_flash_kernels_run_at_nine_percent_of_their_roofline(readers):
    o = _observed("vit_l16_384", 24, 27, 0.4374,
                  {"idle_pct": 3.4, "scoped_ops": 900, "scope_ms": {},
                   "kernel_ms": {"pallas_call": 136.3}})
    o.update(flash_calls=72, flash_kernels=("pallas_call",))
    assert readers["flash_ms"](o) == 136.3
    # 24 layers x (forward + backward) at (24, 577, 16, 64): 12.0 ms at the peaks
    assert readers["flash_roofline"](o) == pytest.approx(8.8, abs=0.1)
    assert readers["mfu_pct"](o) == pytest.approx(31.9, abs=0.3)


def test_off_the_tpu_no_reader_gives_a_device_number(readers):
    manifest = harness.load_manifest()
    o = _observed("vit_l16_384", 24, 27, 0.4374,
                  {"idle_pct": 3.4, "scoped_ops": 9, "scope_ms": {"fwd_bwd": 1},
                   "kernel_ms": {"pallas_call": 1.0}})
    o.update(platform="cpu", device_kind="cpu", flash_calls=72,
             flash_kernels=("pallas_call",))
    from_the_device = [m["name"] for m in manifest["per_layer"]
                       if m["source"] == "device_trace"] + ["mfu_pct"]
    assert len(from_the_device) >= 6
    assert [readers[name](o) for name in from_the_device] == [None] * len(
        from_the_device)
