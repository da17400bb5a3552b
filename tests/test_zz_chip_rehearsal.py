"""`chip_smoke.py --rehearse` end to end on the CPU: every phase runs at the
tiny size, the device reported is the one it ran on, and the four-device
phase spreads the state.

The file sorts last on purpose. These are the most expensive tests this
change added (two fresh processes, ~40 s), and the suite runs close to its
time limit: if the limit cuts it, it should lose these before forty cheaper
tests that would have run in the same time.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def rehearsals(tmp_path_factory):
    """Both rehearsals, each in a process of its own (a fresh backend with
    its own device count), side by side: {mode: (returncode, stdout lines)}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(
        tmp_path_factory.mktemp("jax-cache")))
    env.pop("XLA_FLAGS", None)  # the suite's eight virtual devices
    procs = {mode: subprocess.Popen(
        [sys.executable, str(REPO / "chip_smoke.py"), "--rehearse", *flags],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for mode, flags in (("one_chip", []), ("multichip", ["--multichip"]))}
    out = {}
    for mode, proc in procs.items():
        stdout, stderr = proc.communicate(timeout=600)
        assert proc.returncode == 0, stderr[-3000:]
        out[mode] = [json.loads(line) for line in stdout.splitlines()
                     if line.startswith("{")]
    return out


def test_rehearsal_runs_every_phase_and_reports_the_cpu(rehearsals):
    rows = rehearsals["one_chip"]
    assert rows[-1] == {"ok": True, "device": {"platform": "cpu",
                                               "kind": "cpu", "count": 1}}
    passed = {r["phase"] for r in rows if r.get("passed")}
    assert passed == {"train", "kernels"}
    cases = [r for r in rows if r.get("phase") == "kernels" and "case" in r]
    families = {r["case"].split(" ")[0] for r in cases}
    assert families == {"flash_attention", "flash_attention_masked",
                        "layer_norm", "fp8_matmul", "quantized_linear"}
    # off the chip the kernels ran interpreted, and the script says so
    assert all(r["ok"] and r["lowering"] == "interpreter" for r in cases)
    train = next(r for r in rows if r.get("phase") == "train" and "loss" in r)
    assert train["steps"] == 10 and train["loss"][-1] < train["loss"][0]
    assert train["compile_requests_after_first_step"] == []


def test_multichip_rehearsal_spreads_state_over_four_devices(rehearsals):
    rows = rehearsals["multichip"]
    assert rows[-1] == {"ok": True, "device": {"platform": "cpu",
                                               "kind": "cpu", "count": 4}}
    # with the option nothing else runs
    assert {r["phase"] for r in rows[:-1]} == {"setup", "multichip"}
    shares = next(r for r in rows
                  if "state_share_by_device" in r)["state_share_by_device"]
    assert len(shares) == 4
    assert max(shares.values()) < 0.5  # no device holds all of it
    losses = next(r for r in rows if "loss_four_chips" in r)
    assert losses["same_batches"]
    assert losses["loss_four_chips"] == pytest.approx(
        losses["loss_one_chip"], rel=2e-2)
