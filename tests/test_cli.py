"""CLI smoke tests (`python -m jimm_tpu ...`), in-process via `cli.main`.

The reference has no CLI at all (SURVEY §5 config row); ours must at least
list presets, train offline on synthetic data with checkpoint/resume, and
inspect safetensors files.
"""

import json

import numpy as np
import pytest

from jimm_tpu.cli import main
from jimm_tpu.weights.safetensors_io import save_file


def test_presets_lists_all(capsys):
    assert main(["presets"]) == 0
    out = capsys.readouterr().out
    for name in ("vit-base-patch16-224", "clip-vit-large-patch14",
                 "siglip-so400m-patch14-384", "siglip2-large-patch16-512"):
        assert name in out


def test_train_tiny_vit(tmp_path, capsys):
    metrics = tmp_path / "metrics.jsonl"
    assert main(["train", "--preset", "vit-base-patch16-224", "--tiny",
                 "--steps", "3", "--batch-size", "8",
                 "--metrics-file", str(metrics)]) == 0
    records = [json.loads(line) for line in metrics.read_text().splitlines()]
    assert len(records) == 3
    assert all(np.isfinite(r["loss"]) for r in records)


def test_train_resume(tmp_path):
    args = ["train", "--preset", "vit-base-patch16-224", "--tiny",
            "--batch-size", "8", "--ckpt-dir", str(tmp_path / "ckpt"),
            "--save-every", "1", "--log-every", "0"]
    assert main(args + ["--steps", "2"]) == 0
    metrics = tmp_path / "metrics.jsonl"
    assert main(args + ["--steps", "4", "--resume",
                        "--metrics-file", str(metrics)]) == 0
    records = [json.loads(line) for line in metrics.read_text().splitlines()]
    # resumed at step 2: only steps 2 and 3 ran in the second invocation
    assert [r["step"] for r in records] == [2, 3]


@pytest.mark.parametrize("mesh", [None, "data=2"])
def test_train_rows_carry_the_loops_phases(tmp_path, capsys, mesh,
                                           eight_devices):
    """Every ``--metrics-file`` row holds what the accounter measured since
    the row before: its own step's phases and the previous step's host_sync
    and checkpoint, on the Unix clock, adding up to the goodput buckets."""
    import time
    metrics = tmp_path / "metrics.jsonl"
    argv = ["train", "--preset", "vit-base-patch16-224", "--tiny",
            "--steps", "5", "--batch-size", "8", "--log-every", "1",
            "--ckpt-dir", str(tmp_path / "ckpt"), "--save-every", "1",
            "--metrics-file", str(metrics)]
    if mesh:
        argv += ["--mesh", mesh, "--rules", "dp", "--max-devices", "2"]
    t0 = time.time_ns()
    assert main(argv) == 0
    t1 = time.time_ns()
    out = capsys.readouterr().out
    assert "phases" not in out, "the console does not show them"
    goodput = json.loads(next(line for line in out.splitlines()
                              if line.startswith("goodput: "))[9:])
    rows = [json.loads(line) for line in metrics.read_text().splitlines()]
    assert len(rows) == 5 and all("phases" in r for r in rows)

    own = ["next_batch", "dispatch", "device_wait"] if mesh else [
        "next_batch", "place", "dispatch", "device_wait"]
    assert [p[0] for p in rows[0]["phases"]] == own
    for r in rows[1:]:
        assert [p[0] for p in r["phases"]] == ["host_sync", "checkpoint",
                                               *own]
    spans = [p for r in rows for p in r["phases"]]
    assert t0 <= spans[0][1] and spans[-1][1] + spans[-1][2] <= t1
    for (_, a0, adur), (_, b0, _) in zip(spans, spans[1:]):
        assert a0 + adur <= b0, "ordered, and never overlapping"

    def total(row, *names):
        return sum(dur for name, _, dur in row["phases"]
                   if name in names) / 1e9

    for r in rows:
        # StepTimer starts before the dispatch span and stops inside the
        # device_wait span: the same stretch but for two clock reads
        assert total(r, "dispatch", "device_wait") == pytest.approx(
            r["step_time_s"], abs=5e-3)
        assert r["time"] * 1e9 >= r["phases"][-1][1] + r["phases"][-1][2]
    # the buckets are the sums of their phases (the line rounds to 0.1 ms)
    near = dict(abs=3e-4)
    assert goodput["compile_s"] == pytest.approx(
        total(rows[0], "dispatch", "device_wait"), **near)
    assert goodput["step_s"] == pytest.approx(
        sum(total(r, "dispatch", "device_wait") for r in rows[1:]), **near)
    assert goodput["data_wait_s"] == pytest.approx(
        sum(total(r, "next_batch", "place") for r in rows), **near)
    # the last step's host_sync and checkpoint end after the last row
    logged = sum(total(r, "host_sync") for r in rows)
    assert 0 < logged < goodput["host_sync_s"]
    assert 0 < sum(total(r, "checkpoint") for r in rows) < goodput[
        "checkpoint_s"]


@pytest.mark.slow
def test_train_sharded_ring_loss(tmp_path, eight_devices, capsys):
    assert main(["train", "--preset", "siglip-base-patch16-256", "--tiny",
                 "--steps", "2", "--batch-size", "8",
                 "--mesh", "data=4,model=2", "--rules", "fsdp_tp",
                 "--loss", "siglip_ring", "--log-every", "1"]) == 0
    assert "loss=" in capsys.readouterr().out


def test_inspect(tmp_path, capsys):
    path = tmp_path / "m.safetensors"
    save_file({"w": np.zeros((3, 5), np.float32)}, path)
    assert main(["inspect", str(path)]) == 0
    out = capsys.readouterr().out
    assert "w" in out and "(3, 5)" in out


def test_bench_forward_tiny(capsys):
    assert main(["bench-forward", "--preset", "siglip-base-patch16-256",
                 "--tiny", "--batch-size", "4", "--steps", "2"]) == 0
    assert "images/sec" in capsys.readouterr().out


def test_train_profile_capture(tmp_path, capsys):
    assert main(["train", "--preset", "vit-base-patch16-224", "--tiny",
                 "--steps", "6", "--batch-size", "8", "--log-every", "0",
                 "--profile-dir", str(tmp_path / "prof")]) == 0
    assert "profile trace written" in capsys.readouterr().out
    assert (tmp_path / "prof" / "plugins" / "profile").is_dir()
