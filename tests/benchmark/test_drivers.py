"""Each cell rehearsed on the CPU at a tiny size through the real entry point:
the last line holds exactly the contract's keys and names the CPU as its
device; and without the rehearsal flag nothing is printed."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness, run as bench_run

MANIFEST = harness.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(autouse=True)
def _leave_nothing_in_the_checkout(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "RUNS_DIR", tmp_path / "runs")
    # the suite's process keeps its own compile-cache settings
    monkeypatch.setattr(harness, "enable_caches", lambda: "off (test)")


def _last_line(capsys, argv):
    rc = bench_run.main(argv)
    out = capsys.readouterr().out
    assert rc == 0, out[-3000:]
    return json.loads(out.strip().splitlines()[-1])


def _names(kind, cell):
    return {m["name"] for m in MANIFEST[kind]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contracts_line(cell, capsys):
    line = _last_line(capsys, ["--workload", cell, "--seed", "5", "--seconds",
                               "2", "--trace", "0", "--rehearse"])
    assert set(line) == KEYS
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    chips = harness.find(MANIFEST["workloads"], cell, "workload")["chips"]
    assert line["device"]["count"] == chips
    assert set(line["metrics"]) == _names("end_to_end", cell)
    assert all(set(m) == {"value", "unit"} and m["value"] > 0
               for m in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    assert isinstance(line["correct"], bool)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_layers_but_no_device_number(cell, capsys):
    line = _last_line(capsys, ["--workload", cell, "--seed", "6", "--seconds",
                               "4", "--trace", "1", "--rehearse"])
    assert set(line) == KEYS | {"breakdown"}
    assert line["device"]["platform"] == "cpu"
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in line["breakdown"].values())
    allowed = _names("per_layer", cell)
    from_the_device = {m["name"] for m in MANIFEST["per_layer"]
                       if m["source"] == "device_trace"} | {"mfu_pct"}
    assert set(line["metrics"]) <= allowed
    assert line["metrics"] and not set(line["metrics"]) & from_the_device, (
        "a CPU run never prints under a device metric's name")
    assert line["correct"] is True


@pytest.mark.parametrize("cell", CELLS)
def test_step_count_depends_on_seconds_and_the_cells_file_alone(cell):
    """``cli train`` bakes ``--steps`` into the compiled step, so a count
    that moved with the seed or an earlier run would be a cold compile."""
    import math

    from benchmarks.drivers import train_cli
    runs = [harness.load_run(harness.REPO, cell, seed=seed, seconds=10,
                             trace=trace, rehearse=False, t_process_start=0.0)
            for seed, trace in ((1, False), (2, True))]
    est = runs[0].cell["traffic_params"]["est_step_ms"]
    want = (train_cli.WARMUP_STEPS + math.ceil(10e3 / est)
            + train_cli.TRACED_STEPS)
    assert [train_cli.planned_steps(r) for r in runs] == [want, want]
    argv = train_cli.cli_argv(runs[0], want, "m.jsonl")
    for flag in ("--attn-impl", "--ln-impl", "--scan-unroll", "--data",
                 "--tiny"):
        assert flag not in argv, "the cell measures what the code chooses"


def test_without_a_tpu_and_without_the_flag_nothing_is_printed(capsys):
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "0", "--seconds",
                         "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc == 2
    assert captured.out == ""
    assert "Nothing ran" in captured.err


def test_the_command_itself_exits_non_zero_without_a_tpu():
    done = subprocess.run(
        [sys.executable, *MANIFEST["command"][1:], "--workload", CELLS[0],
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=harness.REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert done.returncode == 2
    assert done.stdout == ""


def test_the_pool_is_a_function_of_the_seed():
    import numpy as np

    from benchmarks import traffic
    kw = dict(image_size=8, seed=7, vocab_size=50, seq_len=4)
    a, b = (traffic.make_pool("contrastive", 2, **kw) for _ in range(2))
    assert len(a) == traffic.POOL
    for (ia, ta), (ib, tb) in zip(a, b, strict=True):
        assert ia.dtype == np.float32 and ia.shape == (2, 8, 8, 3)
        assert ta.dtype == np.int32 and ta.shape == (2, 4) and ta.max() < 50
        assert (ia == ib).all() and (ta == tb).all()
    assert not (a[0][0] == a[1][0]).all(), "the batches of a pool differ"
    other = traffic.make_pool("contrastive", 2, **{**kw, "seed": 8})
    assert not (other[0][0] == a[0][0]).all()
    _, labels = traffic.make_pool("classification", 5, image_size=8, seed=7,
                                  num_classes=10)[0]
    assert labels.shape == (5,) and 0 <= labels.min() <= labels.max() < 10


def test_the_pool_feeds_the_cli_only_while_asked_to():
    import jimm_tpu.data as data

    from benchmarks import traffic
    own = (data.contrastive_pairs, data.blob_classification)
    with traffic.feed_cli(seed=3) as drawn:
        assert data.contrastive_pairs is not own[0]
        batches = data.contrastive_pairs(2, image_size=8, vocab_size=50,
                                         seq_len=4)
        first = [next(batches) for _ in range(traffic.POOL + 1)]
        assert drawn["batches"] == traffic.POOL + 1
        assert (first[0][0] == first[-1][0]).all(), "the pool is cycled"
    assert (data.contrastive_pairs, data.blob_classification) == own
