"""CLI smoke tests (`python -m jimm_tpu ...`), in-process via `cli.main`.

The reference has no CLI at all (SURVEY §5 config row); ours must at least
list presets, train offline on synthetic data with checkpoint/resume, and
inspect safetensors files.
"""

import json
import pathlib

import numpy as np
import pytest

from jimm_tpu.cli import main
from jimm_tpu.configs import PRESETS
from jimm_tpu.weights.safetensors_io import save_file


# (``presets`` lists every preset: `tests/test_families.py`)

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
    """Every ``--metrics-file`` row holds its own step's phases after the
    ``host_sync`` of the step before, on the Unix clock, adding up to the
    goodput buckets. The loop keeps one step in flight, so the rows group by
    step and not by the clock: step i+1 is dispatched before step i's loss
    is waited for."""
    import time
    steps = 5
    metrics = tmp_path / "metrics.jsonl"
    argv = ["train", "--preset", "vit-base-patch16-224", "--tiny",
            "--steps", str(steps), "--batch-size", "8", "--log-every", "1",
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
    assert [r["step"] for r in rows] == list(range(steps))
    assert all("phases" in r for r in rows)

    # what stands before the first next_batch / place / dispatch belongs to
    # the step before (benchmarks/trace/host_join.py::host_spans); the
    # step's checkpoint runs after its dispatch and so stands after it
    own = ["next_batch", "dispatch", "checkpoint", "device_wait"] if mesh \
        else ["next_batch", "place", "dispatch", "checkpoint", "device_wait"]
    # the first row begins with the set-up spans: everything train() did
    # before its loop, from its first statement (--ckpt-dir: the manager)
    assert [p[0] for p in rows[0]["phases"]] == [
        "backend_init", "imports", "backend_init", "model_build",
        "optimizer_build", "checkpoint", "data_build", "data_build", *own]
    for r in rows[1:]:
        assert [p[0] for p in r["phases"]] == ["host_sync", *own]

    def span(row, name):
        (found,) = [p for p in row["phases"] if p[0] == name]
        return found

    spans = [p for r in rows for p in r["phases"]]
    assert t0 <= min(p[1] for p in spans)
    assert max(p[1] + p[2] for p in spans) <= t1
    # no two spans of the run overlap (one thread measured them all) ...
    by_clock = sorted(spans, key=lambda p: p[1])
    for (_, a0, adur), (_, b0, _) in zip(by_clock, by_clock[1:]):
        assert a0 + adur <= b0, "never overlapping"
    # ... and those of one step are in order in its row
    for r in rows:
        mine = [p for p in r["phases"] if p[0] != "host_sync"]
        assert mine == sorted(mine, key=lambda p: p[1])
    # one step in flight: the next step's dispatch begins before this
    # step's loss is waited for, and ends before it too
    for r, nxt in zip(rows, rows[1:]):
        ahead, wait = span(nxt, "dispatch"), span(r, "device_wait")
        assert ahead[1] + ahead[2] <= wait[1]
        # the host_sync that opens a row is the one that wrote the row before
        log = span(nxt, "host_sync")
        assert wait[1] + wait[2] <= log[1] <= r["time"] * 1e9 <= (
            log[1] + log[2])

    def total(row, *names):
        return sum(dur for name, _, dur in row["phases"]
                   if name in names) / 1e9

    for r in rows:
        # the host's time on the step: its call and its wait, which no
        # longer touch; the same stretches but for two clock reads each
        assert total(r, "dispatch", "device_wait") == pytest.approx(
            r["step_time_s"], abs=5e-3)
        assert r["time"] * 1e9 >= max(p[1] + p[2] for p in r["phases"])
    # the buckets are the sums of their phases (the line rounds to 0.1 ms)
    near = dict(abs=3e-4)
    assert goodput["compile_s"] == pytest.approx(
        total(rows[0], "dispatch", "device_wait"), **near)
    assert goodput["step_s"] == pytest.approx(
        sum(total(r, "dispatch", "device_wait") for r in rows[1:]), **near)
    assert goodput["data_wait_s"] == pytest.approx(
        sum(total(r, "next_batch", "place") for r in rows), **near)
    from jimm_tpu.obs.goodput import SETUP_PHASES
    assert goodput["setup_s"] == pytest.approx(
        total(rows[0], *SETUP_PHASES), **near)
    # the wall runs from train()'s first statement to the goodput line
    assert sum(goodput[f"{b}_s"] for b in ("setup", "compile", "step")) < (
        goodput["wall_s"]) <= (t1 - t0) / 1e9
    # every checkpoint is in a row; the last step's host_sync ends after it
    assert goodput["checkpoint_s"] == pytest.approx(
        sum(total(r, "checkpoint") for r in rows), **near)
    logged = sum(total(r, "host_sync") for r in rows)
    assert 0 < logged < goodput["host_sync_s"]


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


# ---------------------------------------------------------------------------
# resolve_runtime: how a train run executes, asked on the CPU what the TPU gets
# ---------------------------------------------------------------------------

REPO = pathlib.Path(__file__).resolve().parents[1]
CELLS = sorted(p.stem for p in (REPO / "benchmarks/workloads").glob("*.json"))


def _resolve(argv, backend):
    from jimm_tpu import cli, preset
    args = cli.build_parser().parse_args(argv)
    cfg = preset(args.preset)
    if args.num_layers:  # as `train` shapes a language model before it asks
        cfg = cli._replace_towers(cfg, depth=args.num_layers)
    return cli.resolve_runtime(args, cfg, None, backend)


@pytest.mark.parametrize("name", sorted(PRESETS))
def test_unflagged_runtime_is_shape_and_platform_only(name):
    """No runtime flag: nothing is chosen for the run but the unroll, from
    the model's depth, on the TPU. No preset's name chooses anything."""
    cfg = PRESETS[name]
    main_tower = cfg.decoder if hasattr(cfg, "decoder") else cfg.vision
    argv = ["train", "--preset", name]
    assert _resolve(argv, "tpu") == {"scan_unroll": main_tower.depth}
    assert _resolve(argv, "cpu") == {}


#: `scan_unroll` of each cell's `resolved_runtime` line (ledger, PR 28); a
#: new cell gets its row here
LEDGER_SCAN_UNROLL = {"siglip_b16_256.train": 12, "vit_l16_384.train": 24,
                      "ouro_2_6b.train": 8, "kanana_2_30b_a3b.train": 6,
                      "trinity_large.train": 5,
                      "kimi_linear_48b_a3b.train": 5,
                      "granite_4_0_h_micro.train": 10}
#: the remat policy each cell's `--remat` argument resolves to
LEDGER_REMAT_POLICY = {"trinity_large.train": "none",
                       "granite_4_0_h_micro.train": "none"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_argv_resolves_as_the_ledger_says(cell):
    """The benchmark's cells, argv as `benchmarks/drivers` build it from the
    cell's and the configuration's files (read here, never edited): the
    `resolved_runtime` the ledger's rows were measured under."""
    workload = json.loads(
        (REPO / "benchmarks/workloads" / f"{cell}.json").read_text())
    config = json.loads((REPO / "benchmarks/configs"
                         / f"{workload['config']}.json").read_text())
    traffic = workload["traffic_params"]
    argv = ["train", "--preset", config["preset"], "--seed", "1",
            "--batch-size", str(traffic["batch_size"]), "--steps", "40",
            "--log-every", "1", "--metrics-file", "m.jsonl",
            *traffic["cli_args"]]
    if workload["driver"] in ("train_lm", "train_moe_lm", "train_gqa_moe_lm",
                              "train_hybrid_lm"):
        argv += ["--num-layers", str(config["num_layers"]),
                 "--seq-len", str(traffic["seq_len"])]
    assert _resolve(argv, "tpu") == {
        "remat": True,
        "remat_policy": LEDGER_REMAT_POLICY.get(cell, "dots"),
        "scan_unroll": LEDGER_SCAN_UNROLL[cell]}


def test_explicit_flags_and_a_checkpoint_outrank_the_platform():
    assert _resolve(["train", "--preset", "vit-base-patch16-224",
                     "--scan-unroll", "1"], "tpu") == {"scan_unroll": 1}
    # a checkpoint's depth is not known when the run is resolved
    assert _resolve(["train", "--preset", "vit-base-patch16-224",
                     "--from-pretrained", "x"], "tpu") == {}
    assert _resolve(["train", "--preset", "vit-base-patch16-224", "--rules",
                     "pp", "--pipeline-microbatches", "4"], "cpu") == {
        "pipeline": True, "pp_microbatches": 4}
    with pytest.raises(SystemExit, match="--pipeline-virtual needs"):
        _resolve(["train", "--preset", "vit-base-patch16-224",
                  "--pipeline-virtual", "2"], "cpu")
    with pytest.raises(SystemExit, match="--remat: "):
        _resolve(["train", "--preset", "vit-base-patch16-224", "--remat",
                  "dots+nosuch"], "cpu")


# what `benchmarks/drivers/train_cli.py::resolved_runtime` and
# `train_lm.py::resolved_runtime` read off `model.config.<tower>`, and what
# `benchmarks/reference/parity*.py` read to size their references. The
# drivers are the yardstick: a PR that is not a `benchmark` PR cannot edit
# them, so a refactor that renames one of these finds out here, in seconds,
# and not on the chip (ROADMAP, Design: named debts).
SURFACE = {
    "vision": ("siglip-base-patch16-256",
               ["attn_impl", "scan_unroll", "remat", "remat_policy",
                "ln_impl", "precision", "num_heads", "ln_eps", "act",
                "patch_size", "width", "mlp_dim", "depth", "image_size",
                "channels"]),
    "text": ("siglip-base-patch16-256",
             ["context_length", "vocab_size", "num_heads", "ln_eps", "act",
              "width", "mlp_dim", "depth"]),
    "decoder": ("ouro-2.6b",
                ["attn_impl", "scan_unroll", "remat", "remat_policy",
                 "precision", "depth", "loops", "seq_len", "width",
                 "mlp_dim", "num_heads", "vocab_size", "ln_eps",
                 "rope_theta", "act"]),
    # what train_moe_lm.py and parity_moe_lm.py read off the sparse decoder
    "decoder_moe": ("kanana-2-30b-a3b",
                    ["attn_impl", "scan_unroll", "remat", "remat_policy",
                     "precision", "depth", "dense_layers", "seq_len", "width",
                     "mlp_dim", "num_heads", "vocab_size", "ln_eps",
                     "rope_theta", "act", "mla", "moe"]),
}


@pytest.mark.parametrize("tower", sorted(SURFACE))
def test_config_surface_the_benchmark_reads(tower):
    from jimm_tpu import preset
    name, fields = SURFACE[tower]
    cfg = getattr(preset(name), tower.split("_")[0])
    assert [f for f in fields if not hasattr(cfg, f)] == []
    if tower == "decoder_moe":
        assert [f for f in ("kv_lora_rank", "qk_nope_dim", "qk_rope_dim",
                            "v_head_dim") if not hasattr(cfg.mla, f)] == []
        assert [f for f in ("num_experts", "top_k", "expert_dim",
                            "shared_experts", "routed_scale", "held_experts",
                            "first_expert") if not hasattr(cfg.moe, f)] == []
        from jimm_tpu.train.trainer import moe_lm_loss_fn
        assert callable(moe_lm_loss_fn)
    # and the names the harness, the drivers and tests/benchmark import
    from jimm_tpu import Ouro, cli, obs, tune  # noqa: F401
    from jimm_tpu.aot.export import enable_persistent_cache
    from jimm_tpu.parallel import shard_batch, use_sharding
    from jimm_tpu.train.metrics import train_step_flops
    from jimm_tpu.train.trainer import contrastive_loss_fn, lm_loss_fn
    for fn in (cli._tiny_override, cli._model_cls, cli.build_parser,
               cli.train, tune.configure, enable_persistent_cache,
               obs.snapshot, train_step_flops, shard_batch, use_sharding,
               contrastive_loss_fn, lm_loss_fn):
        assert callable(fn)
