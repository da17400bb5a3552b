"""The benchmark's side of the dense hybrid language model (Mamba-2 layers
beside grouped-query attention ones, a SwiGLU in every layer, a tied head):
the configuration against the catalog row it was cut from, the comparison at a
small size (float32 model against the float32 reference: the same mathematics
must agree to float32 rounding) and its power to refuse a lower precision and
a state kept in bfloat16, the yardstick's counts, the driver that takes the
cell's modules from the cell's file, the readers of the cell's device numbers
on a hand-made trace, and the cell's rehearsal."""

import json
import types

import jax
import jax.numpy as jnp
import pytest
from flax import nnx

from benchmarks import flops_granite, harness
from benchmarks.drivers import train_hybrid_lm
from benchmarks.layer_metrics import granite as readers_module
from benchmarks.reference import control_lm, granite, parity_granite
from jimm_tpu import Granite, preset
from jimm_tpu.cli import _tiny_override

CELL = "granite_4_0_h_micro.train"
TIGHT = {"hidden": 2e-4, "logits": 2e-4, "loss": 2e-5, "scan": 2e-5,
         "scan_memory": 2e-5,
         "scan_grads": dict.fromkeys(("x", "dt", "A", "B", "C"), 2e-5),
         "update": 2e-3, "moment": 2e-3,
         "grads": dict.fromkeys(granite.GRAD_LEAVES, 2e-3)}
MINE = ["ssm_ms", "ssm_proj_ms", "ssm_scan_ms", "ssm_out_ms",
        "ssm_scan_roofline", "ssm_scan_steps"]
#: the catalog row's ``config`` (ibm-granite/granite-4.0-h-micro's own
#: config.json), the numbers the configuration's file must hold
CATALOG_CONFIG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": ["attention" if i % 10 == 5 else "mamba"
                    for i in range(40)],
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


def _run(seed=3, **kw) -> harness.Run:
    return harness.load_run(harness.REPO, CELL, seed=seed, seconds=10,
                            trace=False, t_process_start=0.0,
                            **{"rehearse": True, **kw})


@pytest.fixture(scope="module")
def trained():
    """What ``cli.train`` hands the comparison after a run of three steps of
    the tiny float32 model (every vector-shaped weight moved off its start):
    the model, its optimizer (the CLI's AdamW: the family's rate, ramped over
    the run less one step, clipped), the compiled step and the last batch."""
    from jimm_tpu.train.trainer import (OptimizerConfig, make_lm_train_step,
                                        make_optimizer)
    model = Granite(_tiny_override(preset("granite-4.0-h-micro")),
                    rngs=nnx.Rngs(0))
    keys = iter(jax.random.split(jax.random.key(7), 256))
    nnx.update(model, jax.tree.map(
        lambda a: a + 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
        if a.ndim <= 2 and a.shape[-1] < 512 else a,
        nnx.state(model, nnx.Param)))
    steps = 3
    optimizer = make_optimizer(model, OptimizerConfig(
        learning_rate=1e-4, weight_decay=1e-4, warmup_steps=steps - 1,
        total_steps=steps))
    step_fn = make_lm_train_step("granite", donate=True)
    tokens = jax.random.randint(
        jax.random.key(11), (2, model.config.decoder.seq_len + 1), 0,
        model.config.decoder.vocab_size, jnp.int32)
    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            step_fn(model, optimizer, tokens)
    return types.SimpleNamespace(model=model, optimizer=optimizer,
                                 step_fn=step_fn, mesh=None, rules=None,
                                 batch=(tokens,))


def _agree(trained, monkeypatch, tolerance=None, **changed):
    # the cell's own limits, not a rehearsal's: the float32 model meets them
    monkeypatch.setattr(granite, "REHEARSAL_TOLERANCE",
                        tolerance or granite.TOLERANCE)
    result = types.SimpleNamespace(**{**vars(trained), **changed})
    with jax.default_matmul_precision("highest"):
        return parity_granite.check_train(_run(), result)


def test_float32_model_agrees_with_the_reference(trained, monkeypatch):
    """Final hidden state, logits, loss, the ten gradient leaves, the chunked
    scan alone, and what the compiled step did to the parameters and to
    Adam's first moment."""
    agree = _agree(trained, monkeypatch, TIGHT)
    assert agree["ok"], agree
    step = agree["timed_step"]
    assert step["steps"] == 3 and step["rate"] == 1e-4  # the run's last rate
    assert int(trained.optimizer.step[...]) == 3        # set back, stepped
    assert 0 < agree["errors"]["moment"] < 2e-3
    assert set(agree["errors"]["grads"]) == set(granite.GRAD_LEAVES)
    assert set(agree["errors"]["scan_grads"]) == {"x", "dt", "A", "B", "C"}
    assert agree["not_held"] == []
    # a rehearsal holds every reading
    assert set(granite.REHEARSAL_TOLERANCE["grads"]) \
        == set(granite.GRAD_LEAVES)
    assert set(granite.REHEARSAL_TOLERANCE) == set(agree["errors"])
    # published layers 0-4 are the first Mamba-2 run, layer 5 the attention
    assert agree["grad_leaves"]["A_log"] == "run0/blocks/0/attn/A_log"
    assert agree["grad_leaves"]["attn_q"] == "run5/blocks/0/attn/q/kernel"
    assert agree["sizes_differ_from_file"] == []


@pytest.mark.parametrize("control, attribute, change, refused_by", [
    ("float8", "matmul", control_lm.float8_matmul, ("scan",)),
    ("state_bf16", "STATE_BF16", lambda _: True, ("scan",)),
])
def test_the_shipped_limits_refuse(control, attribute, change, refused_by,
                                   trained, monkeypatch):
    """The cell's limits against a reference in the nearest precision below
    the configuration's, and against one whose recurrence keeps its state in
    bfloat16. At this size the float32 model is far nearer the reference than
    the bfloat16 one the limits were set for, so only the scan's readings
    (float32 against float32 in both) are held to refuse here."""
    monkeypatch.setattr(granite, attribute, change(getattr(granite,
                                                           attribute)))
    agree = _agree(trained, monkeypatch)
    assert not agree["ok"], (control, agree["errors"])
    for key in refused_by:
        assert agree["errors"][key] > granite.TOLERANCE[key], (control, key)
    # what float8 does not separate on the chip is reported, not held
    assert agree["not_held"] == sorted(
        ["logits", "moment"] + [f"grads/{name}" for name in granite.GRAD_LEAVES
                                if name != "attn_q"])


def _blind_backward():
    """The scan with a backward that takes every chunk as entered from a
    state of zeros."""
    from jimm_tpu.ops import ssd
    scan = jax.custom_vjp(lambda *a: ssd._forward(*a)[0])
    scan.defvjp(ssd._ssd_fwd, lambda res, dy: ssd._ssd_bwd(
        (*res[:5], jnp.zeros_like(res[5])), dy))
    return scan


@pytest.mark.parametrize("fault, attribute, change, refused_by", [
    ("no_hand_off", "_enter", lambda: lambda written, totals:
     jnp.zeros_like(written), ("scan_memory",)),
    ("blind_backward", "_ssd", _blind_backward, ("A", "C")),
])
def test_the_scan_checks_see_the_walk_from_chunk_to_chunk(
        fault, attribute, change, refused_by, trained, monkeypatch):
    """At a trained model's steps the state one chunk hands the next carries
    the output: a scan that drops it, or whose backward does not see the
    states the chunks were entered with, is refused by ``scan_memory`` or
    ``scan_grads`` (at the timed shape the start's steps hand on nothing)."""
    from jimm_tpu.ops import ssd
    monkeypatch.setattr(ssd, attribute, change())
    with jax.default_matmul_precision("highest"):
        errors = parity_granite.scan_errors(granite, trained.model, 3,
                                            lambda fn: fn)
    limits = granite.TOLERANCE
    if fault == "no_hand_off":
        assert errors["scan_memory"] > limits["scan_memory"]
    else:
        assert errors["scan_memory"] <= limits["scan_memory"]
        for name in refused_by:
            assert errors["scan_grads"][name] > limits["scan_grads"][name]


def test_a_step_that_leaves_the_state_as_it_was_reads_one(trained,
                                                          monkeypatch):
    agree = _agree(trained, monkeypatch,
                   step_fn=lambda model, optimizer, tokens: {"loss": 0.0})
    assert agree["errors"]["update"] == agree["errors"]["moment"] == 1.0
    assert not agree["ok"]


def test_the_reference_is_plain_and_shares_nothing_with_the_program():
    source = (harness.BENCH / "reference" / "granite.py").read_text()
    code = source.split('"""', 2)[2]
    for word in ("import jimm_tpu", "from jimm_tpu", "flax", "pallas",
                 "cumsum", "segment", "shard"):
        assert word not in code, word
    assert "jax.lax.scan" in code          # the state, token by token
    assert granite.matmul is jnp.matmul and not granite.STATE_BF16
    for line in ("S_t,h = exp(dt_t,h A_h) S_t-1,h + dt_t,h x_t,h B_t^T",
                 "y_t,h = S_t,h C_t + D_h x_t,h",
                 "z = RMS_f(x) E^T / 8"):
        assert line in source, line


def test_the_recurrence_in_blocks_is_the_same_recurrence():
    keys = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(keys[0], (1, 256, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, 256, 4)))
    A = -jnp.arange(1.0, 5.0)
    B, C = (jax.random.normal(k, (1, 256, 2, 8)) for k in keys[2:])
    plain = granite.ssm_scan(x, dt, A, B, C)
    assert jnp.allclose(granite.ssm_scan(x, dt, A, B, C, jax.checkpoint),
                        plain, rtol=1e-6, atol=1e-6)
    assert jnp.allclose(granite.ssm_scan(x[:, :100], dt[:, :100], A,
                                         B[:, :100], C[:, :100]),
                        plain[:, :100], rtol=1e-5, atol=1e-6)


def test_configuration_file_holds_the_catalogs_numbers():
    """Every number of the catalog row's ``config`` under the same key, the
    depth alone reduced; no width reduced; the preset is the file's model."""
    run = _run(rehearse=False)
    config = run.config
    assert config["source"] == ("https://huggingface.co/ibm-granite/"
                                "granite-4.0-h-micro/blob/main/config.json")
    assert config["reduced"] == ["num_layers"]
    for key, value in CATALOG_CONFIG.items():
        assert config[key] == value, key
    assert (config["num_layers"], config["first_layer"]) == (10, 0)
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["attention_layers"] == [5, 15, 25, 35]
    assert config["assumed"]["chunk"] == config["mamba_chunk_size"] == 256
    assert "four pipeline stages" in config["deployment"]
    built = nnx.eval_shape(lambda: Granite(rngs=nnx.Rngs(0)))
    assert parity_granite.check_sizes(run, built) == []
    assert sum(flops_granite.parameter_count(config).values()) \
        == 951_991_232


def test_yardstick_counts_the_cells_numbers():
    config = _run(rehearse=False).config
    assert flops_granite.layer_mixers(config) \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    per = flops_granite.fwd_flops_per_token(config, 16384)
    step = {k: 3 * v * 16384 / 1e12 for k, v in per.items()}
    assert step["mamba_projections"] == pytest.approx(22.86, abs=0.01)
    assert step["mamba_recurrence"] == pytest.approx(0.928, abs=0.001)
    assert step["attention_core"] == pytest.approx(3.299, abs=0.001)
    assert step["head"] == pytest.approx(20.20, abs=0.01)
    assert flops_granite.train_step_flops(config, 1, 16384) / 1e12 \
        == pytest.approx(97.80, abs=0.01)
    # the scan's least time is bytes: 7.79 ms a step at 819 GB/s
    least = flops_granite.ssm_scan_least_seconds(config, 1, 16384,
                                                 "TPU v5 lite")
    assert least * 1e3 == pytest.approx(7.79, abs=0.01)
    fwd = flops_granite.ssm_scan_cost(1, 16384, 64, 64, 128, 1,
                                      backward=False)
    assert fwd["flops"] == 2 * 2 * 64 * 128 * 64 * 16384
    assert fwd["bytes"] == 16384 * (64 * 64 * 2 * 2 + 64 * 4 + 2 * 128 * 2)


def test_driver_takes_its_modules_from_the_cells_file():
    run = _run(rehearse=False)
    traffic = run.cell["traffic_params"]
    assert run.cell["driver"] == "train_hybrid_lm"
    assert (traffic["flops_module"], traffic["parity_module"],
            traffic["reader_module"]) == ("flops_granite", "parity_granite",
                                          "granite")
    assert traffic["flash_kernels"] == ["attn_full/pallas_call"]
    argv = train_hybrid_lm.cli_argv(run, 20, "m.jsonl")
    pairs = dict(zip(argv, argv[1:]))
    assert pairs["--preset"] == "granite-4.0-h-micro"
    assert pairs["--batch-size"] == "1"
    assert pairs["--num-layers"] == "10" and pairs["--seq-len"] == "16384"
    assert pairs["--remat"] == "full" and "--bf16" in argv
    for flag in ("--lr", "--warmup-steps", "--attn-impl", "--scan-unroll",
                 "--data", "--tiny"):
        assert flag not in argv
    manifest = harness.load_manifest()
    cell = harness.find(manifest["workloads"], CELL, "workload")
    assert cell["chips"] == 1 and cell["config"] == "granite_4_0_h_micro"
    assert [w["name"] for w in manifest["workloads"]
            if w["config"] == "granite_4_0_h_micro"] == [CELL]
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == MINE
    assert all(m["moves"] == "train_img_per_s" for m in manifest["per_layer"]
               if m["name"] in MINE)
    assert set(readers_module.READERS) == set(MINE)


def _observed(trace, **kw):
    run = _run()
    return {"platform": "tpu", "device_kind": "TPU v5 lite", "chips": 1,
            "config": run.config, "global_batch": 1,
            "granite_shape": {"seq_len": 16384}, "trace": trace,
            "ssm_counters": {"jimm_ssm_calls_total": 5.0,
                             "jimm_ssm_chunks_total": 320.0}, **kw}


def test_readers_find_the_scopes_and_the_counters():
    readers = harness.load_readers("layer_metrics")
    o = _observed({"scoped_ops": 900, "kernel_ms": {},
                   "scope_ms": {"ssm": 900.0, "ssm_proj": 200.0,
                                "ssm_scan": 623.2, "ssm_out": 77.0,
                                "attn": 60.0}})
    assert readers["ssm_ms"](o) == 900.0
    assert readers["ssm_proj_ms"](o) == 200.0
    assert readers["ssm_scan_ms"](o) == 623.2
    assert readers["ssm_out_ms"](o) == 77.0
    # 7.79 ms of bytes over 623.2 ms taken
    assert readers["ssm_scan_roofline"](o) == pytest.approx(1.25, abs=0.01)
    # 64 chunks a built scan, nine Mamba-2 layers
    assert readers["ssm_scan_steps"](o) == 576.0
    # the other cells' readers find nothing here
    for name in ("kda_scan_ms", "gqa_attn_ms", "mla_ms"):
        assert readers[name](o) is None, name
    bare = _observed({"scoped_ops": 900, "kernel_ms": {}, "scope_ms": {}},
                     ssm_counters={})
    other_driver = {k: v for k, v in o.items()
                    if k not in ("granite_shape", "ssm_counters")}
    for name in MINE:
        assert readers[name](bare) is None, name
        assert readers[name](other_driver) is None, name
        if name != "ssm_scan_steps":
            # a count is no device number
            assert readers[name]({**o, "platform": "cpu"}) is None, name
    assert readers_module.scope_names("lm_head") == (
        "jvp(lm_head)", "transpose(jvp(lm_head))")
    assert readers_module.scope_names("ssm_scan") == ("ssm_scan",)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_prints_the_contracts_line(trace, capsys):
    from benchmarks import run as bench_run
    from jimm_tpu import obs
    # the registry is the process's: another test's scans would count here
    obs.get_registry("jimm_ssm").reset()
    assert bench_run.main(["--workload", CELL, "--seed", "2147483659",
                           "--seconds", "1", "--trace", str(trace),
                           "--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    parity = next(json.loads(ln)["parity"] for ln in lines
                  if ln.startswith('{"event": "correct"'))
    assert parity["errors"]["scan"] < 1e-4
    runtime = next(json.loads(ln) for ln in lines
                   if ln.startswith('{"event": "resolved_runtime"'))
    assert runtime["runs"] == {
        "run0": {"mixer": "attention", "sparse": False, "layers": 5},
        "run5": {"mixer": "gqa", "sparse": False, "layers": 1},
        "run6": {"mixer": "attention", "sparse": False, "layers": 4}}
    assert runtime["remat_policy"] == "none" and runtime["remat"]
    if trace:
        # 32 tokens in chunks of 16, nine Mamba-2 layers
        assert line["metrics"]["ssm_scan_steps"]["value"] == 18.0
        assert not {"ssm_ms", "ssm_scan_ms", "ssm_scan_roofline",
                    "mfu_pct"} & set(line["metrics"])
        assert {"busy_s", "window_s"} <= set(line["device"])
    else:
        assert set(line["metrics"]) == {"train_img_per_s", "setup_s"}


def test_the_float32_witness_reads_rounding_where_bfloat16_reads_more():
    """The witness's two builds of the program against the reference: the
    float32 one to float32 rounding, the bfloat16 one far above it; forward
    only, the hidden state and the loss alone."""
    from benchmarks.reference import witness_granite
    f32, bf16 = witness_granite.readings(48, 2147483659, tiny=True)
    assert (f32["build"], bf16["build"]) == ("float32_highest", "bfloat16")
    assert set(f32["grads"]) == set(bf16["grads"]) == set(granite.GRAD_LEAVES)
    assert f32["hidden"] < 1e-5 and f32["loss"] < 1e-5
    assert f32["hidden_by_token"]["max"] < 1e-5
    assert max(f32["grads"].values()) < 1e-5
    assert bf16["hidden"] > 1e-3 and min(bf16["grads"].values()) > 1e-3
    forward = witness_granite.readings(48, 2147483659, tiny=True,
                                       grads=False)
    assert [r["grads"] for r in forward] == [{}, {}]
    assert forward[0]["hidden"] == pytest.approx(f32["hidden"], rel=1e-3)
