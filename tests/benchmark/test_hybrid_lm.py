"""The benchmark's side of the hybrid language model (linear-attention layers
with a gated delta-rule state beside latent-attention ones, a mixture of
experts behind both): its plain reference against the program's model at a
small size (both float32: the same mathematics must agree to float32
rounding), the share the reference is given, the comparison's power to refuse a
lower precision and a state kept in bfloat16, the yardstick's counts, the
driver that takes its modules from the cell's file, and the readers of the
cell's device numbers."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

from benchmarks import flops_hybrid_lm, harness
from benchmarks.drivers import train_hybrid_lm
from benchmarks.layer_metrics import hybrid_lm
from benchmarks.reference import control_lm, kimi_linear, parity_hybrid_lm
from jimm_tpu import KimiLinear, preset
from jimm_tpu.cli import _tiny_override

CELL = "kimi_linear_48b_a3b.train"
TIGHT = {"hidden": 2e-4, "logits": 2e-4, "loss": 2e-5, "routing": 0.0,
         "scan": 2e-5, "update": 2e-3, "moment": 2e-3,
         "grads": dict.fromkeys(kimi_linear.GRAD_LEAVES, 2e-3)}
MINE = ["kda_ms", "kda_proj_ms", "kda_scan_ms", "kda_scan_roofline",
        "kda_scan_steps", "hybrid_mla_ms", "hybrid_mla_flash_ms",
        "hybrid_mla_flash_roofline", "hybrid_moe_ms", "hybrid_experts_ms",
        "hybrid_lm_head_ms", "hybrid_held_rows", "kda_out_ms",
        "hybrid_route_ms", "hybrid_shared_ms", "hybrid_experts_roofline"]


def _run(seed=3, **kw) -> harness.Run:
    return harness.load_run(harness.REPO, CELL, seed=seed, seconds=10,
                            trace=False, t_process_start=0.0,
                            **{"rehearse": True, **kw})


@pytest.fixture(scope="module")
def model():
    """width 64, KDA of 4 heads of 16 (chunks of 16 under 32 tokens), latent
    attention of 4 heads (16 + 8, 16; latent 32), dense MLP 176, 16 experts of
    48 (4 held, top-2, 1 shared), vocabulary 512, published layers 1-5 (KDA,
    KDA, KDA, full, KDA), float32; every vector-shaped weight (norm scales,
    taps, ``A_log``, ``dt_bias``) given weight and the selection biases
    moved."""
    model = KimiLinear(_tiny_override(preset("kimi-linear-48b-a3b")),
                       rngs=nnx.Rngs(0))
    keys = iter(jax.random.split(jax.random.key(7), 256))
    nnx.update(model, jax.tree.map(
        lambda a: a + 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
        if a.ndim <= 2 and a.shape[-1] in (64, 32, 16, 4) else a,
        nnx.state(model, nnx.Param)))
    for run in model.sparse_runs():
        bias = run.blocks.mlp.router_bias
        bias[...] = 0.05 * jax.random.normal(next(keys), bias.shape)
    return model


@pytest.fixture(scope="module")
def trained(model):
    """What ``cli.train`` hands the comparison after a run of three steps:
    the model, its optimizer (the CLI's AdamW: the family's rate, ramped over
    the run less one step, clipped), the compiled step and the last batch."""
    from jimm_tpu.train.trainer import (OptimizerConfig, make_lm_train_step,
                                        make_optimizer)
    steps = 3
    optimizer = make_optimizer(model, OptimizerConfig(
        learning_rate=1e-4, weight_decay=1e-4, warmup_steps=steps - 1,
        total_steps=steps))
    step_fn = make_lm_train_step("kimi", donate=True)
    tokens = jax.random.randint(
        jax.random.key(11), (2, model.config.decoder.seq_len + 1), 0,
        model.config.decoder.vocab_size, jnp.int32)
    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            step_fn(model, optimizer, tokens)
    return types.SimpleNamespace(model=model, optimizer=optimizer,
                                 step_fn=step_fn, mesh=None, rules=None,
                                 batch=(tokens,))


def _agree(trained, monkeypatch, tolerance=None, run=None, **changed):
    # the cell's own limits, not a rehearsal's: the float32 model meets them
    monkeypatch.setattr(kimi_linear, "REHEARSAL_TOLERANCE",
                        tolerance or kimi_linear.TOLERANCE)
    result = types.SimpleNamespace(**{**vars(trained), **changed})
    with jax.default_matmul_precision("highest"):
        return parity_hybrid_lm.check_train(run or _run(), result)


def _params(model):
    return kimi_linear.params_from_state(
        nnx.to_pure_dict(nnx.state(model, nnx.Param)), model.router_bias())


def test_float32_model_agrees_with_the_reference(trained, monkeypatch):
    """Final hidden state, logits, loss, the eight gradient leaves, every
    routing choice, the chunked scan alone, and what the compiled step did to
    the parameters and to Adam's first moment."""
    agree = _agree(trained, monkeypatch, TIGHT)
    assert agree["ok"], agree
    step = agree["timed_step"]
    assert step["steps"] == 3 and step["rate"] == 1e-4  # the run's last rate
    assert int(trained.optimizer.step[...]) == 3        # set back, stepped
    assert 0 < agree["errors"]["update"] < 2e-3
    assert 0 < agree["errors"]["moment"] < 2e-3
    # (of the embedding, the rows of the ids the batch holds)
    assert min(step["moved_share"].values()) > 0.1
    assert np.isfinite(agree["loss_reference"])
    assert set(agree["errors"]["grads"]) == set(kimi_linear.GRAD_LEAVES)
    assert agree["routing_differs_per_layer"] == [0.0, 0.0, 0.0, 0.0]
    # published layers 2-3 are the first sparse KDA run, layer 4 the full one
    assert agree["grad_leaves"]["kda_q"] == "run1/blocks/0/attn/q/kernel"
    assert agree["grad_leaves"]["kda_dt_bias"] \
        == "run1/blocks/0/attn/dt_bias"
    assert agree["grad_leaves"]["kda_conv"] == "run1/blocks/0/attn/k_conv"
    assert agree["grad_leaves"]["mla_kvb"] == "run3/blocks/0/attn/kv_b/kernel"
    assert agree["grad_leaves"]["expert_down"] == "run1/blocks/0/mlp/down"


def test_every_gradient_leaf_agrees_with_the_reference(model):
    """Not the eight of the chip comparison alone: the whole tree, and with
    the reference routing by itself (nothing forced)."""
    from jimm_tpu.train.trainer import moe_lm_loss_fn
    tokens = jax.random.randint(jax.random.key(5), (2, 33), 0, 512, jnp.int32)
    sizes = parity_hybrid_lm.sizes_of(model)
    with jax.default_matmul_precision("highest"):
        got = nnx.to_pure_dict(nnx.grad(
            lambda m: moe_lm_loss_fn(m, tokens)[0])(model))
        want = jax.grad(kimi_linear.loss)(_params(model), tokens, sizes)
    for name in kimi_linear.run_names(want):
        for p in want[name]["blocks"]:
            p["mlp"].pop("router_bias", None)
        want[name]["blocks"] = jax.tree.map(
            lambda *layers: jnp.stack(layers), *want[name]["blocks"])
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert set(flat_got) == set(flat_want)
    # embedding, head, final norm; (KDA 15 + 2 norms + dense 3) + 2 x (KDA 15
    # + 2 + sparse 7) + (MLA 5 + 2 + 7)
    assert len(flat_got) == 3 + 20 + 2 * 24 + 14
    for path, g in flat_got.items():
        w = flat_want[path]
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err < 2e-3, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("control, attribute, change, refused_by", [
    ("float8", "matmul", control_lm.float8_matmul,
     ("hidden", "logits", "scan")),
    ("state_bf16", "STATE_BF16", lambda _: True, ("scan",)),
])
def test_the_shipped_limits_refuse(control, attribute, change, refused_by,
                                   trained, monkeypatch):
    """The cell's limits against a reference in the nearest precision below
    the configuration's, and against one whose recurrence keeps its state in
    bfloat16."""
    monkeypatch.setattr(kimi_linear, attribute,
                        change(getattr(kimi_linear, attribute)))
    agree = _agree(trained, monkeypatch)
    assert not agree["ok"], (control, agree["errors"])
    for key in refused_by:
        assert agree["errors"][key] > kimi_linear.TOLERANCE[key], (control,
                                                                   key)


def test_a_step_that_leaves_the_state_as_it_was_reads_one(trained,
                                                          monkeypatch):
    """The comparison steps the program the window timed: one that computes
    everything and updates nothing reads exactly 1 in both numbers of the step
    and is refused by them alone."""
    agree = _agree(trained, monkeypatch,
                   step_fn=lambda model, optimizer, tokens: {"loss": 0.0})
    assert agree["errors"]["update"] == agree["errors"]["moment"] == 1.0
    assert not agree["ok"]
    others = {k: v for k, v in agree["errors"].items()
              if k not in ("update", "moment", "grads")}
    assert all(v <= kimi_linear.TOLERANCE[k] for k, v in others.items())


@pytest.mark.parametrize("count, steps", [(0, 15), (7, 15), (14, 15),
                                          (15, 15), (20, 40), (30, 40),
                                          (39, 40), (0, 1)])
def test_the_references_optimizer_is_the_clis(count, steps):
    """``learning_rate`` and ``adamw_step`` (numpy) against the optimizer
    ``jimm-tpu train`` builds, on a matrix and a vector, clipped and not."""
    from jimm_tpu.cli import LM_FAMILIES
    from jimm_tpu.train.trainer import (OptimizerConfig, make_optimizer,
                                        make_schedule)
    o = kimi_linear.OPTIMIZER
    assert LM_FAMILIES["kimi"] == {"lr": o["lr"],
                                   "warmup_steps": o["warmup_steps"]}
    cfg = OptimizerConfig(
        learning_rate=o["lr"], weight_decay=o["weight_decay"],
        warmup_steps=min(o["warmup_steps"], max(steps - 1, 0)),  # cli.train
        total_steps=steps)
    assert (cfg.b1, cfg.b2, cfg.grad_clip_norm) == (o["b1"], o["b2"],
                                                    o["clip_norm"])
    assert kimi_linear.learning_rate(count, steps) == pytest.approx(
        float(make_schedule(cfg)(count)), rel=1e-5, abs=1e-12)
    if count >= steps:
        return

    class Two(nnx.Module):
        def __init__(self, key):
            a, b = jax.random.split(key)
            # small, so that float32 holds ``after - before`` to five digits
            self.matrix = nnx.Param(0.01 * jax.random.normal(a, (8, 4)))
            self.vector = nnx.Param(0.01 * jax.random.normal(b, (4,)))

    module = Two(jax.random.key(count))
    optimizer = make_optimizer(module, cfg)
    scale = 0.05 if count % 2 else 3.0     # under the clip's norm, and over
    for i in range(count + 1):
        grads = jax.tree.map(
            lambda p, i=i: scale * jnp.cos(100.0 * p * (i + 1.0)),
            nnx.state(module, nnx.Param))
        if i == count:
            before = jax.tree.map(np.asarray, nnx.to_pure_dict(
                nnx.state(module, nnx.Param)))
            adam = parity_hybrid_lm.adam_state(optimizer)
            moments = jax.tree.map(np.asarray, (adam["mu"], adam["nu"]))
        optimizer.update(module, grads)
    norm = float(jnp.sqrt(sum(jnp.sum(g * g)
                              for g in jax.tree.leaves(grads))))
    after = parity_hybrid_lm.adam_state(optimizer)["mu"]
    for name, g in nnx.to_pure_dict(grads).items():
        change, moment = kimi_linear.adamw_step(
            before[name], moments[0][name], moments[1][name], np.asarray(g),
            count=count, steps=steps, grad_norm=norm)
        np.testing.assert_allclose(
            change, np.asarray(getattr(module, name)[...]) - before[name],
            rtol=2e-4, atol=2e-9)
        np.testing.assert_allclose(moment, after[name], rtol=1e-5, atol=1e-9)
    # the decay is on matrices alone (1e-8 of a weight a step: under float32's
    # sight in the line above)
    zero, ones = np.zeros((2, 2), np.float32), np.ones((2, 2), np.float32)
    rate = kimi_linear.learning_rate(count, steps)
    np.testing.assert_allclose(kimi_linear.adamw_step(
        ones, zero, zero, zero, count=count, steps=steps, grad_norm=0.0)[0],
        -rate * o["weight_decay"] * ones, rtol=1e-6)
    assert not kimi_linear.adamw_step(
        ones[0], zero[0], zero[0], zero[0], count=count, steps=steps,
        grad_norm=0.0)[0].any()


def test_the_shares_of_the_reference_add_up_to_the_uncut_layer(model):
    """Four references of 4 experts each and one router: the routed parts,
    with the shared expert counted once, are the reference's uncut layer."""
    p = dict(kimi_linear.layers(_params(model))[1]["mlp"])
    sizes = parity_hybrid_lm.sizes_of(model)
    keys = jax.random.split(jax.random.key(11), 4)
    whole = {**p, **{name: 0.3 * jax.random.normal(k, (16, *p[name].shape[1:]))
                     for name, k in zip(("gate", "up", "down"), keys)}}
    x = jax.random.normal(keys[3], (2, 32, 64))
    with jax.default_matmul_precision("highest"):
        want, chosen = kimi_linear.moe(x, whole, {**sizes, "first_expert": 0})
        xt = x.reshape(-1, 64)
        shared = kimi_linear.swiglu(
            xt, p["shared"]["gate"]["kernel"], p["shared"]["fc1"]["kernel"],
            p["shared"]["fc2"]["kernel"]).reshape(x.shape)
        total = shared
        for first in (0, 4, 8, 12):
            share = {**whole, **{name: whole[name][first:first + 4]
                                 for name in ("gate", "up", "down")}}
            y, chosen_here = kimi_linear.moe(
                x, share, {**sizes, "first_expert": first})
            assert (chosen_here == chosen).all()
            total = total + (y - shared)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_the_recurrence_in_blocks_is_the_same_recurrence():
    """``wrap`` and ``STATE_BLOCK`` are identities of what is computed, and
    attention in blocks is the same attention."""
    keys = jax.random.split(jax.random.key(0), 5)
    shape = (1, 256, 2, 8)
    q, k, v = (jax.random.normal(key, shape) for key in keys[:3])
    g = -jax.nn.softplus(jax.random.normal(keys[3], shape))
    b = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    plain = kimi_linear.delta_rule(q, k, v, g, b)
    np.testing.assert_allclose(
        kimi_linear.delta_rule(q, k, v, g, b, jax.checkpoint), plain,
        rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        kimi_linear.delta_rule(q[:, :100], k[:, :100], v[:, :100],
                               g[:, :100], b[:, :100]), plain[:, :100],
        rtol=1e-5, atol=1e-6)
    blocked = kimi_linear.in_blocks(kimi_linear.causal_attention, 1, 64,
                                    jax.checkpoint)
    np.testing.assert_allclose(blocked(q, k, v),
                               kimi_linear.causal_attention(q, k, v),
                               rtol=2e-5, atol=2e-6)


def test_the_reference_is_plain_and_shares_nothing_with_the_program():
    source = (harness.BENCH / "reference" / "kimi_linear.py").read_text()
    code = source.split('"""', 2)[2]
    for word in ("import jimm_tpu", "from jimm_tpu", "flax", "pallas",
                 "solve_triangular", "cumsum",
                 "argsort", "ragged", "shard"):
        assert word not in code, word
    assert "jax.lax.scan" in code          # the state, token by token
    assert kimi_linear.matmul is jnp.matmul and not kimi_linear.STATE_BF16
    for line in ("S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}"
                 " + b_t k_t v_t^T", "o_t = S_t^T q_t",
                 "y_t = sum_{i=0..3} w_i u_{t-3+i}"):
        assert line in source, line
    program = (harness.REPO / "jimm_tpu" / "nn" / "kda.py").read_text()
    assert "S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T" \
        in program


def test_configuration_file_holds_the_catalogs_numbers():
    """Every number of the catalog row's ``config`` under the same key, but
    for the keys in ``reduced``; nested groups whole; no width reduced."""
    import pathlib
    catalog = pathlib.Path("/opt/skills/guides/model-configs/"
                           "architectures.jsonl")
    run = _run(rehearse=False)
    config = run.config
    assert config["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    assert config["published"]["num_experts"] == 256
    assert config["published"]["vocab_size"] == 163840
    assert config["published"]["num_hidden_layers"] \
        == config["num_hidden_layers"] == 27
    assert (config["num_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 16, 163840 // 8)
    assert config["assumed"]["gate_rank"] == 128
    assert "16 chips" in config["deployment"]
    if catalog.is_file():
        row = next(json.loads(line) for line in catalog.read_text()
                   .splitlines() if "Kimi-Linear-48B-A3B" in line)
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
    # the preset is the file's model
    built = nnx.eval_shape(lambda: KimiLinear(rngs=nnx.Rngs(0)))
    assert parity_hybrid_lm.check_sizes(run, built) == []
    assert sum(flops_hybrid_lm.parameter_count(config).values()) \
        == 828_925_824


def test_yardstick_counts_the_cells_numbers():
    config = _run(rehearse=False).config
    assert flops_hybrid_lm.layer_mixers(config) \
        == ["kda", "kda", "kda", "mla", "kda"]
    per = flops_hybrid_lm.fwd_flops_per_token(config, 16384)
    step = {k: 3 * v * 16384 / 1e12 for k, v in per.items()}
    assert step["kda_projections"] == pytest.approx(15.54, abs=0.01)
    assert step["kda_recurrence"] == pytest.approx(0.618, abs=0.001)
    assert step["attention_core"] == pytest.approx(8.246, abs=0.001)
    assert flops_hybrid_lm.train_step_flops(config, 1, 16384) / 1e12 \
        == pytest.approx(42.57, abs=0.01)
    parts = flops_hybrid_lm.parameter_count(config)
    assert parts["kda"] / 4 == pytest.approx(39.51e6, rel=1e-3)   # ISSUE 38
    assert parts["mla"] == pytest.approx(29.11e6, rel=1e-3)
    # the scan's least time is bytes: 9.19 ms a step at the measured 576 GB/s
    kind = "TPU v5 lite"
    least = flops_hybrid_lm.kda_scan_least_seconds(config, 1, 16384, kind)
    assert least * 1e3 == pytest.approx(9.19, abs=0.02)
    fwd = flops_hybrid_lm.kda_scan_cost(1, 16384, 32, 128, backward=False)
    assert fwd["flops"] == 3 * 2 * 128 * 128 * 32 * 16384
    assert fwd["bytes"] == 16384 * 32 * (5 * 128 + 1) * 2
    # one full layer's causal call at 16,384 tokens: what two of kanana's cost
    assert flops_hybrid_lm.mla_flash_least_seconds(config, 1, 16384, kind) \
        * 1e3 == pytest.approx(41.86, abs=0.02)


def test_driver_takes_its_modules_from_the_cells_file():
    run = _run(rehearse=False)
    traffic = run.cell["traffic_params"]
    assert run.cell["driver"] == "train_hybrid_lm"
    assert (traffic["flops_module"], traffic["parity_module"],
            traffic["reader_module"]) == ("flops_hybrid_lm",
                                          "parity_hybrid_lm", "hybrid_lm")
    assert traffic["flash_kernels"] == ["mla/pallas_call"]
    # nothing of a family is written into the driver
    source = (harness.BENCH / "drivers" / "train_hybrid_lm.py").read_text()
    code = source.split('"""', 2)[2]
    for word in ("kimi", "hybrid_lm", "kda", "mla"):
        assert word not in code.replace('"jimm_kda_"', "") \
            .replace('("kda", "mla", "gqa")', ""), word
    argv = train_hybrid_lm.cli_argv(run, 20, "m.jsonl")
    pairs = dict(zip(argv, argv[1:]))
    assert pairs["--preset"] == "kimi-linear-48b-a3b"
    assert pairs["--batch-size"] == "1"
    assert pairs["--num-layers"] == "5" and pairs["--seq-len"] == "16384"
    assert pairs["--remat"] == "dots" and "--bf16" in argv
    assert "--lr" not in pairs and "--warmup-steps" not in pairs
    for flag in ("--attn-impl", "--ln-impl", "--scan-unroll", "--data",
                 "--tiny"):
        assert flag not in argv
    rehearsal = train_hybrid_lm.cli_argv(_run(), 12, "m.jsonl")
    assert "--tiny" in rehearsal and "--num-layers" not in rehearsal
    assert "--bf16" not in rehearsal and "--remat" in rehearsal
    window = train_hybrid_lm.planned_steps(run) \
        - train_hybrid_lm.WARMUP_STEPS - train_hybrid_lm.TRACED_STEPS
    assert window == 8     # a step takes over a second: 10 s hold eight
    # one configuration, one cell and sixteen per-layer metrics, each listing
    # the cell alone
    manifest = harness.load_manifest()
    cell = harness.find(manifest["workloads"], CELL, "workload")
    assert cell["chips"] == 1 and cell["config"] == "kimi_linear_48b_a3b"
    assert [w["name"] for w in manifest["workloads"]
            if w["config"] == "kimi_linear_48b_a3b"] == [CELL]
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == MINE
    assert all(m["moves"] == "train_img_per_s" for m in manifest["per_layer"]
               if m["name"] in MINE)
    assert set(hybrid_lm.READERS) == set(MINE)


def test_the_setup_entries_keep_their_readers_and_their_order():
    """What `test_setup_timeline.py::
    test_the_module_has_one_reader_per_manifest_entry` asserts, less its one
    line on the END of `per_layer`, which this cell's appended entries end
    (`tests/conftest.py::_LAPSED`): that file is not this PR's to edit, so its
    other assertions run here until a `benchmark` PR drops the line there and
    this test with it."""
    from benchmarks.layer_metrics import setup_timeline
    import test_setup_timeline as older
    manifest = harness.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    mine = [m for m in manifest["per_layer"] if m["moves"] == "setup_s"]
    assert [m["name"] for m in mine] == list(older.EXPECTED)
    assert set(setup_timeline.READERS) == set(older.EXPECTED) \
        == {*older.PARTS, *older.INSIDE}
    first = names.index(mine[0]["name"])
    assert manifest["per_layer"][first:first + len(mine)] == mine
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["moves"] != "setup_s"}
    for m in mine:
        assert "workloads" not in m, "every cell reports setup_s"
        assert m["better"] == "lower" and m["layer"] in layers
        assert m["unit"] == ("s" if m["name"].endswith("_s") else "count")
    # and behind them this cell's twelve, appended
    assert names[first + len(mine):] == MINE


def _observed(trace, **kw):
    run = _run()
    return {"platform": "tpu", "device_kind": "TPU v5 lite", "chips": 1,
            "config": run.config, "global_batch": 1,
            "moe_lm_shape": {"seq_len": 16384}, "flash_calls": 50,
            "flash_kernels": ("mla/pallas_call",), "trace": trace,
            "kda_counters": {"jimm_kda_calls_total": 6.0,
                             "jimm_kda_chunks_total": 1536.0},
            "window_rows": [{"moe_held_rows": 32000.0},
                            {"moe_held_rows": 33536.0}], **kw}


def test_readers_find_the_scopes_the_kernels_and_the_counters():
    readers = harness.load_readers("layer_metrics")
    o = _observed({"scoped_ops": 900,
                   "kernel_ms": {"mla/pallas_call": 83.72},
                   "scope_ms": {"kda": 400.0, "kda_proj": 150.0,
                                "kda_scan": 183.8, "kda_out": 60.0,
                                "mla": 120.0, "moe": 100.0,
                                "moe_experts": 30.0, "moe_route": 48.0,
                                "moe_shared": 17.0, "attn": 7.0,
                                "jvp(lm_head)": 10.0,
                                "transpose(jvp(lm_head))": 21.0}})
    assert readers["kda_ms"](o) == 400.0
    assert readers["kda_proj_ms"](o) == 150.0
    assert readers["kda_scan_ms"](o) == 183.8
    # 9.19 ms of bytes over 183.8 ms taken
    assert readers["kda_scan_roofline"](o) == pytest.approx(5.0, abs=0.02)
    # 256 chunks a built scan, four KDA layers
    assert readers["kda_scan_steps"](o) == 1024.0
    assert readers["hybrid_mla_ms"](o) == 120.0
    assert readers["hybrid_mla_flash_ms"](o) == 83.72
    # 41.86 ms at the peaks over 83.72 ms taken
    assert readers["hybrid_mla_flash_roofline"](o) == pytest.approx(50.0,
                                                                     abs=0.1)
    assert readers["hybrid_moe_ms"](o) == 100.0
    assert readers["hybrid_experts_ms"](o) == 30.0
    assert readers["hybrid_lm_head_ms"](o) == 31.0
    assert readers["hybrid_held_rows"](o) == 32768.0
    assert readers["kda_out_ms"](o) == 60.0
    assert readers["hybrid_route_ms"](o) == 48.0
    assert readers["hybrid_shared_ms"](o) == 17.0
    # 7.07 ms at the peaks (32,768 rows over four sparse layers of 16 held
    # experts, 2304 x 1024) over 30 ms taken
    assert readers["hybrid_experts_roofline"](o) == pytest.approx(23.6,
                                                                   abs=0.3)
    for name in ("kda_scan_roofline", "hybrid_mla_flash_roofline",
                 "hybrid_experts_roofline"):
        assert 0 < readers[name](o) < 100
    # the grouped-query cell's readers find nothing here
    for name in ("gqa_attn_ms", "sparse_ffn_ms", "gqa_flash_roofline"):
        assert readers[name](o) is None, name
    # a program without the scopes, the kernels or the counters (the parent),
    # another driver's observations, another platform: nothing, and no raise
    bare = _observed({"scoped_ops": 900, "kernel_ms": {}, "scope_ms": {}},
                     flash_calls=0, window_rows=[{"loss": 1.0}],
                     kda_counters={})
    other_driver = {k: v for k, v in o.items()
                    if k not in ("moe_lm_shape", "kda_counters",
                                 "window_rows")}
    for name in MINE:
        assert readers[name](bare) is None, name
        assert readers[name](other_driver) is None, name
        if name not in ("hybrid_held_rows", "kda_scan_steps"):
            # a count is no device number
            assert readers[name]({**o, "platform": "cpu"}) is None, name
    assert hybrid_lm.scope_names("lm_head") == ("jvp(lm_head)",
                                                "transpose(jvp(lm_head))")
    assert hybrid_lm.scope_names("kda_scan") == ("kda_scan",)
    assert hybrid_lm.scope_names("mla") == ("mla",)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_prints_the_contracts_line(trace, capsys):
    from benchmarks import run as bench_run
    assert bench_run.main(["--workload", CELL, "--seed", "2147483659",
                           "--seconds", "1", "--trace", str(trace),
                           "--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    line = json.loads(lines[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    parity = next(json.loads(ln)["parity"] for ln in lines
                  if ln.startswith('{"event": "correct"'))
    assert len(parity["routing_differs_per_layer"]) == 4
    assert parity["errors"]["scan"] < 1e-4
    runtime = next(json.loads(ln) for ln in lines
                   if ln.startswith('{"event": "resolved_runtime"'))
    assert runtime["runs"] == {
        "run0": {"mixer": "kda", "sparse": False, "layers": 1},
        "run1": {"mixer": "kda", "sparse": True, "layers": 2},
        "run3": {"mixer": "mla", "sparse": True, "layers": 1},
        "run4": {"mixer": "kda", "sparse": True, "layers": 1}}
    assert runtime["program_counters"]["jimm_kda_calls_total"] >= 3
    if trace:
        assert {"hybrid_held_rows", "kda_scan_steps"} <= set(line["metrics"])
        assert line["metrics"]["kda_scan_steps"]["value"] == 8.0
        assert not {"kda_ms", "kda_scan_ms", "kda_scan_roofline",
                    "hybrid_mla_flash_roofline", "mfu_pct"} \
            & set(line["metrics"])
        assert {"busy_s", "window_s"} <= set(line["device"])
    else:
        assert set(line["metrics"]) == {"train_img_per_s", "setup_s"}
