"""The sparse language model's side of the benchmark: its plain reference
against the program's model at a small size (both float32: the same
mathematics must agree to float32 rounding), the share the reference is given,
the comparison's power to refuse left-out mathematics and a lower precision,
the yardstick's counts, the driver's arguments and the readers of the cell's
device numbers."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

from benchmarks import flops_moe_lm, harness
from benchmarks.drivers import train_moe_lm
from benchmarks.reference import control_lm, kanana, parity_moe_lm
from jimm_tpu import Kanana, preset
from jimm_tpu.cli import _tiny_override

CELL = "kanana_2_30b_a3b.train"
TIGHT = {"hidden": 2e-4, "logits": 2e-4, "loss": 2e-5, "routing": 0.0,
         "grads": dict.fromkeys(kanana.GRAD_LEAVES, 2e-3)}


def _run(seed=3, **kw) -> harness.Run:
    return harness.load_run(harness.REPO, CELL, seed=seed, seconds=10,
                            trace=False, t_process_start=0.0,
                            **{"rehearse": True, **kw})


@pytest.fixture(scope="module")
def model():
    """width 64, 4 heads of 16 + 8 / 16, latent 32, dense MLP 176, 16 experts
    of 48 (4 held, top-2, 2 shared), vocabulary 512, 1 + 2 layers, S = 32,
    float32; every norm scale given weight and the selection biases moved."""
    model = Kanana(_tiny_override(preset("kanana-2-30b-a3b")),
                   rngs=nnx.Rngs(0))
    keys = iter(jax.random.split(jax.random.key(7), 64))
    nnx.update(model, jax.tree.map(
        lambda a: a + 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
        if a.ndim <= 2 and a.shape[-1] in (64, 32) else a,
        nnx.state(model, nnx.Param)))
    bias = model.sparse.blocks.mlp.router_bias
    bias[...] = 0.05 * jax.random.normal(next(keys), bias.shape)
    return model


def _agree(model, monkeypatch, tolerance=None, run=None):
    if tolerance is not None:
        monkeypatch.setattr(kanana, "TOLERANCE", tolerance)
    tokens = jnp.zeros((2, model.config.decoder.seq_len + 1), jnp.int32)
    result = types.SimpleNamespace(model=model, batch=(tokens,))
    with jax.default_matmul_precision("highest"):
        return parity_moe_lm.check_train(run or _run(), result)


def _params(model):
    return kanana.params_from_state(
        nnx.to_pure_dict(nnx.state(model, nnx.Param)),
        model.sparse.blocks.mlp.router_bias[...])


def test_float32_model_agrees_with_the_reference(model, monkeypatch):
    """Final hidden state, logits, loss, the five gradient leaves, and every
    routing choice."""
    agree = _agree(model, monkeypatch, TIGHT)
    assert agree["ok"], agree
    assert np.isfinite(agree["loss_reference"])
    assert set(agree["errors"]["grads"]) == set(kanana.GRAD_LEAVES)
    assert agree["routing_differs_per_layer"] == [0.0, 0.0]
    assert agree["grad_leaves"]["middle_layer_kvb"] \
        == "sparse/blocks/1/attn/kv_b/kernel"


def test_every_gradient_leaf_agrees_with_the_reference(model):
    """Not the five of the chip comparison alone: the whole tree, and with
    the reference routing by itself (nothing forced)."""
    from jimm_tpu.train.trainer import moe_lm_loss_fn
    tokens = jax.random.randint(jax.random.key(5), (2, 33), 0, 512, jnp.int32)
    sizes = parity_moe_lm.sizes_of(model)
    with jax.default_matmul_precision("highest"):
        got = nnx.to_pure_dict(nnx.grad(
            lambda m: moe_lm_loss_fn(m, tokens)[0])(model))
        want = jax.grad(kanana.loss)(_params(model), tokens, sizes)
    for stack in ("dense", "sparse"):
        for p in want[stack]["blocks"]:
            p["mlp"].pop("router_bias", None)
        want[stack]["blocks"] = jax.tree.map(
            lambda *layers: jnp.stack(layers), *want[stack]["blocks"])
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert set(flat_got) == set(flat_want) and len(flat_got) == 27
    for path, g in flat_got.items():
        w = flat_want[path]
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err < 2e-3, (jax.tree_util.keystr(path), err)


def _without(monkeypatch, what):
    """Take one piece of the mathematics out of the reference, or put
    another in its place."""
    if what == "latent_norm":
        plain = kanana.rms_norm
        monkeypatch.setattr(
            kanana, "rms_norm", lambda x, scale, eps:
            x if x.shape[-1] == 32 else plain(x, scale, eps))
    elif what == "rotate_half_for_pairs":
        def rotate_half(x, theta):
            d = x.shape[-1]
            inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
            angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
            angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
            x1, x2 = x[..., :d // 2], x[..., d // 2:]
            return x * jnp.cos(angle) \
                + jnp.concatenate([-x2, x1], -1) * jnp.sin(angle)
        monkeypatch.setattr(kanana, "rotate_pairs", rotate_half)
    elif what in ("softmax_for_sigmoid", "routed_scale",
                  "normalised_over_held_only"):
        def route(x, p, sizes, forced=None):
            logits = kanana.matmul(x, p["router"])
            scores = (jax.nn.softmax(logits, axis=-1)
                      if what == "softmax_for_sigmoid"
                      else jax.nn.sigmoid(logits))
            _, own = jax.lax.top_k(scores + p["router_bias"],
                                   sizes["num_experts_per_tok"])
            chosen = own if forced is None else forced
            picked = jnp.take_along_axis(scores, chosen, axis=-1)
            counted = picked
            if what == "normalised_over_held_only":
                held = (chosen >= sizes["first_expert"]) & (
                    chosen < sizes["first_expert"] + p["gate"].shape[0])
                counted = jnp.where(held, picked, 0.0)
            scale = (1.0 if what == "routed_scale"
                     else sizes["routed_scaling_factor"])
            return chosen, scale * picked / (
                jnp.sum(counted, -1, keepdims=True) + 1e-20), own
        monkeypatch.setattr(kanana, "route", route)
    elif what == "float8_matmuls":  # the control the chip runs, too
        monkeypatch.setattr(kanana, "matmul",
                            control_lm.float8_matmul(kanana.matmul))
    elif what == "int8_matmuls":
        plain = kanana.matmul

        def q(a):  # symmetric per-tensor int8, straight-through gradient
            scale = jnp.max(jnp.abs(a)) / 127.0
            return a + jax.lax.stop_gradient(
                jnp.round(a / scale) * scale - a)
        monkeypatch.setattr(kanana, "matmul", lambda a, b: plain(q(a), q(b)))


@pytest.mark.parametrize("what", [
    "latent_norm", "rotate_half_for_pairs", "softmax_for_sigmoid",
    "routed_scale", "normalised_over_held_only", "float8_matmuls",
    "int8_matmuls"])
def test_the_shipped_limits_refuse(what, model, monkeypatch):
    """The bfloat16 limits of the chip comparison already refuse a reference
    with a piece taken out or exchanged, or computed in a lower precision
    than bfloat16 (against a float32 model, so nothing else differs)."""
    _without(monkeypatch, what)
    agree = _agree(model, monkeypatch)
    assert not agree["ok"], agree["errors"]
    e, tol = agree["errors"], agree["tolerance"]
    over = [k for k in ("hidden", "logits", "loss", "routing")
            if e[k] > tol[k]]
    over += [k for k, v in e["grads"].items() if v > tol["grads"][k]]
    assert over


def test_the_shares_of_the_reference_add_up_to_the_uncut_layer(model):
    """The guide's share test: the routed parts that the four chips of this
    small deployment compute (experts 0-3, 4-7, 8-11, 12-15 of one 16-wide
    router), with the shared experts counted once, are what the reference
    gives for the uncut layer."""
    sizes = parity_moe_lm.sizes_of(model)
    p = _params(model)["sparse"]["blocks"][0]["mlp"]
    keys = jax.random.split(jax.random.key(11), 4)
    whole = {**p, **{name: 0.1 * jax.random.normal(k, (16, *p[name].shape[1:]))
                     for name, k in zip(("gate", "up", "down"), keys)}}
    x = jax.random.normal(keys[3], (2, 32, 64))
    with jax.default_matmul_precision("highest"):
        want, chosen = kanana.moe(x, whole, {**sizes, "first_expert": 0})
        xt = x.reshape(-1, 64)
        s = whole["shared"]
        shared = kanana.swiglu(xt, s["gate"]["kernel"], s["fc1"]["kernel"],
                               s["fc2"]["kernel"]).reshape(x.shape)
        total = shared
        for first in (0, 4, 8, 12):
            share = {**whole, **{name: whole[name][first:first + 4]
                                 for name in ("gate", "up", "down")}}
            y, own = kanana.moe(x, share, {**sizes, "first_expert": first})
            assert (own == chosen).all()
            total = total + (y - shared)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    # and every token does choose two of the sixteen
    assert chosen.shape == (64, 2) and len(np.unique(chosen)) > 8


def test_attention_in_blocks_is_the_same_attention():
    keys = jax.random.split(jax.random.key(12), 3)
    q, k = (jax.random.normal(key, (2, 64, 4, 24)) for key in keys[:2])
    v = jax.random.normal(keys[2], (2, 64, 4, 16))
    blocked = kanana.in_blocks(kanana.causal_attention, 2, 16, jax.checkpoint)

    def f(attend):
        return lambda q, k, v: jnp.sum(jnp.sin(attend(q, k, v)))

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(blocked(q, k, v),
                                   kanana.causal_attention(q, k, v),
                                   rtol=1e-5, atol=1e-6)
        got = jax.grad(f(blocked), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(f(kanana.causal_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want, strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_the_reference_is_plain_and_shares_nothing_with_the_program():
    text = (harness.BENCH / "reference" / "kanana.py").read_text()
    for word in ("jimm_tpu", "lax.scan", "pallas", "nnx", "flax"):
        assert f"import {word}" not in text and f"from {word}" not in text
    assert "jax.checkpoint(" not in text and "lax.scan(" not in text
    assert "ragged_dot" not in text.split('"""')[2] \
        and "argsort" not in text.split('"""')[2]
    assert 'default_matmul_precision("highest")' in text
    # position 0 is not turned; element 0 turns with element 1
    x = jnp.arange(16.0).reshape(1, 2, 1, 8)
    r = kanana.rotate_pairs(x, 1e6)
    np.testing.assert_allclose(r[0, 0], x[0, 0])
    np.testing.assert_allclose(r[0, 1, 0, 0], 8 * np.cos(1) - 9 * np.sin(1),
                               rtol=1e-5)
    np.testing.assert_allclose(r[0, 1, 0, 1], 9 * np.cos(1) + 8 * np.sin(1),
                               rtol=1e-5)
    # a forced choice replaces the router's own in the result, not in what
    # it reports
    p = {"router": jnp.eye(4), "router_bias": jnp.zeros((4,))}
    sizes = {"num_experts_per_tok": 2, "routed_scaling_factor": 2.0}
    xt = jnp.asarray([[3.0, 2.0, 1.0, 0.0]])
    chosen, weights, own = kanana.route(xt, p, sizes)
    assert chosen.tolist() == [[0, 1]] and own.tolist() == [[0, 1]]
    np.testing.assert_allclose(jnp.sum(weights), 2.0, rtol=1e-6)
    forced = jnp.asarray([[2, 3]])
    chosen, weights, own = kanana.route(xt, p, sizes, forced)
    assert chosen.tolist() == [[2, 3]] and own.tolist() == [[0, 1]]


def test_configuration_file_holds_the_catalogs_numbers():
    config = json.loads((harness.BENCH / "configs"
                         / "kanana_2_30b_a3b.json").read_text())
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 2,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 48,
        "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-6, "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 1000000, "routed_scaling_factor": 2.448,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    assert {k: config[k] for k in published} == published
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (6, 16, 16032)
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"]["n_routed_experts"] == 128
    assert config["published"]["vocab_size"] == 128256 == 8 * 16032
    assert config["published"]["num_hidden_layers"] == 48
    assert {"dtype", "weights", "gamma", "training_seq_len", "held_experts",
            "rope_interleave"} <= set(config["assumed"])
    assert "eight chips share each layer" in config["deployment"]
    assert config["reference"] == "benchmarks/reference/kanana.py"
    manifest = harness.load_manifest()
    entry = harness.find(manifest["configs"], "kanana_2_30b_a3b", "config")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # the program builds what the file says
    run = _run(rehearse=False)
    cfg = preset(config["preset"])
    import dataclasses
    cut = dataclasses.replace(cfg, decoder=dataclasses.replace(
        cfg.decoder, depth=config["num_layers"],
        seq_len=run.cell["traffic_params"]["seq_len"]))
    assert cfg.bias_update_rate == config["assumed"]["bias_update_rate"]
    built = nnx.eval_shape(lambda: Kanana(cut, rngs=nnx.Rngs(0)))
    assert parity_moe_lm.check_sizes(run, built) == []
    n = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(
        nnx.state(built, nnx.Param)))
    # 64.1 M (dense layer) + 5 x 111.5 M + 65.7 M (embedding and head slices)
    assert n == 687_502_336


def test_yardstick_counts_the_issues_numbers():
    config = _run().config
    assert flops_moe_lm.train_step_flops(config, 2, 8192) == pytest.approx(
        53.7e12, rel=2e-3)
    per_token = flops_moe_lm.fwd_flops_per_token(config, 8192)
    per_layer = {"mla_projections": 52.7e6, "attention_core": 83.9e6}
    for name, want in per_layer.items():
        assert per_token[name] / 6 == pytest.approx(want, rel=2e-3)
    assert per_token["dense_ffn"] == pytest.approx(75.5e6, rel=2e-3)
    assert per_token["shared_experts"] / 5 == pytest.approx(18.9e6, rel=2e-3)
    assert per_token["router"] / 5 == pytest.approx(0.5e6, rel=5e-2)
    assert per_token["held_experts"] / 5 == pytest.approx(7.1e6, rel=5e-3)
    assert per_token["head"] == pytest.approx(65.7e6, rel=2e-3)
    # attention at half of S^2 and the unpadded widths, 192 and 128
    fwd = flops_moe_lm.mla_flash_cost(2, 8192, 32, 192, 128, backward=False)
    bwd = flops_moe_lm.mla_flash_cost(2, 8192, 32, 192, 128, backward=True)
    assert fwd["flops"] == 2 * 2 * 32 * 8192 * 8192 * (192 + 128) / 2
    assert bwd["flops"] == 2 * fwd["flops"] and bwd["bytes"] == 2 * fwd["bytes"]
    assert fwd["bytes"] == 2 * 32 * 8192 * 2 * (2 * 192 + 2 * 128)
    assert 6 * 3 * fwd["flops"] == pytest.approx(24.7e12, rel=5e-3)
    assert flops_moe_lm.mla_flash_least_seconds(
        config, 2, 8192, "TPU v5 lite") == pytest.approx(125.6e-3, rel=2e-3)
    # the grouped products at the step's own count of rows, never a buffer's
    one = flops_moe_lm.grouped_products_cost(12288, config, backward=False)
    assert one["flops"] == 2 * 12288 * 3 * 2048 * 768
    assert flops_moe_lm.grouped_products_least_seconds(
        2 * 5 * 12288, config, "TPU v5 lite") > \
        flops_moe_lm.grouped_products_least_seconds(
            5 * 12288, config, "TPU v5 lite")
    # the program's own copy counts the same
    import dataclasses

    from jimm_tpu.train.metrics import train_step_flops
    cfg = preset("kanana-2-30b-a3b")
    cut = dataclasses.replace(cfg, decoder=dataclasses.replace(cfg.decoder,
                                                               depth=6))
    assert train_step_flops(cut, 2) == pytest.approx(
        flops_moe_lm.train_step_flops(config, 2, 8192), rel=1e-12)


def test_driver_takes_depth_and_length_from_the_files():
    run = _run(rehearse=False)
    argv = train_moe_lm.cli_argv(run, 20, "m.jsonl")
    pairs = dict(zip(argv, argv[1:]))
    assert pairs["--preset"] == "kanana-2-30b-a3b"
    assert pairs["--batch-size"] == "2"
    assert pairs["--num-layers"] == "6" and pairs["--seq-len"] == "8192"
    assert pairs["--remat"] == "dots" and "--bf16" in argv
    assert "--lr" not in pairs and "--warmup-steps" not in pairs
    for flag in ("--attn-impl", "--ln-impl", "--scan-unroll", "--data",
                 "--tiny"):
        assert flag not in argv
    rehearsal = train_moe_lm.cli_argv(_run(), 12, "m.jsonl")
    assert "--tiny" in rehearsal and "--num-layers" not in rehearsal
    window = train_moe_lm.planned_steps(run) - train_moe_lm.WARMUP_STEPS \
        - train_moe_lm.TRACED_STEPS
    assert 10 <= window <= 20
    # one configuration, one cell and nine per-layer metrics, all appended
    manifest = harness.load_manifest()
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["workloads"][-1]["chips"] == 1
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["mla_ms", "moe_ms", "moe_route_ms", "moe_experts_ms",
                    "lm_head_ms", "mla_flash_ms", "mla_flash_roofline",
                    "moe_experts_roofline", "moe_held_rows"]
    assert [m["name"] for m in manifest["per_layer"]][-9:] == mine


def _observed(trace, **kw):
    run = _run()
    return {"platform": "tpu", "device_kind": "TPU v5 lite", "chips": 1,
            "config": run.config, "global_batch": 2,
            "moe_lm_shape": {"seq_len": 8192}, "flash_calls": 48,
            "flash_kernels": ("mla/pallas_call",), "trace": trace,
            "window_rows": [{"moe_held_rows": 61000.0},
                            {"moe_held_rows": 61880.0}], **kw}


def test_readers_find_the_scopes_and_the_kernels():
    readers = harness.load_readers("layer_metrics")
    o = _observed({"scoped_ops": 900,
                   "kernel_ms": {"mla/pallas_call": 502.4},
                   "scope_ms": {"mla": 600.0, "moe": 110.0, "moe_route": 50.0,
                                "moe_experts": 35.3, "moe_shared": 20.0,
                                "jvp(lm_head)": 10.0,
                                "transpose(jvp(lm_head))": 25.0}})
    assert readers["mla_ms"](o) == 600.0 and readers["moe_ms"](o) == 110.0
    assert readers["moe_route_ms"](o) == 50.0
    assert readers["moe_experts_ms"](o) == 35.3
    assert readers["lm_head_ms"](o) == 35.0
    assert readers["mla_flash_ms"](o) == 502.4
    # 125.6 ms at the peaks over 502.4 ms taken
    assert readers["mla_flash_roofline"](o) == pytest.approx(25.0, abs=0.1)
    assert readers["moe_held_rows"](o) == 61440.0
    least = flops_moe_lm.grouped_products_least_seconds(
        61440.0, o["config"], "TPU v5 lite")
    assert readers["moe_experts_roofline"](o) == pytest.approx(
        100 * least * 1e3 / 35.3)
    assert 0 < readers["moe_experts_roofline"](o) < 100
    # a program without the scopes, the kernels or the counter (the parent),
    # another driver's observations, another platform: nothing, and no raise
    bare = _observed({"scoped_ops": 900, "kernel_ms": {}, "scope_ms": {}},
                     flash_calls=0, window_rows=[{"loss": 1.0}])
    other_driver = {k: v for k, v in o.items() if k != "moe_lm_shape"}
    for name in ("mla_ms", "moe_ms", "moe_route_ms", "moe_experts_ms",
                 "lm_head_ms", "mla_flash_ms", "mla_flash_roofline",
                 "moe_experts_roofline", "moe_held_rows"):
        assert readers[name](bare) is None, name
        if name != "moe_held_rows":  # a count is no device number
            assert readers[name]({**o, "platform": "cpu"}) is None, name
            assert readers[name](other_driver) is None, name
    assert train_moe_lm.moe_lm.scope_names("lm_head") == (
        "jvp(lm_head)", "transpose(jvp(lm_head))")
    assert train_moe_lm.moe_lm.scope_names("moe_route") == ("moe_route",)


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
    assert len(parity["routing_differs_per_layer"]) == 2
    if trace:
        assert "moe_held_rows" in line["metrics"]
        assert not {"mla_ms", "moe_ms", "mla_flash_roofline",
                    "moe_experts_roofline", "mfu_pct"} & set(line["metrics"])
        assert {"busy_s", "window_s"} <= set(line["device"])
    else:
        assert set(line["metrics"]) == {"train_img_per_s", "setup_s"}


def test_the_control_reads_the_comparison_twice(capsys):
    """`control_moe_lm.py`: the cell once, then its comparison with every
    matmul operand of the reference rounded to float8, which the shipped
    limits refuse."""
    from benchmarks.reference import control_moe_lm
    assert control_moe_lm.main(["--workload", CELL, "--seed", "2147483693",
                                "--seconds", "1", "--trace", "0",
                                "--rehearse"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    low = next(ln for ln in lines if ln.get("event") == "lowp_reading")
    assert low["refused"] is True
    assert low["errors"]["hidden"] > low["tolerance"]["hidden"]
    assert lines[-1]["correct"] is True
    assert parity_moe_lm.check_train.__module__ == parity_moe_lm.__name__
