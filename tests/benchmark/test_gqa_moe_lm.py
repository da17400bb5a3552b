"""The benchmark's side of the sparse language model with grouped-query,
window-and-full attention: its plain reference against the program's model at
a small size (both float32: the same mathematics must agree to float32
rounding), the share the reference is given, the comparison's power to refuse
left-out mathematics (the window, the missing rotary of a full layer) and a
lower precision, the yardstick's counts, the driver's arguments and the
readers of the cell's device numbers."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

from benchmarks import flops_gqa_moe_lm, harness
from benchmarks.drivers import train_gqa_moe_lm
from benchmarks.reference import control_lm, parity_gqa_moe_lm, trinity
from jimm_tpu import Trinity, preset
from jimm_tpu.cli import _tiny_override

CELL = "trinity_large.train"
TIGHT = {"hidden": 2e-4, "logits": 2e-4, "loss": 2e-5, "routing": 0.0,
         "grads": dict.fromkeys(trinity.GRAD_LEAVES, 2e-3)}
MINE = ["gqa_attn_ms", "gqa_flash_ms", "window_flash_ms",
        "gqa_flash_roofline", "sparse_ffn_ms", "sparse_experts_ms",
        "sparse_experts_roofline", "sparse_held_rows", "sparse_route_ms",
        "sparse_shared_ms", "sparse_lm_head_ms"]


def _run(seed=3, **kw) -> harness.Run:
    return harness.load_run(harness.REPO, CELL, seed=seed, seconds=10,
                            trace=False, t_process_start=0.0,
                            **{"rehearse": True, **kw})


@pytest.fixture(scope="module")
def model():
    """width 64, 4 heads of 32 over 2 key/value heads, a window of 8 under 32
    tokens, dense MLP 176, 8 experts of 48 (4 held, top-2, 1 shared),
    vocabulary 512, layers 5 | 6 7 8 of the pattern (7 is the full one),
    float32; every norm scale given weight and the selection biases moved."""
    model = Trinity(_tiny_override(preset("trinity-large")), rngs=nnx.Rngs(0))
    keys = iter(jax.random.split(jax.random.key(7), 96))
    nnx.update(model, jax.tree.map(
        lambda a: a + 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
        if a.ndim <= 2 and a.shape[-1] in (64, 32) else a,
        nnx.state(model, nnx.Param)))
    bias = model.sparse.blocks.mlp.router_bias
    bias[...] = 0.05 * jax.random.normal(next(keys), bias.shape)
    return model


def _agree(model, monkeypatch, tolerance=None, run=None):
    # the cell's own limits, not a rehearsal's: the float32 model meets them
    monkeypatch.setattr(trinity, "REHEARSAL_TOLERANCE",
                        tolerance or trinity.TOLERANCE)
    tokens = jnp.zeros((2, model.config.decoder.seq_len + 1), jnp.int32)
    result = types.SimpleNamespace(model=model, batch=(tokens,))
    with jax.default_matmul_precision("highest"):
        return parity_gqa_moe_lm.check_train(run or _run(), result)


def _params(model):
    return trinity.params_from_state(
        nnx.to_pure_dict(nnx.state(model, nnx.Param)),
        model.sparse.blocks.mlp.router_bias[...])


def test_float32_model_agrees_with_the_reference(model, monkeypatch):
    """Final hidden state, logits, loss, the six gradient leaves, and every
    routing choice."""
    agree = _agree(model, monkeypatch, TIGHT)
    assert agree["ok"], agree
    assert np.isfinite(agree["loss_reference"])
    assert set(agree["errors"]["grads"]) == set(trinity.GRAD_LEAVES)
    assert agree["routing_differs_per_layer"] == [0.0, 0.0, 0.0]
    # sparse layers 6, 7, 8: the first windowed one and the full one
    assert agree["grad_leaves"]["window_layer_k"] \
        == "sparse/blocks/0/attn/k/kernel"
    assert agree["grad_leaves"]["full_layer_gate"] \
        == "sparse/blocks/1/attn/gate/kernel"


def test_every_gradient_leaf_agrees_with_the_reference(model):
    """Not the six of the chip comparison alone: the whole tree, and with
    the reference routing by itself (nothing forced)."""
    from jimm_tpu.train.trainer import moe_lm_loss_fn
    tokens = jax.random.randint(jax.random.key(5), (2, 33), 0, 512, jnp.int32)
    sizes = parity_gqa_moe_lm.sizes_of(model)
    with jax.default_matmul_precision("highest"):
        got = nnx.to_pure_dict(nnx.grad(
            lambda m: moe_lm_loss_fn(m, tokens)[0])(model))
        want = jax.grad(trinity.loss)(_params(model), tokens, sizes)
    for stack in ("dense", "sparse"):
        for p in want[stack]["blocks"]:
            p["mlp"].pop("router_bias", None)
        want[stack]["blocks"] = jax.tree.map(
            lambda *layers: jnp.stack(layers), *want[stack]["blocks"])
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert set(flat_got) == set(flat_want) and len(flat_got) == 35
    for path, g in flat_got.items():
        w = flat_want[path]
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err < 2e-3, (jax.tree_util.keystr(path), err)


def _without(monkeypatch, what):
    """Take one piece of the mathematics out of the reference, or put
    another in its place."""
    if what == "window_ignored":          # the chip's mechanism control
        monkeypatch.setattr(trinity, "IGNORE_WINDOW", True)
    elif what == "rope_on_full":          # the chip's other one
        monkeypatch.setattr(trinity, "ROPE_ON_FULL", True)
    elif what == "window_off_by_one":     # i - j <= window
        plain = trinity.visible
        monkeypatch.setattr(
            trinity, "visible", lambda rows, keys, window: plain(
                rows, keys, None if window is None else window + 1))
    elif what == "qk_norm":
        plain = trinity.rms_norm
        monkeypatch.setattr(
            trinity, "rms_norm", lambda x, scale, eps:
            x if x.shape[-1] == 32 else plain(x, scale, eps))
    elif what == "gate":                  # a constant 1/2 in its place
        plain = trinity.gqa

        def gqa(x, p, sizes, full, attend=trinity.attention):
            zero = {"kernel": jnp.zeros_like(p["gate"]["kernel"])}
            return plain(x, {**p, "gate": zero}, sizes, full, attend)
        monkeypatch.setattr(trinity, "gqa", gqa)
    elif what == "key_heads_interleaved":  # head h reads head h % n_kv
        monkeypatch.setattr(
            jnp, "repeat", lambda x, n, axis: jnp.tile(
                x, [n if a == axis else 1 for a in range(x.ndim)]))
    elif what == "embedding_scale":       # sqrt(1) in the place of sqrt(64)
        plain = parity_gqa_moe_lm.sizes_of
        monkeypatch.setattr(parity_gqa_moe_lm, "sizes_of",
                            lambda model: {**plain(model), "hidden_size": 1})
    elif what == "post_norms":
        plain = trinity.layer

        def layer(x, p, sizes, full, attend=trinity.attention, forced=None):
            ones = {"scale": jnp.ones_like(p["ln1_post"]["scale"])}
            return plain(x, {**p, "ln1_post": ones}, sizes, full, attend,
                         forced)
        monkeypatch.setattr(trinity, "layer", layer)
    elif what == "float8_matmuls":        # the control the chip runs, too
        monkeypatch.setattr(trinity, "matmul",
                            control_lm.float8_matmul(trinity.matmul))


@pytest.mark.parametrize("what", [
    "window_ignored", "rope_on_full", "window_off_by_one", "qk_norm", "gate",
    "key_heads_interleaved", "embedding_scale", "post_norms",
    "float8_matmuls"])
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
    if what in ("window_ignored", "rope_on_full"):
        assert "hidden" in over


def test_the_shares_of_the_reference_add_up_to_the_uncut_layer(model):
    """The guide's share test: the routed parts that the chips of this small
    deployment compute (experts 0-3 and 4-7 of one 8-wide router), with the
    shared expert counted once, are what the reference gives for the uncut
    layer; and at the published count, the 32 shares of 8 of one 256-wide
    router."""
    sizes = parity_gqa_moe_lm.sizes_of(model)
    p = _params(model)["sparse"]["blocks"][0]["mlp"]
    for experts, held, top_k in ((8, 4, 2), (256, 8, 4)):
        keys = jax.random.split(jax.random.key(11), 5)
        whole = {**p, **{name: 0.1 * jax.random.normal(
            k, (experts, *p[name].shape[1:]))
            for name, k in zip(("gate", "up", "down"), keys)},
            "router": jax.random.normal(keys[3], (64, experts)),
            "router_bias": jnp.zeros((experts,))}
        cut = {**sizes, "num_experts_per_tok": top_k}
        x = jax.random.normal(keys[4], (2, 32, 64))
        with jax.default_matmul_precision("highest"):
            want, chosen = trinity.moe(x, whole, {**cut, "first_expert": 0})
            xt = x.reshape(-1, 64)
            s = whole["shared"]
            shared = trinity.swiglu(xt, s["gate"]["kernel"], s["fc1"]["kernel"],
                                    s["fc2"]["kernel"]).reshape(x.shape)
            total = shared
            for first in range(0, experts, held):
                share = {**whole, **{name: whole[name][first:first + held]
                                     for name in ("gate", "up", "down")}}
                y, own = trinity.moe(x, share, {**cut, "first_expert": first})
                assert (own == chosen).all()
                total = total + (y - shared)
        np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
        assert chosen.shape == (64, top_k)


def test_attention_in_blocks_is_the_same_attention():
    """Per key/value head and block of query rows, windowed and full."""
    keys = jax.random.split(jax.random.key(12), 3)
    q = jax.random.normal(keys[0], (2, 64, 6, 24))
    k, v = (jax.random.normal(key, (2, 64, 2, 24)) for key in keys[1:])
    blocked = trinity.in_blocks(trinity.attention, 16, jax.checkpoint)

    def f(attend, window):
        return lambda q, k, v: jnp.sum(jnp.sin(attend(q, k, v, window)))

    for window in (None, 20):
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(blocked(q, k, v, window),
                                       trinity.attention(q, k, v, window),
                                       rtol=1e-5, atol=1e-6)
            got = jax.grad(f(blocked, window), argnums=(0, 1, 2))(q, k, v)
            want = jax.grad(f(trinity.attention, window),
                            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want, strict=True):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_the_reference_is_plain_and_shares_nothing_with_the_program():
    text = (harness.BENCH / "reference" / "trinity.py").read_text()
    for word in ("jimm_tpu", "lax.scan", "pallas", "nnx", "flax"):
        assert f"import {word}" not in text and f"from {word}" not in text
    assert "jax.checkpoint(" not in text and "lax.scan(" not in text
    assert "ragged_dot(" not in text and "argsort(" not in text
    assert "dot_product_attention" not in text and "jnp.repeat(" in text
    assert 'default_matmul_precision("highest")' in text
    # the mask is the two inequalities, the query's own position counted
    mask = trinity.visible(jnp.arange(6), jnp.arange(6), 3)
    assert mask.tolist() == [[j <= i and i - j < 3 for j in range(6)]
                             for i in range(6)]
    assert trinity.visible(jnp.arange(4), jnp.arange(4), None).tolist() \
        == np.tril(np.ones((4, 4), bool)).tolist()
    # position 0 is not turned; element 0 turns with element D/2
    x = jnp.arange(16.0).reshape(1, 2, 1, 8)
    r = trinity.rotate_half(x, 1e4)
    np.testing.assert_allclose(r[0, 0], x[0, 0])
    np.testing.assert_allclose(r[0, 1, 0, 0], 8 * np.cos(1) - 12 * np.sin(1),
                               rtol=1e-5)
    np.testing.assert_allclose(r[0, 1, 0, 4], 12 * np.cos(1) + 8 * np.sin(1),
                               rtol=1e-5)
    # query head h reads key/value head h // group
    q = jnp.zeros((1, 1, 4, 2))
    v = jnp.asarray([10.0, 20.0]).reshape(1, 1, 2, 1)
    out = trinity.attention(q, jnp.zeros((1, 1, 2, 2)), v)
    assert out[0, 0, :, 0].tolist() == [10.0, 10.0, 20.0, 20.0]
    # published layers 5-9: the full layer is the third held
    sizes = {"first_layer": 5, "global_attn_every_n_layers": 4}
    assert [trinity.is_full(i, sizes) for i in range(5)] == [
        False, False, True, False, False]


def test_configuration_file_holds_the_catalogs_numbers():
    config = json.loads((harness.BENCH / "configs"
                         / "trinity_large.json").read_text())
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 3072, "intermediate_size": 12288,
        "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
        "model_type": "afmoe", "moe_intermediate_size": 3072,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 48,
        "num_dense_layers": 6, "num_expert_groups": 1,
        "num_experts_per_tok": 4, "num_hidden_layers": 60,
        "num_key_value_heads": 8, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
        "score_func": "sigmoid", "sliding_window": 4096,
        "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True}
    assert {k: config[k] for k in published} == published
    assert len(config["layer_types"]) == 60
    assert [i for i, kind in enumerate(config["layer_types"])
            if kind == "full_attention"] == list(range(3, 60, 4))
    assert (config["num_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 25024)
    assert config["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    assert config["published"]["num_experts"] == 256
    assert config["published"]["vocab_size"] == 200192 == 8 * 25024
    assert config["published"]["num_hidden_layers"] == 60
    assert {"dtype", "weights", "gamma", "training_seq_len", "held_experts",
            "window", "rotary", "not_built"} <= set(config["assumed"])
    assert "32 chips share each layer" in config["deployment"]
    assert config["reference"] == "benchmarks/reference/trinity.py"
    manifest = harness.load_manifest()
    entry = harness.find(manifest["configs"], "trinity_large", "config")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # the held layers' kinds are the published ones'
    held = config["layer_types"][config["first_layer"]:
                                 config["first_layer"] + config["num_layers"]]
    assert [kind == "full_attention" for kind in held] \
        == flops_gqa_moe_lm.layer_is_full(config)
    # the program builds what the file says
    run = _run(rehearse=False)
    cfg = preset(config["preset"])
    import dataclasses
    cut = dataclasses.replace(cfg, decoder=dataclasses.replace(
        cfg.decoder, depth=config["num_layers"],
        seq_len=run.cell["traffic_params"]["seq_len"]))
    assert cfg.bias_update_rate == config["assumed"]["bias_update_rate"]
    built = nnx.eval_shape(lambda: Trinity(cut, rngs=nnx.Rngs(0)))
    assert parity_gqa_moe_lm.check_sizes(run, built) == []
    n = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(
        nnx.state(built, nnx.Param)))
    # 176.2 M (dense layer) + 4 x 318.5 M + 153.7 M (embedding, head slices)
    assert n == 1_603_993_856 == sum(
        flops_gqa_moe_lm.parameter_count(config).values())


def test_yardstick_counts_the_issues_numbers():
    config = _run().config
    assert flops_gqa_moe_lm.visible_pairs(8192, None) == 33_558_528
    assert flops_gqa_moe_lm.visible_pairs(8192, 4096) == 25_167_872
    assert flops_gqa_moe_lm.visible_pairs(4096, 4096) \
        == flops_gqa_moe_lm.visible_pairs(4096, None)
    # by brute force at a small size
    assert flops_gqa_moe_lm.visible_pairs(50, 7) == sum(
        1 for i in range(50) for j in range(50) if j <= i and i - j < 7)
    assert flops_gqa_moe_lm.pairs_by_layer(config, 8192) == [
        25_167_872, 25_167_872, 33_558_528, 25_167_872, 25_167_872]
    assert flops_gqa_moe_lm.train_step_flops(config, 1, 8192) \
        == pytest.approx(41.1e12, rel=2e-3)
    parts = flops_gqa_moe_lm.fwd_flops_per_sequence(config, 8192)
    # attention (projections and kernels) is 62 % of the model FLOPs, its
    # kernels 24 %
    total = sum(parts.values())
    assert (parts["attention_projections"] + parts["attention_core"]) / total \
        == pytest.approx(0.62, abs=0.01)
    assert parts["attention_core"] / total == pytest.approx(0.24, abs=0.01)
    assert parts["attention_projections"] / 5 / 8192 == 2 * 62_914_560
    assert parts["held_experts"] / 4 / 8192 == 2 * 3 * 3072 * 3072 * 4 * 8 / 256
    count = flops_gqa_moe_lm.parameter_count(config)
    assert count["attention"] / 5 == 62_914_560 + 256
    assert count["held_experts"] / 4 / 8 == 28_311_552
    # k and v read once at 8 heads, q and o at 48
    fwd = flops_gqa_moe_lm.gqa_flash_cost(33_558_528, 1, 8192, 48, 8, 128,
                                          backward=False)
    bwd = flops_gqa_moe_lm.gqa_flash_cost(33_558_528, 1, 8192, 48, 8, 128,
                                          backward=True)
    assert fwd["flops"] == 4 * 128 * 33_558_528 * 48
    assert fwd["bytes"] == 2 * 8192 * 128 * 2 * (48 + 8)
    assert bwd["flops"] == 2 * fwd["flops"] and bwd["bytes"] == 2 * fwd["bytes"]
    assert flops_gqa_moe_lm.gqa_flash_least_seconds(
        config, 1, 8192, "TPU v5 lite") == pytest.approx(50.2e-3, rel=2e-3)
    # the grouped products at the step's own count of rows: set by the eight
    # experts' weight bytes, which a few rows more do not move
    one = flops_gqa_moe_lm.grouped_products_cost(1024, config, backward=False)
    assert one["flops"] == 2 * 1024 * 3 * 3072 * 3072
    assert one["bytes"] > 8 * 3 * 3072 * 3072 * 2
    least = flops_gqa_moe_lm.grouped_products_least_seconds(
        4096, config, "TPU v5 lite")
    assert least == pytest.approx(7.19e-3, rel=5e-3)
    assert flops_gqa_moe_lm.grouped_products_least_seconds(
        2048, config, "TPU v5 lite") == pytest.approx(least, rel=0.05)
    # the program's own copy counts the same
    import dataclasses

    from jimm_tpu.train.metrics import train_step_flops
    cfg = preset("trinity-large")
    cut = dataclasses.replace(cfg, decoder=dataclasses.replace(cfg.decoder,
                                                               depth=5))
    assert train_step_flops(cut, 1) == pytest.approx(
        flops_gqa_moe_lm.train_step_flops(config, 1, 8192), rel=1e-4)


def test_driver_takes_depth_and_length_from_the_files():
    run = _run(rehearse=False)
    argv = train_gqa_moe_lm.cli_argv(run, 20, "m.jsonl")
    pairs = dict(zip(argv, argv[1:]))
    assert pairs["--preset"] == "trinity-large"
    assert pairs["--batch-size"] == "1"
    assert pairs["--num-layers"] == "5" and pairs["--seq-len"] == "8192"
    assert pairs["--remat"] == "full" and "--bf16" in argv
    assert "--lr" not in pairs and "--warmup-steps" not in pairs
    for flag in ("--attn-impl", "--ln-impl", "--scan-unroll", "--data",
                 "--tiny"):
        assert flag not in argv
    rehearsal = train_gqa_moe_lm.cli_argv(_run(), 12, "m.jsonl")
    assert "--tiny" in rehearsal and "--num-layers" not in rehearsal
    window = train_gqa_moe_lm.planned_steps(run) \
        - train_gqa_moe_lm.WARMUP_STEPS - train_gqa_moe_lm.TRACED_STEPS
    assert 10 <= window <= 24
    # one configuration, one cell and eleven per-layer metrics, each listing
    # the cell alone
    manifest = harness.load_manifest()
    cell = harness.find(manifest["workloads"], CELL, "workload")
    assert cell["chips"] == 1 and cell["config"] == "trinity_large"
    assert [w["name"] for w in manifest["workloads"]
            if w["config"] == "trinity_large"] == [CELL]
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == MINE
    assert all(m["moves"] == "train_img_per_s" for m in manifest["per_layer"]
               if m["name"] in MINE)
    assert run.cell["traffic_params"]["flash_kernels"] == [
        "attn_window/pallas_call", "attn_full/pallas_call"]


def test_the_older_sparse_cell_keeps_its_driver_and_its_entries():
    """What `test_moe_lm.py::test_driver_takes_depth_and_length_from_the_files`
    asserts, less its three lines on the END of the manifest's lists, which an
    appended cell ends (`tests/conftest.py::_LAPSED`): that file is not this
    PR's to edit, so its other assertions run here until a `benchmark` PR
    drops the three lines there and this test with them."""
    from benchmarks.drivers import train_moe_lm
    older = "kanana_2_30b_a3b.train"

    def older_run(**kw):
        return harness.load_run(harness.REPO, older, seed=3, seconds=10,
                                trace=False, t_process_start=0.0, **kw)

    run = older_run(rehearse=False)
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
    rehearsal = train_moe_lm.cli_argv(older_run(rehearse=True), 12, "m.jsonl")
    assert "--tiny" in rehearsal and "--num-layers" not in rehearsal
    window = train_moe_lm.planned_steps(run) - train_moe_lm.WARMUP_STEPS \
        - train_moe_lm.TRACED_STEPS
    assert 10 <= window <= 20
    # its cell on one chip, and its nine metrics in their order, side by side
    manifest = harness.load_manifest()
    assert harness.find(manifest["workloads"], older, "workload")["chips"] == 1
    names = [m["name"] for m in manifest["per_layer"]]
    its = [m["name"] for m in manifest["per_layer"]
           if m.get("workloads") == [older]]
    assert its == ["mla_ms", "moe_ms", "moe_route_ms", "moe_experts_ms",
                   "lm_head_ms", "mla_flash_ms", "mla_flash_roofline",
                   "moe_experts_roofline", "moe_held_rows"]
    first = names.index(its[0])
    assert names[first:first + 9] == its


def _observed(trace, **kw):
    run = _run()
    return {"platform": "tpu", "device_kind": "TPU v5 lite", "chips": 1,
            "config": run.config, "global_batch": 1,
            "gqa_moe_lm_shape": {"seq_len": 8192}, "flash_calls": 68,
            "flash_kernels": ("attn_window/pallas_call",
                              "attn_full/pallas_call"), "trace": trace,
            "window_rows": [{"moe_held_rows": 4000.0},
                            {"moe_held_rows": 4192.0}], **kw}


def test_readers_find_the_scopes_and_the_kernels():
    readers = harness.load_readers("layer_metrics")
    o = _observed({"scoped_ops": 900,
                   "kernel_ms": {"attn_window/pallas_call": 100.0,
                                 "attn_full/pallas_call": 25.6},
                   "scope_ms": {"attn": 300.0, "moe": 80.0, "moe_route": 30.0,
                                "moe_experts": 14.38, "moe_shared": 20.0,
                                "mla": 7.0, "jvp(lm_head)": 9.0,
                                "transpose(jvp(lm_head))": 18.0}})
    assert readers["gqa_attn_ms"](o) == 300.0
    assert readers["sparse_ffn_ms"](o) == 80.0
    assert readers["sparse_experts_ms"](o) == 14.38
    assert readers["sparse_route_ms"](o) == 30.0
    assert readers["sparse_shared_ms"](o) == 20.0
    assert readers["sparse_lm_head_ms"](o) == 27.0
    assert readers["gqa_flash_ms"](o) == pytest.approx(125.6)
    assert readers["window_flash_ms"](o) == 100.0
    # 50.2 ms at the peaks over 125.6 ms taken
    assert readers["gqa_flash_roofline"](o) == pytest.approx(40.0, abs=0.1)
    assert readers["sparse_held_rows"](o) == 4096.0
    # 7.19 ms of weight traffic over 14.38 ms taken
    assert readers["sparse_experts_roofline"](o) == pytest.approx(50.0,
                                                                   abs=0.2)
    assert 0 < readers["sparse_experts_roofline"](o) < 100
    # the other sparse cell's readers find nothing here, and these nothing
    # there: a metric has one reader and one cell
    for name in ("mla_ms", "moe_ms", "mla_flash_ms", "moe_experts_roofline"):
        assert readers[name](o) is None, name
    # a program without the scopes, the kernels or the counter (the parent),
    # another driver's observations, another platform: nothing, and no raise
    bare = _observed({"scoped_ops": 900, "kernel_ms": {}, "scope_ms": {}},
                     flash_calls=0, window_rows=[{"loss": 1.0}])
    other_driver = {**{k: v for k, v in o.items()
                       if k != "gqa_moe_lm_shape"},
                    "moe_lm_shape": {"seq_len": 8192}}
    for name in MINE:
        assert readers[name](bare) is None, name
        assert readers[name](other_driver) is None, name
        if name != "sparse_held_rows":  # a count is no device number
            assert readers[name]({**o, "platform": "cpu"}) is None, name
    scopes = train_gqa_moe_lm.gqa_moe_lm.scope_names
    assert scopes("lm_head") == ("jvp(lm_head)", "transpose(jvp(lm_head))")
    assert scopes("attn") == ("attn",)


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
    assert len(parity["routing_differs_per_layer"]) == 3
    runtime = next(json.loads(ln) for ln in lines
                   if ln.startswith('{"event": "resolved_runtime"'))
    assert runtime["full_layers"] == [False, False, True, False]
    assert runtime["heads"] == [4, 2, 32] and runtime["window"] == 8
    if trace:
        assert "sparse_held_rows" in line["metrics"]
        assert not {"gqa_attn_ms", "gqa_flash_ms", "gqa_flash_roofline",
                    "sparse_experts_roofline", "mfu_pct"} \
            & set(line["metrics"])
        assert {"busy_s", "window_s"} <= set(line["device"])
    else:
        assert set(line["metrics"]) == {"train_img_per_s", "setup_s"}


def test_the_controls_read_the_comparison_again(capsys):
    """`control_gqa_moe_lm.py`: the cell once, then its comparison with every
    matmul operand of the reference rounded to float8, with the window
    ignored, and with rotary on the full layer: the shipped limits refuse all
    three."""
    from benchmarks.reference import control_gqa_moe_lm
    assert control_gqa_moe_lm.main(
        ["--workload", CELL, "--seed", "2147483693", "--seconds", "1",
         "--trace", "0", "--rehearse"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    readings = {ln["control"]: ln for ln in lines
                if ln.get("event") == "control_reading"}
    assert set(readings) == {"float8", "window_ignored", "rope_on_full"}
    for control, reading in readings.items():
        assert reading["refused"] is True, control
        assert reading["errors"]["hidden"] > reading["tolerance"]["hidden"]
    assert lines[-1]["correct"] is True
    assert parity_gqa_moe_lm.check_train.__module__ \
        == parity_gqa_moe_lm.__name__
    assert trinity.IGNORE_WINDOW is False and trinity.ROPE_ON_FULL is False


def test_state_goes_to_the_host_and_back():
    """What makes room for the reference on the chip: a module's arrays leave
    the device (numpy in their place, the device buffers deleted) and come
    back bit for bit; the module works again afterwards."""
    model = Trinity(_tiny_override(preset("trinity-large")), rngs=nnx.Rngs(1))
    tokens = jax.random.randint(jax.random.key(0), (1, 32), 0, 512)
    before = model(tokens)
    kept = jax.tree.leaves(nnx.state(model, nnx.Param))
    parity_gqa_moe_lm.to_host(model)
    assert all(x.is_deleted() for x in kept)
    assert all(isinstance(x, np.ndarray)
               for x in jax.tree.leaves(nnx.state(model, nnx.Param)))
    parity_gqa_moe_lm.to_host(model)    # nothing left to move: no error
    parity_gqa_moe_lm.to_device(model, jax.devices()[0])
    assert all(isinstance(x, jax.Array)
               for x in jax.tree.leaves(nnx.state(model, nnx.Param)))
    np.testing.assert_array_equal(model(tokens), before)
