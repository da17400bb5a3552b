"""The looped language model's side of the benchmark: its plain reference
against the program's model at a small size (both float32: the same
mathematics must agree to float32 rounding), the comparison's power to refuse
left-out mathematics and a lower precision, the yardstick's counts, the
driver's arguments and the readers of the cell's device numbers."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

from benchmarks import flops_lm, harness
from benchmarks.drivers import train_lm
from benchmarks.reference import control_lm, ouro, parity_lm
from jimm_tpu import Ouro, preset
from jimm_tpu.cli import _tiny_override

CELL = "ouro_2_6b.train"
TIGHT = {"hidden": 2e-4, "gates": 2e-5, "logits": 2e-4, "loss": 2e-5,
         "grads": dict.fromkeys(ouro.GRAD_LEAVES, 2e-3)}


def _run(seed=3, **kw) -> harness.Run:
    return harness.load_run(harness.REPO, CELL, seed=seed, seconds=10,
                            trace=False, t_process_start=0.0,
                            **{"rehearse": True, **kw})


@pytest.fixture(scope="module")
def model():
    """width 64, 4 heads of 16, MLP 176, vocabulary 512, n = 2, R = 4,
    S = 32, float32, every norm scale and the gate's bias given weight."""
    model = Ouro(_tiny_override(preset("ouro-2.6b")), rngs=nnx.Rngs(0))
    keys = iter(jax.random.split(jax.random.key(7), 64))
    nnx.update(model, jax.tree.map(
        lambda a: a + 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
        if a.ndim <= 2 and a.shape[-1] in (64, 1) else a,
        nnx.state(model, nnx.Param)))
    return model


def _agree(model, monkeypatch, tolerance=None, run=None):
    if tolerance is not None:
        monkeypatch.setattr(ouro, "TOLERANCE", tolerance)
    tokens = jnp.zeros((2, model.config.decoder.seq_len + 1), jnp.int32)
    result = types.SimpleNamespace(model=model, batch=(tokens,))
    with jax.default_matmul_precision("highest"):
        return parity_lm.check_train(run or _run(), result)


def test_float32_model_agrees_with_the_reference(model, monkeypatch):
    """``h_r``, gates, logits, loss and the four gradient leaves."""
    agree = _agree(model, monkeypatch, TIGHT)
    assert agree["ok"], agree
    assert np.isfinite(agree["loss_reference"])
    assert set(agree["errors"]["grads"]) == set(ouro.GRAD_LEAVES)
    assert agree["grad_leaves"]["middle_layer_q"] \
        == "decoder/blocks/1/attn/q/kernel"


def test_every_gradient_leaf_agrees_with_the_reference(model):
    """Not the four of the chip comparison alone: the whole tree."""
    from jimm_tpu.train.trainer import lm_loss_fn
    tokens = jax.random.randint(jax.random.key(5), (2, 33), 0, 512, jnp.int32)
    sizes = parity_lm.sizes_of(model)
    with jax.default_matmul_precision("highest"):
        got = nnx.to_pure_dict(nnx.grad(
            lambda m: lm_loss_fn(m, tokens)[0])(model))
        params = ouro.params_from_state(
            nnx.to_pure_dict(nnx.state(model, nnx.Param)))
        want = jax.grad(ouro.loss)(params, tokens, sizes)
    want["decoder"]["blocks"] = jax.tree.map(
        lambda *layers: jnp.stack(layers), *want["decoder"]["blocks"])
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert set(flat_got) == set(flat_want) and len(flat_got) == 16
    for path, g in flat_got.items():
        w = flat_want[path]
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err < 2e-3, (jax.tree_util.keystr(path), err)


def _without(monkeypatch, what):
    """Take one piece of the mathematics out of the reference."""
    if what == "rotary":
        monkeypatch.setattr(ouro, "rotate", lambda x, theta: x)
    elif what == "post_norm":
        def block(x, p, sizes):
            eps = sizes["rms_norm_eps"]
            a = x + ouro.attention(ouro.rms_norm(x, p["ln1"], eps),
                                   p["attn"], sizes)
            return a + ouro.swiglu(ouro.rms_norm(a, p["ln2"], eps), p["mlp"])
        monkeypatch.setattr(ouro, "block", block)
    elif what in ("pass", "between_pass_norm"):
        def hidden_states(params, tokens, sizes, wrap=lambda f: f):
            x = params["embed"]["embedding"][tokens]
            out = []
            for r in range(sizes["total_ut_steps"]):
                if not (what == "pass" and r == 1):
                    for layer in params["decoder"]["blocks"]:
                        x = ouro.block(x, layer, sizes)
                normed = ouro.rms_norm(x, params["decoder"]["norm"],
                                       sizes["rms_norm_eps"])
                out.append(normed)
                x = x if what == "between_pass_norm" else normed
            return out
        monkeypatch.setattr(ouro, "hidden_states", hidden_states)
    elif what == "float8_matmuls":  # the control the chip runs, too
        monkeypatch.setattr(ouro, "matmul",
                            control_lm.float8_matmul(ouro.matmul))
    elif what == "int8_matmuls":
        plain = ouro.matmul

        def q(a):  # symmetric per-tensor int8, straight-through gradient
            scale = jnp.max(jnp.abs(a)) / 127.0
            return a + jax.lax.stop_gradient(
                jnp.round(a / scale) * scale - a)
        monkeypatch.setattr(ouro, "matmul", lambda a, b: plain(q(a), q(b)))


@pytest.mark.parametrize("what", ["pass", "post_norm", "between_pass_norm",
                                  "rotary", "float8_matmuls", "int8_matmuls"])
def test_the_shipped_limits_refuse(what, model, monkeypatch):
    """The bfloat16 limits of the chip comparison already refuse a reference
    with a piece taken out, or computed in a lower precision than bfloat16
    (against a float32 model, so nothing else differs)."""
    _without(monkeypatch, what)
    agree = _agree(model, monkeypatch)
    assert not agree["ok"], agree["errors"]
    e, tol = agree["errors"], agree["tolerance"]
    over = [k for k in ("hidden", "gates", "logits", "loss") if e[k] > tol[k]]
    over += [k for k, v in e["grads"].items() if v > tol["grads"][k]]
    assert over


def test_the_reference_is_plain_and_shares_nothing_with_the_program():
    text = (harness.BENCH / "reference" / "ouro.py").read_text()
    for word in ("jimm_tpu", "lax.scan", "pallas", "nnx"):
        assert f"import {word}" not in text and f"from {word}" not in text
    assert "jax.checkpoint(" not in text and "lax.scan(" not in text
    p = [np.asarray(a) for a in ouro.exit_distribution(
        [jnp.asarray([-2.0, 0.3]), jnp.asarray([1.0, 0.0]),
         jnp.asarray([5.0, -1.0])])]
    np.testing.assert_allclose(sum(p), [1.0, 1.0], rtol=1e-6)
    # a pass's rotation of position 0 is the identity; pairs are (i, i + D/2)
    x = jnp.arange(16.0).reshape(1, 2, 1, 8)
    r = ouro.rotate(x, 1e6)
    np.testing.assert_allclose(r[0, 0], x[0, 0])
    np.testing.assert_allclose(r[0, 1, 0, 0], 8 * np.cos(1) - 12 * np.sin(1),
                               rtol=1e-5)


def test_configuration_file_holds_the_catalogs_numbers():
    config = json.loads((harness.BENCH / "configs"
                         / "ouro_2_6b.json").read_text())
    published = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
                 "intermediate_size": 5632, "max_position_embeddings": 65536,
                 "num_attention_heads": 16, "num_hidden_layers": 48,
                 "num_key_value_heads": 16, "rms_norm_eps": 1e-6,
                 "rope_theta": 1000000, "tie_word_embeddings": False,
                 "total_ut_steps": 4, "early_exit_threshold": 1,
                 "vocab_size": 49152, "model_type": "ouro"}
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["num_layers"] == 8 and config["reduced"] == ["num_layers"]
    assert {"dtype", "weights", "projection_bias", "norm_placement",
            "objective", "training_seq_len"} <= set(config["assumed"])
    # the program builds what the file says
    run = _run(rehearse=False)
    cfg = preset(config["preset"])
    import dataclasses
    cut = dataclasses.replace(cfg, decoder=dataclasses.replace(
        cfg.decoder, depth=config["num_layers"],
        seq_len=run.cell["traffic_params"]["seq_len"]))
    built = nnx.eval_shape(lambda: Ouro(cut, rngs=nnx.Rngs(0)))
    assert parity_lm.check_sizes(run, built) == []
    n = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(
        nnx.state(built, nnx.Param)))
    assert n == 612_438_017  # 8 x 51.39 M + 2 x 100.66 M + norm, gate


def test_yardstick_counts_the_issues_numbers():
    config = _run().config
    assert flops_lm.train_step_flops(config, 1, 4096) == pytest.approx(
        56.9e12, rel=2e-3)
    fwd = flops_lm.causal_flash_cost(1, 4096, 16, 128, backward=False)
    bwd = flops_lm.causal_flash_cost(1, 4096, 16, 128, backward=True)
    assert fwd["flops"] == 2 * 2 * 4096 * 4096 * 128 * 16 / 2
    assert bwd["flops"] == 2 * fwd["flops"] and bwd["bytes"] == 2 * fwd["bytes"]
    assert flops_lm.causal_flash_least_seconds(
        config, 1, 4096, "TPU v5 lite") == pytest.approx(33.5e-3, rel=2e-3)
    # the program's own copy counts the same
    import dataclasses

    from jimm_tpu.train.metrics import train_step_flops
    cfg = preset("ouro-2.6b")
    cut = dataclasses.replace(cfg, decoder=dataclasses.replace(cfg.decoder,
                                                               depth=8))
    assert train_step_flops(cut, 1) == flops_lm.train_step_flops(config, 1,
                                                                 4096)


def test_driver_takes_depth_and_length_from_the_files():
    run = _run(rehearse=False)
    argv = train_lm.cli_argv(run, 20, "m.jsonl")
    pairs = dict(zip(argv, argv[1:]))
    assert pairs["--preset"] == "ouro-2.6b" and pairs["--batch-size"] == "1"
    assert pairs["--num-layers"] == "8" and pairs["--seq-len"] == "4096"
    assert pairs["--remat"] == "dots" and "--bf16" in argv
    assert "--lr" not in pairs and "--warmup-steps" not in pairs
    for flag in ("--attn-impl", "--ln-impl", "--scan-unroll", "--data",
                 "--tiny"):
        assert flag not in argv
    rehearsal = train_lm.cli_argv(_run(), 12, "m.jsonl")
    assert "--tiny" in rehearsal and "--num-layers" not in rehearsal
    assert dict(zip(rehearsal, rehearsal[1:]))["--batch-size"] == "2"
    window = train_lm.planned_steps(run) - train_lm.WARMUP_STEPS \
        - train_lm.TRACED_STEPS
    assert 12 <= window <= 20


def _observed(trace, **kw):
    run = _run()
    return {"platform": "tpu", "device_kind": "TPU v5 lite", "chips": 1,
            "config": run.config, "global_batch": 1,
            "lm_shape": {"seq_len": 4096}, "flash_calls": 32,
            "flash_kernels": ("pallas_call",), "trace": trace, **kw}


def test_readers_sum_a_scopes_forward_and_backward():
    readers = harness.load_readers("layer_metrics")
    o = _observed({"scoped_ops": 900, "kernel_ms": {"pallas_call": 134.0},
                   "scope_ms": {"jvp(loop_stack)": 200.0,
                                "transpose(jvp(loop_stack))": 450.0,
                                "jvp(exit_head)": 30.0,
                                "transpose(jvp(exit_head))": 70.0}})
    assert readers["loop_stack_ms"](o) == 650.0
    assert readers["exit_head_ms"](o) == 100.0
    assert readers["causal_flash_ms"](o) == 134.0
    # 33.5 ms at the peaks over 134 ms taken
    assert readers["causal_flash_roofline"](o) == pytest.approx(25.0, abs=0.1)
    # a program without the scopes or the kernels (the parent): nothing
    bare = _observed({"scoped_ops": 900, "kernel_ms": {}, "scope_ms": {}},
                     flash_calls=0)
    other_driver = {k: v for k, v in o.items() if k != "lm_shape"}
    for name in ("loop_stack_ms", "exit_head_ms", "causal_flash_ms",
                 "causal_flash_roofline"):
        assert readers[name](bare) is None
        assert readers[name]({**o, "platform": "cpu"}) is None
    assert readers["causal_flash_roofline"](other_driver) is None
    assert train_lm.looped_lm.scope_names("embed") == (
        "jvp(embed)", "transpose(jvp(embed))")
