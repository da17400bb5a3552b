"""The sparse decoder language model (`models/kanana.py`, `nn/mla.py`,
`nn/moe.py`) at a small size on the CPU: its shapes, the share an expert layer
holds, routing without a dropped assignment, the routers' bias update, and the
family's way through `jimm-tpu train`. Agreement with the plain reference is
`tests/benchmark/test_moe_lm.py`'s; its entries in the CLI's tables and its
`presets` line are `tests/test_families.py`'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

from family_util import train_rows
from jimm_tpu import Kanana, KananaConfig, preset
from jimm_tpu.cli import _tiny_override, main
from jimm_tpu.configs import MLAConfig, MoEConfig, TransformerConfig
from jimm_tpu.nn.moe import (RouterBias, SparseMoe, chunk_rows,
                             routing_counts)


def _tiny(**decoder) -> KananaConfig:
    cfg = _tiny_override(preset("kanana-2-30b-a3b"))
    return dataclasses.replace(cfg, decoder=dataclasses.replace(cfg.decoder,
                                                                **decoder))


def test_preset_is_one_chips_share_of_the_published_shapes():
    d = preset("kanana-2-30b-a3b").decoder
    assert (d.width, d.depth, d.dense_layers, d.num_heads, d.mlp_dim) \
        == (2048, 48, 1, 32, 6144)
    assert d.mla == MLAConfig(kv_lora_rank=512, qk_nope_dim=128,
                              qk_rope_dim=64, v_head_dim=128)
    assert d.moe == MoEConfig(num_experts=128, top_k=6, expert_dim=768,
                              shared_experts=2, routed_scale=2.448,
                              held_experts=16, first_expert=0)
    assert d.vocab_size * 8 == 128256 and d.seq_len == 8192
    dense, sparse = d.encoder(sparse=False), d.encoder(sparse=True)
    assert (dense.depth, sparse.depth) == (1, 47)
    assert dense.moe is None and sparse.moe == d.moe
    for enc in (dense, sparse):
        assert enc.norm == "rms" and not enc.post_norm and not enc.use_bias
        assert enc.rope_dim == 64 and enc.causal and enc.loops == 0
    # the image towers' and the looped decoder's blocks know neither
    assert TransformerConfig().mla is None and TransformerConfig().moe is None
    assert preset("ouro-2.6b").decoder.encoder().rope_dim == 128


def test_tiny_model_shapes_and_outputs():
    model = Kanana(_tiny(), rngs=nnx.Rngs(0))
    attn = model.sparse.blocks.attn
    assert attn.q.kernel.shape == (2, 64, 4 * (16 + 8))
    assert attn.kv_a.kernel.shape == (2, 64, 32 + 8)
    assert attn.kv_b.kernel.shape == (2, 32, 4 * (16 + 16))
    assert attn.out.kernel.shape == (2, 4 * 16, 64)
    moe = model.sparse.blocks.mlp
    assert moe.router.shape == (2, 64, 16) and moe.gate.shape == (2, 4, 64, 48)
    assert moe.down.shape == (2, 4, 48, 64)
    assert moe.shared.fc1.kernel.shape == (2, 64, 96)
    assert model.dense.blocks.mlp.fc1.kernel.shape == (1, 64, 176)
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 512)
    hidden, chosen = model.hidden_states(tokens)
    assert hidden.shape == (2, 32, 64) and chosen.shape == (2, 64, 2)
    assert chosen.dtype == jnp.int32 and 0 <= chosen.min() \
        and chosen.max() < 16
    assert model(tokens).shape == (2, 32, 512)
    # the selection bias is no parameter: no gradient, no optimizer state
    params = nnx.state(model, nnx.Param)
    assert not any("router_bias" in jax.tree_util.keystr(path)
                   for path, _ in jax.tree_util.tree_leaves_with_path(params))
    assert nnx.state(model, RouterBias)["sparse"]["blocks"]["mlp"][
        "router_bias"][...].shape == (2, 16)


@pytest.mark.parametrize("decoder,message", [
    ({"depth": 1}, "at least one dense and one sparse"),
    ({"moe": MoEConfig(num_experts=16, held_experts=4, first_expert=13)},
     "not among the router's"),
])
def test_model_refuses_a_share_that_is_none(decoder, message):
    with pytest.raises(ValueError, match=message):
        Kanana(_tiny(**decoder), rngs=nnx.Rngs(0))


def _layer(held=4, first=0, num=16, top_k=2, width=64, seed=0) -> SparseMoe:
    cfg = TransformerConfig(
        width=width, act="silu", use_bias=False, gated_mlp=True,
        moe=MoEConfig(num_experts=num, top_k=top_k, expert_dim=48,
                      shared_experts=2, routed_scale=2.448,
                      held_experts=held, first_expert=first))
    return SparseMoe(cfg, nnx.Rngs(seed))


def _dense_loops(layer: SparseMoe, x):
    """The layer's mathematics by a loop over the held experts."""
    m = layer.moe
    xt = x.reshape(-1, x.shape[-1])
    chosen, weights = layer.route(xt)
    y = jnp.zeros_like(xt)
    for e in range(m.held_experts):
        w = jnp.sum(jnp.where(chosen == m.first_expert + e, weights, 0.0), -1)
        h = jax.nn.silu(xt @ layer.gate[...][e]) * (xt @ layer.up[...][e])
        y = y + w[:, None] * (h @ layer.down[...][e])
    return y.reshape(x.shape) + layer.shared(x)


@pytest.mark.parametrize("routing,chunks_run", [
    ("as_it_falls", 1), ("every_token_to_held_experts", 3),
    ("no_token_to_a_held_expert", 0)])
def test_no_assignment_is_dropped_whatever_the_routing(routing, chunks_run,
                                                       monkeypatch):
    """2048 tokens, top-2 of 16 experts, 4 held: a chunk is 1536 rows of the
    4096 assignments (without the floor that real sizes never reach down
    to). With every token on held experts all three chunks run; with none the
    routed part is zero."""
    from jimm_tpu.nn import moe
    monkeypatch.setattr(moe, "_MIN_CHUNK_ROWS", 0)
    layer = _layer()
    x = jax.random.normal(jax.random.key(1), (2, 1024, 64))
    assert chunk_rows(2048, 2, 4, 16) == 1536
    push = {"as_it_falls": 0.0, "every_token_to_held_experts": 10.0,
            "no_token_to_a_held_expert": -10.0}[routing]
    layer.router_bias[...] = jnp.zeros((16,)).at[:4].set(push)
    with jax.default_matmul_precision("highest"):
        y, chosen = layer(x)
        want = _dense_loops(layer, x)
    held = int(jnp.sum(chosen < 4))
    assert -(-held // 1536) == chunks_run
    assert held == {"every_token_to_held_experts": 4096,
                    "no_token_to_a_held_expert": 0}.get(routing, held)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
    if routing == "no_token_to_a_held_expert":
        np.testing.assert_allclose(y, layer.shared(x), rtol=1e-6)
    # and its gradients, compiled as the train step compiles them
    g = nnx.jit(nnx.grad(lambda m, x: jnp.sum(m(x)[0] ** 2), argnums=1))(
        layer, x)
    gm = nnx.jit(nnx.grad(lambda m, x: jnp.sum(m(x)[0] ** 2)))(layer, x)
    with jax.default_matmul_precision("highest"):
        gmw = nnx.grad(lambda m, x: jnp.sum(_dense_loops(m, x) ** 2))(layer, x)
    for name in ("gate", "up", "down", "router"):
        np.testing.assert_allclose(gm[name][...], gmw[name][...], rtol=2e-3,
                                   atol=2e-3, err_msg=name)
    with jax.default_matmul_precision("highest"):
        gw = jax.grad(lambda x: jnp.sum(_dense_loops(layer, x) ** 2))(x)
    np.testing.assert_allclose(g, gw, rtol=2e-3, atol=2e-4)


def test_the_shares_of_all_chips_add_up_to_the_whole_layer():
    """Four chips of 4 experts each, one router: the routed parts of the
    four shares, with the shared experts counted once, are the uncut
    layer."""
    whole = _layer(held=16)
    x = jax.random.normal(jax.random.key(2), (2, 64, 64))
    with jax.default_matmul_precision("highest"):
        want, chosen = whole(x)
        shared = whole.shared(x)
        total = shared
        for first in (0, 4, 8, 12):
            share = _layer(held=4, first=first)
            share.router[...] = whole.router[...]
            nnx.update(share.shared, nnx.state(whole.shared))
            for name in ("gate", "up", "down"):
                getattr(share, name)[...] = \
                    getattr(whole, name)[...][first:first + 4]
            y, chosen_here = share(x)
            assert (chosen_here == chosen).all()
            total = total + (y - shared)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_weights_are_normalised_over_all_chosen_experts():
    layer = _layer()
    xt = jax.random.normal(jax.random.key(3), (32, 64))
    chosen, weights = layer.route(xt)
    np.testing.assert_allclose(jnp.sum(weights, -1), 2.448, rtol=1e-5)
    assert chosen.shape == (32, 2) and (chosen[:, 0] != chosen[:, 1]).all()
    counts = routing_counts(chosen, 16)
    assert counts.shape == (16,) and int(counts.sum()) == 64
    assert (routing_counts(chosen[None], 16)[0] == counts).all()


def test_bias_update_moves_toward_balance_and_takes_no_gradient():
    model = Kanana(_tiny(), rngs=nnx.Rngs(0))
    counts = jnp.stack([jnp.arange(16), jnp.full((16,), 8).at[3].set(40)])
    model.update_router_bias(counts)
    bias = model.sparse.blocks.mlp.router_bias[...]
    # layer 0: experts under the mean of 7.5 go up, over it down
    np.testing.assert_allclose(bias[0], np.where(np.arange(16) < 7.5, 1e-3,
                                                 -1e-3))
    # layer 1: the overloaded expert goes down, the fifteen others up
    np.testing.assert_allclose(bias[1], np.full(16, 1e-3) - 2e-3
                               * (np.arange(16) == 3))
    # a bias that favours an expert draws tokens to it
    layer = _layer()
    xt = jax.random.normal(jax.random.key(4), (256, 64))
    before = routing_counts(layer.route(xt)[0], 16)[5]
    layer.router_bias[...] = jnp.zeros((16,)).at[5].set(0.2)
    assert routing_counts(layer.route(xt)[0], 16)[5] > before
    # and no gradient reaches it
    grads = nnx.grad(lambda m: jnp.sum(m(xt[None])[0]),
                     argnums=nnx.DiffState(0, RouterBias))(layer)
    assert not jnp.any(grads["router_bias"][...])


def test_train_step_moves_the_bias_and_reports_the_routing():
    from jimm_tpu.train import (OptimizerConfig, make_lm_train_step,
                                make_optimizer)
    model = Kanana(_tiny(), rngs=nnx.Rngs(0))
    optimizer = make_optimizer(model, OptimizerConfig(learning_rate=1e-3))
    tokens = jax.random.randint(jax.random.key(5), (2, 33), 0, 512)
    metrics = make_lm_train_step("kanana")(model, optimizer, tokens)
    assert set(metrics) == {"loss", "moe_held_rows", "moe_load_max_over_mean",
                            "router_bias_absmax"}
    assert 0 < float(metrics["moe_held_rows"]) <= 2 * 64 * 2
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0
    assert float(metrics["router_bias_absmax"]) == pytest.approx(1e-3)
    bias = model.sparse.blocks.mlp.router_bias[...]
    assert set(np.unique(np.abs(bias)).tolist()) <= {0.0,
                                                     np.float32(1e-3).item()}


def test_loss_falls_on_a_batch_seen_again():
    """`jimm-tpu train` draws fresh uniform ids every step, on which nothing
    can be learnt; on one batch shown thirty times the loss must fall."""
    from jimm_tpu.train import (OptimizerConfig, make_lm_train_step,
                                make_optimizer)
    model = Kanana(_tiny(), rngs=nnx.Rngs(0))
    optimizer = make_optimizer(model, OptimizerConfig(learning_rate=3e-3))
    step = make_lm_train_step("kanana")
    tokens = jax.random.randint(jax.random.key(6), (4, 33), 0, 512)
    losses = [float(step(model, optimizer, tokens)["loss"])
              for _ in range(30)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 1.0


# -- through the CLI ------------------------------------------------------------------
# (its entries in the CLI's tables and its `presets` line:
# `tests/test_families.py`)

def test_train_cli_runs_the_family_through_the_same_loop(tmp_path, capsys):
    from jimm_tpu import obs
    before = obs.snapshot()
    rows = train_rows(tmp_path, capsys, [
        "--preset", "kanana-2-30b-a3b", "--tiny", "--steps", "30",
        "--batch-size", "2", "--remat", "dots", "--log-every", "0"])
    for row in rows:
        assert 0 <= row["moe_held_rows"] <= 2 * 64 * 2
        # uniform random ids: nothing to learn but ln(vocabulary)
        assert abs(row["loss"] - np.log(512)) < 0.15
    assert rows[-1]["router_bias_absmax"] > rows[0]["router_bias_absmax"]
    after = obs.snapshot()

    def grew(name):
        return after.get(name, 0) - before.get(name, 0)
    assert grew("jimm_lm_tokens_total") == 30 * 2 * 32
    assert grew("jimm_moe_assignments_total") == 30 * 2 * 32 * 2 * 2
    assert grew("jimm_moe_held_assignments_total") \
        == sum(r["moe_held_rows"] for r in rows)
    assert grew("jimm_loop_block_applications_total") == 0


def test_num_layers_and_seq_len_shape_the_preset(tmp_path):
    from jimm_tpu import cli
    result = cli.train(cli.build_parser().parse_args(
        ["train", "--preset", "kanana-2-30b-a3b", "--tiny", "--steps", "1",
         "--batch-size", "1", "--num-layers", "4", "--seq-len", "16",
         "--log-every", "0"]))
    d = result.model.config.decoder
    assert (d.depth, d.dense_layers, d.seq_len) == (4, 1, 16)
    assert result.batch[0].shape == (1, 17)
    assert result.model.sparse.blocks.mlp.router.shape == (3, 64, 16)


@pytest.mark.parametrize("argv,message", [
    (["--preset", "kanana-2-30b-a3b", "--data", "x.tfrecord"],
     "token generator"),
    (["--preset", "kanana-2-30b-a3b", "--ln-impl", "fused"], "does not take"),
    (["--preset", "kanana-2-30b-a3b", "--num-layers", "1"],
     "at least one dense and one sparse"),
])
def test_train_cli_refuses_what_the_family_does_not_have(argv, message):
    with pytest.raises((SystemExit, ValueError), match=message):
        main(["train", "--tiny", "--steps", "1", *argv])


def test_model_flops_of_the_benchmarks_cut():
    """The dense layer and five sparse ones, two sequences of 8192 tokens:
    53.7 TFLOP a step (ISSUE 32), causal attention at half of S^2 and the
    unpadded widths, the routed experts at 6 * 16 / 128 applications."""
    from jimm_tpu.train.metrics import train_step_flops
    cfg = preset("kanana-2-30b-a3b")
    cut = dataclasses.replace(cfg, decoder=dataclasses.replace(cfg.decoder,
                                                               depth=6))
    assert train_step_flops(cut, 2) == pytest.approx(53.7e12, rel=2e-3)
