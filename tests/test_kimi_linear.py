"""The hybrid decoder language model (`models/kimi_linear.py`, `nn/kda.py`,
the runs of `models/kanana.py`) at a small size on the CPU: the mixed
stack's runs, position-free latent attention, the share an expert layer
holds, the scopes and counters, and its way through `jimm-tpu train`.
Agreement with the plain reference is `tests/benchmark/test_hybrid_lm.py`'s;
the chunked delta rule and its kernels (`ops/delta_rule.py`) are
`tests/test_delta_rule.py`'s; its entries in the CLI's tables, its `presets`
line and the modules no other preset may import are
`tests/test_families.py`'s."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

from family_util import train_rows
from jimm_tpu import KimiLinear, KimiLinearConfig, preset
from jimm_tpu.cli import _tiny_override
from jimm_tpu.configs import (KDAConfig, MLAConfig, MoEConfig,
                              TransformerConfig)
from jimm_tpu.nn.moe import SparseMoe


def _tiny(**decoder) -> KimiLinearConfig:
    cfg = _tiny_override(preset("kimi-linear-48b-a3b"))
    return dataclasses.replace(cfg, decoder=dataclasses.replace(cfg.decoder,
                                                                **decoder))


# -- the preset, the mixed stack ---------------------------------------------

def test_preset_is_one_chips_share_of_the_published_shapes():
    d = preset("kimi-linear-48b-a3b").decoder
    assert (d.width, d.depth, d.dense_layers, d.num_heads, d.mlp_dim) \
        == (2304, 5, 1, 32, 9216)
    assert (d.vocab_size, d.seq_len, d.ln_eps, d.rope_theta) \
        == (163840 // 8, 16384, 1e-5, None)
    assert d.kda == KDAConfig(num_heads=32, head_dim=128, conv_taps=4,
                              gate_rank=128, chunk=64)
    assert d.mla == MLAConfig(kv_lora_rank=512, qk_nope_dim=128,
                              qk_rope_dim=64, v_head_dim=128)
    assert d.moe == MoEConfig(num_experts=256, top_k=8, expert_dim=1024,
                              shared_experts=1, routed_scale=2.446,
                              held_experts=16, first_expert=0)
    # the published 27 layers: full attention on 4, 8, ..., 24 and 27
    assert len(d.mixers) == 27
    assert [i + 1 for i, m in enumerate(d.mixers) if m == "mla"] \
        == [4, 8, 12, 16, 20, 24, 27]
    assert d.held_mixers == ("kda", "kda", "kda", "mla", "kda")
    model = nnx.eval_shape(lambda: KimiLinear(rngs=nnx.Rngs(0)))
    n = sum(int(np.prod(v.shape)) for _, v in
            nnx.to_flat_state(nnx.state(model, nnx.Param)))
    assert n == 828_925_824  # ISSUE 38's 828.9 M: 6.63 GB at 8 bytes


@pytest.mark.parametrize("first, depth, want", [
    (0, 5, [("run0", "kda", False, 1), ("run1", "kda", True, 2),
            ("run3", "mla", True, 1), ("run4", "kda", True, 1)]),
    (0, 9, [("run0", "kda", False, 1), ("run1", "kda", True, 2),
            ("run3", "mla", True, 1), ("run4", "kda", True, 3),
            ("run7", "mla", True, 1), ("run8", "kda", True, 1)]),
    (3, 3, [("run0", "mla", False, 1), ("run1", "kda", True, 2)]),
    (22, 5, [("run0", "kda", False, 1), ("run1", "mla", True, 1),
             ("run2", "kda", True, 2), ("run4", "mla", True, 1)]),
])
def test_runs_follow_the_published_order(first, depth, want):
    d = dataclasses.replace(preset("kimi-linear-48b-a3b").decoder,
                            first_layer=first, depth=depth)
    runs = d.runs()
    assert [(name, "kda" if c.kda else "mla", c.moe is not None, c.depth)
            for name, c in runs] == want
    assert all((c.kda is None) != (c.mla is None) for _, c in runs)
    assert sum(c.depth for _, c in runs) == depth


def test_a_stack_of_one_kind_is_one_run_and_the_default_two():
    d = preset("kimi-linear-48b-a3b").decoder
    one = dataclasses.replace(d, mixers=("kda",) * 6, depth=6, dense_layers=0)
    assert [(n, c.depth) for n, c in one.runs()] == [("run0", 6)]
    assert [(n, c.depth) for n, c in dataclasses.replace(
        one, dense_layers=2).runs()] == [("run0", 2), ("run2", 4)]
    # no `mixers`: every layer alike, the two stacks the older families hold
    for name in ("kanana-2-30b-a3b", "trinity-large"):
        old = preset(name).decoder
        runs = old.runs()
        assert [n for n, _ in runs] == ["dense", "sparse"]
        assert runs[0][1] == old.encoder(sparse=False)
        assert runs[1][1] == old.encoder(sparse=True)
        assert all(c.kda is None for _, c in runs)
    with pytest.raises(ValueError, match="not among"):
        dataclasses.replace(d, first_layer=25).held_mixers


@pytest.fixture(scope="module")
def model():
    return KimiLinear(_tiny(), rngs=nnx.Rngs(0))


def test_tiny_model_shapes_and_outputs(model):
    d = model.config.decoder
    assert model.run_names == ("run0", "run1", "run3", "run4")
    assert [r.cfg.depth for r in model.sparse_runs()] == [2, 1, 1]
    kda, mla = model.run1.blocks.attn, model.run3.blocks.attn
    assert type(kda).__name__ == "KimiDeltaAttention"
    assert type(mla).__name__ == "LatentAttention"
    # fifteen leaves a KDA mixer, five a latent-attention one
    assert len(jax.tree.leaves(nnx.state(kda, nnx.Param))) == 15
    assert len(jax.tree.leaves(nnx.state(mla, nnx.Param))) == 5
    assert kda.q_conv[...].shape == (2, 4, 64) and kda.A_log.shape == (2, 4)
    a = jnp.exp(kda.A_log[...])
    assert float(a.min()) >= 1.0 and float(a.max()) <= 16.0
    assert float(jnp.abs(kda.dt_bias[...]).max()) == 0.0
    tokens = jax.random.randint(jax.random.key(1), (2, d.seq_len), 0,
                                d.vocab_size)
    hidden, chosen = model.hidden_states(tokens)
    assert hidden.shape == (2, d.seq_len, d.width)
    assert chosen.shape == (4, 2 * d.seq_len, d.moe.top_k)
    assert model(tokens).shape == (2, d.seq_len, d.vocab_size)
    assert bool(jnp.all(jnp.isfinite(model(tokens))))
    assert model.router_bias().shape == (4, d.moe.num_experts)


def test_the_model_is_causal_and_takes_no_position_signal(model):
    """A later token moves no earlier output (the convolutions, the
    recurrence and the latent attention all look left); and there is no
    position table anywhere in the tree."""
    d = model.config.decoder
    tokens = jax.random.randint(jax.random.key(2), (1, d.seq_len), 0,
                                d.vocab_size)
    moved = tokens.at[0, 20].set((tokens[0, 20] + 1) % d.vocab_size)
    a, b = model(tokens), model(moved)
    np.testing.assert_array_equal(a[0, :20], b[0, :20])
    assert float(jnp.abs(a[0, 20:] - b[0, 20:]).max()) > 0
    assert all(run.cfg.rope_theta is None
               for run in map(model.__getattribute__, model.run_names))


def test_the_bias_update_reaches_every_sparse_run(model):
    d = model.config.decoder
    before = model.router_bias()
    counts = jnp.zeros((4, d.moe.num_experts), jnp.int32) \
        .at[jnp.arange(4), jnp.arange(4)].set(100)
    model.update_router_bias(counts)
    moved = model.router_bias() - before
    rate = model.config.bias_update_rate
    # layer l's overloaded expert is expert l: down there, up everywhere else
    for layer in range(4):
        want = jnp.full((d.moe.num_experts,), rate).at[layer].set(-rate)
        np.testing.assert_allclose(moved[layer], want, rtol=1e-6)
    per_run = [r.blocks.mlp.router_bias[...] for r in model.sparse_runs()]
    assert [b.shape[0] for b in per_run] == [2, 1, 1]
    np.testing.assert_array_equal(jnp.concatenate(per_run),
                                  model.router_bias())


def test_train_step_moves_the_bias_and_reports_the_routing():
    from jimm_tpu.train import OptimizerConfig, make_optimizer
    from jimm_tpu.train.trainer import make_lm_train_step
    model = KimiLinear(_tiny(), rngs=nnx.Rngs(0))
    d = model.config.decoder
    optimizer = make_optimizer(model, OptimizerConfig(learning_rate=1e-3,
                                                      total_steps=4))
    tokens = jax.random.randint(jax.random.key(0), (2, d.seq_len + 1), 0,
                                d.vocab_size)
    step = make_lm_train_step("kimi")
    first = step(model, optimizer, tokens)
    assert set(first) == {"loss", "moe_held_rows", "moe_load_max_over_mean",
                          "router_bias_absmax"}
    assert float(first["router_bias_absmax"]) == pytest.approx(1e-3)
    assert 0 < float(first["moe_held_rows"]) <= 4 * 2 * d.seq_len * 2
    losses = [float(first["loss"])] + [
        float(step(model, optimizer, tokens)["loss"]) for _ in range(3)]
    assert losses[-1] < losses[0]
    assert all(float(jnp.abs(r.blocks.mlp.router_bias[...]).max()) > 0
               for r in model.sparse_runs())


# -- position-free latent attention -------------------------------------------

def _mla(rope_theta):
    cfg = TransformerConfig(
        width=64, depth=1, num_heads=4, causal=True, norm="rms",
        rope_theta=rope_theta, use_bias=False, attn_impl="xla",
        mla=MLAConfig(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                      v_head_dim=16))
    from jimm_tpu.nn.mla import LatentAttention
    return LatentAttention(cfg, nnx.Rngs(0))


def test_latent_attention_without_rope_is_the_reference_and_with_it_kananas():
    from benchmarks.reference import kanana as ref_rope, kimi_linear as ref
    from jimm_tpu.nn.transformer import rope_tables
    layer = _mla(None)
    x = jax.random.normal(jax.random.key(3), (2, 24, 64))
    params = jax.tree.map(jnp.asarray, nnx.to_pure_dict(
        nnx.state(layer, nnx.Param)))
    sizes = {"num_attention_heads": 4, "qk_nope_head_dim": 16,
             "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
             "rms_norm_eps": 1e-6, "rope_theta": 1e4}
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(layer(x, rope=None),
                                   ref.mla(x, params, sizes),
                                   rtol=2e-5, atol=2e-6)
        # kanana's path: the same weights under rotary equal ITS reference,
        # and differ from the position-free result
        turned = layer(x, rope=rope_tables(24, 8, 1e4))
        np.testing.assert_allclose(turned, ref_rope.mla(x, params, sizes),
                                   rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(turned - layer(x, rope=None)).max()) > 1e-3
    # without a position signal, a permutation of the earlier tokens leaves
    # the last token's output where it was
    perm = jnp.concatenate([jnp.arange(23)[::-1], jnp.array([23])])
    np.testing.assert_allclose(layer(x[:, perm], rope=None)[:, -1],
                               layer(x, rope=None)[:, -1], rtol=2e-5,
                               atol=2e-6)


# -- the share of an expert layer ----------------------------------------------

def _layer(held, first=0):
    cfg = TransformerConfig(
        width=64, act="silu", norm="rms", use_bias=False,
        moe=MoEConfig(num_experts=32, top_k=8, expert_dim=48,
                      shared_experts=1, routed_scale=2.446,
                      held_experts=held, first_expert=first))
    return SparseMoe(cfg, nnx.Rngs(5))


def test_the_shares_of_all_sixteen_chips_add_up_to_the_whole_layer():
    """This model's layer at a small size (32 experts, top-8 of one group,
    one shared expert, the routed scale 2.446): sixteen chips of 2 experts
    each and one router; the routed parts of the sixteen shares, with the
    shared expert counted once, are the uncut layer."""
    whole = _layer(held=32)
    x = jax.random.normal(jax.random.key(2), (2, 64, 64))
    with jax.default_matmul_precision("highest"):
        want, chosen = whole(x)
        shared = whole.shared(x)
        total = shared
        for first in range(0, 32, 2):
            share = _layer(held=2, first=first)
            share.router[...] = whole.router[...]
            nnx.update(share.shared, nnx.state(whole.shared))
            for name in ("gate", "up", "down"):
                getattr(share, name)[...] = \
                    getattr(whole, name)[...][first:first + 2]
            y, chosen_here = share(x)
            assert (chosen_here == chosen).all()
            total = total + (y - shared)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_the_grouped_products_keep_the_tuned_tiles_at_this_width():
    """2304 wide, experts of 1024: the tile rule of kanana's and Trinity's
    shapes holds here unchanged (tiles fitted to 2304 / 1024 read 28.93 ms
    against 29.03 on the chip, PERF.md section 6: nothing to tell apart, so
    no rule was added), and the rows' tile and chunk follow the load."""
    from jimm_tpu.nn import moe
    assert moe.tiling(512, 2) == moe._GMM_TILING == (512, 1024, 768)
    assert moe.tiling(128, 2) == moe._SMALL_GROUP_TILING
    assert moe.row_tile(16384, 8, 256) == 512
    assert moe.chunk_rows(16384, 8, 16, 256) == 11264


# -- scopes and counters ---------------------------------------------------------

def test_scopes_are_in_the_lowered_step_and_the_counters_count():
    from jimm_tpu import obs
    from jimm_tpu.train import OptimizerConfig, make_optimizer
    from jimm_tpu.train.trainer import make_lm_train_step
    model = KimiLinear(_tiny(), rngs=nnx.Rngs(0))
    optimizer = make_optimizer(model, OptimizerConfig(total_steps=4))
    tokens = jnp.zeros((2, model.config.decoder.seq_len + 1), jnp.int32)
    before = obs.snapshot()
    text = make_lm_train_step("kimi").lower(model, optimizer, tokens) \
        .as_text(debug_info=True)
    for scope in ("kda", "kda_proj", "kda_scan", "kda_out", "mla", "moe",
                  "moe_route", "moe_experts", "moe_shared", "embed",
                  "decoder_stack", "lm_head"):
        assert re.search(rf'[/"(]{scope}[/")]', text), scope
    after = obs.snapshot()
    calls = after["jimm_kda_calls_total"] \
        - before.get("jimm_kda_calls_total", 0)
    chunks = after["jimm_kda_chunks_total"] \
        - before.get("jimm_kda_chunks_total", 0)
    # three runs of KDA layers, each traced at least once; 32 tokens in
    # chunks of 16: two chunk steps a call
    assert calls >= 3 and chunks == 2 * calls


# -- through the CLI ------------------------------------------------------------------
# (its entries in the CLI's tables, its `presets` line and the modules no
# other preset may import: `tests/test_families.py`)

def test_train_cli_runs_the_family_through_the_same_loop(tmp_path, capsys):
    rows = train_rows(tmp_path, capsys, [
        "--preset", "kimi-linear-48b-a3b", "--tiny", "--steps", "3",
        "--batch-size", "2", "--log-every", "1", "--bf16", "--remat", "dots"])
    assert all("moe_held_rows" in r for r in rows)


def test_num_layers_and_seq_len_shape_the_preset():
    from jimm_tpu import cli
    cfg = cli._replace_towers(preset("kimi-linear-48b-a3b"), depth=9,
                              seq_len=4096)
    assert [n for n, _ in cfg.decoder.runs()] \
        == ["run0", "run1", "run3", "run4", "run7", "run8"]
    assert cfg.decoder.seq_len == 4096


def test_model_flops_of_the_benchmarks_cut():
    """The program's own count (`train/metrics.py`) of the cell's step: KDA's
    projections and recurrence on four layers, latent attention at half of
    S^2 on one; the benchmark's yardstick (`benchmarks/flops_hybrid_lm.py`,
    kept apart) counts the convolutions besides."""
    from jimm_tpu.train.metrics import model_fwd_flops
    step = 3 * model_fwd_flops(preset("kimi-linear-48b-a3b"))
    assert step == pytest.approx(42.55e12, rel=2e-3)
