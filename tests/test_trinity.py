"""Trinity-Large-Preview's family on the CPU at its tiny preset: grouped-query
attention with qk-norm and a gate, windowed layers with rotary beside full
layers without any position signal, the two stacks, the train step and the
CLI. The model against its plain reference is
``tests/benchmark/test_gqa_moe_lm.py``; its entries in the CLI's tables and
its ``presets`` line are ``tests/test_families.py``'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

from family_util import train_rows
from jimm_tpu import Trinity, preset
from jimm_tpu.cli import _tiny_override
from jimm_tpu.configs import GQAConfig, TransformerConfig, TrinityConfig
from jimm_tpu.nn import moe
from jimm_tpu.nn.transformer import (Attention, Block, Transformer,
                                     rope_tables)


def _tiny(**decoder) -> TrinityConfig:
    cfg = _tiny_override(preset("trinity-large"))
    return dataclasses.replace(cfg, decoder=dataclasses.replace(
        cfg.decoder, **decoder))


def test_preset_is_one_chips_share_of_the_published_shapes():
    d = preset("trinity-large").decoder
    assert (d.width, d.num_heads, d.mlp_dim, d.ln_eps, d.rope_theta) == (
        3072, 48, 12288, 1e-5, 1e4)
    assert d.gqa == GQAConfig(head_dim=128, kv_heads=8, qk_norm=True,
                              gate=True, window=4096, full_every=4)
    assert d.mla is None and d.post_norm
    assert (d.moe.num_experts, d.moe.top_k, d.moe.expert_dim,
            d.moe.shared_experts, d.moe.routed_scale, d.moe.held_experts) == (
        256, 4, 3072, 1, 2.448, 8)
    # the share: from the last dense layer on, an eighth of the vocabulary
    assert (d.first_layer, d.dense_layers, d.depth) == (5, 1, 55)
    assert d.vocab_size * 8 == 200192
    # published layers 5 | 6 7 8 9: the full layer is layer 7
    cut = dataclasses.replace(d, depth=5)
    assert cut.encoder(sparse=False).gqa.full_layers(1) == (False,)
    assert cut.encoder(sparse=True).gqa.full_layers(4) == (
        False, True, False, False)
    assert [i for i in range(60)
            if GQAConfig().full_layers(60)[i]] == list(range(3, 60, 4))
    assert not any(GQAConfig(full_every=0).full_layers(8))
    assert cut.encoder(sparse=True).head_dim == 128 == \
        cut.encoder(sparse=True).rope_dim


@pytest.fixture(scope="module")
def model():
    return Trinity(_tiny(), rngs=nnx.Rngs(0))


def test_tiny_model_shapes_and_outputs(model):
    d = model.config.decoder
    assert (d.depth, d.dense_layers, d.gqa.kv_heads, d.gqa.window,
            d.moe.held_experts) == (4, 1, 2, 8, 4)
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, 512)
    hidden, chosen = model.hidden_states(tokens)
    assert hidden.shape == (2, 32, 64) and chosen.shape == (3, 64, 2)
    assert model(tokens).shape == (2, 32, 512)
    assert np.isfinite(np.asarray(model(tokens))).all()
    attn = model.sparse.blocks.attn
    assert attn.q.kernel.shape == (3, 64, 128)      # 4 heads of 32
    assert attn.k.kernel.shape == attn.v.kernel.shape == (3, 64, 64)
    assert attn.gate.kernel.shape == (3, 64, 128)
    assert attn.out.kernel.shape == (3, 128, 64)
    assert attn.q_norm.scale.shape == attn.k_norm.scale.shape == (3, 32)
    assert model.sparse.blocks.ln1_post.scale.shape == (3, 64)
    # the embedding is scaled by sqrt(width)
    one = Trinity(_tiny(depth=2), rngs=nnx.Rngs(0))
    np.testing.assert_allclose(
        jnp.std(one.embed(tokens)) * 8.0, 0.02 * 8.0, rtol=0.1)


def _attention(**gqa) -> Attention:
    return Attention(64, 4, nnx.Rngs(0), is_causal=True, impl="xla",
                     use_bias=False,
                     gqa=GQAConfig(head_dim=32, kv_heads=2, window=8, **gqa))


def test_the_sandwich_is_depth_scaled_where_the_preset_says_so(model):
    """The second norm of each sub-layer starts at 1 / sqrt(60 published
    layers) whatever depth is held, the first norms and the final one at 1;
    a sandwich without the field (the looped decoder's) starts at 1."""
    d = preset("trinity-large").decoder
    assert d.post_norm_gain == pytest.approx(60 ** -0.5)
    assert dataclasses.replace(d, depth=5).encoder(sparse=True) \
        .post_norm_gain == d.post_norm_gain
    for stack in (model.dense, model.sparse):
        for name, start in (("ln1", 1.0), ("ln2", 1.0),
                            ("ln1_post", 60 ** -0.5),
                            ("ln2_post", 60 ** -0.5)):
            np.testing.assert_allclose(
                getattr(stack.blocks, name).scale[...], start, rtol=1e-6)
    np.testing.assert_allclose(model.norm.scale[...], 1.0)
    plain = TransformerConfig(width=32, depth=1, num_heads=2, mlp_dim=64,
                              norm="rms", post_norm=True)
    assert plain.post_norm_gain == 1.0
    np.testing.assert_array_equal(
        Block(plain, nnx.Rngs(0)).ln1_post.scale[...], 1.0)
    assert preset("kanana-2-30b-a3b").decoder.post_norm_gain == 1.0


def test_windowed_layers_take_rotary_and_full_layers_no_position_at_all():
    attn = _attention()
    x = jax.random.normal(jax.random.key(0), (1, 32, 64))
    rope = rope_tables(32, 32, 1e4)
    # a full layer ignores the rotary tables; a windowed one does not
    np.testing.assert_array_equal(attn(x, rope=rope, full=True),
                                  attn(x, rope=None, full=True))
    assert not np.allclose(attn(x, rope=rope), attn(x, rope=None), atol=1e-3)
    # a full layer sees every earlier token whatever the order they came in:
    # the last position's output does not change when its past is shuffled
    order = jnp.concatenate([jax.random.permutation(jax.random.key(1), 31),
                             jnp.asarray([31])])
    np.testing.assert_allclose(attn(x[:, order], full=True)[0, -1],
                               attn(x, full=True)[0, -1], rtol=1e-4,
                               atol=1e-5)
    # a windowed layer sees the 8 newest positions, its own counted
    moved = x.at[0, 23].add(1.0)    # 31 - 23 = 8: just outside
    np.testing.assert_allclose(attn(moved, rope=rope)[0, 31],
                               attn(x, rope=rope)[0, 31], atol=1e-6)
    moved = x.at[0, 24].add(1.0)    # 31 - 24 = 7: inside
    assert not np.allclose(attn(moved, rope=rope)[0, 31],
                           attn(x, rope=rope)[0, 31], atol=1e-4)
    assert not np.allclose(attn(x.at[0, 3].add(1.0), full=True)[0, 31],
                           attn(x, full=True)[0, 31], atol=1e-5)


def test_a_traced_layer_kind_picks_between_the_two_calls():
    attn = _attention()
    x = jax.random.normal(jax.random.key(0), (2, 32, 64))
    rope = rope_tables(32, 32, 1e4)
    picked = jax.jit(lambda full: attn(x, rope=rope, full=full))
    for full in (False, True):
        np.testing.assert_allclose(picked(jnp.asarray(full)),
                                   attn(x, rope=rope, full=full), rtol=1e-5,
                                   atol=1e-6)


def test_gate_and_qk_norm_are_what_the_equations_say():
    x = jax.random.normal(jax.random.key(0), (1, 16, 64))
    gated, plain = _attention(), _attention(gate=False)
    nnx.update(plain, nnx.state(plain).__class__(
        {k: v for k, v in nnx.state(gated).items() if k != "gate"}))
    gated.gate.kernel[...] = jnp.zeros_like(gated.gate.kernel[...])
    # sigmoid(0) = 1/2 on every lane before the output projection
    np.testing.assert_allclose(gated(x), 0.5 * plain(x), rtol=1e-5, atol=1e-6)
    # q and k are normed per head: scaling W_q changes nothing
    before = plain(x)
    plain.q.kernel[...] = 3.0 * plain.q.kernel[...]
    np.testing.assert_allclose(plain(x), before, rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="causal"):
        Attention(64, 4, nnx.Rngs(0), gqa=GQAConfig(head_dim=32, kv_heads=2))


def test_a_stack_mixes_windowed_and_full_layers():
    """Layers 6-9 of the pattern in one scanned stack (the full layer is its
    second) against the same blocks applied one by one with a static kind."""
    cfg = TransformerConfig(
        width=64, depth=4, num_heads=4, mlp_dim=96, act="silu", causal=True,
        norm="rms", post_norm=True, rope_theta=1e4, gated_mlp=True,
        use_bias=False, attn_impl="xla",
        gqa=GQAConfig(head_dim=32, kv_heads=2, window=8, first_layer=6))
    assert cfg.gqa.full_layers(4) == (False, True, False, False)
    x = jax.random.normal(jax.random.key(0), (2, 32, 64))
    for remat in (False, True):
        stack = Transformer(dataclasses.replace(cfg, remat=remat),
                            nnx.Rngs(0))
        got = stack(x)
        stacked = nnx.to_pure_dict(nnx.state(stack.blocks, nnx.Param))
        want, rope = x, rope_tables(32, 32, 1e4)
        for i, full in enumerate(cfg.gqa.full_layers(4)):
            block = Block(cfg, nnx.Rngs(0))
            nnx.update(block, jax.tree.map(lambda p: p[i], stacked))
            want = block(want, rope=rope, full=full)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        # and the gradient passes the cond under remat
        g = nnx.grad(lambda m: jnp.sum(m(x) ** 2))(stack)
        assert all(np.isfinite(np.asarray(leaf)).all()
                   for leaf in jax.tree.leaves(g))
    # a stack of one kind takes no cond
    one = Transformer(dataclasses.replace(
        cfg, depth=2, gqa=dataclasses.replace(cfg.gqa, first_layer=4)),
        nnx.Rngs(0))
    def jaxpr(stack):
        graphdef, state = nnx.split(stack)
        return str(jax.make_jaxpr(
            lambda state, x: nnx.merge(graphdef, state)(x))(state, x))

    assert " cond[" not in jaxpr(one)
    assert " cond[" in jaxpr(Transformer(cfg, nnx.Rngs(0)))


@pytest.mark.parametrize("routing", ["every_token_to_held_experts",
                                     "as_it_falls"])
def test_the_overflow_loops_own_backward_is_the_plain_one(routing,
                                                          monkeypatch):
    """Over `_LOOP_RESIDUAL_LIMIT` the overflow's backward walks the chunks
    itself instead of differentiating the loop: the same gradients."""
    monkeypatch.setattr(moe, "_MIN_CHUNK_ROWS", 0)
    cfg = _tiny().decoder.encoder(sparse=True)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=16, held_experts=4))
    layer = moe.SparseMoe(cfg, nnx.Rngs(0))
    x = jax.random.normal(jax.random.key(1), (2, 1024, 64))
    push = 10.0 if routing == "every_token_to_held_experts" else 0.0
    layer.router_bias[...] = jnp.zeros((16,)).at[:4].set(push)

    def grads():
        fn = nnx.jit(nnx.grad(lambda m, x: jnp.sum(m(x)[0] ** 2),
                              argnums=(0, 1)))
        gm, gx = fn(layer, x)
        return [gx, *(gm[name][...] for name in ("gate", "up", "down",
                                                 "router"))]

    plain = grads()
    monkeypatch.setattr(moe, "_LOOP_RESIDUAL_LIMIT", 0)
    own = grads()
    for a, b in zip(own, plain, strict=True):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_a_chunk_has_room_and_its_row_tile_follows_the_groups(monkeypatch):
    """The two shapes the benchmark runs: 16,384 rows in tiles of 512 where an
    expert expects 768 (as before this floor), 4,096 rows in tiles of 128
    where 8 experts expect 128 each and a random router sends them 400-2,300
    a layer."""
    assert moe.chunk_rows(16384, 6, 16, 128) == 16384
    assert moe.row_tile(16384, 6, 128) == 512
    assert moe.chunk_rows(8192, 4, 8, 256) == 4096
    assert moe.row_tile(8192, 4, 256) == 128
    assert moe.row_tile(8192, 4, 128) == 256 and moe.row_tile(64, 2, 8) == 128
    # groups of 128 rows keep an expert's weights in VMEM over their row
    # tiles; larger ones keep the tiles tuned at kanana's shape
    assert moe.tiling(512, 2) == (512, 1024, 768) == moe._GMM_TILING
    assert moe.tiling(256, 2) == (256, 1024, 768)
    assert moe.tiling(128, 2) == (128, 3072, 512)
    assert moe.tiling(128, 4) == (128, 1536, 512)   # float32: half the depth
    assert moe.chunk_rows(64, 2, 4, 8) == 128      # at most every assignment
    monkeypatch.setattr(moe, "_MIN_CHUNK_ROWS", 0)
    assert moe.chunk_rows(8192, 4, 8, 256) == 1536


def test_the_limit_sorts_the_two_sparse_families():
    """The accepted sparse cell stays on the plain path (5 later chunks x 151
    MB of expert weights), this family's takes its own (21 x 453 MB)."""
    from jimm_tpu.nn.moe import chunk_rows
    limit = moe._LOOP_RESIDUAL_LIMIT

    def kept(tokens, top_k, held, experts, width, expert_dim):
        rows = chunk_rows(tokens, top_k, held, experts)
        later = len(range(rows, -(-tokens * top_k // rows) * rows, rows))
        return later * 3 * held * width * expert_dim * 2

    assert kept(16384, 6, 16, 128, 2048, 768) < limit     # kanana's cell
    assert kept(8192, 4, 8, 256, 3072, 3072) > 2 * limit  # this family's


def test_train_step_moves_the_bias_and_reports_the_routing():
    from jimm_tpu.train import OptimizerConfig, make_optimizer
    from jimm_tpu.train.trainer import make_lm_train_step
    model = Trinity(_tiny(), rngs=nnx.Rngs(0))
    optimizer = make_optimizer(model, OptimizerConfig(total_steps=4))
    step = make_lm_train_step("trinity")
    tokens = jax.random.randint(jax.random.key(2), (2, 33), 0, 512)
    metrics = step(model, optimizer, tokens)
    assert set(metrics) == {"loss", "moe_held_rows", "moe_load_max_over_mean",
                            "router_bias_absmax"}
    assert float(metrics["router_bias_absmax"]) == pytest.approx(1e-3)
    # 64 tokens x top-2 x 3 sparse layers, half of the 8 experts held
    assert 0 < float(metrics["moe_held_rows"]) < 2 * 64 * 3
    first = float(metrics["loss"])
    for _ in range(3):
        metrics = step(model, optimizer, tokens)
    assert float(metrics["loss"]) < first


# -- through the CLI ------------------------------------------------------------------
# (its entries in the CLI's tables and its `presets` line:
# `tests/test_families.py`)

def test_train_cli_runs_the_family_through_the_same_loop(tmp_path, capsys):
    logged = train_rows(tmp_path, capsys, [
        "--preset", "trinity-large", "--tiny", "--steps", "3",
        "--batch-size", "2"])
    for row in logged:
        assert {"loss", "moe_held_rows", "moe_load_max_over_mean",
                "router_bias_absmax", "phases"} <= set(row)
    assert logged[-1]["router_bias_absmax"] > logged[0]["router_bias_absmax"]


def test_num_layers_and_seq_len_shape_the_preset():
    from jimm_tpu import cli
    args = cli.build_parser().parse_args(
        ["train", "--preset", "trinity-large", "--num-layers", "5",
         "--seq-len", "8192", "--bf16", "--remat", "full"])
    cfg = cli._replace_towers(preset(args.preset), depth=5, seq_len=8192)
    built = nnx.eval_shape(lambda: Trinity(cfg, rngs=nnx.Rngs(0)))
    n = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(
        nnx.state(built, nnx.Param)))
    assert n == 1_603_993_856
    assert built.sparse.blocks.mlp.gate.shape == (4, 8, 3072, 3072)


def test_model_flops_of_the_benchmarks_cut():
    """The program's own count: 41.1 TFLOP a step at one sequence of 8192
    (attention at the visible pairs of each layer kind, experts at
    4 * 8 / 256 applications a token)."""
    from jimm_tpu.train.metrics import train_step_flops
    cfg = preset("trinity-large")
    cut = dataclasses.replace(cfg, decoder=dataclasses.replace(cfg.decoder,
                                                               depth=5))
    assert train_step_flops(cut, 1) == pytest.approx(41.12e12, rel=2e-3)


def test_full_remat_keeps_a_sparse_layers_routing_choices():
    """`--remat full` recomputes everything but the (tokens, top_k) expert
    ids: a recomputed top-k breaks near-ties its own way, and the backward
    would run other routes than the forward (PERF.md, PR 34)."""
    sparse = _tiny(remat=True).decoder.encoder(sparse=True)
    dense = _tiny(remat=True).decoder.encoder(sparse=False)
    assert sparse.remat_policy == dense.remat_policy == "none"
    stack = Transformer(sparse, nnx.Rngs(0))
    assert Transformer(dense, nnx.Rngs(0))._remat_policy() is None
    assert stack._remat_policy() is not None
    graphdef, state = nnx.split(stack)
    x = jax.random.normal(jax.random.key(0), (2, 32, 64))
    text = str(jax.make_jaxpr(lambda state, x: nnx.grad(
        lambda m: jnp.sum(m(x)[0] ** 2))(nnx.merge(graphdef, state)))(
            state, x))
    assert "name=moe_chosen" in text
