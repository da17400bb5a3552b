"""The looped decoder language model (`models/ouro.py`) at a small size on
the CPU: width 64, 4 heads of 16, MLP 176, vocabulary 512, n = 2 layers,
R = 4 passes, S = 32, seeded random weights, float32. Its entries in the
CLI's tables and its `presets` line are `tests/test_families.py`'s."""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

from family_util import train_rows
from jimm_tpu import DecoderConfig, Ouro, OuroConfig, preset
from jimm_tpu.cli import _tiny_override, main
from jimm_tpu.nn.transformer import Transformer, apply_rope, rope_tables
from jimm_tpu.train.losses import (blocked_cross_entropy, exit_distribution,
                                   expected_exit_loss)
from jimm_tpu.train.trainer import lm_loss_fn

SMALL = DecoderConfig(vocab_size=512, seq_len=32, width=64, depth=2,
                      num_heads=4, mlp_dim=176, loops=4)


def _model(decoder=SMALL, seed=0, **kw):
    model = Ouro(OuroConfig(decoder=dataclasses.replace(decoder, **kw)),
                 rngs=nnx.Rngs(seed))
    # the norm scales are born as ones: give them weight, so that a norm
    # left out or applied twice shows
    keys = iter(jax.random.split(jax.random.key(seed + 100), 64))
    state = jax.tree.map(
        lambda a: a + 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
        if a.ndim <= 2 and a.shape[-1] == decoder.width else a,
        nnx.state(model, nnx.Param))
    nnx.update(model, state)
    return model


def _tokens(seed=1, batch=2, decoder=SMALL):
    return jax.random.randint(jax.random.key(seed),
                              (batch, decoder.seq_len + 1), 0,
                              decoder.vocab_size, jnp.int32)


def test_tiny_preset_is_the_small_size_and_keeps_its_passes():
    tiny = _tiny_override(preset("ouro-2.6b")).decoder
    assert dataclasses.replace(tiny, **{
        f.name: getattr(SMALL, f.name) for f in dataclasses.fields(SMALL)
        if f.name in ("vocab_size", "seq_len", "width", "depth", "num_heads",
                      "mlp_dim", "loops")}) == tiny
    published = preset("ouro-2.6b").decoder
    assert (published.depth, published.loops, published.width,
            published.width // published.num_heads, published.mlp_dim,
            published.vocab_size) == (48, 4, 2048, 128, 5632, 49152)


class _Unrolled(nnx.Module):
    """The looped stack written out: ``passes`` independent COPIES of the
    stacked blocks, one per pass, so that each copy has a gradient of its
    own."""

    def __init__(self, transformer: Transformer, passes: int,
                 between_pass_norm: bool = True):
        self.passes, self.between_pass_norm = passes, between_pass_norm
        for r in range(passes):
            setattr(self, f"copy{r}", nnx.clone(transformer.blocks))
        self.norm = nnx.clone(transformer.norm)
        self.stack = transformer._apply_stack
        self.head_dim = transformer.cfg.head_dim
        self.theta = transformer.cfg.rope_theta

    def __call__(self, x):
        rope = rope_tables(x.shape[1], self.head_dim, self.theta)
        out = []
        for r in range(self.passes):
            x = self.stack(getattr(self, f"copy{r}"), x, None, rope)
            normed = self.norm(x)
            out.append(normed)
            x = normed if self.between_pass_norm else x
        return jnp.stack(out)


@pytest.mark.parametrize("taken_out", [None, "pass", "between_pass_norm"])
def test_looped_stack_is_an_unrolled_stack_with_tied_weights(taken_out):
    """Equal outputs, and a shared leaf's gradient is the sum of the four
    copies'; with a pass or the between-pass norm taken out of the unrolled
    side they differ."""
    model = _model()
    x = jax.random.normal(jax.random.key(3), (2, SMALL.seq_len, SMALL.width))
    probe = jax.random.normal(jax.random.key(4),
                              (SMALL.loops, 2, SMALL.seq_len, SMALL.width))
    passes = SMALL.loops - (taken_out == "pass")
    unrolled = _Unrolled(model.decoder, passes,
                         between_pass_norm=taken_out != "between_pass_norm")
    got, want = model.decoder(x), unrolled(x)
    if taken_out is not None:
        assert float(jnp.max(jnp.abs(got[-1] - want[-1]))) > 0.1
        return
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    shared = nnx.to_pure_dict(nnx.grad(
        lambda m: jnp.sum(m.decoder(x) * probe))(model))["decoder"]["blocks"]
    copies = nnx.to_pure_dict(nnx.grad(
        lambda u: jnp.sum(u(x) * probe))(unrolled))
    for path in (("attn", "q", "kernel"), ("mlp", "gate", "kernel"),
                 ("ln1_post", "scale")):
        def leaf(tree):
            for key in path:
                tree = tree[key]
            return tree
        each = [leaf(copies[f"copy{r}"]) for r in range(passes)]
        np.testing.assert_allclose(leaf(shared), sum(each), rtol=2e-4,
                                   atol=2e-4)
        assert float(jnp.max(jnp.abs(leaf(shared) - each[0]))) > 1e-3, (
            "one copy's gradient is not the shared leaf's")


def test_one_pass_is_a_plain_decoder():
    """R = 1: blocks, the final norm, the head; the exit distribution is all
    on the one pass and the loss is that pass's cross-entropy."""
    model = _model(loops=1)
    tokens = _tokens()
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    hidden = model.hidden_states(inputs)
    assert hidden.shape == (1, 2, SMALL.seq_len, SMALL.width)
    t = model.decoder
    rope = rope_tables(SMALL.seq_len, t.cfg.head_dim, t.cfg.rope_theta)
    plain = t.norm(t._apply_stack(t.blocks, model.embed(inputs), None, rope))
    np.testing.assert_allclose(hidden[0], plain, rtol=1e-5, atol=1e-5)
    logits, gates = model(inputs)
    logp = jax.nn.log_softmax(logits[0], axis=-1)
    ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
    loss, per_pass = lm_loss_fn(model, tokens)
    np.testing.assert_allclose(loss, ce, rtol=1e-5)
    np.testing.assert_allclose(per_pass["p"], [1.0], rtol=1e-6)
    assert gates.shape == (1, 2, SMALL.seq_len)


@pytest.mark.parametrize("gates", ["random", "shut", "open"])
def test_exit_distribution_and_what_the_loss_reduces_to(gates):
    r, n = 4, 50
    g = {"random": 3 * jax.random.normal(jax.random.key(0), (r, n)),
         "shut": jnp.full((r, n), -40.0),
         "open": jnp.full((r, n), 40.0)}[gates]
    ce = jax.random.uniform(jax.random.key(1), (r, n), minval=1.0, maxval=9.0)
    p, log_p = exit_distribution(g)
    np.testing.assert_allclose(p.sum(axis=0), np.ones(n), rtol=1e-5)
    assert np.all(np.isfinite(log_p))
    loss, per_pass = expected_exit_loss(ce, g, beta=0.1)
    np.testing.assert_allclose(per_pass["ce"], ce.mean(axis=1), rtol=1e-6)
    if gates == "shut":   # nobody leaves early: the last pass's loss
        np.testing.assert_allclose(loss, ce[-1].mean(), rtol=1e-5)
    elif gates == "open":  # everybody leaves after the first pass
        np.testing.assert_allclose(loss, ce[0].mean(), rtol=1e-5)
    else:                  # against the definition, written out
        lam = jax.nn.sigmoid(g)
        want = jnp.stack([lam[0], lam[1] * (1 - lam[0]),
                          lam[2] * (1 - lam[0]) * (1 - lam[1]),
                          (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])])
        np.testing.assert_allclose(p, want, rtol=1e-4, atol=1e-7)
        entropy = -(want * jnp.log(want)).sum(axis=0)
        np.testing.assert_allclose(
            loss, ((want * ce).sum(axis=0) - 0.1 * entropy).mean(), rtol=1e-4)


@pytest.mark.parametrize("positions,block", [(64, 16), (50, 16), (8, 1024)])
def test_blocked_cross_entropy_is_cross_entropy(positions, block):
    """Value and both gradients, with a ragged last block and with one block
    larger than the input."""
    h = jax.random.normal(jax.random.key(0), (2, positions // 2, 24))
    w = 0.3 * jax.random.normal(jax.random.key(1), (24, 97))
    t = jax.random.randint(jax.random.key(2), (positions // 2,), 0, 97)

    def whole(h, w):
        logp = jax.nn.log_softmax(h @ w, axis=-1)
        return -jnp.take_along_axis(
            logp, jnp.broadcast_to(t, h.shape[:-1])[..., None], -1)[..., 0]

    got = blocked_cross_entropy(h, w, t, block=block)
    np.testing.assert_allclose(got, whole(h, w), rtol=1e-5, atol=1e-5)
    weights = jax.random.normal(jax.random.key(3), got.shape)
    g = jax.grad(lambda h, w: jnp.sum(weights * blocked_cross_entropy(
        h, w, t, block=block)), argnums=(0, 1))(h, w)
    want = jax.grad(lambda h, w: jnp.sum(weights * whole(h, w)),
                    argnums=(0, 1))(h, w)
    for a, b in zip(g, want, strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_rotary_is_the_rotate_half_pairing_and_keeps_relative_position():
    s, d, theta = 16, 8, 1e6
    rope = rope_tables(s, d, theta)
    x = jax.random.normal(jax.random.key(0), (1, s, 2, d))
    got = apply_rope(x, rope)
    # written out: element i turns with element i + d/2 by t * theta^(-2i/d)
    want = np.zeros_like(x)
    for t in range(s):
        for i in range(d // 2):
            a = t * theta ** (-2 * i / d)
            want[:, t, :, i] = x[:, t, :, i] * np.cos(a) \
                - x[:, t, :, i + d // 2] * np.sin(a)
            want[:, t, :, i + d // 2] = x[:, t, :, i + d // 2] * np.cos(a) \
                + x[:, t, :, i] * np.sin(a)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)
    # q_t . k_u depends on t - u alone
    q = jnp.broadcast_to(x[:, :1], x.shape)
    k = jnp.broadcast_to(x[:, 1:2], x.shape)
    scores = jnp.einsum("bqnd,bknd->bnqk", apply_rope(q, rope),
                        apply_rope(k, rope))
    np.testing.assert_allclose(scores[0, 0, 5, 3], scores[0, 0, 9, 7],
                               rtol=1e-4)
    assert abs(float(scores[0, 0, 5, 3] - scores[0, 0, 5, 4])) > 1e-3


def test_causal_flash_at_head_width_128_in_the_tiled_regime():
    """S = 1280 is over the single-tile rule at D = 128: the tiled kernels,
    the ones the 4096-token cell runs (interpret mode here)."""
    from jimm_tpu.obs.registry import get_registry
    from jimm_tpu.ops.attention import reference_attention
    from jimm_tpu.ops.flash_attention import flash_attention
    tiled = get_registry("jimm_flash").counter("tiled_total")
    single = get_registry("jimm_flash").counter("single_tile_total")
    before = (tiled.value, single.value)
    q, k, v = (jax.random.normal(jax.random.key(i), (1, 1280, 2, 128))
               for i in range(3))
    probe = jax.random.normal(jax.random.key(9), q.shape)

    def f(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v, is_causal=True) * probe)

    got = flash_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(got, reference_attention(q, k, v,
                                                        is_causal=True),
                               rtol=2e-4, atol=2e-5)
    grads = jax.grad(f(flash_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(f(reference_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, want, strict=True):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
    assert tiled.value > before[0] and single.value == before[1]


# -- through the CLI ------------------------------------------------------------------
# (its entries in the CLI's tables and its `presets` line:
# `tests/test_families.py`)

def test_train_cli_runs_the_family_through_the_same_loop(tmp_path, capsys):
    from jimm_tpu import obs
    before = obs.snapshot()
    rows = train_rows(tmp_path, capsys, [
        "--preset", "ouro-2.6b", "--tiny", "--steps", "3", "--batch-size",
        "2", "--remat", "dots"])
    for row in rows:
        p = [row[f"exit_p{r}"] for r in range(1, 5)]
        assert abs(sum(p) - 1.0) < 1e-3
        assert all(4.0 < row[f"loss_exit{r}"] < 8.0 for r in range(1, 5))
    after = obs.snapshot()
    assert after["jimm_lm_tokens_total"] \
        - before.get("jimm_lm_tokens_total", 0) == 3 * 2 * 32
    assert after["jimm_loop_block_applications_total"] \
        - before.get("jimm_loop_block_applications_total", 0) == 3 * 4 * 2


@pytest.mark.parametrize("argv,message", [
    (["--preset", "vit-base-patch16-224", "--num-layers", "2"], "ouro preset"),
    (["--preset", "ouro-2.6b", "--data", "x.tfrecord"], "token generator"),
    (["--preset", "ouro-2.6b", "--ln-impl", "fused"], "does not take"),
])
def test_train_cli_refuses_what_the_family_does_not_have(argv, message):
    with pytest.raises(SystemExit, match=message):
        main(["train", "--tiny", "--steps", "1", *argv])


def test_num_layers_and_seq_len_shape_the_preset(tmp_path):
    metrics = tmp_path / "metrics.jsonl"
    from jimm_tpu import cli
    result = cli.train(cli.build_parser().parse_args(
        ["train", "--preset", "ouro-2.6b", "--tiny", "--steps", "1",
         "--batch-size", "1", "--num-layers", "3", "--seq-len", "16",
         "--log-every", "0", "--metrics-file", str(metrics)]))
    d = result.model.config.decoder
    assert (d.depth, d.seq_len, d.loops) == (3, 16, 4)
    assert result.batch[0].shape == (1, 17)
    assert result.model.decoder.blocks.attn.q.kernel.shape == (3, 64, 64)


@pytest.mark.parametrize("argv,lr,warmup", [
    (["--preset", "ouro-2.6b", "--steps", "25"], 1e-4, 20),
    (["--preset", "ouro-2.6b", "--steps", "3"], 1e-4, 2),
    (["--preset", "ouro-2.6b", "--lr", "3e-4", "--warmup-steps", "0"],
     3e-4, 0),
    (["--preset", "vit-base-patch16-224"], 1e-3, 0),
])
def test_the_family_carries_its_own_optimizer_defaults(argv, lr, warmup):
    """At the loop's 1e-3 with no warm-up the exit gates saturate within 20
    steps, so an ouro preset starts lower and ramps (over at most the run's
    steps); a flag still wins, and the image families keep what they had."""
    from jimm_tpu import cli
    args = cli.build_parser().parse_args(
        ["train", "--tiny", "--steps", "1", "--batch-size", "1",
         "--log-every", "0", *argv])
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)  # the default fits the run
        cli.train(args)
    assert (args.lr, args.warmup_steps) == (lr, warmup)


def test_token_sequences_are_a_function_of_the_seed():
    from jimm_tpu.data import token_sequences
    a, b = (token_sequences(2, seq_len=9, vocab_size=100, seed=7)
            for _ in range(2))
    (x,), (y,) = next(a), next(b)
    assert x.dtype == np.int32 and x.shape == (2, 10)
    assert 0 <= x.min() and x.max() < 100 and (x == y).all()
    assert not (next(a)[0] == x).all()
    assert not (next(token_sequences(2, seq_len=9, vocab_size=100,
                                     seed=8))[0] == x).all()


def test_model_flops_of_the_benchmarks_cut():
    """8 of the 48 layers, four passes, one sequence of 4096 tokens:
    56.9 TFLOP a step (ISSUE 27), causal attention at half of S^2."""
    from jimm_tpu.train.metrics import train_step_flops
    cfg = preset("ouro-2.6b")
    cut = dataclasses.replace(cfg, decoder=dataclasses.replace(cfg.decoder,
                                                               depth=8))
    assert train_step_flops(cut, 1) == pytest.approx(56.9e12, rel=2e-3)
    per_token_layer = 2 * (4 * 2048 ** 2 + 3 * 2048 * 5632) + 2 * 4096 * 2048
    assert train_step_flops(cut, 2) == pytest.approx(
        3 * 2 * 4096 * 4 * (8 * per_token_layer + 2 * 2048 * 49153))
