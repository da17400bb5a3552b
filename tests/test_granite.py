"""granite-4.0-h-micro (`models/granite.py`, `nn/mamba2.py`, `ops/ssd.py`, the
dense runs of `models/kanana.py`) at a small size on the CPU, against the plain
float32 reference `benchmarks/reference/granite.py` on seeded random weights:
the chunked selective scan against the token-by-token recurrence (forward and
every gradient), its segment sums, its Pallas kernels against its XLA path
(interpret mode) and the rule that picks them, the mixer, the whole model's
loss and gradients, one test a multiplier, the dense-only stack, the scopes
and counters, the benchmark's cut, what the family may not cost the others,
and its way through `jimm-tpu train`."""

import dataclasses
import json
import math
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

from benchmarks import flops_granite
from benchmarks.reference import granite as ref
from benchmarks.reference import parity_granite
from jimm_tpu import Granite, GraniteConfig, Kanana, KananaConfig, preset
from jimm_tpu.cli import _tiny_override, main
from jimm_tpu.configs import (Mamba2Config, MLAConfig, MoEDecoderConfig,
                              TransformerConfig)
from jimm_tpu.nn.mamba2 import Mamba2
from jimm_tpu.ops import ssd
from jimm_tpu.ops.ssd import chunk_ssd


def _tiny(**decoder) -> GraniteConfig:
    cfg = _tiny_override(preset("granite-4.0-h-micro"))
    return dataclasses.replace(cfg, decoder=dataclasses.replace(cfg.decoder,
                                                                **decoder))


def _rel(a, b) -> float:
    assert bool(jnp.all(jnp.isfinite(a)))
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _perturbed(model, key=7):
    """Every vector-shaped weight (norm scales, taps, ``A_log``, ``dt_bias``,
    ``D``) moved off its start, so that a dropped one shows."""
    keys = iter(jax.random.split(jax.random.key(key), 512))
    nnx.update(model, jax.tree.map(
        lambda a: a + 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
        if a.ndim <= 2 and a.shape[-1] < 512 else a,
        nnx.state(model, nnx.Param)))
    return model


# -- the chunked selective scan ----------------------------------------------

def _scan_inputs(strength: float, s=150, b=2, h=4, p=8, g=1, n=16):
    """x, dt (softplus around 1), A = -(1 .. H) * strength, B, C and a
    cotangent; head h loses about 1.3 h * strength nats a token."""
    keys = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(keys[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h)) + 1.0)
    A = -jnp.arange(1, h + 1, dtype=jnp.float32) * strength
    B = jax.random.normal(keys[2], (b, s, g, n))
    C = jax.random.normal(keys[3], (b, s, g, n))
    return (x, dt, A, B, C), jax.random.normal(keys[4], (b, s, h, p))


@pytest.mark.parametrize("strength", [0.05, 1.0, 20.0],
                         ids=["long_memory", "the_start", "strong"])
@pytest.mark.parametrize("groups,chunk,pair_bytes", [
    (1, 64, 32 << 20), (1, 64, 1), (4, 32, 32 << 20), (2, 256, 32 << 20)],
    ids=["G1-one_slab", "G1-slabs_of_one", "GH-chunk32", "G2-chunk256"])
def test_chunked_scan_is_the_recurrence(strength, groups, chunk, pair_bytes,
                                        monkeypatch):
    """Forward and the gradients of all five inputs at 150 tokens (no
    multiple of any chunk here), with one group, one group a head and two,
    in one slab of chunks and in slabs of one. At ``strong`` the running sum
    of a chunk reaches -2e4 (float32's step there is 2e-3)."""
    monkeypatch.setattr(ssd, "_PAIR_BYTES", pair_bytes)
    inputs, w = _scan_inputs(strength, g=groups)
    with jax.default_matmul_precision("highest"):
        want = ref.ssm_scan(*inputs)
        want_grads = jax.grad(lambda *a: jnp.sum(ref.ssm_scan(*a) * w),
                              argnums=range(5))(*inputs)
    got = chunk_ssd(*inputs, chunk=chunk)
    got_grads = jax.grad(lambda *a: jnp.sum(chunk_ssd(*a, chunk=chunk) * w),
                         argnums=range(5))(*inputs)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert _rel(got, want) < 2e-6
    for name, a, b in zip(("x", "dt", "A", "B", "C"), got_grads, want_grads):
        assert _rel(a, b) < 1e-5, name


def test_segment_sums_hold_digits_a_difference_of_running_sums_loses():
    """Within a chunk of 256 tokens at 80 nats a token the running sum
    reaches -2e4. Where an exponent still matters (above -20), a difference of
    two running sums is off by up to 1e-3 (float32's step at -2e4); the sums
    of same-signed parts `chunk_ssd` takes are off by the step of the exponent
    itself."""
    a = -80.0 * jax.nn.softplus(jax.random.normal(jax.random.key(1), (3, 256))
                                + 1.0)
    into, out_of, pair = ssd._segment_sums(a)
    a64 = np.asarray(a, np.float64)
    run = np.cumsum(a64, axis=-1)
    exact = run[:, :, None] - run[:, None, :]
    lower = np.tril(np.ones((256, 256), bool))
    near = lower & (exact > -20.0)
    assert near.sum() >= 3 * 256     # the diagonal, and more
    ours = np.abs(np.asarray(pair, np.float64) - exact)[near]
    running = np.cumsum(np.asarray(a), axis=-1)    # float32
    differences = np.abs((running[:, :, None] - running[:, None, :])
                         .astype(np.float64) - exact)[near]
    assert ours.max() < 1e-5 and differences.max() > 5e-4
    assert np.all(np.isneginf(np.asarray(pair)[:, ~lower]))
    np.testing.assert_allclose(into, run, rtol=1e-6)
    np.testing.assert_allclose(out_of, run[:, -1:] - run, rtol=1e-6,
                               atol=1e-3)


def test_padding_tokens_leave_the_state_alone():
    """A length of one token past a chunk: the last token's output is the
    recurrence's, whatever the padding holds."""
    inputs, _ = _scan_inputs(1.0, s=65)
    want = ref.ssm_scan(*inputs)
    np.testing.assert_allclose(chunk_ssd(*inputs, chunk=64)[:, -1],
                               want[:, -1], rtol=1e-5, atol=1e-5)


# -- the Pallas kernels (interpret mode here) ----------------------------------

def _on_path(monkeypatch, path: str) -> None:
    """What `chunk_ssd` decides from the backend: the kernels where the
    backend is a TPU (interpret mode follows the real backend)."""
    monkeypatch.setattr(ssd, "_default_backend",
                        lambda: "tpu" if path == "kernel" else "cpu")


def _kernel_inputs(case: str, s: int, h=4, p=64, n=128):
    """Heads of 64 and a state of 128, the widths the kernels take. At
    ``the_start`` ``dt`` is about softplus(1) and A = -(1 .. H), as the
    model starts (no state outlives a chunk), x, B, C in bfloat16 as the
    model hands them over; at ``memory`` each head's step is log-spaced over
    `parity_granite.DT_RANGE` at A = -1, so the state handed from chunk to
    chunk carries the output, all in float32 (``memory_bfloat16``: x, B, C in
    bfloat16, so the state enters the kernels' three-pass products)."""
    keys = jax.random.split(jax.random.key(3), 5)
    x = jax.nn.silu(jax.random.normal(keys[0], (1, s, h, p)))
    if case == "the_start":
        bias, A = 1.0, -jnp.arange(1, h + 1, dtype=jnp.float32)
    else:
        bias = jnp.log(jnp.expm1(jnp.geomspace(*parity_granite.DT_RANGE, h)))
        A = -jnp.ones((h,))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, s, h)) + bias)
    B = jax.nn.silu(jax.random.normal(keys[2], (1, s, 1, n)))
    C = jax.nn.silu(jax.random.normal(keys[3], (1, s, 1, n)))
    if case != "memory":
        x, B, C = (t.astype(jnp.bfloat16) for t in (x, B, C))
    return (x, dt, A, B, C), jax.random.normal(keys[4], (1, s, h, p))


@pytest.mark.parametrize("case,tokens", [
    ("the_start", 512), ("memory", 1024), ("memory", 384),
    ("memory_bfloat16", 1024)],
    ids=["the_start", "memory", "memory-ragged_length", "memory-bfloat16"])
def test_the_kernels_are_the_xla_path(case, tokens, monkeypatch):
    """y and the gradients of all five inputs on the kernels against the
    XLA path, chunks of 256 as two tiles of 128: at the model's start, with
    a state that lives over many chunks (in float32 and in bfloat16), and at
    a length that is no multiple of the chunk (padded with tokens of ``dt =
    0``). A gradient in bfloat16 is held to its rounding, one in float32 to
    float32's."""
    inputs, w = _kernel_inputs(case, tokens)
    got = {}
    for path in ("xla", "kernel"):
        _on_path(monkeypatch, path)
        y, vjp = jax.vjp(lambda *a: chunk_ssd(*a, chunk=256), *inputs)
        got[path] = (y, *vjp(w))
    for name, a, b in zip(("y", "x", "dt", "A", "B", "C"), got["kernel"],
                          got["xla"]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        limit = 5e-4 if a.dtype == jnp.bfloat16 else 2e-6
        assert _rel(a.astype(jnp.float32), b.astype(jnp.float32)) < limit, \
            name


@pytest.mark.parametrize("x_shape,b_shape,chunk,backend,takes", [
    ((1, 16384, 64, 64), (1, 16384, 1, 128), 256, "tpu", True),  # the cell
    ((2, 300, 8, 128), (2, 300, 2, 256), 128, "tpu", True),
    ((1, 16384, 64, 64), (1, 16384, 1, 128), 256, "cpu", False),
    ((2, 32, 4, 16), (2, 32, 1, 16), 16, "tpu", False),    # the tiny preset
    ((1, 4096, 64, 64), (1, 4096, 1, 64), 256, "tpu", False),
    ((1, 4096, 64, 64), (1, 4096, 1, 128), 64, "tpu", False),
    ((1, 4096, 8, 64), (1, 4096, 8, 128), 256, "tpu", False),  # a head a group
    ((1, 4096, 8, 72), (1, 4096, 1, 128), 256, "tpu", False),
])
def test_the_kernels_take_the_tpu_shapes_and_xla_the_rest(
        x_shape, b_shape, chunk, backend, takes):
    assert ssd.kernel_takes(x_shape, b_shape, chunk, backend) is takes


def test_the_tiny_preset_keeps_the_xla_path():
    m = _tiny().decoder.mamba
    x_shape = (2, 32, m.num_heads, m.head_dim)
    b_shape = (2, 32, m.groups, m.state)
    assert not ssd.kernel_takes(x_shape, b_shape, m.chunk, "tpu")
    assert ssd._default_backend() == "cpu"


def test_the_kernel_counter_counts_the_scans_built_on_the_kernels(
        monkeypatch):
    """``jimm_ssm_kernel_total`` beside ``calls_total`` / ``chunks_total``
    (the registry is the process's: reset it first)."""
    from jimm_tpu import obs
    obs.get_registry("jimm_ssm").reset()
    _on_path(monkeypatch, "kernel")
    wide, _ = _kernel_inputs("memory", 256)
    small, _ = _scan_inputs(1.0, s=32)

    def counts():
        snap = obs.snapshot()
        return [snap.get(f"jimm_ssm_{k}_total", 0)
                for k in ("calls", "chunks", "kernel")]

    chunk_ssd(*wide, chunk=128)
    assert counts() == [1, 2, 1]
    chunk_ssd(*small, chunk=16)
    assert counts() == [2, 4, 1]


_FORMS = {"nn": ((4, 128, 96), (4, 96, 64)), "nt": ((4, 128, 96), (4, 64, 96)),
          "tn": ((4, 96, 128), (4, 96, 64))}


@pytest.mark.parametrize("values", ["random", "bfloat16_exact"])
@pytest.mark.parametrize("exact_side", ["left", "right"])
@pytest.mark.parametrize("form", sorted(_FORMS))
def test_a_split_product_is_the_highest_product(form, exact_side, values):
    """`ssd._mx` of a float32 operand and a bfloat16 one (its three pieces
    side by side against the exact operand three times) against the
    ``HIGHEST`` product, batched over heads: to float32's rounding of a sum
    for random values, and exactly for a float32 operand that is itself
    bfloat16-exact, on a grid where every sum is exact in float32 whatever
    its order (so a dropped or rounded piece shows, and the order of the
    summation does not)."""
    keys = jax.random.split(jax.random.key(11), 2)

    def draw(key, shape, exact):
        v = jax.random.normal(key, shape)
        if values == "bfloat16_exact":
            v = jnp.round(v * 32) / 64
        return v.astype(jnp.bfloat16) if exact else v

    shape_a, shape_b = _FORMS[form]
    a = draw(keys[0], shape_a, exact_side == "left")
    b = draw(keys[1], shape_b, exact_side == "right")
    got = jax.jit(lambda a, b: ssd._mx(a, b, form))(a, b)
    want = ssd._dot(a.astype(jnp.float32), b.astype(jnp.float32), form)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    if values == "random":
        assert _rel(got, want) < 1e-6
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_pieces_sum_to_the_operand_exactly():
    """`ssd._pieces` of float32 values over many binades: three bfloat16
    pieces, each at most a third of the bits, whose float32 sum is the value
    bit for bit."""
    v = jax.random.normal(jax.random.key(5), (4096,)) \
        * jnp.exp2(jnp.linspace(-60.0, 60.0, 4096))
    pieces = ssd._pieces(v)
    assert all(t.dtype == jnp.bfloat16 for t in pieces)
    hi, mid, lo = (t.astype(jnp.float32) for t in pieces)
    np.testing.assert_array_equal(np.asarray(hi + mid + lo), np.asarray(v))


@pytest.mark.parametrize("path,dtype,want", [
    ("kernel", jnp.bfloat16, (9, 28)), ("kernel", jnp.float32, (0, 0)),
    ("xla", jnp.bfloat16, (0, 0))], ids=["kernel-bfloat16", "kernel-float32",
                                        "xla-bfloat16"])
def test_the_split_counter_counts_the_products_on_three_passes(
        path, dtype, want, monkeypatch):
    """``jimm_ssm_split_products_total``: the kernels' products built on the
    three-piece form, counted as a body is traced. In chunks of two tiles,
    with x, B, C in bfloat16, every product of the forward but ``C B^T``
    (9) and then the backward's, all but its three of two float32 operands
    (19 more); none with float32 inputs or on the XLA path."""
    from jimm_tpu import obs
    obs.get_registry("jimm_ssm").reset()
    _on_path(monkeypatch, path)
    (x, dt, A, B, C), w = _kernel_inputs("memory", 256)
    x, B, C = (t.astype(dtype) for t in (x, B, C))

    def split():
        return obs.snapshot().get("jimm_ssm_split_products_total", 0)

    def scan(*a):
        return chunk_ssd(*a, chunk=256)

    jax.jit(scan).lower(x, dt, A, B, C)
    forward = split()
    jax.jit(lambda *a: jax.vjp(scan, *a)[1](w)).lower(x, dt, A, B, C)
    assert (forward, split()) == (want[0], want[0] + want[1])


# -- the mixer, the model, the multipliers -------------------------------------

def _mixer_sizes(cfg: TransformerConfig) -> dict:
    m = cfg.mamba
    return {"mamba_n_heads": m.num_heads, "mamba_d_head": m.head_dim,
            "mamba_n_groups": m.groups, "mamba_d_state": m.state,
            "rms_norm_eps": cfg.ln_eps}


def test_the_mixer_is_the_references():
    cfg = TransformerConfig(width=64, ln_eps=1e-5, mamba=Mamba2Config(
        num_heads=4, head_dim=16, state=16, groups=2, chunk=16))
    mixer = _perturbed(Mamba2(cfg, nnx.Rngs(0)))
    u = jax.random.normal(jax.random.key(3), (2, 40, 64))
    params = jax.tree.map(jnp.asarray,
                          nnx.to_pure_dict(nnx.state(mixer, nnx.Param)))
    with jax.default_matmul_precision("highest"):
        got = mixer(u)
        want = ref.mamba(u, params, _mixer_sizes(cfg))
    assert _rel(got, want) < 1e-5
    with pytest.raises(ValueError, match="no attention mask"):
        mixer(u, mask=jnp.ones((2, 40), bool))


@pytest.fixture(scope="module")
def model():
    """width 64, Mamba-2 of 4 heads of 16 (state 16, chunks of 16 under 32
    tokens), attention of 4 heads over 2 of 16, SwiGLU 176, vocabulary 512,
    published layers 0-9 (Mamba-2 x 5, attention, Mamba-2 x 4), float32,
    every vector-shaped weight moved off its start."""
    return _perturbed(Granite(_tiny(), rngs=nnx.Rngs(0)))


def _params(model):
    return ref.params_from_state(nnx.to_pure_dict(nnx.state(model, nnx.Param)))


def test_the_models_loss_and_every_gradient_are_the_references(model):
    from jimm_tpu.train.trainer import dense_lm_loss_fn
    tokens = jax.random.randint(jax.random.key(5), (2, 33), 0, 512, jnp.int32)
    sizes = parity_granite.sizes_of(model)
    with jax.default_matmul_precision("highest"):
        loss, grads = nnx.value_and_grad(
            lambda m: dense_lm_loss_fn(m, tokens)[0])(model)
        want_loss, want = jax.value_and_grad(ref.loss)(_params(model), tokens,
                                                       sizes)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    for name in ref.run_names(want):
        want[name]["blocks"] = jax.tree.map(
            lambda *layers: jnp.stack(layers), *want[name]["blocks"])
    flat_got = dict(jax.tree_util.tree_leaves_with_path(
        nnx.to_pure_dict(grads)))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert set(flat_got) == set(flat_want)
    # the embedding (the head too), the final norm; two runs of Mamba-2 (8
    # leaves + 2 norms + SwiGLU 3) and the attention run (4 + 2 + 3)
    assert len(flat_got) == 2 + 2 * 13 + 9
    for path, g in flat_got.items():
        assert _rel(g, flat_want[path]) < 1e-4, jax.tree_util.keystr(path)


@pytest.mark.parametrize("key, published", [
    ("embedding_multiplier", 12), ("residual_multiplier", 0.22),
    ("attention_multiplier", 0.015625), ("logits_scaling", 8)])
def test_each_multiplier_is_in_the_model(key, published, model):
    """The model's logits are the reference's with the published multiplier
    and far from the reference's with that one multiplier dropped (1), so a
    model that dropped it would fail."""
    tokens = jax.random.randint(jax.random.key(9), (2, 32), 0, 512, jnp.int32)
    sizes = parity_granite.sizes_of(model)
    assert sizes[key] == published
    params = _params(model)

    def logits(sizes):
        return ref.logits(params, ref.hidden_states(params, tokens, sizes),
                          sizes)

    with jax.default_matmul_precision("highest"):
        got = model(tokens)
        want, dropped = logits(sizes), logits({**sizes, key: 1.0})
    assert _rel(got, want) < 1e-5
    assert _rel(dropped, want) > 1e-2


def test_the_attention_layer_takes_the_scale_and_no_position():
    """32 heads over 8 in the preset; q is multiplied by ``1/64 * 8``, exact
    in bfloat16, and no rotary table is built."""
    d = preset("granite-4.0-h-micro").decoder
    assert (d.num_heads, d.gqa.kv_heads, d.gqa.head_dim) == (32, 8, 64)
    assert d.rope_theta is None and d.gqa.window is None
    assert not d.gqa.qk_norm and not d.gqa.gate
    attn = dict(d.runs())["run5"]
    assert attn.gqa.full_layers(1) == (True,)
    assert attn.attn_scale * math.sqrt(64) == 0.125
    assert jnp.bfloat16(0.125) * 8 == 1.0


# -- the stack ---------------------------------------------------------------------

def test_the_preset_holds_published_layers_0_to_9():
    d = preset("granite-4.0-h-micro").decoder
    assert d.moe is None and d.mla is None and d.depth == 10
    assert (d.seq_len, d.width, d.mlp_dim, d.vocab_size) == (16384, 2048,
                                                             8192, 100352)
    assert d.mamba == Mamba2Config(num_heads=64, head_dim=64, state=128,
                                   groups=1, conv_taps=4, chunk=256)
    assert [(n, c.depth, c.mamba is not None, c.moe) for n, c in d.runs()] \
        == [("run0", 5, True, None), ("run5", 1, False, None),
            ("run6", 4, True, None)]
    assert d.residual_scale == 0.22 and d.attn_scale == 1 / 64
    assert [i for i, m in enumerate(d.mixers) if m == "attention"] \
        == [5, 15, 25, 35]


def test_a_stack_without_a_sparse_layer():
    """``moe=None``: every layer dense, one run named ``dense`` where no
    mixer differs; no routing out of the stack. With experts the stack must
    still hold a sparse layer."""
    cfg = KananaConfig(decoder=MoEDecoderConfig(
        vocab_size=64, seq_len=16, width=32, depth=2, num_heads=2, mlp_dim=48,
        moe=None, mla=MLAConfig(kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=8,
                                v_head_dim=8)))
    assert [n for n, _ in cfg.decoder.runs()] == ["dense"]
    m = Kanana(cfg, rngs=nnx.Rngs(0))
    assert m.sparse_runs() == []
    x, chosen = m.hidden_states(jnp.zeros((1, 16), jnp.int32))
    assert x.shape == (1, 16, 32) and chosen is None
    assert m(jnp.zeros((1, 16), jnp.int32)).shape == (1, 16, 64)
    with pytest.raises(ValueError, match="at least one dense and one sparse"):
        Kanana(dataclasses.replace(cfg, decoder=dataclasses.replace(
            cfg.decoder, moe=KananaConfig().decoder.moe, dense_layers=2)))


def test_the_head_is_the_embedding():
    m = nnx.eval_shape(lambda: Granite(_tiny(), rngs=nnx.Rngs(0)))
    assert m.tied_head and not hasattr(m, "head")
    names = {"/".join(map(str, p)) for p, _ in
             nnx.to_flat_state(nnx.state(m, nnx.Param))}
    assert "embed/embedding" in names and not any("head" in n for n in names)


def test_the_benchmarks_cut_holds_951_991_232_parameters():
    config = json.loads(open("benchmarks/configs/granite_4_0_h_micro.json")
                        .read())
    built = nnx.eval_shape(lambda: Granite(rngs=nnx.Rngs(0)))
    count = sum(math.prod(v.shape) for _, v in
                nnx.to_flat_state(nnx.state(built, nnx.Param)))
    assert count == sum(flops_granite.parameter_count(config).values()) \
        == 951_991_232
    parts = flops_granite.parameter_count(config)
    assert parts["mamba"] / 9 + 3 * 2048 * 8192 + 4096 == 76_182_976
    assert parts["attention"] + 3 * 2048 * 8192 + 4096 == 60_821_504


def test_the_programs_flops_are_the_yardsticks():
    """The program's count (`train/metrics.py`, for its own log) and the
    benchmark's (`benchmarks/flops_granite.py`, kept apart) of the cell's
    step: 97.80 TFLOP."""
    from jimm_tpu.train.metrics import train_step_flops
    config = json.loads(open("benchmarks/configs/granite_4_0_h_micro.json")
                        .read())
    want = flops_granite.train_step_flops(config, 1, 16384)
    assert train_step_flops(preset("granite-4.0-h-micro"), 1) \
        == pytest.approx(want, rel=1e-9)
    assert want / 1e12 == pytest.approx(97.80, abs=0.01)


# -- scopes and counters ---------------------------------------------------------

def test_scopes_are_in_the_lowered_step_and_the_counters_count():
    from jimm_tpu import obs
    from jimm_tpu.train import OptimizerConfig, make_optimizer
    from jimm_tpu.train.trainer import make_lm_train_step
    model = Granite(_tiny(), rngs=nnx.Rngs(0))
    optimizer = make_optimizer(model, OptimizerConfig(total_steps=4))
    tokens = jnp.zeros((2, model.config.decoder.seq_len + 1), jnp.int32)
    before = obs.snapshot()
    text = make_lm_train_step("granite").lower(model, optimizer, tokens) \
        .as_text(debug_info=True)
    for scope in ("ssm", "ssm_proj", "ssm_scan", "ssm_out", "attn",
                  "attn_full", "embed", "decoder_stack", "lm_head"):
        assert re.search(rf'[/"(]{scope}[/")]', text), scope
    for scope in ("moe", "mla", "kda", "attn_window"):
        assert not re.search(rf'[/"(]{scope}[/")]', text), scope
    after = obs.snapshot()
    calls = after["jimm_ssm_calls_total"] \
        - before.get("jimm_ssm_calls_total", 0)
    chunks = after["jimm_ssm_chunks_total"] \
        - before.get("jimm_ssm_chunks_total", 0)
    # two runs of Mamba-2 layers, each traced at least once; 32 tokens in
    # chunks of 16: two chunk steps a call
    assert calls >= 2 and chunks == 2 * calls


# -- what the family may not cost the others ---------------------------------------

def test_no_other_preset_imports_the_familys_modules():
    code = (
        "import sys\n"
        "import jimm_tpu, jimm_tpu.cli as cli\n"
        "from flax import nnx\n"
        "from jimm_tpu import PRESETS\n"
        "mine = ('jimm_tpu.nn.mamba2', 'jimm_tpu.ops.ssd',\n"
        "        'jimm_tpu.models.granite')\n"
        "assert not [m for m in mine if m in sys.modules], 'at import'\n"
        "for name, cfg in PRESETS.items():\n"
        "    if name.startswith('granite'):\n"
        "        continue\n"
        "    cfg = cli._tiny_override(cfg)\n"
        "    nnx.eval_shape(lambda: cli._model_cls(cli._family(name))(\n"
        "        cfg, rngs=nnx.Rngs(0)))\n"
        "    assert not [m for m in mine if m in sys.modules], name\n"
        "cli._model_cls('granite')\n"
        "assert 'jimm_tpu.models.granite' in sys.modules\n"
        "print('ok')\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300,
                          env={**__import__("os").environ,
                               "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0 and done.stdout.strip() == "ok", done.stderr


def test_the_remat_policies_keep_the_scans_output_and_states():
    """So that neither `--remat dots` nor a layer's backward runs the scan
    again: the names the scan and the mixer give are in the save list."""
    import inspect

    from jimm_tpu.nn.transformer import Transformer
    source = inspect.getsource(Transformer._remat_policy)
    assert '"ssm_y"' in source and '"ssm_states"' in source
    assert 'checkpoint_name(entered, "ssm_states")' in inspect.getsource(
        ssd._ssd_fwd)
    assert 'checkpoint_name(kept, "ssm_states")' in inspect.getsource(
        ssd._kernel_scan_fwd)


# -- through the CLI ------------------------------------------------------------------

def test_train_cli_runs_the_family_through_the_same_loop(tmp_path, capsys):
    metrics = tmp_path / "m.jsonl"
    assert main(["train", "--preset", "granite-4.0-h-micro", "--tiny",
                 "--steps", "3", "--batch-size", "2", "--log-every", "1",
                 "--bf16", "--remat", "full", "--metrics-file",
                 str(metrics)]) == 0
    rows = [json.loads(line) for line in metrics.read_text().splitlines()]
    assert len(rows) == 3
    assert all(np.isfinite(r["loss"]) and "moe_held_rows" not in r
               for r in rows)
    assert "goodput:" in capsys.readouterr().out


def test_the_family_is_an_entry_of_the_tables():
    from jimm_tpu import cli
    from jimm_tpu.train.trainer import LM_STEPS, dense_lm_loss_fn
    assert cli._family("granite-4.0-h-micro") == "granite"
    assert cli._model_cls("granite") is Granite
    assert cli.LM_FAMILIES["granite"] == {"lr": 1e-4, "warmup_steps": 20}
    assert LM_STEPS["granite"][0] is dense_lm_loss_fn
    # no expert counters for a stack without experts
    assert [name for _, name, _ in cli._lm_counters(_tiny(), 2)] \
        == ["tokens_total"]
    cfg = cli._replace_towers(preset("granite-4.0-h-micro"), depth=20,
                              seq_len=4096)
    assert [n for n, _ in cfg.decoder.runs()] \
        == ["run0", "run5", "run6", "run15", "run16"]


def test_presets_lists_the_dense_stack(capsys):
    assert main(["presets"]) == 0
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("granite-4.0-h-micro"))
    assert "952.0M" in line and "dense" in line
    assert "depth=10" in line and "seq=16384" in line
