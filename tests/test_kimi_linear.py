"""The hybrid decoder language model (`models/kimi_linear.py`, `nn/kda.py`,
`ops/delta_rule.py`, the runs of `models/kanana.py`) at a small size on the
CPU: the chunked delta rule against the token-by-token recurrence, the mixed
stack's runs, position-free latent attention, the share an expert layer holds,
the scopes and counters, what the family may not cost the others, and its way
through `jimm-tpu train`. Agreement with the plain reference is
`tests/benchmark/test_hybrid_lm.py`'s."""

import dataclasses
import json
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

from jimm_tpu import KimiLinear, KimiLinearConfig, preset
from jimm_tpu.cli import _tiny_override, main
from jimm_tpu.configs import (KDAConfig, MLAConfig, MoEConfig,
                              TransformerConfig)
from jimm_tpu.nn.moe import SparseMoe
from jimm_tpu.ops import delta_rule
from jimm_tpu.ops.delta_rule import chunk_kda


def _tiny(**decoder) -> KimiLinearConfig:
    cfg = _tiny_override(preset("kimi-linear-48b-a3b"))
    return dataclasses.replace(cfg, decoder=dataclasses.replace(cfg.decoder,
                                                                **decoder))


# -- the chunked delta rule ---------------------------------------------------

def _recurrence(q, k, v, g, beta):
    """The equations as written, one token at a time."""
    def token(state, xs):
        q, k, v, g, b = xs
        state = state * jnp.exp(g)[..., None]
        read = jnp.einsum("bhd,bhde->bhe", k, state)
        state = state + jnp.einsum("bhd,bhe->bhde", k,
                                   (v - read) * b[..., None])
        return state, jnp.einsum("bhd,bhde->bhe", q, state)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    zero = jnp.zeros((*q.shape[:1], *q.shape[2:], v.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(token, zero, xs)[1], 0, 1)


def _scan_inputs(decay: float, s=150, b=2, h=3, d=32):
    keys = jax.random.split(jax.random.key(0), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(keys[0], (b, s, h, d))) * d ** -0.5
    k = unit(jax.random.normal(keys[1], (b, s, h, d)))
    v = jax.random.normal(keys[2], (b, s, h, d))
    g = -decay * jax.nn.softplus(jax.random.normal(keys[3], (b, s, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, h)))
    return (q, k, v, g, beta), jax.random.normal(keys[5], (b, s, h, d))


#: (path, chunk, bytes of pairwise exponents a slab): the XLA path in one
#: slab of chunks and in several, at heads of 32; the Pallas kernels (in
#: interpret mode here) at the widths they take, heads of 128, in segments of
#: two chunks of 64 and of one chunk of 128
SCAN_PATHS = [("xla", 16, 256 << 20), ("xla", 16, 1 << 16),
              ("xla", 64, 256 << 20), ("xla", 64, 1 << 16),
              ("kernel", 64, None), ("kernel", 128, None)]


def _on_path(monkeypatch, path: str) -> None:
    """What `chunk_kda` decides from the backend: the kernels where the
    backend is a TPU (interpret mode follows the real backend)."""
    monkeypatch.setattr(delta_rule, "_default_backend",
                        lambda: "tpu" if path == "kernel" else "cpu")


@pytest.mark.parametrize("path,chunk,slab_bytes", SCAN_PATHS,
                         ids=["xla-16-one_slab", "xla-16-many_slabs",
                              "xla-64-one_slab", "xla-64-many_slabs",
                              "kernel-64", "kernel-128"])
@pytest.mark.parametrize("decay", [1.0, 16.0], ids=["mild", "strongest"])
def test_chunked_delta_rule_is_the_recurrence(decay, path, chunk, slab_bytes,
                                              monkeypatch):
    """Forward and the gradients of all five inputs, at 150 tokens (no
    multiple of either chunk), in one slab of chunks and in several, and on
    the kernels. At ``exp(A_log) = 16`` a channel loses up to 16 * softplus
    nats a token: ``exp(-G)`` overflows float32 inside a chunk, so only
    exponents of differences ``G_i - G_j``, ``i >= j``, keep this finite and
    equal."""
    _on_path(monkeypatch, path)
    if slab_bytes is not None:
        monkeypatch.setattr(delta_rule, "_PAIRWISE_BYTES", slab_bytes)
    inputs, w = _scan_inputs(decay, **({"b": 1, "h": 3, "d": 128}
                                       if path == "kernel" else {}))
    assert float(jnp.min(jnp.cumsum(inputs[3][:, :64], axis=1))) < (
        -88 if decay == 16.0 else -20)
    with jax.default_matmul_precision("highest"):
        want = _recurrence(*inputs)
        want_grads = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * w),
                              argnums=range(5))(*inputs)
    got = chunk_kda(*inputs, chunk=chunk)
    got_grads = jax.grad(lambda *a: jnp.sum(chunk_kda(*a, chunk=chunk) * w),
                         argnums=range(5))(*inputs)
    assert got.shape == want.shape and got.dtype == jnp.float32

    def rel(a, b):
        assert bool(jnp.all(jnp.isfinite(a)))
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    assert rel(got, want) < 5e-6
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        assert rel(a, b) < (1e-4 if name == "g" else 1e-5), name


def test_the_kernels_backward_is_the_xla_paths(monkeypatch):
    """The kernels' five gradients against the XLA path's (``jax.vjp`` of
    `_slab` a slab at a time) to float32 rounding: 512 tokens, four segments
    of two chunks, at a gate between the two above."""
    inputs, w = _scan_inputs(4.0, s=512, b=1, h=2, d=128)
    grads = {}
    for path in ("xla", "kernel"):
        _on_path(monkeypatch, path)
        o, vjp = jax.vjp(lambda *a: chunk_kda(*a, chunk=64), *inputs)
        grads[path] = (o, *vjp(w))
    for name, a, b in zip("o q k v g beta".split(), grads["kernel"],
                          grads["xla"]):
        err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert err < 3e-6, (name, err)


@pytest.mark.parametrize("k_shape,v_shape,chunk,backend,takes", [
    ((1, 16384, 32, 128), (1, 16384, 32, 128), 64, "tpu", True),
    ((2, 100, 4, 256), (2, 100, 4, 256), 128, "tpu", True),
    ((1, 16384, 32, 128), (1, 16384, 32, 128), 64, "cpu", False),
    ((2, 32, 2, 16), (2, 32, 2, 16), 16, "tpu", False),    # the tiny preset
    ((1, 4096, 8, 72), (1, 4096, 8, 72), 64, "tpu", False),
    ((1, 4096, 8, 128), (1, 4096, 8, 256), 64, "tpu", False),
    ((1, 4096, 8, 128), (1, 4096, 8, 128), 32, "tpu", False),
    ((1, 4096, 8, 128), (1, 4096, 8, 128), 256, "tpu", False),
])
def test_the_kernels_take_the_tpu_shapes_and_xla_the_rest(
        k_shape, v_shape, chunk, backend, takes):
    assert delta_rule.kernel_takes(k_shape, v_shape, chunk, backend) is takes


def test_the_kernel_counter_counts_the_calls_built_on_the_kernels(
        monkeypatch):
    from jimm_tpu import obs
    _on_path(monkeypatch, "kernel")
    inputs, _ = _scan_inputs(1.0, s=128, b=1, h=2, d=128)
    small, _ = _scan_inputs(1.0, s=32)

    def counts():
        snap = obs.snapshot()
        return [snap.get(f"jimm_kda_{k}_total", 0)
                for k in ("calls", "chunks", "kernel")]

    before = counts()
    chunk_kda(*inputs, chunk=64)
    after_kernel = counts()
    chunk_kda(*small, chunk=16)
    after_xla = counts()
    assert [a - b for a, b in zip(after_kernel, before)] == [1, 2, 1]
    assert [a - b for a, b in zip(after_xla, after_kernel)] == [1, 2, 0]


@pytest.mark.parametrize("chunk", [4, 24])
def test_chunk_is_a_power_of_two_that_holds_a_sub_chunk(chunk):
    inputs, _ = _scan_inputs(1.0, s=32)
    with pytest.raises(ValueError, match="no power of two from 8 up"):
        chunk_kda(*inputs, chunk=chunk)


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


# -- what the family may not cost the others ---------------------------------------

def test_no_other_preset_imports_the_familys_modules():
    code = (
        "import sys\n"
        "import jimm_tpu, jimm_tpu.cli as cli\n"
        "from flax import nnx\n"
        "from jimm_tpu import PRESETS\n"
        "mine = ('jimm_tpu.nn.kda', 'jimm_tpu.ops.delta_rule',\n"
        "        'jimm_tpu.models.kimi_linear')\n"
        "assert not [m for m in mine if m in sys.modules], 'at import'\n"
        "for name, cfg in PRESETS.items():\n"
        "    if name.startswith(('kimi', 'granite')):\n"
        "        continue  # granite's Mamba-2 shares nn/kda.py's convolution\n"
        "    cfg = cli._tiny_override(cfg)\n"
        "    nnx.eval_shape(lambda: cli._model_cls(cli._family(name))(\n"
        "        cfg, rngs=nnx.Rngs(0)))\n"
        "    assert not [m for m in mine if m in sys.modules], name\n"
        "cli._model_cls('kimi')\n"
        "assert 'jimm_tpu.models.kimi_linear' in sys.modules\n"
        "print('ok')\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300,
                          env={**__import__("os").environ,
                               "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0 and done.stdout.strip() == "ok", done.stderr


# -- through the CLI ------------------------------------------------------------------

def test_train_cli_runs_the_family_through_the_same_loop(tmp_path, capsys):
    metrics = tmp_path / "m.jsonl"
    assert main(["train", "--preset", "kimi-linear-48b-a3b", "--tiny",
                 "--steps", "3", "--batch-size", "2", "--log-every", "1",
                 "--bf16", "--remat", "dots", "--metrics-file",
                 str(metrics)]) == 0
    rows = [json.loads(line) for line in metrics.read_text().splitlines()]
    assert len(rows) == 3
    assert all(np.isfinite(r["loss"]) and "moe_held_rows" in r for r in rows)
    out = capsys.readouterr().out
    assert "goodput:" in out


def test_num_layers_and_seq_len_shape_the_preset():
    from jimm_tpu import cli
    cfg = cli._replace_towers(preset("kimi-linear-48b-a3b"), depth=9,
                              seq_len=4096)
    assert [n for n, _ in cfg.decoder.runs()] \
        == ["run0", "run1", "run3", "run4", "run7", "run8"]
    assert cfg.decoder.seq_len == 4096
    assert cli._family("kimi-linear-48b-a3b") == "kimi"
    assert cli._model_cls("kimi") is KimiLinear
    assert cli.LM_FAMILIES["kimi"] == {"lr": 1e-4, "warmup_steps": 20}
    assert [name for _, name, _ in cli._lm_counters(_tiny(), 2)] \
        == ["tokens_total", "assignments_total", "held_assignments_total"]


def test_model_flops_of_the_benchmarks_cut():
    """The program's own count (`train/metrics.py`) of the cell's step: KDA's
    projections and recurrence on four layers, latent attention at half of
    S^2 on one; the benchmark's yardstick (`benchmarks/flops_hybrid_lm.py`,
    kept apart) counts the convolutions besides."""
    from jimm_tpu.train.metrics import model_fwd_flops
    step = 3 * model_fwd_flops(preset("kimi-linear-48b-a3b"))
    assert step == pytest.approx(42.55e12, rel=2e-3)


def test_presets_lists_the_share(capsys):
    assert main(["presets"]) == 0
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("kimi-linear-48b-a3b"))
    assert "828.9M" in line and "experts=16/256 held" in line
    assert "depth=5" in line and "seq=16384" in line
