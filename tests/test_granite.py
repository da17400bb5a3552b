"""granite-4.0-h-micro (`models/granite.py`, `nn/mamba2.py`, the dense runs
of `models/kanana.py`) at a small size on the CPU, against the plain float32
reference `benchmarks/reference/granite.py` on seeded random weights: the
mixer, the whole model's loss and gradients, one test a multiplier, the
dense-only stack, the scopes and counters, the benchmark's cut, what the
family may not cost the others, and its way through `jimm-tpu train`. The
chunked scan and its kernels (`ops/ssd.py`) are `tests/test_ssd.py`'s; its
entries in the CLI's tables, its `presets` line and the modules no other
preset may import are `tests/test_families.py`'s."""

import dataclasses
import json
import math
import re

import jax
import jax.numpy as jnp
import pytest
from flax import nnx

from benchmarks import flops_granite
from benchmarks.reference import granite as ref
from benchmarks.reference import parity_granite
from family_util import train_rows
from jimm_tpu import Granite, GraniteConfig, Kanana, KananaConfig, preset
from jimm_tpu.cli import _tiny_override
from jimm_tpu.configs import (Mamba2Config, MLAConfig, MoEDecoderConfig,
                              TransformerConfig)
from jimm_tpu.nn.mamba2 import Mamba2
from jimm_tpu.ops import ssd


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


# -- the path the tiny preset takes (the scan itself: `tests/test_ssd.py`) ----

def test_the_tiny_preset_keeps_the_xla_path():
    m = _tiny().decoder.mamba
    x_shape = (2, 32, m.num_heads, m.head_dim)
    b_shape = (2, 32, m.groups, m.state)
    assert not ssd.kernel_takes(x_shape, b_shape, m.chunk, "tpu")
    assert ssd._default_backend() == "cpu"


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


@pytest.fixture(scope="module")
def published_logits(model):
    """``(sizes, logits, got, want)``: one batch's logits, the model's and
    the reference's at the published multipliers, and the reference's as a
    function of the sizes, for the four multipliers to share."""
    tokens = jax.random.randint(jax.random.key(9), (2, 32), 0, 512, jnp.int32)
    sizes = parity_granite.sizes_of(model)
    params = _params(model)

    def logits(sizes):
        return ref.logits(params, ref.hidden_states(params, tokens, sizes),
                          sizes)

    with jax.default_matmul_precision("highest"):
        return sizes, logits, model(tokens), logits(sizes)


@pytest.mark.parametrize("key, published", [
    ("embedding_multiplier", 12), ("residual_multiplier", 0.22),
    ("attention_multiplier", 0.015625), ("logits_scaling", 8)])
def test_each_multiplier_is_in_the_model(key, published, published_logits):
    """The model's logits are the reference's with the published multiplier
    and far from the reference's with that one multiplier dropped (1), so a
    model that dropped it would fail."""
    sizes, logits, got, want = published_logits
    assert sizes[key] == published
    with jax.default_matmul_precision("highest"):
        dropped = logits({**sizes, key: 1.0})
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
# (no other preset imports its modules: `tests/test_families.py`)

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
# (its entries in the CLI's tables and its `presets` line:
# `tests/test_families.py`)

def test_train_cli_runs_the_family_through_the_same_loop(tmp_path, capsys):
    rows = train_rows(tmp_path, capsys, [
        "--preset", "granite-4.0-h-micro", "--tiny", "--steps", "3",
        "--batch-size", "2", "--log-every", "1", "--bf16", "--remat", "full"])
    assert all("moe_held_rows" not in r for r in rows)


def test_num_layers_and_seq_len_shape_the_preset():
    from jimm_tpu import cli
    cfg = cli._replace_towers(preset("granite-4.0-h-micro"), depth=20,
                              seq_len=4096)
    assert [n for n, _ in cfg.decoder.runs()] \
        == ["run0", "run5", "run6", "run15", "run16"]
