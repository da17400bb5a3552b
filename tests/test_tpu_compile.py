"""The Pallas kernels, at the widths the presets route through them, compiled
by the TPU's own compiler for a *described* v5e chip (no chip attached,
nothing runs). Interpret mode — what every other kernel test uses — cannot
see what Mosaic refuses: a block that misses the tiling, too much VMEM, a
layout XLA and Mosaic disagree on. These compiles can, at no chip time.

A compile that passes is not a chip run: `chip_smoke.py` is what runs them.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from flax import nnx

from jimm_tpu.ops import (delta_rule as dr, flash_attention as fa,
                          fp8_matmul as f8, int8_matmul as i8,
                          layer_norm as ln, ssd)

HBM_BYTES = 16 * 1000 ** 3  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    """Sharding on one chip of a described v5e 2x2 host, with the persistent
    compile cache off: such a compile is written to it but cannot be read
    back without a chip, so the next run would warn on every entry."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed here
        pytest.skip(f"cannot describe a v5e topology: {e!r}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernels pick interpret mode from ``jax.default_backend()``, which
    is the CPU here: steer that in the test, not through a program option."""
    for module in (fa, f8, i8, ln, dr, ssd):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    for module in (dr, ssd):
        monkeypatch.setattr(module, "_default_backend", lambda: "tpu")


def _fwd_bwd(fn):
    def run(*args):
        out, vjp = jax.vjp(fn, *args)
        return out, vjp(jnp.ones_like(out))
    return run


def _flash(shape, **kw):
    return (functools.partial(fa.flash_attention, **kw),
            [(shape, jnp.bfloat16)] * 3)


M, K, N = 4096, 768, 3072  # ViT-B MLP up-projection at batch 16 x 256 tokens

KERNEL_CASES = {
    # ViT-L/16-384 and CLIP-L/14-336: S=577, D=64 — the single-tile forward
    # and the fused backward
    "flash_s577_d64": _flash((32, 577, 16, 64)),
    # SigLIP-B/16-256's vision tower as its cell calls it (batch 128, 12 heads:
    # four heads a cell over three lane groups), the shortest call `auto`
    # sends here (one whole lane tile of tokens), a 224 px tower's edge tile
    # and the masked member at the cell's shape
    "flash_s256_d64": _flash((128, 256, 12, 64)),
    "flash_s128_d64": _flash((128, 128, 12, 64)),
    "flash_s196_d64": _flash((128, 196, 12, 64)),
    "flash_masked_s256_d64": (
        fa.flash_attention_masked,
        [((128, 256, 12, 64), jnp.bfloat16)] * 3 + [((128, 256), jnp.bool_)]),
    # So400m/14-384: S=729, head width 72 (lane-padded inside the wrapper)
    "flash_s729_d72": _flash((16, 729, 16, 72)),
    # the largest working set the single-tile rule admits (S_p 1152 at 128
    # lanes, one head per cell): a VMEM refusal shows here, not on the chip
    "flash_s1152_d128": _flash((4, 1152, 16, 128)),
    # ViT-Ti/16: 3 heads of 64 are no whole lane tile, so a cell holds the
    # whole 192-lane row; S=197 is an odd row count under the edge block
    "flash_s197_d64_whole_row": _flash((64, 197, 3, 64)),
    # the first length over the rule: the tiled kernels, as at the parent
    "flash_s1153_d64": _flash((4, 1153, 16, 64)),
    # the looped decoder's training sequence: causal, 4096 tokens, 16 heads
    # of 128: the tiled kernels (forward, and ONE backward with four heads'
    # 8 MiB of fp32 dq resident)
    "flash_causal_s4096_d128": _flash((1, 4096, 16, 128), is_causal=True),
    # latent attention at the sparse decoder's training size: 8192 tokens,
    # 32 heads, q/k 192 wide (256 lanes), v 128: the tiled kernels with v, o,
    # do and dv at their own tile; the fused backward holds four heads' dq,
    # 32 MiB (the residency bound itself), and states 94 MiB of the 128
    "flash_causal_s8192_qk192_v128": (
        functools.partial(fa.flash_attention, is_causal=True),
        [((2, 8192, 32, 192), jnp.bfloat16)] * 2
        + [((2, 8192, 32, 128), jnp.bfloat16)]),
    # a non-causal length over the rule: four heads of 64 a cell at blocks of
    # 512 under the VMEM limit the call states
    "flash_s2048_d64": _flash((2, 2048, 16, 64)),
    # the bias variant over the rule, blocks of 512 at two heads a cell: the
    # fourth call, dbias, holds the fp32 bias tile, its output and its
    # scratch beside the score tiles (16.9 MB by the model, over the default
    # 16 MiB scope) and states its limit like the other three
    "flash_bias_s2048_d64": (
        fa.flash_attention_bias,
        [((2, 2048, 16, 64), jnp.bfloat16)] * 3
        + [((16, 2048, 2048), jnp.float32)]),
    # causal at 256 lanes: Mosaic refuses this dbias call in its default scope
    "flash_bias_causal_s2048_d256": (
        functools.partial(fa.flash_attention_bias, is_causal=True),
        [((2, 2048, 16, 256), jnp.bfloat16)] * 3
        + [((16, 2048, 2048), jnp.float32)]),
    # the longest causal length compiled: 256 blocks of 512 a side, 32,896
    # live pairs, so 263 KB of scalar-prefetched tables in SMEM; a head's dq
    # is 64 MiB, over the residency bound, so the backward stays dq + dk/dv
    "flash_causal_s131072_d128": (
        functools.partial(fa.flash_attention, is_causal=True),
        [((1, 131072, 4, 128), jnp.bfloat16)] * 3),
    # grouped-query attention at the sparse decoder's training size: 8192
    # tokens, 48 heads of 128 over 8 key/value heads, three heads a cell; under
    # a window of 4096 (108 of 136 live pairs; the backward over two cells a
    # group, both cells' dq resident: 24 MiB) and full
    "flash_gqa_window_s8192_48over8_d128": (
        functools.partial(fa.flash_attention, is_causal=True, window=4096),
        [((1, 8192, 48, 128), jnp.bfloat16)]
        + [((1, 8192, 8, 128), jnp.bfloat16)] * 2),
    "flash_gqa_full_s8192_48over8_d128": (
        functools.partial(fa.flash_attention, is_causal=True),
        [((1, 8192, 48, 128), jnp.bfloat16)]
        + [((1, 8192, 8, 128), jnp.bfloat16)] * 2),
    # the window in the single-tile kernels
    "flash_window_s577_d64": _flash((8, 577, 16, 64), is_causal=True,
                                    window=128),
    "flash_masked_s577_d64": (
        fa.flash_attention_masked,
        [((32, 577, 16, 64), jnp.bfloat16)] * 3 + [((32, 577), jnp.bool_)]),
    "layer_norm_768": (ln.layer_norm, [((32768, 768), jnp.bfloat16),
                                       ((768,), jnp.bfloat16),
                                       ((768,), jnp.bfloat16)]),
    "layer_norm_1152": (ln.layer_norm, [((11664, 1152), jnp.bfloat16),
                                        ((1152,), jnp.bfloat16),
                                        ((1152,), jnp.bfloat16)]),
    "fp8_matmul": (f8.fp8_matmul, [((M, K), jnp.bfloat16),
                                   ((K, N), jnp.bfloat16),
                                   ((N,), jnp.bfloat16)]),
    "quantized_linear": (i8.quantized_linear, [((M, K), jnp.bfloat16),
                                               ((K, N), jnp.int8),
                                               ((N,), jnp.float32),
                                               ((N,), jnp.float32)]),
    # the delta-rule scan at kimi_linear_48b_a3b.train's shape: 16,384
    # tokens, 32 heads of 128, chunks of 64; q, k, v in bfloat16
    "kda_scan_s16384_h32_d128": (
        functools.partial(dr.chunk_kda, chunk=64),
        [((1, 16384, 32, 128), jnp.bfloat16)] * 3
        + [((1, 16384, 32, 128), jnp.float32), ((1, 16384, 32), jnp.float32)]),
    # the Mamba-2 scan at granite_4_0_h_micro.train's shape: 16,384 tokens, 64
    # heads of 64, B and C 128 wide in one group, chunks of 256; x, B, C in
    # bfloat16, dt and A in float32
    "ssd_scan_s16384_h64_p64_n128": (
        functools.partial(ssd.chunk_ssd, chunk=256),
        [((1, 16384, 64, 64), jnp.bfloat16), ((1, 16384, 64), jnp.float32),
         ((64,), jnp.float32)] + [((1, 16384, 1, 128), jnp.bfloat16)] * 2),
}


#: Mosaic calls in forward + backward: the single-tile regime is one forward
#: and ONE fused backward, and so is the tiled regime while the heads' fp32
#: dq fits in VMEM (`_DQ_RESIDENT_BUDGET`); above it dq and dk/dv are two
#: calls, and the bias variant adds dbias
SINGLE_TILE_CALLS = {"flash_s256_d64": 2, "flash_s128_d64": 2,
                     "flash_s196_d64": 2, "flash_masked_s256_d64": 2,
                     "flash_s577_d64": 2, "flash_s729_d72": 2,
                     "flash_masked_s577_d64": 2, "flash_s1152_d128": 2,
                     "flash_s197_d64_whole_row": 2,
                     "flash_s1153_d64": 2, "flash_causal_s4096_d128": 2,
                     "flash_causal_s8192_qk192_v128": 2,
                     "flash_s2048_d64": 2, "flash_bias_s2048_d64": 3,
                     "flash_bias_causal_s2048_d256": 3,
                     "flash_causal_s131072_d128": 3,
                     "flash_gqa_window_s8192_48over8_d128": 2,
                     "flash_gqa_full_s8192_48over8_d128": 2,
                     "flash_window_s577_d64": 2}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_compiles_for_v5e(case, one_chip, compiled_kernels):
    fn, arg_shapes = KERNEL_CASES[case]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_shapes]
    text = jax.jit(_fwd_bwd(fn)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if case in SINGLE_TILE_CALLS:
        assert text.count("custom_call_target=\"tpu_custom_call\"") \
            == SINGLE_TILE_CALLS[case]


#: the three decoder cells' attention calls: case -> (rows of the kernels'
#: batch, query heads to a k/v head, D padded, S_q) and the heads a cell the
#: fused backward runs at there
FUSED_BACKWARD_CALLS = {
    "flash_causal_s8192_qk192_v128": ((64, 1, 256, 8192), 4),
    "flash_causal_s4096_d128": ((16, 1, 128, 4096), 4),
    "flash_gqa_window_s8192_48over8_d128": ((48, 6, 128, 8192), 3),
    "flash_gqa_full_s8192_48over8_d128": ((48, 6, 128, 8192), 3),
}


@pytest.mark.parametrize("case", sorted(FUSED_BACKWARD_CALLS))
def test_fused_backward_compiles_under_the_limit_it_states(case, one_chip,
                                                           compiled_kernels):
    """The one backward call of a decoder cell's shape, at the heads a cell
    and the ``vmem_limit_bytes`` the code picks: Mosaic is given that scope
    and uses less (the resident dq once, the tiles' pipeline twice)."""
    import re
    (bn, group, d, sq), hb = FUSED_BACKWARD_CALLS[case]
    assert fa._pick_hb(bn, 512, 512, d, group=group, dq_seq=sq) == hb
    cells = group // hb if group > 1 else 1
    stated = fa._tiled_vmem_limit(hb, 512, 512, d, fa._SOFTMAX,
                                  dq_rows=cells * sq)
    resident = hb * cells * sq * d * 4
    assert resident <= fa._DQ_RESIDENT_BUDGET
    fn, arg_shapes = KERNEL_CASES[case]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_shapes]
    text = jax.jit(_fwd_bwd(fn)).lower(*args).compile().as_text()
    scopes = [tuple(int(re.search(key + r'":\[\{"memory_space":"1",'
                                  r'"offset":"\d+","size":"(\d+)"', ln)[1])
                    for key in ("\"scoped_memory_configs",
                                "used_scoped_memory_configs"))
              for ln in text.splitlines()
              if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(scopes) == 2             # the forward, the backward
    given, used = scopes[1]
    assert given == stated <= 96 * 1024 * 1024
    assert resident < used <= given


def _kernel_calls(text: str) -> list[tuple[str, int, int]]:
    """``(op_name, scope Mosaic was given, scope it used)`` of each Pallas
    call in a compiled program's text."""
    import re
    calls = []
    for ln in text.splitlines():
        if "custom_call_target=\"tpu_custom_call\"" not in ln:
            continue
        given, used = (int(re.search(key + r'":\[\{"memory_space":"1",'
                                     r'"offset":"\d+","size":"(\d+)"', ln)[1])
                       for key in ("\"scoped_memory_configs",
                                   "used_scoped_memory_configs"))
        calls.append((re.search(r'op_name="([^"]*)"', ln)[1], given, used))
    return calls


def test_kda_kernels_compile_under_the_limit_they_state(one_chip,
                                                        compiled_kernels):
    """The scan's forward and backward at the cell's shape: two Pallas calls,
    named, each under the ``vmem_limit_bytes`` it states, and both under the
    ``kda_scan`` scope the call is made in (the backward too: a custom vjp's
    rule keeps its caller's scopes; the model's step is the next test)."""
    fn, arg_shapes = KERNEL_CASES["kda_scan_s16384_h32_d128"]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_shapes]

    def scoped(*a):
        with jax.named_scope("kda"), jax.named_scope("kda_scan"):
            return fn(*a)

    calls = _kernel_calls(jax.jit(_fwd_bwd(scoped)).lower(*args).compile()
                          .as_text())
    assert [name.rsplit("/", 2)[1] for name, _, _ in calls] \
        == ["kda_fwd", "kda_bwd"]
    for name, given, used in calls:
        assert "/kda_scan/" in name
        assert used <= given == dr._VMEM_LIMIT


def test_kda_kernels_carry_the_scopes_in_the_models_step(one_chip,
                                                         compiled_kernels):
    """A hybrid decoder's train step compiled for the v5e with its KDA layers
    at heads of 128 in chunks of 64: every Pallas call of the scan, forward
    and backward, has ``kda`` and ``kda_scan`` as plain components of its
    ``op_name``, which is how `benchmarks/layer_metrics/hybrid_lm.py` finds
    ``kda_scan_ms`` (a backward without them would leave the scan's time to
    the stray ops around it)."""
    import dataclasses
    import re

    from jimm_tpu import KimiLinear, preset
    from jimm_tpu.cli import _tiny_override
    from jimm_tpu.train import OptimizerConfig, make_optimizer
    from jimm_tpu.train.trainer import make_lm_train_step
    cfg = _tiny_override(preset("kimi-linear-48b-a3b"))
    cfg = dataclasses.replace(cfg, decoder=dataclasses.replace(
        cfg.decoder, kda=dataclasses.replace(cfg.decoder.kda, num_heads=2,
                                             head_dim=128, chunk=64)))

    def build():
        model = KimiLinear(cfg, rngs=nnx.Rngs(0))
        return model, make_optimizer(model, OptimizerConfig(total_steps=4))

    model, optimizer = nnx.eval_shape(build)
    for module in (model, optimizer):
        nnx.update(module, jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip),
            nnx.state(module)))
    tokens = jax.ShapeDtypeStruct((2, cfg.decoder.seq_len + 1), jnp.int32,
                                  sharding=one_chip)
    text = make_lm_train_step("kimi").lower(model, optimizer, tokens) \
        .compile().as_text()
    kinds = {}
    for name, _, _ in _kernel_calls(text):
        kind = name.rsplit("/", 2)[1]
        if kind.startswith("kda_"):
            assert re.search(r"(^|/)kda/kda_scan/kda_(fwd|bwd)/", name), name
            kinds[kind] = kinds.get(kind, 0) + 1
    # one forward and one backward a run of KDA layers (three in the tiny
    # stack; a scanned run's body is one call in the text)
    assert kinds == {"kda_fwd": 3, "kda_bwd": 3}, kinds


def test_ssd_kernels_compile_under_the_limit_they_state(one_chip,
                                                        compiled_kernels):
    """The Mamba-2 scan's forward and backward at the cell's shape: two
    Pallas calls, named, each under the ``vmem_limit_bytes`` it states, and
    both under the ``ssm_scan`` scope the call is made in."""
    fn, arg_shapes = KERNEL_CASES["ssd_scan_s16384_h64_p64_n128"]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_shapes]

    def scoped(*a):
        with jax.named_scope("ssm"), jax.named_scope("ssm_scan"):
            return fn(*a)

    calls = _kernel_calls(jax.jit(_fwd_bwd(scoped)).lower(*args).compile()
                          .as_text())
    assert [name.rsplit("/", 2)[1] for name, _, _ in calls] \
        == ["ssd_fwd", "ssd_bwd"]
    for name, given, used in calls:
        assert "/ssm_scan/" in name
        assert used <= given == ssd._VMEM_LIMIT


def test_ssd_kernels_carry_the_scopes_in_the_models_step(one_chip,
                                                         compiled_kernels):
    """A granite train step compiled for the v5e with its Mamba-2 layers at
    heads of 64 and a state of 128 in chunks of 128: every Pallas call of
    the scan, forward and backward, has ``ssm`` and ``ssm_scan`` as plain
    components of its ``op_name``, which is how
    `benchmarks/layer_metrics/granite.py` finds ``ssm_scan_ms``."""
    import dataclasses
    import re

    from jimm_tpu import Granite, preset
    from jimm_tpu.cli import _tiny_override
    from jimm_tpu.train import OptimizerConfig, make_optimizer
    from jimm_tpu.train.trainer import make_lm_train_step
    cfg = _tiny_override(preset("granite-4.0-h-micro"))
    cfg = dataclasses.replace(cfg, decoder=dataclasses.replace(
        cfg.decoder, mamba=dataclasses.replace(
            cfg.decoder.mamba, num_heads=2, head_dim=64, state=128,
            chunk=128)))

    def build():
        model = Granite(cfg, rngs=nnx.Rngs(0))
        return model, make_optimizer(model, OptimizerConfig(total_steps=4))

    model, optimizer = nnx.eval_shape(build)
    for module in (model, optimizer):
        nnx.update(module, jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip),
            nnx.state(module)))
    tokens = jax.ShapeDtypeStruct((2, cfg.decoder.seq_len + 1), jnp.int32,
                                  sharding=one_chip)
    text = make_lm_train_step("granite").lower(model, optimizer, tokens) \
        .compile().as_text()
    kinds = {}
    for name, _, _ in _kernel_calls(text):
        kind = name.rsplit("/", 2)[1]
        if kind.startswith("ssd_"):
            assert re.search(r"(^|/)ssm/ssm_scan/ssd_(fwd|bwd)/", name), name
            kinds[kind] = kinds.get(kind, 0) + 1
    # one forward and one backward a run of Mamba-2 layers (two in the tiny
    # stack; a scanned run's body is one call in the text)
    assert kinds == {"ssd_fwd": 2, "ssd_bwd": 2}, kinds


@pytest.mark.parametrize("rows,width,expert_dim,experts,tile_m", [
    (16384, 2048, 768, 16, 512),   # kanana_2_30b_a3b.train's chunk
    (4096, 3072, 3072, 8, 128),    # trinity_large.train's: small groups
])
def test_grouped_products_compile_for_v5e(rows, width, expert_dim, experts,
                                          tile_m, one_chip):
    """The three grouped products of a chunk, forward and backward, in the
    tiles `nn/moe.py` gives them at the two cells' shapes (the whole
    contraction in a tile where groups are small: 3 MB of weights a tile)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from jimm_tpu.nn import moe
    tiles = moe.tiling(tile_m, 2)

    def products(xs, gate, up, down, sizes):
        sizes = jnp.concatenate([sizes, rows - jnp.sum(sizes, keepdims=True)])
        h = jax.nn.silu(gmm(xs, gate, sizes, xs.dtype, tiles)) \
            * gmm(xs, up, sizes, xs.dtype, tiles)
        return gmm(h, down, sizes, xs.dtype, tiles)

    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    text = jax.jit(_fwd_bwd(products)).lower(
        bf16(rows, width), bf16(experts, width, expert_dim),
        bf16(experts, width, expert_dim), bf16(experts, expert_dim, width),
        jax.ShapeDtypeStruct((experts,), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") >= 9


@pytest.mark.slow
def test_siglip_b16_256_train_step_fits_one_v5e_chip(one_chip, monkeypatch,
                                                     compiled_kernels):
    """The whole contrastive train step of `chip_smoke.py`'s train phase
    (published widths, bf16, batch 128, remat=dots, donated state) compiles
    for one chip and asks for less than its 16 GB, with ``auto`` deciding as
    it does on the chip: the vision tower's attention at `(128, 256, 12, 64)`
    is the single-tile kernel pair (a scanned layer body: one forward and one
    backward call in the text), the text tower's at 64 tokens stays on XLA. A
    silent return of the vision tower to XLA fails here."""
    from jimm_tpu import SigLIP, preset
    from jimm_tpu.configs import parse_remat, with_runtime
    from jimm_tpu.ops import attention
    from jimm_tpu.train import (OptimizerConfig, make_contrastive_train_step,
                                make_optimizer)
    monkeypatch.setattr(attention, "_default_backend", lambda: "tpu")

    cfg = with_runtime(preset("siglip-base-patch16-256"),
                       **parse_remat("dots"), attn_impl="auto",
                       ln_impl="xla", scan_unroll=1)

    def build():
        model = SigLIP(cfg, rngs=nnx.Rngs(0), dtype=jnp.bfloat16,
                       param_dtype=jnp.bfloat16)
        return model, make_optimizer(model, OptimizerConfig(total_steps=10))

    model, optimizer = nnx.eval_shape(build)
    for module in (model, optimizer):
        nnx.update(module, jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip),
            nnx.state(module)))
    batch = 128
    images = jax.ShapeDtypeStruct((batch, 256, 256, 3), jnp.float32,
                                  sharding=one_chip)
    text = jax.ShapeDtypeStruct((batch, 64), jnp.int32, sharding=one_chip)
    step = make_contrastive_train_step("siglip", donate=True)
    compiled = step.lower(model, optimizer, images, text).compile()
    assert compiled.as_text().count(
        "custom_call_target=\"tpu_custom_call\"") == 2
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert resident < HBM_BYTES, mem
