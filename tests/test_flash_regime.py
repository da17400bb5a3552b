"""Which regime of the flash kernels (`ops/flash_attention.py`) a call
gets, at which blocks and heads a cell: the rule and its tables, the
resolved blocks of each family member, the heads of the fused backward,
`impl="auto"`'s dispatch, the regime counters, and the TPU lowering's text
of the tiled regime. Traced or lowered on shapes alone, nearly all of it:
the kernels' numbers are the other `test_flash_*.py` files'."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flash_util import kernel_calls, kernel_grids, qkv, qkv_split, tpu_text
from jimm_tpu.obs.registry import snapshot
from jimm_tpu.ops import flash_attention as fa
from jimm_tpu.ops.attention import reference_attention
from jimm_tpu.ops.flash_attention import flash_attention, flash_attention_bias


# ---------------------------------------------------------------------------
# The rule between the two regimes
# ---------------------------------------------------------------------------

def _first_refused(d, itemsize, spec=fa._SOFTMAX):
    return next(s for s in range(128, 1 << 16, 128)
                if not fa._single_tile_hb(8, s, s, d, itemsize, spec))


#: (S, D, spec) -> regime, for the lengths `impl="auto"` sends here: the
#: image presets' (577 ViT-L / CLIP-L, 576 SigLIP-L, 729 So400m at D 72,
#: 257 / 577 ViT-H at D 80) and the first lengths that stay tiled
RULE_TABLE = [
    (577, 64, fa._SOFTMAX, "single"),
    (576, 64, fa._SOFTMAX, "single"),
    (729, 72, fa._SOFTMAX, "single"),
    (257, 80, fa._SOFTMAX, "single"),
    (577, 80, fa.VariantSpec(has_mask=True), "single"),
    (729, 72, fa.VariantSpec(kind="sigmoid", has_mask=True), "single"),
    (577, 64, fa.VariantSpec(has_bias=True), "tiled"),
    (2048, 64, fa._SOFTMAX, "tiled"),
    (4096, 128, fa.VariantSpec(has_mask=True), "tiled"),
]


@pytest.mark.parametrize("s,d,spec,regime", RULE_TABLE)
def test_regime_rule_table(s, d, spec, regime):
    """The rule is a test of (padded lengths, padded head width, dtype,
    spec) alone: `_fit_blocks` hands a sequence it admits over as one block
    and `_single_tile_hb` picks the heads per cell."""
    dp, s_p = fa._head_pad_target(d), fa._ceil_to(s, 128)
    blocks = fa._fit_blocks(s, s, dp, 2, spec, 512, 512)
    if regime == "single":
        assert blocks == (s_p, s_p)
        assert fa._single_tile_hb(32, s_p, s_p, dp, 2, spec) in (8, 4, 2, 1)
        # an explicit request still gets the tiles it asked for
        assert fa._fit_blocks(s, s, dp, 2, spec, 128, 128,
                              requested=True) == (128, 128)
    else:
        assert max(blocks) <= 512 and blocks != (s_p, s_p)
        assert not fa._single_tile_hb(32, s_p, s_p, dp, 2, spec)


def test_regime_rule_bound_is_monotone_and_stays_tiled_above():
    """One length just over the bound keeps the tiled kernels: the
    three-dimensional (heads, q, kv) grids of the forward and of the one
    backward (once two calls, dq and dk/dv)."""
    bound = _first_refused(64, 2)
    assert bound > 768, "So400m/14-384 (S_p 768) must be admitted"
    assert _first_refused(128, 2) > 768
    assert _first_refused(128, 2) <= bound <= _first_refused(64, 2,
        fa.VariantSpec(kind="sigmoid"))
    assert all(not fa._single_tile_hb(8, s, s, 64, 2, fa._SOFTMAX)
               for s in range(bound, bound + 2048, 128))
    spec = jax.ShapeDtypeStruct((1, bound - 100, 2, 64), jnp.bfloat16)

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(
            flash_attention(*a).astype(jnp.float32)), argnums=(0, 1, 2))(
                q, k, v)

    assert [len(g) for g in kernel_grids(grads, spec, spec, spec)] == [3, 3]
    under = jax.ShapeDtypeStruct((1, bound - 128, 2, 64), jnp.bfloat16)
    assert [len(g) for g in kernel_grids(grads, under, under, under)] == [2, 2]


def test_bias_variant_stays_tiled(rng):
    q, k, v = qkv(rng, b=1, s=64, n=2)
    bias = jnp.asarray(rng.randn(2, 64, 64).astype(np.float32))
    assert kernel_calls(jax.grad(lambda *a: jnp.sum(flash_attention_bias(*a)),
                           argnums=(0, 1, 2, 3)), q, k, v, bias) == (0, 3)


def test_regime_counters_are_published():
    """`jimm_flash_single_tile_total` / `jimm_flash_tiled_total`: one count
    per pallas_call built, in the unified snapshot beside `jimm_tune_*`."""
    spec = jax.ShapeDtypeStruct((1, 577, 2, 64), jnp.bfloat16)
    before = snapshot()
    jax.make_jaxpr(flash_attention)(spec, spec, spec)
    jax.make_jaxpr(lambda *a: flash_attention(
        *a, block_q=128, block_k=128))(spec, spec, spec)
    after = snapshot()
    for name in ("jimm_flash_single_tile_total", "jimm_flash_tiled_total"):
        assert after[name] - before.get(name, 0) == 1


@pytest.mark.parametrize("shape,kw,want", [
    ((2, 577, 16, 64), {}, (2, 2, 0)),            # ViT-L: forward + backward
    ((2, 729, 16, 72), {}, (2, 2, 0)),            # So400m: D padded once
    ((1, 197, 3, 64), {}, (2, 2, 0)),             # 3 heads: the whole row
    ((1, 4096, 16, 128), {"is_causal": True}, (0, 0, 2)),   # the LM cell
    ((1, 577, 2, 64), {"block_q": 128, "block_k": 128}, (0, 0, 2)),
])
def test_direct_counter(shape, kw, want):
    """`jimm_flash_direct_total`: every single-tile call built reads the
    model's layout; the tiled regime counts none."""
    spec = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert kernel_calls(jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, **kw).astype(jnp.float32)), argnums=(0, 1, 2)),
        spec, spec, spec,
        regimes=("direct", "single_tile", "tiled")) == want


#: sha256 of the TPU lowering (kernel bodies cut out) of the gradients at
#: shapes over the rule. The tiled regime's wrappers (flatten, pad to the
#: blocks, slice back) are those it had before the single-tile regime moved
#: to the model's layout. Re-pinned on purpose, and only where the calls'
#: operands changed: with the causal grid of live block pairs the causal
#: calls take the two int32 tables of pairs as their first operands (36 pairs
#: at 4096 tokens in blocks of 512; the text read 193b84c1... before); with
#: the fused backward the dq call went and the dk/dv call has dq as a third
#: result (same operands, and in the causal text the row-major pair of tables
#: went with the dq call), and the three reshapes and transposes back to
#: ``(B, S, N, D)`` read that call's results (with three calls the texts read
#: 799339d3... and e26f2d15...).
TILED_TEXT_SHA = {
    ((1, 1153, 2, 64), False): "7af58bb443ccc48e67b08ae9f4b9adb7abe90e35115244d9a53b78fb455ba713",
    ((1, 4096, 4, 128), True): "2c23840c8052e4459901f2dffa9bc47da71c704e10300ed00f27b3debad2feb4",
}


@pytest.mark.parametrize("shape,causal", sorted(TILED_TEXT_SHA))
def test_tiled_regime_text_is_the_parents(monkeypatch, shape, causal):
    import hashlib
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    spec = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    text = tpu_text(jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, is_causal=causal).astype(jnp.float32)), argnums=(0, 1, 2)),
        spec, spec, spec)
    assert text.count("@tpu_custom_call") == 2
    assert (hashlib.sha256(text.encode()).hexdigest()
            == TILED_TEXT_SHA[shape, causal])


# ---------------------------------------------------------------------------
# Blocks and heads a cell, from the shapes
# ---------------------------------------------------------------------------

#: (S, D, spec) -> the blocks and heads per cell an untuned call runs at
RESOLVED = [
    # the two LM cells (kanana's latent attention at 256 lanes, Ouro): the
    # parent's blocks, four heads a cell where the 8 MiB budget held 1 and 2
    (8192, 192, fa._SOFTMAX, (512, 512), 4),
    (4096, 128, fa._SOFTMAX, (512, 512), 4),
    # a non-causal length over the single-tile rule (the parent: 2 heads)
    (2048, 64, fa._SOFTMAX, (512, 512), 4),
    (2048, 64, fa.VariantSpec(has_mask=True), (512, 512), 4),
    # `_pick_block`'s first rule: never a block that pads the sequence
    # further
    (1153, 64, fa._SOFTMAX, (256, 256), 8),
    (1280, 64, fa._SOFTMAX, (256, 256), 8),
    (1536, 128, fa._SOFTMAX, (512, 512), 4),
    # the bias variant: its fp32 bias and dbias tiles are in the model
    (577, 64, fa.VariantSpec(has_bias=True), (128, 128), 8),
    (4096, 64, fa.VariantSpec(has_bias=True), (512, 512), 2),
    (2048, 128, fa.VariantSpec(has_bias=True), (512, 512), 2),
]


@pytest.mark.parametrize("s,d,spec,blocks,hb", RESOLVED)
def test_resolved_blocks_and_heads(s, d, spec, blocks, hb):
    """Blocks and heads per cell are a function of the call's shapes and
    variant: the blocks are the parent's, the heads follow the VMEM model
    under the budget the call states."""
    dp = fa._head_pad_target(d)
    assert fa._fit_blocks(s, s, dp, 2, spec, fa.DEFAULT_BLOCK_Q,
                          fa.DEFAULT_BLOCK_K) == blocks
    assert fa._pick_hb(32, *blocks, dp, spec, 16) == hb
    assert hb * fa._spec_vmem_bytes(*blocks, dp, spec) <= fa._VMEM_BUDGET
    limit = fa._tiled_vmem_limit(hb, *blocks, dp, spec)
    assert fa._VMEM_BUDGET <= limit <= 64 * 1024 * 1024
    # an asked-for or tuned block wins up to 512, as it always has
    assert fa._fit_blocks(8192, 4096, dp, 2, spec, 2048, 256,
                          requested=True) == (512, 256)


def test_single_tile_and_int8_blocks_are_the_parents():
    """577 tokens are one resident tile of 640; the int8 kernels keep their
    own default of 512 and budget."""
    from jimm_tpu.ops import flash_attention_int8 as fi
    assert fa._fit_blocks(577, 577, 64, 2, fa._SOFTMAX, 512, 512) \
        == (640, 640)
    assert (fi.DEFAULT_BLOCK_Q, fi.DEFAULT_BLOCK_K) == (512, 512)
    assert fi._VMEM_BUDGET == 8 * 1024 * 1024
    for s, want in ((577, 128), (1153, 256), (1280, 256), (2048, 512),
                    (4096, 512), (8192, 512)):
        assert fa._pick_block(s, fi.DEFAULT_BLOCK_Q) == want
    assert fi._pick_hb(32, 512, 512, 128) == 2
    assert fi._pick_hb(32, 512, 512, 128, fi._per_head_bwd_vmem_bytes) == 1


#: (rows of the kernels' batch, query heads to a k/v head, D padded, S_q
#: padded) -> heads a cell of the fused backward, 0: the pair
FUSED_RESOLVED = [
    # the three decoder cells at blocks of 512
    ((64, 1, 256, 8192), 4),       # kanana: 4 x 8 MiB of dq, the bound itself
    ((16, 1, 128, 4096), 4),       # Ouro: 4 x 2 MiB
    ((48, 6, 128, 8192), 3),       # Trinity: a group's two cells, 24 MiB
    # the bound at one head: 65,536 tokens at 128 lanes, 32,768 at 256
    ((4, 1, 128, 32768), 2), ((4, 1, 128, 65536), 1),
    ((4, 1, 128, 65536 + 512), 0), ((4, 1, 128, 131072), 0),
    ((4, 1, 256, 32768), 1), ((4, 1, 256, 32768 + 512), 0),
    ((4, 1, 64, 131072), 1),
    # a group's dq is resident whatever the cell holds of it
    ((48, 6, 128, 8192 + 4096), 0),
]


@pytest.mark.parametrize("call,hb", FUSED_RESOLVED)
def test_fused_backward_heads_by_shape(call, hb):
    bn, group, d, sq_p = call
    assert fa._pick_hb(bn, 512, 512, d, group=group, dq_seq=sq_p) == hb
    if hb:
        cells = group // hb if group > 1 else 1
        limit = fa._tiled_vmem_limit(hb, 512, 512, d, fa._SOFTMAX,
                                     dq_rows=cells * sq_p)
        assert limit == (2 * hb * fa._per_head_vmem_bytes(512, 512, d)
                         + hb * cells * sq_p * d * 4)
        assert limit <= 96 * 1024 * 1024   # of the v5e's 128 MiB
        assert (fa._per_head_vmem_bytes(512, 512, d, dq_rows=cells * sq_p)
                - fa._per_head_vmem_bytes(512, 512, d)
                == cells * sq_p * d * 4)


#: (member, VariantSpec) -> blocks and heads a cell the parent resolved at
#: (S 2048 / 4096, D 64 / 128): grouped heads and the window changed neither
MEMBERS_RESOLVED = [
    ("masked", fa.VariantSpec(has_mask=True), 2048, 64, (512, 512), 4),
    ("bias", fa.VariantSpec(has_bias=True), 2048, 64, (512, 512), 2),
    ("bias", fa.VariantSpec(has_bias=True), 4096, 128, (512, 512), 2),
    ("sigmoid", fa.VariantSpec(kind="sigmoid"), 2048, 64, (512, 512), 4),
    ("sigmoid_masked", fa.VariantSpec(kind="sigmoid", has_mask=True), 4096,
     128, (512, 512), 4),
]


@pytest.mark.parametrize("member,spec,s,d,blocks,hb", MEMBERS_RESOLVED)
def test_other_members_keep_their_blocks_and_heads(member, spec, s, d, blocks,
                                                   hb):
    assert fa._fit_blocks(s, s, d, 2, spec, fa.DEFAULT_BLOCK_Q,
                          fa.DEFAULT_BLOCK_K) == blocks
    assert fa._pick_hb(32, *blocks, d, spec, 16) == hb


def test_the_int8_kernels_keep_their_schedule():
    """`flash_attention_int8.py` has its own head rule and causal rectangle,
    and takes neither a window nor grouped heads."""
    import inspect

    from jimm_tpu.ops import flash_attention_int8 as fi8
    assert "window" not in inspect.signature(fi8.flash_attention_int8) \
        .parameters
    text = inspect.getsource(fi8)
    assert "_live_pairs" not in text and "pl.when" in text


# ---------------------------------------------------------------------------
# impl="auto" on a TPU
# ---------------------------------------------------------------------------

def test_auto_reaches_flash_with_unequal_widths(rng, monkeypatch):
    """``impl="auto"`` on a TPU sends a call `_flash_eligible` admits (here
    512 tokens: every call from there up) to the flash kernels whatever the
    value width; off the TPU XLA's op gets v padded and cut back."""
    from jimm_tpu.ops import attention
    q, k, v = qkv_split(rng, 1, 512, 2, 24, 16)
    want = reference_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(
        attention.dot_product_attention(q, k, v, is_causal=True), want,
        rtol=2e-4, atol=2e-5)
    seen = []
    monkeypatch.setattr(attention, "_default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "flash_attention",
                        lambda q, k, v, **kw: seen.append(v.shape) or want)
    attention.dot_product_attention(q, k, v, is_causal=True)
    assert seen == [(1, 512, 2, 16)]


def _auto_path(monkeypatch, q_shape, k_shape, dtype=jnp.bfloat16, **kw):
    """Where ``impl="auto"`` sends a call on a (faked) TPU backend:
    ``"flash"``, ``"flash_masked"``, ``"flash_bias"`` or ``"xla"``. Traced
    on shapes alone: nothing runs."""
    from jimm_tpu.ops import attention
    seen = []

    def spy(name):
        def call(q, k, v, *rest, **kwargs):
            seen.append(name)
            return jnp.zeros((*q.shape[:3], v.shape[-1]), q.dtype)
        return call

    monkeypatch.setattr(attention, "_default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "flash_attention", spy("flash"))
    monkeypatch.setattr(fa, "flash_attention_masked", spy("flash_masked"))
    monkeypatch.setattr(fa, "flash_attention_bias", spy("flash_bias"))
    monkeypatch.setattr(jax.nn, "dot_product_attention", spy("xla"))
    q = jax.ShapeDtypeStruct(q_shape, dtype)
    k = jax.ShapeDtypeStruct(k_shape, dtype)
    if kw.pop("masked", False):
        kw["mask"] = jnp.ones((q_shape[0], 1, 1, k_shape[1]), bool)
    if kw.pop("biased", False):
        kw["bias"] = jnp.zeros((q_shape[2], q_shape[1], k_shape[1]))
    jax.eval_shape(lambda q, k, v: attention.dot_product_attention(
        q, k, v, **kw), q, k, k)
    assert len(seen) == 1, seen
    return seen[0]


#: (q's shape, k and v's shape or None for q's, call, path): every shape
#: read on the chip for the short rule (`_flash_eligible` has the readings)
#: on the side its reading chose, the lengths from 512 up where they were, and the calls
#: the short rule keeps off the kernels whatever their size
AUTO_TABLE = [
    # SigLIP-B/16-256's vision tower at the cell's batch: plain, causal,
    # windowed, with a key-padding mask, in float32
    ((128, 256, 12, 64), None, {}, "flash"),
    ((128, 256, 12, 64), None, {"is_causal": True}, "flash"),
    ((128, 256, 12, 64), None, {"is_causal": True, "window": 128}, "flash"),
    ((128, 256, 12, 64), None, {"masked": True}, "flash_masked"),
    ((128, 256, 12, 64), None, {"dtype": jnp.float32}, "flash"),
    # the other presets under 512 at a training batch
    ((128, 197, 12, 64), None, {}, "flash"),         # ViT-B/16-224
    ((128, 196, 12, 64), None, {}, "flash"),         # SigLIP-B/16-224
    ((128, 196, 12, 64), None, {"masked": True}, "flash_masked"),
    ((64, 257, 16, 64), None, {}, "flash"),          # CLIP-L/14
    ((64, 256, 8, 128), None, {}, "flash"),          # a head width of 128
    ((24, 400, 16, 64), None, {}, "flash"),
    ((128, 128, 12, 64), None, {}, "flash"),         # one whole lane tile
    ((128, 129, 12, 64), None, {}, "flash"),
    ((32, 256, 12, 64), None, {}, "flash"),          # 24 Mi scores
    # under one lane tile of tokens, whatever the batch
    ((128, 64, 12, 64), None, {}, "xla"),            # SigLIP's text tower
    ((128, 50, 12, 64), None, {}, "xla"),            # ViT-B/32
    ((128, 77, 8, 64), None, {"is_causal": True}, "xla"),   # CLIP's text
    ((128, 112, 12, 64), None, {}, "xla"),
    ((4096, 64, 12, 64), None, {}, "xla"),
    ((128, 1, 12, 64), (128, 256, 12, 64), {}, "xla"),      # the MAP probe
    ((128, 1, 12, 64), (128, 256, 12, 64), {"masked": True}, "xla"),
    ((4096, 1, 12, 64), (4096, 256, 12, 64), {}, "xla"),
    # too few scores: XLA keeps them on the chip (serving batches)
    ((8, 256, 12, 64), None, {}, "xla"),
    ((1, 256, 12, 64), None, {}, "xla"),
    ((32, 197, 12, 64), None, {}, "xla"),
    ((64, 128, 12, 64), None, {}, "xla"),
    ((8, 256, 12, 64), None, {"masked": True}, "xla"),
    # a head width off the tiles pays its padding under 512
    ((32, 256, 16, 72), None, {}, "xla"),            # So400m/14-224
    ((32, 257, 16, 80), None, {}, "xla"),            # ViT-H/14
    ((32, 256, 16, 96), None, {}, "xla"),
    # grouped key/value heads run the tiled kernels: under 512 they lose
    ((128, 256, 12, 64), (128, 256, 4, 64), {"is_causal": True}, "xla"),
    ((64, 384, 12, 64), (64, 384, 4, 64),
     {"is_causal": True, "window": 128}, "xla"),
    # the bias variant stays tiled and was not read under 512
    ((128, 256, 12, 64), None, {"biased": True}, "xla"),
    # from 512 up: every call, as before
    ((1, 512, 2, 64), None, {}, "flash"),
    ((24, 577, 16, 64), None, {}, "flash"),          # ViT-L/16-384's cell
    ((1, 577, 16, 80), None, {"masked": True}, "flash_masked"),
    ((2, 729, 16, 72), None, {}, "flash"),           # So400m/14-384
    ((1, 4096, 16, 128), None, {"is_causal": True}, "flash"),   # Ouro
    ((1, 4096, 48, 128), (1, 4096, 8, 128),
     {"is_causal": True, "window": 1024}, "flash"),
    ((1, 512, 2, 64), None, {"biased": True}, "flash_bias"),
    ((1, 512, 2, 64), (1, 511, 2, 64), {}, "xla"),
]


@pytest.mark.parametrize("q_shape,k_shape,kw,path", AUTO_TABLE)
def test_auto_dispatch_table(monkeypatch, q_shape, k_shape, kw, path):
    assert _auto_path(monkeypatch, q_shape, k_shape or q_shape,
                      **kw) == path


def test_auto_keeps_a_partitioned_call_under_512_off_the_kernels(
        monkeypatch, eight_devices):
    """Mosaic kernels cannot be partitioned automatically: under a mesh with
    a live axis the short rule admits nothing (SigLIP-B's sharded step keeps
    compiling), a mesh of one device per axis changes nothing, and inside a
    ``shard_map`` over every axis the call is local and takes the kernels."""
    from jax.sharding import PartitionSpec as P

    from jimm_tpu.parallel.mesh import make_mesh
    shape = (128, 256, 12, 64)
    assert _auto_path(monkeypatch, shape, shape) == "flash"
    with jax.set_mesh(make_mesh({"data": 2, "model": 2},
                                devices=eight_devices[:4])):
        assert _auto_path(monkeypatch, shape, shape) == "xla"
        assert _auto_path(monkeypatch, (64, 577, 16, 64),
                          (64, 577, 16, 64)) == "flash"  # as before
        seen = []

        def local(q):
            seen.append(_auto_path(monkeypatch, q.shape, q.shape))
            return q
        jax.eval_shape(jax.shard_map(
            local, in_specs=P("data", None, "model"),
            out_specs=P("data", None, "model")),
            jax.ShapeDtypeStruct((256, 256, 24, 64), jnp.bfloat16))
        assert seen == ["flash"]
    with jax.set_mesh(make_mesh({"data": 1}, devices=eight_devices[:1])):
        assert _auto_path(monkeypatch, shape, shape) == "flash"
