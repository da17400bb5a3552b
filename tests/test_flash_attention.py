"""Flash-attention kernel vs fp32 einsum oracle (SURVEY §4 implication (d)),
in Pallas interpret mode on CPU (compiled path exercised by bench on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jimm_tpu.obs.registry import get_registry, snapshot
from jimm_tpu.ops import flash_attention as fa
from jimm_tpu.ops.attention import (dot_product_attention,
                                    reference_attention,
                                    reference_sigmoid_attention)
from jimm_tpu.ops.flash_attention import (flash_attention,
                                          flash_attention_bias,
                                          flash_attention_lse,
                                          flash_attention_masked,
                                          sigmoid_attention)


def qkv(rng, b=2, s=256, n=2, d=64, dtype=np.float32):
    return tuple(jnp.asarray(rng.randn(b, s, n, d).astype(dtype) * 0.5)
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(rng, causal):
    q, k, v = qkv(rng)
    out = flash_attention(q, k, v, is_causal=causal)
    ref = reference_attention(q, k, v, is_causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_forward_unaligned_seq(rng):
    """Sequence lengths that need padding (ViT: 197, 257, 577 tokens)."""
    q, k, v = qkv(rng, s=197)
    out = flash_attention(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(rng, causal):
    q, k, v = qkv(rng, s=128, n=1)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, is_causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, is_causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-4, err_msg=f"d{name}")


def test_gradients_unaligned_seq(rng):
    q, k, v = qkv(rng, s=197, n=1)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("s", [1, 5, 257])
def test_odd_seq_fwd_bwd(rng, s):
    """Sequence lengths far off the tile grid (single token, tiny crops,
    ViT-odd 257): fwd and grads through the padded+masked kernels."""
    q, k, v = qkv(rng, b=1, s=s, n=1)
    np.testing.assert_allclose(flash_attention(q, k, v),
                               reference_attention(q, k, v), atol=2e-5)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("s", [5, 257])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_odd_seq_lowers_for_tpu(s, dtype):
    """Odd sequence lengths must pass the Mosaic divisibility checks for
    fwd AND bwd (AOT cross-lowering runs them on CPU) — no reliance on the
    block==array escape hatch."""
    dt = jnp.dtype(dtype)
    spec = jax.ShapeDtypeStruct((1, s, 2, 64), dt)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v).astype(jnp.float32))

    fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    fn.trace(spec, spec, spec).lower(lowering_platforms=("tpu",))


def test_bf16_inputs(rng):
    q, k, v = qkv(rng, dtype=np.float32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(out.astype(np.float32), ref, atol=2e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_full_head_block_grid(rng, causal):
    """bn divisible by 8 -> _pick_hb selects 8 heads per grid cell; values
    AND gradients must match the oracle through the blocked indexing."""
    from jimm_tpu.ops.flash_attention import _pick_hb
    q, k, v = qkv(rng, b=4, s=128, n=4)
    assert _pick_hb(16, 128, 128, 64) == 8

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, is_causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, is_causal=causal) ** 2)

    np.testing.assert_allclose(flash_attention(q, k, v, is_causal=causal),
                               reference_attention(q, k, v, is_causal=causal),
                               atol=2e-5)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-4, err_msg=f"d{name}")


@pytest.mark.slow
def test_long_sequence_streams(rng):
    """seq 2048 with 512-blocks: 4x4 kv grid per cell — the K/V tiles
    stream block by block (the long-context configuration, scaled down to
    interpreter speed)."""
    q, k, v = qkv(rng, b=1, s=2048, n=1)
    out = flash_attention(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5)


# ---------------------------------------------------------------------------
# The short-sequence regime: one resident tile per head, one fused backward
# ---------------------------------------------------------------------------

def _calls(fn, *args, regimes=("single_tile", "tiled")):
    """(single-tile, tiled) pallas_calls that tracing ``fn`` builds, or the
    counts of whichever ``jimm_flash_<regime>_total`` are asked for."""
    reg = get_registry("jimm_flash")
    counters = [reg.counter(f"{r}_total") for r in regimes]
    before = [c.value for c in counters]
    jax.make_jaxpr(fn)(*args)
    return tuple(int(c.value - b) for c, b in zip(counters, before))


def _grids(fn, *args):
    """The grid of every pallas_call in ``fn``'s jaxpr, custom_vjp rules
    and all (take them from a gradient's jaxpr)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _grad_err(flash_loss, ref_loss, args):
    gf = jax.grad(flash_loss, argnums=tuple(range(len(args))))(*args)
    gr = jax.grad(ref_loss, argnums=tuple(range(len(args))))(*args)
    return max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(gf, gr))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("d", [64, 72, 80, 128])
@pytest.mark.parametrize("s", [257, 577, 729])
def test_single_tile_matches_reference(rng, s, d, dtype):
    """Forward and gradients of the single-tile kernels at the image
    presets' lengths and head widths (72 and 80 lane-pad to 128), read from
    and written to the ``(B, S, N * D)`` layout: the interpreter fills the
    edge block's rows past S with NaN, as the chip leaves them unspecified."""
    q32 = qkv(rng, b=1, s=s, n=2, d=d)
    q, k, v = args = tuple(x.astype(dtype) for x in q32)
    fwd_tol, grad_tol = (2e-5, 5e-4) if dtype == "float32" else (2e-2, 6e-2)
    assert _calls(lambda *a: jax.grad(
        lambda *b: jnp.sum(flash_attention(*b).astype(jnp.float32)),
        argnums=(0, 1, 2))(*a), *args) == (2, 0)
    out = flash_attention(q, k, v)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(out.astype(np.float32),
                               reference_attention(*q32), atol=fwd_tol)
    # the reference differentiates in fp32 from the same (rounded) inputs
    err = _grad_err(
        lambda *a: jnp.sum(flash_attention(*a).astype(jnp.float32) ** 2),
        lambda *a: jnp.sum(reference_attention(
            *(x.astype(jnp.float32) for x in a)) ** 2), args)
    assert err <= grad_tol


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("s,causal", [(256, False), (197, False),
                                      (196, False), (128, False),
                                      (64, False), (77, True)])
def test_short_lengths_match_reference_in_the_models_layout(rng, s, causal,
                                                            dtype):
    """Forward and gradients of the single-tile pair at the lengths under
    512 (SigLIP-B/16-256's vision tower at its 12 heads of 64, the 224 px
    towers, one whole lane tile, and the text towers' 64 and 77 causal, which
    `auto` leaves on XLA but ``impl="flash"`` still takes), called as
    `Attention` calls it: ``(B, S, N * D)`` in and out."""
    n, d = 12, 64
    x32 = tuple(jnp.asarray(rng.randn(2, s, n * d).astype(np.float32) * 0.5)
                for _ in range(3))
    args = tuple(x.astype(dtype) for x in x32)
    fwd_tol, grad_tol = (2e-5, 5e-4) if dtype == "float32" else (2e-2, 6e-2)

    def attend(fn, cast=lambda x: x):
        def run(q, k, v):
            q, k, v = (cast(x).reshape(2, s, n, d) for x in (q, k, v))
            return fn(q, k, v, is_causal=causal).reshape(2, s, n * d)
        return run

    grads = jax.grad(lambda *a: jnp.sum(attend(flash_attention)(*a).astype(
        jnp.float32)), argnums=(0, 1, 2))
    assert _calls(grads, *args) == (2, 0)
    out = attend(flash_attention)(*args)
    assert out.dtype == args[0].dtype
    np.testing.assert_allclose(out.astype(np.float32),
                               attend(reference_attention)(*x32),
                               atol=fwd_tol)
    to32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    assert _grad_err(
        lambda *a: jnp.sum(to32(attend(flash_attention)(*a)) ** 2),
        lambda *a: jnp.sum(attend(reference_attention, to32)(*a) ** 2),
        args) <= grad_tol


def _masked(rng, b, s):
    m = rng.rand(b, s) > 0.3
    m[:, 0] = True
    return jnp.asarray(m)


SINGLE_VARIANTS = {
    "causal": (lambda q, k, v, m: flash_attention(q, k, v, is_causal=True),
               lambda q, k, v, m: reference_attention(q, k, v,
                                                      is_causal=True)),
    "masked": (lambda q, k, v, m: flash_attention_masked(q, k, v, m),
               lambda q, k, v, m: reference_attention(
                   q, k, v, mask=m[:, None, None, :])),
    "masked_causal": (
        lambda q, k, v, m: flash_attention_masked(q, k, v, m,
                                                  is_causal=True),
        lambda q, k, v, m: reference_attention(
            q, k, v, mask=m[:, None, None, :], is_causal=True)),
    "sigmoid": (lambda q, k, v, m: sigmoid_attention(q, k, v),
                lambda q, k, v, m: reference_sigmoid_attention(q, k, v)),
    "sigmoid_masked": (
        lambda q, k, v, m: sigmoid_attention(q, k, v, mask=m),
        lambda q, k, v, m: reference_sigmoid_attention(q, k, v, mask=m)),
}


@pytest.mark.parametrize("s,d", [(577, 64), (257, 72), (729, 128)])
@pytest.mark.parametrize("variant", sorted(SINGLE_VARIANTS))
def test_single_tile_variants_match_reference(rng, variant, s, d):
    """Mask, causal and sigmoid go through the same `_scores` / `_ds_tile`
    as the tiled kernels and take the single-tile regime with the softmax."""
    flash, ref = SINGLE_VARIANTS[variant]
    q, k, v = args = qkv(rng, b=2, s=s, n=1, d=d)
    m = _masked(rng, 2, s)
    assert _calls(lambda *a: jax.grad(
        lambda *b: jnp.sum(flash(*b, m)), argnums=(0, 1, 2))(*a),
        *args) == (2, 0)
    np.testing.assert_allclose(flash(q, k, v, m), ref(q, k, v, m), atol=3e-5)
    err = _grad_err(lambda *a: jnp.sum(flash(*a, m) ** 2),
                    lambda *a: jnp.sum(ref(*a, m) ** 2), args)
    assert err <= 5e-4


def _ref_lse(q, k, v):
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(q.shape[-1])
    return jax.nn.logsumexp(logits, axis=-1)


@pytest.mark.parametrize("s,d", [(577, 64), (257, 80), (729, 128)])
def test_single_tile_lse_cotangent(rng, s, d):
    """`flash_attention_lse` with a NON-ZERO lse cotangent: the fused
    backward folds it into its in-kernel delta (the ring's merge
    differentiates through lse)."""
    q, k, v = args = qkv(rng, b=1, s=s, n=2, d=d)
    w = jnp.asarray(rng.randn(1, 2, s).astype(np.float32))

    def flash_loss(q, k, v):
        o, lse = flash_attention_lse(q, k, v)
        return jnp.sum(o ** 2) + jnp.sum(lse * w)

    def ref_loss(q, k, v):
        return (jnp.sum(reference_attention(q, k, v) ** 2)
                + jnp.sum(_ref_lse(q, k, v) * w))

    assert _calls(jax.grad(flash_loss, argnums=(0, 1, 2)), *args) == (2, 0)
    np.testing.assert_allclose(flash_attention_lse(q, k, v)[1],
                               _ref_lse(q, k, v), atol=2e-5)
    assert _grad_err(flash_loss, ref_loss, args) <= 5e-4


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_single_tile_matches_tiled(rng, dtype):
    """Same inputs through both regimes: the tiled path is forced by asking
    for small blocks, as the tuner does, not by a switch."""
    q, k, v = args = tuple(x.astype(dtype)
                           for x in qkv(rng, b=1, s=577, n=2, d=64))

    def loss(blocks):
        return lambda *a: jnp.sum(
            flash_attention(*a, **blocks).astype(jnp.float32) ** 2)

    tiled = {"block_q": 128, "block_k": 128}
    grad = lambda f: jax.grad(f, argnums=(0, 1, 2))  # noqa: E731
    assert _calls(grad(loss(tiled)), *args) == (0, 2)
    assert _calls(grad(loss({})), *args) == (2, 0)
    tol = 2e-5 if dtype == "float32" else 6e-2
    np.testing.assert_allclose(
        flash_attention(q, k, v).astype(np.float32),
        flash_attention(q, k, v, **tiled).astype(np.float32), atol=tol)
    for a, b in zip(grad(loss({}))(*args), grad(loss(tiled))(*args)):
        np.testing.assert_allclose(a.astype(np.float32),
                                   b.astype(np.float32), atol=tol * 25)


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
    backward (dq + dk/dv before PR 37)."""
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

    assert [len(g) for g in _grids(grads, spec, spec, spec)] == [3, 3]
    under = jax.ShapeDtypeStruct((1, bound - 128, 2, 64), jnp.bfloat16)
    assert [len(g) for g in _grids(grads, under, under, under)] == [2, 2]


def test_bias_variant_stays_tiled(rng):
    q, k, v = qkv(rng, b=1, s=64, n=2)
    bias = jnp.asarray(rng.randn(2, 64, 64).astype(np.float32))
    assert _calls(jax.grad(lambda *a: jnp.sum(flash_attention_bias(*a)),
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


# ---------------------------------------------------------------------------
# The single-tile regime reads and writes its caller's layout
# ---------------------------------------------------------------------------

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
    assert _calls(jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, **kw).astype(jnp.float32)), argnums=(0, 1, 2)),
        spec, spec, spec,
        regimes=("direct", "single_tile", "tiled")) == want


@pytest.mark.parametrize("n,d,dtype,hb", [
    (16, 64, 2, 8), (12, 64, 2, 4), (6, 64, 2, 2), (3, 64, 2, 3),
    (1, 64, 2, 1), (16, 128, 2, 8), (5, 128, 4, 1), (7, 64, 4, 7),
])
def test_head_group_is_whole_lane_tiles_or_the_row(n, d, dtype, hb):
    """A cell's slab of ``hb * d`` lanes is whole 128-lane tiles or the
    array's whole last dimension: D = 64 takes an even hb or every head."""
    got = fa._single_tile_hb(n, 640, 640, d, dtype, fa._SOFTMAX)
    assert got == hb and (got * d % 128 == 0 or got == n)


@pytest.mark.parametrize("n,d,groups", [(8, 64, 2), (16, 64, 2), (4, 128, 2),
                                        (4, 64, 1), (3, 64, 1)])
def test_head_loop_over_lane_groups(rng, n, d, groups):
    """A cell's slab is walked 256 lanes at a time by a rolled loop (4 heads
    of 64 or 2 of 128 an iteration); a narrower slab is one group. Values,
    lse and gradients of every head against the reference."""
    hb = fa._single_tile_hb(n, 256, 256, d, 4, fa._SOFTMAX)
    assert hb * d // fa._group_lanes(hb * d, d) == groups
    q, k, v = args = qkv(rng, b=1, s=150, n=n, d=d)
    w = jnp.asarray(rng.randn(1, n, 150).astype(np.float32))

    def flash_loss(q, k, v):
        o, lse = flash_attention_lse(q, k, v)
        return jnp.sum(o ** 2) + jnp.sum(lse * w)

    def ref_loss(q, k, v):
        return (jnp.sum(reference_attention(q, k, v) ** 2)
                + jnp.sum(_ref_lse(q, k, v) * w))

    o, lse = flash_attention_lse(q, k, v)
    np.testing.assert_allclose(o, reference_attention(q, k, v), atol=2e-5)
    np.testing.assert_allclose(lse, _ref_lse(q, k, v), atol=2e-5)
    assert _grad_err(flash_loss, ref_loss, args) <= 5e-4


def _tpu_text(fn, *specs):
    """StableHLO of ``fn`` lowered for the TPU with the kernels as Mosaic
    calls (their serialized bodies cut out: they carry source lines)."""
    import re
    text = jax.jit(fn).trace(*specs).lower(
        lowering_platforms=("tpu",)).as_text()
    return re.sub(r'backend_config = "[^"]*"', "backend_config = <kernel>",
                  text)


def _grads_of_model_layout(n, **kw):
    """Gradients of flash attention as `Attention` calls it: q, k, v are
    ``(B, S, N * D)`` matmul outputs, reshaped (free) to 4-D and back."""
    def loss(q, k, v):
        b, s, w = q.shape
        heads = lambda x: x.reshape(b, x.shape[1], n, w // n)  # noqa: E731
        o = flash_attention(heads(q), heads(k), heads(v), **kw)
        return jnp.sum(o.reshape(b, s, w).astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("s,n,d", [(577, 16, 64), (257, 4, 128),
                                   (197, 3, 64)])
def test_single_tile_text_moves_no_qkv_sized_array(monkeypatch, s, n, d,
                                                   dtype):
    """Forward + backward of a single-tile shape: two Mosaic calls and no
    transpose, pad or slice at all around them (the parent ran 9 pads, 4
    slices and 8 transposes a layer there)."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    spec = jax.ShapeDtypeStruct((2, s, n * d), jnp.dtype(dtype))
    text = _tpu_text(_grads_of_model_layout(n), spec, spec, spec)
    assert text.count("@tpu_custom_call") == 2
    for op in ("stablehlo.transpose", "stablehlo.pad", "stablehlo.slice",
               "stablehlo.dynamic_slice", "stablehlo.concatenate"):
        assert op not in text, op


def test_off_tile_head_width_is_padded_once_and_never_transposed(monkeypatch):
    """D = 72 (So400m): one pad of q, k, v (and, under AD, of ``do``) on
    the last axis of the 4-D view, one slice of o (and of dq, dk, dv); the
    sequence is not padded and nothing is transposed."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    spec = jax.ShapeDtypeStruct((2, 729, 16 * 72), jnp.bfloat16)
    text = _tpu_text(_grads_of_model_layout(16), spec, spec, spec)
    assert text.count("@tpu_custom_call") == 2
    assert "stablehlo.transpose" not in text
    # q, k, v and, where the cotangent is not a constant, `do`; the slices
    # of o, dq, dk, dv lower to calls of one shared function
    assert text.count("stablehlo.pad") in (3, 4)
    assert text.count("stablehlo.slice") in (1, 4)
    # the lane-padded S_p is the lse rows' extent and no q-sized array's
    assert "x768xf32" in text and "x768x" not in text.replace(
        "x768xf32", "")


#: sha256 of the TPU lowering (kernel bodies cut out) of the gradients at
#: shapes over the rule. The non-causal case is the text of the commit before
#: the single-tile regime moved to the model's layout (PR 27's tree): the
#: tiled regime's wrappers (flatten, pad to the blocks, slice back) have not
#: moved since, and PR 33 changed no operand of its three calls. The causal
#: case was re-pinned by PR 33, by design: its calls take the two int32
#: tables of live block pairs as their first operands (36 pairs at 4096
#: tokens in blocks of 512), and nothing else in the text changed (the
#: wrappers around them are the parent's; it read 193b84c1... before).
#: Both were re-pinned by PR 37, by design: the dq call went, the dk/dv call
#: has dq as a third result (same operands, and in the causal text the
#: row-major pair of tables went with the dq call), and the three reshapes
#: and transposes back to ``(B, S, N, D)`` read that call's results; a diff
#: of the two texts shows nothing else (they read 799339d3... and e26f2d15...
#: with three calls).
TILED_TEXT_SHA = {
    ((1, 1153, 2, 64), False): "7af58bb443ccc48e67b08ae9f4b9adb7abe90e35115244d9a53b78fb455ba713",
    ((1, 4096, 4, 128), True): "2c23840c8052e4459901f2dffa9bc47da71c704e10300ed00f27b3debad2feb4",
}


@pytest.mark.parametrize("shape,causal", sorted(TILED_TEXT_SHA))
def test_tiled_regime_text_is_the_parents(monkeypatch, shape, causal):
    import hashlib
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    spec = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    text = _tpu_text(jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, is_causal=causal).astype(jnp.float32)), argnums=(0, 1, 2)),
        spec, spec, spec)
    assert text.count("@tpu_custom_call") == 2
    assert (hashlib.sha256(text.encode()).hexdigest()
            == TILED_TEXT_SHA[shape, causal])


@pytest.mark.parametrize("poison", ["inf", "-inf", "3e38", "0"])
@pytest.mark.parametrize("variant", ["plain", "causal", "masked", "sigmoid",
                                     "lse"])
def test_edge_rows_hold_anything(rng, monkeypatch, variant, poison):
    """The rows of the edge block past S are unspecified on the chip. The
    interpreter fills them with NaN (every other test here runs so); with
    +-inf, the largest floats or zeros in their place the results are the
    same to the bit, and a kernel whose row clean-up is taken out is
    caught."""
    from jax._src.pallas import primitives
    q, k, v = args = qkv(rng, b=1, s=200, n=2, d=64)
    m = _masked(rng, 1, 200)
    w = jnp.asarray(rng.randn(1, 2, 200).astype(np.float32))

    def lse_loss(q, k, v):
        o, lse = flash_attention_lse(q, k, v)
        return jnp.sum(o ** 2) + jnp.sum(lse * w)

    loss = {
        "plain": lambda *a: jnp.sum(flash_attention(*a) ** 2),
        "causal": lambda *a: jnp.sum(
            flash_attention(*a, is_causal=True) ** 2),
        "masked": lambda *a: jnp.sum(flash_attention_masked(*a, m) ** 2),
        "sigmoid": lambda *a: jnp.sum(sigmoid_attention(*a, mask=m) ** 2),
        "lse": lse_loss,
    }[variant]

    def run():
        jax.clear_caches()  # the fill value is baked in when a call lowers
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(*args)

    want = run()  # edge rows NaN
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(want))
    original = primitives.uninitialized_value

    def filled(shape, dtype):
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.full(shape, float(poison), dtype)
        return original(shape, dtype)

    monkeypatch.setattr(primitives, "uninitialized_value", filled)
    got = run()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
    if poison == "inf":
        # the control: without the row clean-up the poison reaches the sums
        monkeypatch.setattr(fa, "_real_rows", lambda *a: None)
        bad = run()
        assert not all(bool(jnp.all(jnp.isfinite(x)))
                       for x in jax.tree.leaves(bad))
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("kind,masked", [("softmax", False),
                                         ("softmax", True),
                                         ("sigmoid", False)])
def test_one_head_rows_entry_matches_model_layout(rng, kind, masked):
    """The ring's hops hand the kernels ``(B * N, S, D)`` rows: the same
    wrapper pair with one head in a row (``hb * D`` = the whole last
    dimension). Values, lse and all three gradients against the
    ``(B, S, N * D)`` entry."""
    b, s, n, d = 2, 200, 2, 64
    q, k, v = qkv(rng, b=b, s=s, n=n, d=d)
    do = jnp.asarray(rng.randn(b, s, n, d).astype(np.float32))
    mask = _masked(rng, b, s) if masked else None
    spec = fa.VariantSpec(kind=kind, has_mask=masked)
    sm_scale, bias = 1.0 / d ** 0.5, (-np.log(s) if kind == "sigmoid" else 0.0)
    if kind == "sigmoid":
        model = lambda *a: sigmoid_attention(*a, mask=mask)  # noqa: E731
    elif masked:
        model = lambda *a: flash_attention_masked(*a, mask)  # noqa: E731
    else:
        model = flash_attention
    want_o, vjp = jax.vjp(model, q, k, v)
    want_g = vjp(do)

    q3, k3, v3, do3 = map(fa._flatten_heads, (q, k, v, do))
    mask3 = fa._expand_mask(mask, n) if masked else None
    before = snapshot()
    o3, lse3 = fa.ring_hop_fwd(q3, k3, v3, mask3, spec, sm_scale, bias,
                               256, 256)
    got_g = fa.ring_hop_bwd(q3, k3, v3, mask3, o3, lse3, do3, spec, sm_scale,
                            bias, 256, 256)
    after = snapshot()
    assert after["jimm_flash_direct_total"] \
        - before.get("jimm_flash_direct_total", 0) == 2
    np.testing.assert_allclose(fa._unflatten_heads(o3, b, n), want_o,
                               atol=2e-6)
    if kind == "softmax" and not masked:
        assert lse3.shape == (b * n, s)
        np.testing.assert_allclose(lse3.reshape(b, n, s),
                                   flash_attention_lse(q, k, v)[1], atol=2e-6)
    for a, w_ in zip(got_g, want_g):
        np.testing.assert_allclose(fa._unflatten_heads(a, b, n), w_,
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# A value head width of its own (latent attention: q/k 192, v 128)
# ---------------------------------------------------------------------------

def _qkv_split(rng, b, s, n, d_qk, d_v, dtype=np.float32):
    q, k = (jnp.asarray(rng.randn(b, s, n, d_qk).astype(dtype) * 0.5)
            for _ in range(2))
    return q, k, jnp.asarray(rng.randn(b, s, n, d_v).astype(dtype) * 0.5)


@pytest.mark.parametrize("s,d_qk,d_v,blocks,regime", [
    (1280, 192, 128, None, "tiled"),   # over the single-tile rule, causal
    (384, 24, 16, 128, "tiled"),       # asked-for blocks, off-tile widths
    (256, 192, 128, None, "single"),   # under the rule: v padded to q's
    # the causal grid of live blocks (PR 33). The last block of each side
    # holds the diagonal AND the padded tail of S:
    (2000, 192, 128, None, "tiled"),
    (2000, 192, 128, (256, 256), "tiled"),
    # block_q over block_k and the reverse: a diagonal block is crossed by
    # several of the other side's
    (2048, 128, 128, (512, 256), "tiled"),
    (2048, 128, 128, (256, 512), "tiled"),
    (700, 64, 64, (256, 128), "tiled"),
    (700, 64, 64, (128, 256), "tiled"),
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_value_width_of_its_own_matches_reference(rng, s, d_qk, d_v, blocks,
                                                  regime, dtype):
    """Causal forward and all three gradients against the reference; the
    output and dv are ``d_v`` wide and the scale is 1/sqrt(d_qk). Four heads
    in two samples: the tiled cells hold ``hb`` > 1 of them."""
    if dtype == "bfloat16" and regime == "single":
        pytest.skip("the single-tile regime's bf16 cases are above")
    q32 = _qkv_split(rng, 2, s, 2, d_qk, d_v)
    q, k, v = (x.astype(dtype) for x in q32)
    probe = jnp.asarray(rng.randn(2, s, 2, d_v).astype(np.float32))
    counters = get_registry("jimm_flash")
    before = {r: counters.counter(f"{r}_total").value
              for r in ("tiled", "single_tile")}

    def f(attn, **kw):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, is_causal=True, **kw).astype(jnp.float32) * probe)

    kw = {}
    if blocks is not None:
        bq, bk = blocks if isinstance(blocks, tuple) else (blocks, blocks)
        kw = {"block_q": bq, "block_k": bk}
    else:
        assert fa._pick_hb(4, 512, 512, fa._head_pad_target(d_qk)) == 4
    out = flash_attention(q, k, v, is_causal=True, **kw)
    assert out.shape == (2, s, 2, d_v) and out.dtype == q.dtype
    # the reference differentiates in fp32 from the same (rounded) inputs
    in32 = tuple(x.astype(jnp.float32) for x in (q, k, v))
    rtol, atol, grtol, gatol = ((2e-4, 2e-5, 2e-3, 2e-4)
                                if dtype == "float32"
                                else (2e-2, 2e-2, 5e-2, 2e-1))
    np.testing.assert_allclose(out.astype(np.float32),
                               reference_attention(*in32, is_causal=True),
                               rtol=rtol, atol=atol)
    got = jax.grad(f(flash_attention, **kw), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(f(reference_attention), argnums=(0, 1, 2))(*in32)
    for name, a, b in zip("qkv", got, want, strict=True):
        assert a.shape == b.shape
        np.testing.assert_allclose(a.astype(np.float32), b, rtol=grtol,
                                   atol=gatol, err_msg=f"d{name}")
    grew = {r: counters.counter(f"{r}_total").value - before[r]
            for r in before}
    assert (grew["tiled"] > 0) == (regime == "tiled")
    assert (grew["single_tile"] > 0) == (regime == "single")


# ---------------------------------------------------------------------------
# The tiled causal schedule: blocks from the shapes, a grid of live blocks
# ---------------------------------------------------------------------------

def _rectangle_admits(i, j, block_q, block_k):
    """The parent's ``pl.when``: a kv block is needed iff its first key is
    at or left of the q block's last query."""
    return j * block_k <= (i + 1) * block_q - 1


@pytest.mark.parametrize("block_q", [128, 256, 512, 1024, 2048])
@pytest.mark.parametrize("block_k", [128, 256, 512, 1024, 2048])
def test_live_pairs_are_the_rectangles_live_blocks(block_q, block_k):
    """Both tables enumerate exactly the blocks the rectangle computed, for
    every padded length: row-major with a row's pairs together, kv block 0
    first and `_last_kv` last (where the forward and dq init and finalize),
    column-major with a column's first pair where dk/dv init and the last q
    block last."""
    for s in (128, 640, 1024, 2048, 4096, 8192):
        n_q = fa._ceil_to(s, block_q) // block_q
        n_k = fa._ceil_to(s, block_k) // block_k
        want = {(i, j) for i in range(n_q) for j in range(n_k)
                if _rectangle_admits(i, j, block_q, block_k)}
        qi, kj, scored = fa._live_pairs(n_q, n_k, block_q, block_k)
        rows = list(zip(qi.tolist(), kj.tolist()))
        assert qi.dtype == kj.dtype == np.int32
        assert set(rows) == want and len(rows) == len(want) == scored
        assert rows == sorted(rows)
        for i in range(n_q):
            row = [j for a, j in rows if a == i]
            assert row[0] == 0 and row == list(range(len(row)))
            assert row[-1] == int(fa._last_kv(i, block_q, block_k, n_k, True))
        qi, kj, scored = fa._live_pairs(n_q, n_k, block_q, block_k,
                                        kv_major=True)
        cols = list(zip(kj.tolist(), qi.tolist()))
        assert {(i, j) for j, i in cols} == want
        assert len(cols) == len(want) == scored
        assert cols == sorted(cols)
        for j in range(n_k):
            col = [i for b, i in cols if b == j]
            assert col[0] == min(j * block_k // block_q, n_q - 1)
            assert col[-1] == n_q - 1


def test_live_pairs_keep_a_pair_for_keys_right_of_every_query():
    """S_k over S_q: the forward never visits the kv blocks right of the
    last query; dk/dv still write those blocks (zeros), from one pair the
    mask empties."""
    qi, kj, scored = fa._live_pairs(2, 5, 128, 128)
    assert set(kj.tolist()) == {0, 1} and scored == len(qi) == 3
    qi, kj, scored = fa._live_pairs(2, 5, 128, 128, kv_major=True)
    assert list(zip(kj.tolist(), qi.tolist())) == [
        (0, 0), (0, 1), (1, 1), (2, 1), (3, 1), (4, 1)]
    assert scored == 3


@pytest.mark.parametrize("sq,sk", [(300, 700), (700, 300)])
def test_causal_with_unequal_lengths_matches_reference(rng, sq, sk):
    q = jnp.asarray(rng.randn(1, sq, 2, 64).astype(np.float32) * 0.5)
    k, v = (jnp.asarray(rng.randn(1, sk, 2, 64).astype(np.float32) * 0.5)
            for _ in range(2))
    kw = {"block_q": 128, "block_k": 128}
    np.testing.assert_allclose(
        flash_attention(q, k, v, is_causal=True, **kw),
        reference_attention(q, k, v, is_causal=True), atol=2e-5)
    err = _grad_err(
        lambda *a: jnp.sum(flash_attention(*a, is_causal=True, **kw) ** 2),
        lambda *a: jnp.sum(reference_attention(*a, is_causal=True) ** 2),
        (q, k, v))
    assert err <= 5e-4


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


@pytest.mark.parametrize("shape,causal,steps", [
    # kanana's attention call: 16 cells of 4 heads x 136 live blocks of 512
    # x 2 kernels, the forward and the one backward (PR 33's dq + dk/dv: x 3;
    # its parent: 64 x 16 x 16 x 3 = 49,152 steps, 26,112 live)
    ((2, 8192, 32, 192), True, 16 * 136 * 2),
    ((1, 4096, 16, 128), True, 4 * 36 * 2),     # PR 33's parent: 8 x 8 x 8 x 3
    ((2, 2048, 16, 64), False, 8 * 4 * 4 * 2),  # the rectangle, all live
])
def test_tiled_step_counters(shape, causal, steps):
    """`jimm_flash_tiled_grid_steps_total` / `_live_steps_total`: the grid
    steps one execution of each built call takes, and those that compute.
    A causal grid holds no other. `jimm_flash_fused_bwd_total`: the
    backward is one of the two calls."""
    spec = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    got = _calls(jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, is_causal=causal).astype(jnp.float32)), argnums=(0, 1, 2)),
        spec, spec, spec,
        regimes=("tiled", "tiled_grid_steps", "tiled_live_steps",
                 "fused_bwd"))
    assert got == (2, steps, steps, 1)
    after = snapshot()
    assert "jimm_flash_fused_bwd_total" in after
    assert "jimm_flash_tiled_grid_steps_total" in after
    assert "jimm_flash_tiled_live_steps_total" in after


def test_dead_pair_counts_as_a_step_that_does_not_compute():
    """S_k over S_q under causal: dk/dv keep one pair a kv column that no
    query reaches (so its blocks are written); it is a grid step and not a
    live one. The forward's three pairs, then the backward's six."""
    q = jax.ShapeDtypeStruct((1, 256, 1, 64), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 640, 1, 64), jnp.float32)
    got = _calls(jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, is_causal=True, block_q=128, block_k=128)), argnums=(0, 1, 2)),
        q, k, k, regimes=("tiled_grid_steps", "tiled_live_steps"))
    assert got == (3 + 6, 3 + 3)


# ---------------------------------------------------------------------------
# The tiled backward is ONE kernel while the heads' fp32 dq fits in VMEM
# ---------------------------------------------------------------------------

def _fused_case(rng, *, sq=384, sk=None, b=1, n=2, n_kv=None, d=64, d_v=None,
                member="plain", blocks=(128, 128), dtype=np.float32, **kw):
    """``(grads(), pallas_calls of a backward)`` of one family member at
    blocks that force the tiled regime."""
    sk, n_kv, d_v = sk or sq, n_kv or n, d_v or d
    q = jnp.asarray(rng.randn(b, sq, n, d).astype(np.float32) * 0.5, dtype)
    k = jnp.asarray(rng.randn(b, sk, n_kv, d).astype(np.float32) * 0.5, dtype)
    v = jnp.asarray(rng.randn(b, sk, n_kv, d_v).astype(np.float32) * 0.5,
                    dtype)
    probe = jnp.asarray(rng.randn(b, sq, n, d_v).astype(np.float32))
    kw = dict(kw, block_q=blocks[0], block_k=blocks[1])
    extra, argnums, calls = (), (0, 1, 2), 1
    if member == "masked":
        mask = _masked(rng, b, sk)
        attn = lambda q, k, v: flash_attention_masked(q, k, v, mask, **kw)  # noqa: E731
    elif member == "bias":
        extra, argnums, calls = (jnp.asarray(rng.randn(n, sq, sk).astype(
            np.float32)),), (0, 1, 2, 3), 2
        attn = lambda q, k, v, bias: flash_attention_bias(q, k, v, bias, **kw)  # noqa: E731
    elif member == "sigmoid":
        mask = _masked(rng, b, sk)
        attn = lambda q, k, v: sigmoid_attention(q, k, v, mask=mask, **kw)  # noqa: E731
    elif member == "lse":
        lse_probe = jnp.asarray(rng.randn(b, n, sq).astype(np.float32))

        def attn(q, k, v):
            o, lse = flash_attention_lse(q, k, v, **kw)
            return o + 0 * jnp.sum(lse), lse * lse_probe
    else:
        attn = lambda q, k, v: flash_attention(q, k, v, **kw)  # noqa: E731

    def loss(*args):
        out = attn(*args)
        o, rest = (out[0], jnp.sum(out[1])) if member == "lse" else (out, 0.0)
        return jnp.sum(o.astype(jnp.float32) * probe) + rest
    return (lambda: jax.grad(loss, argnums=argnums)(q, k, v, *extra)), calls


#: the family through `_flash_bwd`'s tiled branch: name -> `_fused_case`'s
#: arguments, and the `_VMEM_BUDGET` to run under (1: one head a cell)
FUSED_CASES = {
    "full": (dict(), None),
    "causal": (dict(is_causal=True), None),
    "causal_bf16_four_heads": (dict(is_causal=True, b=2, n=4,
                                    dtype=jnp.bfloat16), None),
    # keys right of every query: the pair that only writes dk/dv's blocks
    "causal_sk_over_sq": (dict(sq=256, sk=640, is_causal=True), None),
    "causal_sq_over_sk": (dict(sq=640, sk=256, is_causal=True), None),
    "full_sk_over_sq": (dict(sq=256, sk=512), None),
    # latent attention's widths scaled down: q, k at 128 lanes, v at 64
    "value_width_of_its_own": (dict(d=96, d_v=64, is_causal=True), None),
    "window": (dict(is_causal=True, window=100), None),
    "window_of_one_block": (dict(is_causal=True, window=128,
                                 blocks=(128, 256), sq=512), None),
    # grouped heads: the group in one cell, and over two cells whose dq both
    # stay resident (one head a cell under a budget of 1)
    "grouped": (dict(n=4, n_kv=2, is_causal=True), None),
    "grouped_two_cells": (dict(n=4, n_kv=2, is_causal=True), 1),
    "grouped_three_cells_window": (dict(n=6, n_kv=2, is_causal=True,
                                        window=130, sq=300), 1),
    "grouped_full_rectangle": (dict(n=4, n_kv=1), 1),
    "masked": (dict(member="masked"), None),
    "masked_causal": (dict(member="masked", is_causal=True), None),
    "bias": (dict(member="bias"), None),
    "bias_causal": (dict(member="bias", is_causal=True), None),
    "sigmoid_masked": (dict(member="sigmoid"), None),
    "sigmoid_causal": (dict(member="sigmoid", is_causal=True), None),
    "lse_cotangent": (dict(member="lse"), None),
    "lse_cotangent_causal": (dict(member="lse", is_causal=True), None),
    # sequences that need padding, each way
    "padded": (dict(sq=300, sk=421), None),
    "padded_causal": (dict(sq=333, is_causal=True), None),
    "blocks_q_over_k": (dict(sq=512, is_causal=True, blocks=(256, 128)),
                        None),
    "blocks_k_over_q": (dict(sq=512, is_causal=True, blocks=(128, 256)),
                        None),
}


@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_fused_backward_equals_the_pair(monkeypatch, case):
    """dq, dk, dv (and dbias) of the fused tiled backward against the dq +
    dk/dv pair, which a resident budget of 0 brings back: EQUAL, bit for bit.
    Both add a q block's ``ds k`` at ascending kv block and a kv block's
    ``ds^T q`` / ``p^T do`` at ascending q block into fp32 accumulators and
    scale and round once, so there is nothing to differ by (interpret mode;
    the chip's reading is in PERF.md, PR 37)."""
    kw, budget = FUSED_CASES[case]
    if budget is not None:
        monkeypatch.setattr(fa, "_VMEM_BUDGET", budget)
    counters = get_registry("jimm_flash")
    names = ("tiled", "fused_bwd")

    def run():
        grads, calls = _fused_case(np.random.RandomState(37), **kw)
        before = [counters.counter(f"{r}_total").value for r in names]
        got = grads()
        grew = [counters.counter(f"{r}_total").value - b
                for r, b in zip(names, before)]
        return got, grew, calls

    fused, grew, calls = run()
    assert grew == [1 + calls, 1]       # the forward, the backward (, dbias)
    monkeypatch.setattr(fa, "_DQ_RESIDENT_BUDGET", 0)
    pair, grew, calls = run()
    assert grew == [2 + calls, 0]       # the forward, dq, dk/dv (, dbias)
    for name, a, b in zip(("dq", "dk", "dv", "dbias"), fused, pair):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=f"{case}: {name}")


@pytest.mark.parametrize("budget,fused_calls,hb", [
    # one head's 384 padded rows x 64 lanes x 4 bytes: AT the bound one head
    # a cell is fused, two heads' worth takes two a cell, a byte under it no
    # head count fits and the pair runs at the forward's heads
    (384 * 64 * 4, 1, 1), (2 * 384 * 64 * 4, 1, 2), (384 * 64 * 4 - 1, 0, 2)])
def test_residency_bound_picks_the_arrangement(monkeypatch, budget,
                                               fused_calls, hb):
    """The rule is the call's shapes against `_DQ_RESIDENT_BUDGET` and
    nothing else; either side of the bound the gradients are the
    reference's."""
    monkeypatch.setattr(fa, "_DQ_RESIDENT_BUDGET", budget)
    assert fa._pick_hb(2, 128, 128, 64, dq_seq=384) == (hb if fused_calls
                                                        else 0)
    assert fa._pick_hb(2, 128, 128, 64) == 2
    grads, _ = _fused_case(np.random.RandomState(3), is_causal=True)
    got = _calls(grads, regimes=("tiled", "fused_bwd"))
    assert got == (3 - fused_calls, fused_calls)
    grids = _grids(grads)
    assert [g[0] for g in grids] == [1] + [2 // hb] * (2 - fused_calls)
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(1, 384, 2, 64).astype(np.float32) * 0.5)
               for _ in range(3))
    probe = jnp.asarray(rng.randn(1, 384, 2, 64).astype(np.float32))
    want = jax.grad(lambda *a: jnp.sum(reference_attention(
        *a, is_causal=True) * probe), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads(), want):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


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


def test_tiled_kernels_keep_v_at_its_own_tile(monkeypatch):
    """q and k blocks 256 lanes (192 padded), v, o, do and dv blocks 128: the
    TPU lowering holds no 256-wide copy of v."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, 2048, 2, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)
    text = _tpu_text(jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, is_causal=True).astype(jnp.float32)), argnums=(0, 1, 2)), q, q, v)
    assert text.count("@tpu_custom_call") == 2
    assert "tensor<2x2048x256xbf16>" in text      # q, k, dq, dk
    assert "tensor<2x2048x128xbf16>" in text      # v, o, do, dv
    calls = [ln for ln in text.splitlines() if "@tpu_custom_call" in ln]
    fwd = calls[0]
    assert fwd.count("tensor<2x2048x256xbf16>") == 2  # q and k in
    assert fwd.count("tensor<2x2048x128xbf16>") == 2  # v in, o out


def test_auto_reaches_flash_with_unequal_widths(rng, monkeypatch):
    """``impl="auto"`` on a TPU sends a call `_flash_eligible` admits (here
    512 tokens: every call from there up) to the flash kernels whatever the
    value width; off the TPU XLA's op gets v padded and cut back."""
    from jimm_tpu.ops import attention
    q, k, v = _qkv_split(rng, 1, 512, 2, 24, 16)
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
#: PR 35 read on the chip (`_flash_eligible` has the readings) on the side
#: its reading chose, the lengths from 512 up where they were, and the calls
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


# ---------------------------------------------------------------------------
# Grouped key/value heads and a causal window
# ---------------------------------------------------------------------------

def _qkv_grouped(rng, b, s, n, n_kv, d, dtype=np.float32):
    q = jnp.asarray(rng.randn(b, s, n, d).astype(dtype) * 0.5)
    k, v = (jnp.asarray(rng.randn(b, s, n_kv, d).astype(dtype) * 0.5)
            for _ in range(2))
    return q, k, v


#: (S, query heads, key/value heads, window, blocks, VMEM budget or None)
GROUPED_WINDOW = [
    # tiled, forced small blocks: the window no multiple of the block
    (384, 4, 2, 100, (128, 128), None),
    # S no multiple of the block, a group of three in one cell
    (300, 6, 2, 130, (128, 128), None),
    # a cell that does NOT span the group: one head a cell, two cells a group,
    # dk and dv summed over the grid's last axis
    (384, 4, 2, 100, (128, 128), 1),
    (384, 6, 2, None, (128, 128), 1),
    # a window that reaches over every key IS the plain causal call
    (384, 4, 2, 1000, (128, 128), None),
    # one key/value head for all; block_q over block_k and the reverse
    (512, 4, 1, 77, (256, 128), None),
    (512, 4, 1, 200, (128, 256), None),
    # a window of one block exactly, and of one position
    (384, 2, 1, 128, (128, 128), None),
    (256, 2, 2, 1, (128, 128), None),
    # the window alone, heads ungrouped: tiled, and the single-tile kernels
    (384, 4, 4, 100, (128, 128), None),
    (384, 4, 4, 100, None, None),
    (577, 2, 2, 64, None, None),
    # grouped heads at a length the single-tile rule admits: the tiled
    # kernels all the same (a single-tile cell slices one head count's lanes)
    (384, 4, 2, 100, None, None),
    (256, 4, 2, None, None, None),
]


@pytest.mark.parametrize("s,n,n_kv,window,blocks,budget", GROUPED_WINDOW)
def test_grouped_heads_and_window_match_reference(rng, monkeypatch, s, n,
                                                  n_kv, window, blocks,
                                                  budget):
    """Forward and all three gradients of the flash kernels (interpret mode)
    and of the XLA path against `reference_attention`, which repeats k and v
    and masks by the two inequalities; dk and dv come back at the key/value
    heads' own count."""
    if budget is not None:
        monkeypatch.setattr(fa, "_VMEM_BUDGET", budget)
    q, k, v = _qkv_grouped(rng, 2, s, n, n_kv, 64)
    probe = jnp.asarray(rng.randn(2, s, n, 64).astype(np.float32))
    kw = ({} if blocks is None
          else {"block_q": blocks[0], "block_k": blocks[1]})
    counters = get_registry("jimm_flash")
    names = ("tiled", "single_tile", "window", "grouped_kv")
    before = {r: counters.counter(f"{r}_total").value for r in names}

    def loss(attn, **kw):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, is_causal=True, window=window, **kw) * probe)

    want_o = reference_attention(q, k, v, is_causal=True, window=window)
    want = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(
        flash_attention(q, k, v, is_causal=True, window=window, **kw),
        want_o, rtol=2e-4, atol=2e-5)
    got = jax.grad(loss(flash_attention, **kw), argnums=(0, 1, 2))(q, k, v)
    grew = {r: counters.counter(f"{r}_total").value - before[r]
            for r in names}
    xla = jax.grad(loss(dot_product_attention, impl="xla"),
                   argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(
        dot_product_attention(q, k, v, is_causal=True, window=window,
                              impl="xla"), want_o, rtol=2e-4, atol=2e-5)
    for name, a, x, b in zip("qkv", got, xla, want, strict=True):
        assert a.shape == b.shape == x.shape
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4,
                                   err_msg=f"flash d{name}")
        np.testing.assert_allclose(x, b, rtol=2e-3, atol=2e-4,
                                   err_msg=f"xla d{name}")
    assert got[1].shape[2] == got[2].shape[2] == n_kv
    # which kernels ran, and what the registry says of them
    single = blocks is None and n == n_kv
    assert (grew["single_tile"] > 0) == single
    assert (grew["tiled"] > 0) == (not single)
    live = window is not None and window < s
    assert (grew["window"] > 0) == live
    assert (grew["grouped_kv"] > 0) == (n != n_kv)
    if not single:  # the forward alone, then forward and the one backward
        assert grew["window"] == (3 if live else 0)
        assert grew["grouped_kv"] == (3 if n != n_kv else 0)


def test_a_window_over_every_key_is_the_plain_causal_call():
    """Dropped before dispatch: the same jaxpr, so the same kernels and the
    same compile-cache key."""
    spec = jax.ShapeDtypeStruct((1, 1280, 4, 64), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 1280, 2, 64), jnp.float32)

    def text(**kw):
        return str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            dot_product_attention(q, k, v, is_causal=True, impl="flash",
                                  **kw)), argnums=(0, 1, 2)))(spec, kv, kv))

    plain = text()
    assert text(window=1280) == plain == text(window=5000)
    assert text(window=1279) != plain
    assert fa.live_window(4096, True, 4096) is None
    assert fa.live_window(4096, True, 8192) == 4096
    for window, causal in ((0, True), (16, False)):
        with pytest.raises(ValueError, match="window"):
            fa.live_window(window, causal, 64)
    q = jnp.zeros((1, 64, 4, 16))
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(q, q[:, :, :3], q[:, :, :3])
    with pytest.raises(ValueError, match="neither grouped"):
        dot_product_attention(q, q[:, :, :2], q[:, :, :2], impl="saveable")
    with pytest.raises(ValueError, match="neither grouped"):
        dot_product_attention(q, q, q, is_causal=True, window=8,
                              impl="flash_int8")


def _visible_block_pairs(s_q, s_k, block_q, block_k, window):
    """By brute force: the block pairs that hold a (query i, key j) with
    ``j <= i`` and ``i - j < window``, over the padded lengths."""
    i = np.arange(s_q)[:, None]
    j = np.arange(s_k)[None, :]
    seen = (j <= i) & (i - j < window)
    return {(a, b) for a in range(s_q // block_q) for b in range(s_k // block_k)
            if seen[a * block_q:(a + 1) * block_q,
                    b * block_k:(b + 1) * block_k].any()}


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 128),
                                             (128, 256), (512, 512)])
@pytest.mark.parametrize("window", [1, 100, 128, 129, 512, 1000, 4096])
def test_window_live_pairs_hold_exactly_the_visible_blocks(block_q, block_k,
                                                           window):
    """Both tables enumerate exactly the block pairs that hold a visible
    score: row-major from `_first_kv` to `_last_kv` (where the forward and dq
    init and finalize), column-major from the diagonal's q block to
    `_last_q` (where dk/dv do)."""
    for s in (512, 1024, 2048):
        n_q, n_k = s // block_q, s // block_k
        want = _visible_block_pairs(s, s, block_q, block_k, window)
        qi, kj, scored = fa._live_pairs(n_q, n_k, block_q, block_k,
                                        window=window)
        rows = list(zip(qi.tolist(), kj.tolist()))
        assert set(rows) == want and len(rows) == len(want) == scored
        assert rows == sorted(rows)
        for i in range(n_q):
            row = [j for a, j in rows if a == i]
            assert row == list(range(row[0], row[-1] + 1))
            assert row[0] == int(fa._first_kv(i, block_q, block_k, window))
            assert row[-1] == int(fa._last_kv(i, block_q, block_k, n_k, True))
        qi, kj, scored = fa._live_pairs(n_q, n_k, block_q, block_k,
                                        kv_major=True, window=window)
        cols = list(zip(kj.tolist(), qi.tolist()))
        assert {(i, j) for j, i in cols} == want and scored == len(want)
        assert cols == sorted(cols)
        for j in range(n_k):
            col = [i for b, i in cols if b == j]
            assert col == list(range(col[0], col[-1] + 1))
            assert col[0] == min(j * block_k // block_q, n_q - 1)
            assert col[-1] == int(fa._last_q(j, block_q, block_k, n_q,
                                             window))


def test_the_cells_window_keeps_108_of_136_pairs():
    """8192 tokens in blocks of 512 under a window of 4096 (252 of 528 at
    16,384), and the built calls count their live steps: 16 cells of three
    heads x 108 pairs."""
    for kv_major in (False, True):
        assert fa._live_pairs(16, 16, 512, 512, kv_major)[2] == 136
        assert fa._live_pairs(16, 16, 512, 512, kv_major, 4096)[2] == 108
        assert fa._live_pairs(32, 32, 512, 512, kv_major, 4096)[2] == 252
    assert fa._pick_hb(48, 512, 512, 128, group=6) == 3
    g = fa._tiled_grid(16, 16, 16, 512, 512, True, window=4096, cells=2)
    assert g.grid == (16, 108) and g.live_steps == 16 * 108
    g = fa._tiled_grid(16, 16, 16, 512, 512, True, True, 4096, 2)
    assert g.grid == (8, 108, 2) and g.live_steps == 16 * 108


@pytest.mark.parametrize("group,budget,hb", [
    (6, None, 3),            # 48 heads over 8 at 512 x 512 under 32 MiB
    (6, 48 << 20, 6), (6, 16 << 20, 2), (6, 1, 1),
    (4, None, 4), (2, None, 2), (8, None, 4), (3, None, 3), (5, None, 1),
    (1, None, 4),            # ungrouped: the parent's
])
def test_heads_of_a_cell_never_straddle_a_group(monkeypatch, group, budget,
                                                hb):
    if budget is not None:
        monkeypatch.setattr(fa, "_VMEM_BUDGET", budget)
    got = fa._pick_hb(8 * group, 512, 512, 128, group=group)
    assert got == hb and (group == 1 or group % got == 0)


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
