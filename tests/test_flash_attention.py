"""Flash-attention kernel vs fp32 einsum oracle (SURVEY §4 implication (d)),
in Pallas interpret mode on CPU (compiled path exercised by bench on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jimm_tpu.obs.registry import get_registry, snapshot
from jimm_tpu.ops import flash_attention as fa
from jimm_tpu.ops.attention import (reference_attention,
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

def _calls(fn, *args):
    """(single-tile, tiled) pallas_calls that tracing ``fn`` builds."""
    reg = get_registry("jimm_flash")
    single, tiled = (reg.counter("single_tile_total"),
                     reg.counter("tiled_total"))
    before = single.value, tiled.value
    jax.make_jaxpr(fn)(*args)
    return int(single.value - before[0]), int(tiled.value - before[1])


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
@pytest.mark.parametrize("d", [64, 72, 80])
@pytest.mark.parametrize("s", [257, 577, 729])
def test_single_tile_matches_reference(rng, s, d, dtype):
    """Forward and gradients of the single-tile kernels at the image
    presets' lengths and head widths (72 and 80 lane-pad to 128)."""
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


@pytest.mark.parametrize("s,d", [(577, 64), (257, 72)])
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


@pytest.mark.parametrize("s,d", [(577, 64), (257, 80)])
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
    assert _calls(grad(loss(tiled)), *args) == (0, 3)
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
    """One length just over the bound keeps the parent's kernels: the
    three-dimensional (heads, q, kv) grids of forward, dq and dk/dv."""
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

    assert [len(g) for g in _grids(grads, spec, spec, spec)] == [3, 3, 3]
    under = jax.ShapeDtypeStruct((1, bound - 128, 2, 64), jnp.bfloat16)
    assert [len(g) for g in _grids(grads, under, under, under)] == [1, 1]


def test_bias_variant_stays_tiled(rng):
    q, k, v = qkv(rng, b=1, s=64, n=2)
    bias = jnp.asarray(rng.randn(2, 64, 64).astype(np.float32))
    assert _calls(jax.grad(lambda *a: jnp.sum(flash_attention_bias(*a)),
                           argnums=(0, 1, 2, 3)), q, k, v, bias) == (0, 4)


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
