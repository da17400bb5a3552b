"""The single-tile regime of the flash kernels (`ops/flash_attention.py`)
reads and writes its caller's ``(B, S, N * D)`` layout: the heads a cell
holds and its lane groups, the TPU lowering around its two calls, the edge
block's rows past S, and the ring's one-head rows entry. Its numbers are
`test_flash_single_tile.py`'s; which calls take this regime is
`test_flash_regime.py`'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flash_util import grad_err, key_mask, qkv, ref_lse, tpu_text
from jimm_tpu.obs.registry import snapshot
from jimm_tpu.ops import flash_attention as fa
from jimm_tpu.ops.attention import reference_attention
from jimm_tpu.ops.flash_attention import (flash_attention,
                                          flash_attention_lse,
                                          flash_attention_masked,
                                          sigmoid_attention)


# ---------------------------------------------------------------------------
# The single-tile regime reads and writes its caller's layout
# ---------------------------------------------------------------------------

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
                + jnp.sum(ref_lse(q, k, v) * w))

    o, lse = flash_attention_lse(q, k, v)
    np.testing.assert_allclose(o, reference_attention(q, k, v), atol=2e-5)
    np.testing.assert_allclose(lse, ref_lse(q, k, v), atol=2e-5)
    assert grad_err(flash_loss, ref_loss, args) <= 5e-4


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
    text = tpu_text(_grads_of_model_layout(n), spec, spec, spec)
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
    text = tpu_text(_grads_of_model_layout(16), spec, spec, spec)
    assert text.count("@tpu_custom_call") == 2
    assert "stablehlo.transpose" not in text
    # q, k, v and, where the cotangent is not a constant, `do`; the slices
    # of o, dq, dk, dv lower to calls of one shared function
    assert text.count("stablehlo.pad") in (3, 4)
    assert text.count("stablehlo.slice") in (1, 4)
    # the lane-padded S_p is the lse rows' extent and no q-sized array's
    assert "x768xf32" in text and "x768x" not in text.replace(
        "x768xf32", "")


EDGE_VARIANTS = ["plain", "causal", "masked", "sigmoid", "lse"]


def _edge_case(variant):
    """``(loss, args)`` of a variant at 200 tokens, its inputs drawn from a
    stream of its own: the same for each poison."""
    rng = np.random.RandomState(EDGE_VARIANTS.index(variant))
    q, k, v = args = qkv(rng, b=1, s=200, n=2, d=64)
    m = key_mask(rng, 1, 200)
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
    return loss, args


def _edge_run(loss, args):
    jax.clear_caches()  # the fill value is baked in when a call lowers
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(*args)


@functools.cache
def _nan_filled(variant):
    """The variant's value and gradients with the edge rows NaN, as the
    interpreter fills them: once a variant, for its four poisons."""
    return _edge_run(*_edge_case(variant))


@pytest.mark.parametrize("poison", ["inf", "-inf", "3e38", "0"])
@pytest.mark.parametrize("variant", EDGE_VARIANTS)
def test_edge_rows_hold_anything(monkeypatch, variant, poison):
    """The rows of the edge block past S are unspecified on the chip. The
    interpreter fills them with NaN (every other test here runs so); with
    +-inf, the largest floats or zeros in their place the results are the
    same to the bit, and a kernel whose row clean-up is taken out is
    caught."""
    from jax._src.pallas import primitives
    loss, args = _edge_case(variant)
    want = _nan_filled(variant)  # edge rows NaN
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(want))
    original = primitives.uninitialized_value

    def filled(shape, dtype):
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.full(shape, float(poison), dtype)
        return original(shape, dtype)

    monkeypatch.setattr(primitives, "uninitialized_value", filled)
    got = _edge_run(loss, args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
    if poison == "inf":
        # the control: without the row clean-up the poison reaches the sums
        monkeypatch.setattr(fa, "_real_rows", lambda *a: None)
        bad = _edge_run(loss, args)
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
    mask = key_mask(rng, b, s) if masked else None
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
