"""The single-tile regime of the flash kernels (`ops/flash_attention.py`):
one resident tile per head and one fused backward at the lengths the rule
admits, forward and gradients against the fp32 reference in Pallas
interpret mode, for the softmax, its variants and the lse cotangent, and
against the tiled regime. Its layout is `test_flash_single_tile_layout.py`'s;
which calls take this regime is `test_flash_regime.py`'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flash_util import grad_err, kernel_calls, key_mask, qkv, ref_lse
from jimm_tpu.ops.attention import (reference_attention,
                                    reference_sigmoid_attention)
from jimm_tpu.ops.flash_attention import (flash_attention,
                                          flash_attention_lse,
                                          flash_attention_masked,
                                          sigmoid_attention)


# ---------------------------------------------------------------------------
# One resident tile per head, one fused backward
# ---------------------------------------------------------------------------

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
    assert kernel_calls(lambda *a: jax.grad(
        lambda *b: jnp.sum(flash_attention(*b).astype(jnp.float32)),
        argnums=(0, 1, 2))(*a), *args) == (2, 0)
    out = flash_attention(q, k, v)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(out.astype(np.float32),
                               reference_attention(*q32), atol=fwd_tol)
    # the reference differentiates in fp32 from the same (rounded) inputs
    err = grad_err(
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
    assert kernel_calls(grads, *args) == (2, 0)
    out = attend(flash_attention)(*args)
    assert out.dtype == args[0].dtype
    np.testing.assert_allclose(out.astype(np.float32),
                               attend(reference_attention)(*x32),
                               atol=fwd_tol)
    to32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    assert grad_err(
        lambda *a: jnp.sum(to32(attend(flash_attention)(*a)) ** 2),
        lambda *a: jnp.sum(attend(reference_attention, to32)(*a) ** 2),
        args) <= grad_tol


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
    m = key_mask(rng, 2, s)
    assert kernel_calls(lambda *a: jax.grad(
        lambda *b: jnp.sum(flash(*b, m)), argnums=(0, 1, 2))(*a),
        *args) == (2, 0)
    np.testing.assert_allclose(flash(q, k, v, m), ref(q, k, v, m), atol=3e-5)
    err = grad_err(lambda *a: jnp.sum(flash(*a, m) ** 2),
                    lambda *a: jnp.sum(ref(*a, m) ** 2), args)
    assert err <= 5e-4


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
                + jnp.sum(ref_lse(q, k, v) * w))

    assert kernel_calls(jax.grad(flash_loss, argnums=(0, 1, 2)), *args) == (2, 0)
    np.testing.assert_allclose(flash_attention_lse(q, k, v)[1],
                               ref_lse(q, k, v), atol=2e-5)
    assert grad_err(flash_loss, ref_loss, args) <= 5e-4


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
    assert kernel_calls(grad(loss(tiled)), *args) == (0, 2)
    assert kernel_calls(grad(loss({})), *args) == (2, 0)
    tol = 2e-5 if dtype == "float32" else 6e-2
    np.testing.assert_allclose(
        flash_attention(q, k, v).astype(np.float32),
        flash_attention(q, k, v, **tiled).astype(np.float32), atol=tol)
    for a, b in zip(grad(loss({}))(*args), grad(loss(tiled))(*args)):
        np.testing.assert_allclose(a.astype(np.float32),
                                   b.astype(np.float32), atol=tol * 25)
