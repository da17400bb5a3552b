"""Flash-attention kernel vs fp32 einsum oracle (SURVEY §4 implication (d)),
in Pallas interpret mode on CPU (compiled path exercised by bench on TPU):
the public entry points at a few shapes. The cases of each regime live
beside this file, as `ops/flash_attention.py` divides the work:
`test_flash_single_tile.py` and `test_flash_single_tile_layout.py` (one
resident tile per head: its numbers, then the caller's layout, the heads a
cell holds, the edge block's rows), `test_flash_tiled.py` (the tiled
kernels: a value width of their own, the grid of live block pairs),
`test_flash_fused_backward.py` (the one tiled backward and its residency
bound), `test_flash_grouped.py` (grouped key/value heads and a causal
window) and `test_flash_regime.py` (which regime, blocks and heads a call
gets: pure tables, counters and the TPU lowering's text); their helpers are
`flash_util.py`'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flash_util import qkv
from jimm_tpu.ops.attention import reference_attention
from jimm_tpu.ops.flash_attention import flash_attention


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
