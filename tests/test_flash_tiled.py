"""The tiled regime of the flash kernels (`ops/flash_attention.py`): a value
head width of its own and the causal grid of live block pairs, against the
fp32 reference in Pallas interpret mode, and the step counters of the grids
built. Its one backward is `test_flash_fused_backward.py`'s, grouped heads
and the window `test_flash_grouped.py`'s; which calls take this regime, at
which blocks and heads, is `test_flash_regime.py`'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flash_util import grad_err, kernel_calls, qkv_split, tpu_text
from jimm_tpu.obs.registry import get_registry, snapshot
from jimm_tpu.ops import flash_attention as fa
from jimm_tpu.ops.attention import reference_attention
from jimm_tpu.ops.flash_attention import flash_attention


# ---------------------------------------------------------------------------
# A value head width of its own (latent attention: q/k 192, v 128)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,d_qk,d_v,blocks,regime", [
    (1280, 192, 128, None, "tiled"),   # over the single-tile rule, causal
    (384, 24, 16, 128, "tiled"),       # asked-for blocks, off-tile widths
    (256, 192, 128, None, "single"),   # under the rule: v padded to q's
    # the causal grid of live blocks. The last block of each side
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
    q32 = qkv_split(rng, 2, s, 2, d_qk, d_v)
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


def test_tiled_kernels_keep_v_at_its_own_tile(monkeypatch):
    """q and k blocks 256 lanes (192 padded), v, o, do and dv blocks 128: the
    TPU lowering holds no 256-wide copy of v."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, 2048, 2, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)
    text = tpu_text(jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, is_causal=True).astype(jnp.float32)), argnums=(0, 1, 2)), q, q, v)
    assert text.count("@tpu_custom_call") == 2
    assert "tensor<2x2048x256xbf16>" in text      # q, k, dq, dk
    assert "tensor<2x2048x128xbf16>" in text      # v, o, do, dv
    calls = [ln for ln in text.splitlines() if "@tpu_custom_call" in ln]
    fwd = calls[0]
    assert fwd.count("tensor<2x2048x256xbf16>") == 2  # q and k in
    assert fwd.count("tensor<2x2048x128xbf16>") == 2  # v in, o out


# ---------------------------------------------------------------------------
# The tiled causal schedule: a grid of live blocks
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
    err = grad_err(
        lambda *a: jnp.sum(flash_attention(*a, is_causal=True, **kw) ** 2),
        lambda *a: jnp.sum(reference_attention(*a, is_causal=True) ** 2),
        (q, k, v))
    assert err <= 5e-4


@pytest.mark.parametrize("shape,causal,steps", [
    # kanana's attention call: 16 cells of 4 heads x 136 live blocks of 512
    # x 2 kernels, the forward and the one backward (as dq + dk/dv: x 3; as
    # the whole rectangle: 64 x 16 x 16 x 3 = 49,152 steps, 26,112 live)
    ((2, 8192, 32, 192), True, 16 * 136 * 2),
    ((1, 4096, 16, 128), True, 4 * 36 * 2),     # the rectangle: 8 x 8 x 8 x 3
    ((2, 2048, 16, 64), False, 8 * 4 * 4 * 2),  # the rectangle, all live
])
def test_tiled_step_counters(shape, causal, steps):
    """`jimm_flash_tiled_grid_steps_total` / `_live_steps_total`: the grid
    steps one execution of each built call takes, and those that compute.
    A causal grid holds no other. `jimm_flash_fused_bwd_total`: the
    backward is one of the two calls."""
    spec = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    got = kernel_calls(jax.grad(lambda *a: jnp.sum(flash_attention(
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
    got = kernel_calls(jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, is_causal=True, block_q=128, block_k=128)), argnums=(0, 1, 2)),
        q, k, k, regimes=("tiled_grid_steps", "tiled_live_steps"))
    assert got == (3 + 6, 3 + 3)
