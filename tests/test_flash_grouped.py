"""Grouped key/value heads and a causal window in the flash kernels
(`ops/flash_attention.py`): forward and gradients against the fp32
reference and the XLA path in Pallas interpret mode, the window's live
block pairs, and the heads a cell holds of a group."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jimm_tpu.obs.registry import get_registry
from jimm_tpu.ops import flash_attention as fa
from jimm_tpu.ops.attention import dot_product_attention, reference_attention
from jimm_tpu.ops.flash_attention import flash_attention


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
