"""The tiled regime's backward (`ops/flash_attention.py::_flash_bwd`): one
kernel while the heads' fp32 dq fits in VMEM, equal bit for bit to the dq +
dk/dv pair across the family, and the residency bound that picks the
arrangement. The heads it takes by shape are `test_flash_regime.py`'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flash_util import kernel_calls, kernel_grids, key_mask
from jimm_tpu.obs.registry import get_registry
from jimm_tpu.ops import flash_attention as fa
from jimm_tpu.ops.attention import reference_attention
from jimm_tpu.ops.flash_attention import (flash_attention,
                                          flash_attention_bias,
                                          flash_attention_lse,
                                          flash_attention_masked,
                                          sigmoid_attention)


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
        mask = key_mask(rng, b, sk)
        attn = lambda q, k, v: flash_attention_masked(q, k, v, mask, **kw)  # noqa: E731
    elif member == "bias":
        extra, argnums, calls = (jnp.asarray(rng.randn(n, sq, sk).astype(
            np.float32)),), (0, 1, 2, 3), 2
        attn = lambda q, k, v, bias: flash_attention_bias(q, k, v, bias, **kw)  # noqa: E731
    elif member == "sigmoid":
        mask = key_mask(rng, b, sk)
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
    the chip's reading is in PERF.md)."""
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
    got = kernel_calls(grads, regimes=("tiled", "fused_bwd"))
    assert got == (3 - fused_calls, fused_calls)
    grids = kernel_grids(grads)
    assert [g[0] for g in grids] == [1] + [2 // hb] * (2 - fused_calls)
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(1, 384, 2, 64).astype(np.float32) * 0.5)
               for _ in range(3))
    probe = jnp.asarray(rng.randn(1, 384, 2, 64).astype(np.float32))
    want = jax.grad(lambda *a: jnp.sum(reference_attention(
        *a, is_causal=True) * probe), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads(), want):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
