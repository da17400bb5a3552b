"""Pallas TPU flash attention with int8-quantized Q/K — the serving variant.

A registered low-precision variant of ``ops/flash_attention.py`` (the
Flashlight template discipline: same grid layout, same online-softmax
recurrence, same DMA-eliding causal index maps — only the score matmul
changes). Q and K are quantized symmetrically per row at trace time
(:func:`_quantize_heads`, scale = max|row|/127) so the (S, S) score matmul
runs int8 x int8 -> int32 on the MXU at twice the bf16 rate; the int32
scores dequantize through the per-row scale outer product inside
:func:`_dequant_scores` (the one sanctioned f32 upcast — JL012), and the
softmax + P@V accumulation stay in f32/storage dtype exactly as in the f32
kernel. V is NOT quantized: the probability-weighted value sum is where
per-row quantization error would compound, and keeping it full-precision is
what holds end-to-end cosine above the 0.999 parity bound the smoke
enforces.

Head dim pads to 128 lanes for the int8 operands (int8 Mosaic tiles are
(32, 128); d=64 towers would otherwise sit below the minimum lane tile).
Zero padding quantizes to zero and contributes nothing to the dot.

Differentiable end-to-end: the forward also emits the per-row lse (same
``(BN, 1, Sq)`` stat layout as the f32 kernel) and a custom VJP pairs it
with dq / dkv backward kernels that **recompute the score tiles from the
saved int8 operands** — bit-identical to what the forward multiplied, so
the softmax recomputation is exact and the gradient is the straight-
through estimate of the quantized forward (the ``int8_qk`` training
policy's contract). dq/dk contract ``ds`` against the dequantized
counterpart operand in the storage dtype, matching the f32 backward's
precision story. Block sizes resolve through
``tune.best_config("flash_attention_int8", ...)``; VMEM per grid cell is
modeled by :func:`_per_head_vmem_bytes` /
:func:`_per_head_bwd_vmem_bytes` (mirrored jax-free in
``tune.space.int8_flash_vmem_bytes`` /
``tune.space.int8_flash_bwd_vmem_bytes``, sync-tested).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.ad_checkpoint import checkpoint_name

from jimm_tpu.ops.flash_attention import (NEG_INF, _LANES, _bcast_lanes,
                                          _ceil_to, _flatten_heads,
                                          _from_lanes, _interpret, _pad_seq,
                                          _pick_block, _unflatten_heads)

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512

#: per-cell budget under Mosaic's default 16 MiB scope, which these calls
#: run in (the bf16 family's tiled calls state their own limit since PR 33)
_VMEM_BUDGET = 8 * 1024 * 1024

_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _causal_kv_index(block_q: int, block_k: int, n_k: int):
    """kv-block index map for causal grids ordered (heads, q, kv): blocks
    strictly above the diagonal (kernel skips them via ``pl.when``) are
    clamped to the q row's last needed block, so the pipeline sees the same
    index twice and elides the HBM->VMEM copy. (The bf16 family's causal
    grid holds the live blocks only; these kernels still walk the
    rectangle.)"""
    def idx(h, i, j):
        jmax = jnp.minimum(n_k - 1, ((i + 1) * block_q - 1) // block_k)
        return (h, jnp.minimum(j, jmax), 0)
    return idx


def _causal_q_index(block_q: int, block_k: int, lse_layout: bool = False):
    """q-side index maps for the causal dk/dv grid ordered (heads, kv, q):
    q blocks entirely left of the diagonal are clamped up to the kv row's
    first needed block — same DMA-eliding trick as `_causal_kv_index`."""
    def idx(h, j, i):
        imin = (j * block_k) // block_q
        i = jnp.maximum(i, imin)
        return (h, 0, i) if lse_layout else (h, i, 0)
    return idx


def _per_head_vmem_bytes(block_q: int, block_k: int, d: int) -> int:
    """Resident VMEM per head in one grid cell. int8 q/k tiles carry the
    128-padded head dim; v and the out tile keep the storage dtype (bf16
    bound); scales ride in the lse-style (hb, 1, block) layout; the f32
    lse out row feeds the backward. Mirrored jax-free in
    ``tune.space.int8_flash_vmem_bytes`` (sync-tested)."""
    dp = _ceil_to(d, _LANES)
    return (block_q * dp + block_k * dp   # int8 q/k tiles
            + 2 * block_k * d * 2         # v in + double-buffer
            + block_q * d * 2             # out tile
            + 2 * block_q * _LANES * 4    # m/l stats scratch
            + block_q * d * 4             # fp32 accumulator
            + (block_q + block_k) * 4     # per-row q/k scale tiles
            + block_q * 4                 # f32 lse out row
            + block_q * block_k * 6)      # s fp32 + p bf16 intermediate


def _per_head_bwd_vmem_bytes(block_q: int, block_k: int, d: int) -> int:
    """Shared upper bound on one backward grid cell's per-head working set
    (the dq and dkv cells overlap heavily; the bound covers both): int8
    q/k tiles, storage-dtype v/do, scale + lse + delta stat rows, the f32
    dq / dk / dv scratch at their lane-padded widths, and the recomputed
    s/p/ds f32 temporaries. Mirrored jax-free in
    ``tune.space.int8_flash_bwd_vmem_bytes`` (sync-tested)."""
    dp = _ceil_to(d, _LANES)
    return (block_q * dp + block_k * dp        # int8 q/k tiles
            + block_k * d * 2 + block_q * d * 2  # v and do tiles
            + (block_q + block_k) * 4          # per-row q/k scale tiles
            + 2 * block_q * 4                  # lse + delta rows
            + (block_k * dp + block_k * d) * 4  # dk/dv f32 scratch
            + block_q * dp * 4                 # dq f32 scratch
            + 3 * block_q * block_k * 4)       # s/p/ds f32 temporaries


def _pick_hb(bn: int, block_q: int, block_k: int, d: int,
             vmem_fn=_per_head_vmem_bytes) -> int:
    per_head = vmem_fn(block_q, block_k, d)
    for hb in (8, 4, 2):
        if bn % hb == 0 and hb * per_head <= _VMEM_BUDGET:
            return hb
    return 1


def _dequant_scores(s: jax.Array, q_scale: jax.Array,
                    k_scale: jax.Array) -> jax.Array:
    """int32 score block -> f32 via the per-row quantization scales' outer
    product. A sanctioned f32 upcast (JL012)."""
    return s.astype(jnp.float32) * q_scale[:, None] * k_scale[None, :]


def _dequant_operand(x_q: jax.Array, x_scale: jax.Array,
                     dtype) -> jax.Array:
    """int8 operand tile -> storage dtype via its per-row scale, for the
    backward's ds contractions (the f32 kernel contracts ds against the
    bf16 k/q tiles; this is the quantized path's equivalent). A sanctioned
    f32 upcast (JL012)."""
    return (x_q.astype(jnp.float32) * x_scale[:, None]).astype(dtype)


def _bwd_scores(qq, kq, q_scale, k_scale, sm_scale, pos):
    """Recompute one masked f32 score tile from the **saved** int8
    operands — the same int8 dot the forward ran, so the softmax
    recomputation in the backward is bit-identical."""
    s_i32 = jax.lax.dot_general(qq, kq, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.int32)
    s = _dequant_scores(s_i32, q_scale, k_scale) * sm_scale
    return jnp.where(pos, s, NEG_INF)


def _ds_tile(s, do, v, lse, delta):
    """Backward score-gradient (softmax recurrence of the f32 template):
    ``p`` from the recomputed score tile and the saved lse, then
    ``ds = p * (dp - delta)`` — unscaled; the chain-rule sm_scale lands at
    the dq/dk finalize. Returns (p, ds)."""
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    p = jnp.exp(s - lse[:, None])
    ds = p * (dp - delta[:, None])
    return p, ds


def _fwd_kernel(qq_ref, kq_ref, v_ref, qs_ref, ks_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sk_real: int, block_k: int,
                causal: bool, sm_scale: float, n_k: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    hb, bq, _ = qq_ref.shape

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def compute():
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        mask = k_pos < sk_real
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            mask = mask & (k_pos <= q_pos)
        for h in range(hb):
            qq = qq_ref[h]                               # (bq, dp) int8
            kq = kq_ref[h]                               # (bk, dp) int8
            v = v_ref[h]                                 # (bk, d)
            s_i32 = jax.lax.dot_general(
                qq, kq, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
            s = _dequant_scores(s_i32, qs_ref[h, 0, :],
                                ks_ref[h, 0, :]) * sm_scale
            s = jnp.where(mask, s, NEG_INF)
            m_prev = _from_lanes(m_scr[h])
            l_prev = _from_lanes(l_scr[h])
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=1)
            acc_scr[h] = acc_scr[h] * corr[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = _bcast_lanes(m_new)
            l_scr[h] = _bcast_lanes(l_new)

    if causal:
        pl.when(kj * block_k <= (qi + 1) * bq - 1)(compute)
        last_j = jnp.minimum(n_k - 1, ((qi + 1) * bq - 1) // block_k)
    else:
        compute()
        last_j = n_k - 1

    @pl.when(kj == last_j)
    def _finalize():
        for h in range(hb):
            m = _from_lanes(m_scr[h])
            l = _from_lanes(l_scr[h])
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[h] = (acc_scr[h] / l_safe[:, None]).astype(o_ref.dtype)
            lse_ref[h, 0, :] = m + jnp.log(l_safe)


def _bwd_dq_kernel(qq_ref, kq_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_scr, *, sk_real: int, block_k: int,
                   causal: bool, sm_scale: float, n_k: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    hb, bq, _ = qq_ref.shape

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def compute():
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        pos = k_pos < sk_real
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            pos = pos & (k_pos <= q_pos)
        for h in range(hb):
            s = _bwd_scores(qq_ref[h], kq_ref[h], qs_ref[h, 0, :],
                            ks_ref[h, 0, :], sm_scale, pos)
            _, ds = _ds_tile(s, do_ref[h], v_ref[h], lse_ref[h, 0, :],
                             delta_ref[h, 0, :])
            kd = _dequant_operand(kq_ref[h], ks_ref[h, 0, :], do_ref.dtype)
            dq_scr[h] += jax.lax.dot_general(
                ds.astype(kd.dtype), kd, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        pl.when(kj * block_k <= (qi + 1) * bq - 1)(compute)
    else:
        compute()

    @pl.when(kj == n_k - 1)
    def _finalize():
        dq_ref[...] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(qq_ref, kq_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    sq_real: int, block_q: int, causal: bool,
                    sm_scale: float, n_q: int):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    hb, bk, _ = kq_ref.shape

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def compute():
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        pos = q_pos < sq_real
        if causal:
            k_pos = kj * bk + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            pos = pos & (k_pos <= q_pos)
        for h in range(hb):
            do = do_ref[h]
            s = _bwd_scores(qq_ref[h], kq_ref[h], qs_ref[h, 0, :],
                            ks_ref[h, 0, :], sm_scale, pos)
            p, ds = _ds_tile(s, do, v_ref[h], lse_ref[h, 0, :],
                             delta_ref[h, 0, :])
            # dv's MXU input is a rounded copy; ds keeps the fp32 p
            # (matching the dq kernel) so dk isn't computed from a
            # double-rounded p
            dv_scr[h] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            qd = _dequant_operand(qq_ref[h], qs_ref[h, 0, :], do.dtype)
            dk_scr[h] += jax.lax.dot_general(
                ds.astype(qd.dtype), qd, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        # q blocks whose last row is left of this kv block never land
        pl.when((qi + 1) * block_q - 1 >= kj * bk)(compute)
    else:
        compute()

    @pl.when(qi == n_q - 1)
    def _finalize():
        # ds was accumulated unscaled; the chain-rule sm_scale lands here
        dk_ref[...] = (dk_scr[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _quantize_heads(x3: jax.Array, seq_p: int,
                    d_p: int) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-row int8 quantization of a head-flattened (BN, S, D)
    tensor, padded to (BN, seq_p, d_p). Returns the int8 tensor and the
    fp32 scales in the kernel's lse-style (BN, 1, seq_p) layout. Padded
    rows get scale 1.0 (finite dequant; their scores are masked anyway)."""
    xf = x3.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    x_q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    x_q = x_q.astype(jnp.int8)
    bn, seq, d = x3.shape
    x_q = jnp.pad(x_q, ((0, 0), (0, seq_p - seq), (0, d_p - d)))
    scale = jnp.pad(scale, ((0, 0), (0, seq_p - seq)), constant_values=1.0)
    return x_q, scale[:, None, :]


def _resolve_blocks(q, k, v, block_q, block_k):
    """Trace-time block resolution through the tune cache — lookup only.
    Explicit ints win, so the tuner's bench closures cannot recurse."""
    if block_q is not None and block_k is not None:
        return int(block_q), int(block_k)
    from jimm_tpu.tune import best_config
    cfg = best_config("flash_attention_int8",
                      (q.shape, k.shape, v.shape),
                      (q.dtype, k.dtype, v.dtype),
                      default={"block_q": DEFAULT_BLOCK_Q,
                               "block_k": DEFAULT_BLOCK_K})
    return (int(block_q if block_q is not None else cfg["block_q"]),
            int(block_k if block_k is not None else cfg["block_k"]))


def _int8_fwd_impl(q3, k3, v3, causal, sm_scale, block_q, block_k):
    bn, sq, d = q3.shape
    sk = k3.shape[1]
    sq_p, sk_p = _ceil_to(sq, block_q), _ceil_to(sk, block_k)
    d_p = _ceil_to(d, _LANES)
    qq, qs = _quantize_heads(q3, sq_p, d_p)
    kq, ks = _quantize_heads(k3, sk_p, d_p)
    vp = _pad_seq(v3, sk_p)
    n_q, n_k = sq_p // block_q, sk_p // block_k
    hb = _pick_hb(bn, block_q, block_k, d)
    kernel = partial(_fwd_kernel, sk_real=sk, block_k=block_k,
                     causal=causal, sm_scale=sm_scale, n_k=n_k)
    kv_idx = (_causal_kv_index(block_q, block_k, n_k) if causal
              else (lambda h, i, j: (h, j, 0)))
    kv_stat_idx = (
        (lambda h, i, j: (h, 0,
                          _causal_kv_index(block_q, block_k, n_k)(h, i, j)[1]))
        if causal else (lambda h, i, j: (h, 0, j)))
    o, lse = pl.pallas_call(
        kernel,
        grid=(bn // hb, n_q, n_k),
        in_specs=[
            pl.BlockSpec((hb, block_q, d_p), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((hb, block_k, d_p), kv_idx),
            pl.BlockSpec((hb, block_k, d), kv_idx),
            pl.BlockSpec((hb, 1, block_q), lambda h, i, j: (h, 0, i)),
            pl.BlockSpec((hb, 1, block_k), kv_stat_idx),
        ],
        out_specs=[
            pl.BlockSpec((hb, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((hb, 1, block_q), lambda h, i, j: (h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, sq_p, d), q3.dtype),
            jax.ShapeDtypeStruct((bn, 1, sq_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, block_q, _LANES), jnp.float32),
            pltpu.VMEM((hb, block_q, _LANES), jnp.float32),
            pltpu.VMEM((hb, block_q, d), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        interpret=_interpret(),
    )(qq, kq, vp, qs, ks)
    # same saveable names as the f32 kernel so remat policies that keep
    # flash outputs keep these too (the backward consumes o via delta)
    o = checkpoint_name(o[:, :sq], "flash_o")
    lse = checkpoint_name(lse[:, 0, :sq], "flash_lse")
    # residuals carry the int8 operands the forward actually multiplied —
    # the backward's score recomputation is bit-identical, at 1B/element
    return o, (qq, qs, kq, ks, v3, o, lse)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_int8(q3, k3, v3, causal, sm_scale, block_q, block_k):
    o, _ = _int8_fwd_impl(q3, k3, v3, causal, sm_scale, block_q, block_k)
    return o


def _int8_fwd(q3, k3, v3, causal, sm_scale, block_q, block_k):
    return _int8_fwd_impl(q3, k3, v3, causal, sm_scale, block_q, block_k)


def _int8_bwd(causal, sm_scale, block_q, block_k, res, do):
    qq, qs, kq, ks, v3, o, lse = res
    bn, sq, d = o.shape
    sk = v3.shape[1]
    sq_p, d_p = qq.shape[1], qq.shape[2]
    sk_p = kq.shape[1]
    n_q, n_k = sq_p // block_q, sk_p // block_k
    vp = _pad_seq(v3, sk_p)
    dop = _pad_seq(do, sq_p)
    # the delta statistic (rowwise sum do*o) is f32 by definition — these
    # are outputs/cotangents, never int8 operand tiles
    do32 = do.astype(jnp.float32)  # jaxlint: disable=JL012 f32 statistic
    o32 = o.astype(jnp.float32)  # jaxlint: disable=JL012 f32 statistic
    delta = jnp.sum(do32 * o32, axis=-1)
    lse_p = jnp.pad(lse, ((0, 0), (0, sq_p - sq)))[:, None]
    delta_p = jnp.pad(delta, ((0, 0), (0, sq_p - sq)))[:, None]
    hb = _pick_hb(bn, block_q, block_k, d, _per_head_bwd_vmem_bytes)

    # ---- dq (grid heads, q, kv) — padded head lanes of the dequantized k
    # are zero, so the extra dq columns are exact zeros, sliced off below
    kv_idx = (_causal_kv_index(block_q, block_k, n_k) if causal
              else (lambda h, i, j: (h, j, 0)))
    kv_stat_idx = (
        (lambda h, i, j: (h, 0,
                          _causal_kv_index(block_q, block_k, n_k)(h, i, j)[1]))
        if causal else (lambda h, i, j: (h, 0, j)))
    q_stat_spec = pl.BlockSpec((hb, 1, block_q), lambda h, i, j: (h, 0, i))
    dq = pl.pallas_call(
        partial(_bwd_dq_kernel, sk_real=sk, block_k=block_k, causal=causal,
                sm_scale=sm_scale, n_k=n_k),
        grid=(bn // hb, n_q, n_k),
        in_specs=[
            pl.BlockSpec((hb, block_q, d_p), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((hb, block_k, d_p), kv_idx),
            pl.BlockSpec((hb, block_k, d), kv_idx),
            q_stat_spec,
            pl.BlockSpec((hb, 1, block_k), kv_stat_idx),
            pl.BlockSpec((hb, block_q, d), lambda h, i, j: (h, i, 0)),
            q_stat_spec,
            q_stat_spec,
        ],
        out_specs=pl.BlockSpec((hb, block_q, d_p),
                               lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bn, sq_p, d_p), o.dtype),
        scratch_shapes=[pltpu.VMEM((hb, block_q, d_p), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=_interpret(),
    )(qq, kq, vp, qs, ks, dop, lse_p, delta_p)[:, :sq, :d]

    # ---- dk / dv (grid heads, kv, q)
    q_idx = (_causal_q_index(block_q, block_k) if causal
             else (lambda h, j, i: (h, i, 0)))
    stat_idx = (_causal_q_index(block_q, block_k, lse_layout=True) if causal
                else (lambda h, j, i: (h, 0, i)))
    stat_spec = pl.BlockSpec((hb, 1, block_q), stat_idx)
    dk, dv = pl.pallas_call(
        partial(_bwd_dkv_kernel, sq_real=sq, block_q=block_q, causal=causal,
                sm_scale=sm_scale, n_q=n_q),
        grid=(bn // hb, n_k, n_q),
        in_specs=[
            pl.BlockSpec((hb, block_q, d_p), q_idx),
            pl.BlockSpec((hb, block_k, d_p), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((hb, block_k, d), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((hb, 1, block_q), stat_idx),
            pl.BlockSpec((hb, 1, block_k), lambda h, j, i: (h, 0, j)),
            pl.BlockSpec((hb, block_q, d), q_idx),
            stat_spec,
            stat_spec,
        ],
        out_specs=[
            pl.BlockSpec((hb, block_k, d_p), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((hb, block_k, d), lambda h, j, i: (h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, sk_p, d_p), o.dtype),
            jax.ShapeDtypeStruct((bn, sk_p, d), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, block_k, d_p), jnp.float32),
            pltpu.VMEM((hb, block_k, d), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        interpret=_interpret(),
    )(qq, kq, vp, qs, ks, dop, lse_p, delta_p)
    return dq, dk[:, :sk, :d], dv[:, :sk]


_flash_int8.defvjp(_int8_fwd, _int8_bwd)


def flash_attention_int8(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         is_causal: bool = False,
                         block_q: int | None = None,
                         block_k: int | None = None) -> jax.Array:
    """int8-activation flash attention over ``(B, S, N, D)`` q/k/v.

    Q/K quantize per row to int8, the score matmul runs on the MXU in
    int8, softmax and P@V stay full-precision. Differentiable: a custom
    VJP recomputes score tiles from the saved int8 operands (straight-
    through gradient of the quantized forward), so the ``int8_qk``
    training policy can route attention here. Scale is 1/sqrt(D) like
    `flash_attention`. Runs the Pallas interpreter off-TPU so CPU tests
    and the quant parity harness exercise the same code path.
    """
    b, sq, n, d = q.shape
    sm_scale = 1.0 / (d ** 0.5)
    block_q, block_k = _resolve_blocks(q, k, v, block_q, block_k)
    block_q = min(_pick_block(sq, block_q), _ceil_to(sq, _LANES))
    block_k = min(_pick_block(k.shape[1], block_k),
                  _ceil_to(k.shape[1], _LANES))
    q3, k3, v3 = map(_flatten_heads, (q, k, v))
    o = _flash_int8(q3, k3, v3, is_causal, sm_scale, block_q, block_k)
    return _unflatten_heads(o, b, n)
