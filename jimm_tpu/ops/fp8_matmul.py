"""Pallas TPU fp8 matmul with an e4m3-forward / e5m2-gradient custom VJP.

The low-precision *training* fast path's workhorse, pairing the serving
int8 kernel (``ops/int8_matmul.py``): forward operands quantize to
``float8_e4m3fn`` (3 mantissa bits — resolution matters more than range
for activations and weights), gradients quantize to ``float8_e5m2``
(5 exponent bits — the backward's dynamic range dwarfs its precision
needs). Both run as fp8 x fp8 -> f32 MXU dots
(``preferred_element_type=jnp.float32``), with the rank-0 dequantizing
rescale fused into the same grid cell's epilogue.

Scaling is per-tensor and **explicit**: every public entry point takes the
fp32 scales as arguments and the custom VJP carries them as residual
state, so the caller decides the strategy —

- **dynamic** (:func:`dynamic_scale`): scale from this tensor's own amax.
  The backward always uses it for the incoming gradient (the cotangent's
  magnitude is unknowable ahead of time).
- **delayed** (:func:`delayed_scale` + :func:`update_amax_history`): scale
  from a rolling amax history, one matmul pass behind. The training
  policy (``jimm_tpu.quant.policy``) keeps the history as module state so
  forward quantization costs no extra reduction over the live tensor.

Quantization (the only sanctioned fp8 casts — lint rule JL016 bans bare
``.astype(jnp.float8_*)`` elsewhere in ops/ and train/) saturates at the
format max instead of overflowing to inf. Shape robustness and block
resolution mirror ``int8_matmul``: rows pad to the fp8 32-sublane tile,
K/N pad to 128 lanes, blocks resolve through
``tune.best_config("fp8_matmul")`` (lookup-only; explicit ints win so the
tuner's bench closures cannot recurse). Off-TPU the kernel runs in the
Pallas interpreter so CPU parity tests exercise the same code path.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 256
_LANES = 128
#: fp8 Mosaic tiles are (32, 128) — row blocks align to 32 sublanes
_FP8_SUBLANES = 32

#: saturation bounds of the two formats (jnp.finfo(...).max)
E4M3_MAX = 448.0
E5M2_MAX = 57344.0

_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"))

#: VMEM budget for one grid cell's resident tiles (mirrors the int8 /
#: flash kernels' budget; sync-tested against tune.space)
_VMEM_BUDGET = 8 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _per_cell_vmem_bytes(block_m: int, block_n: int, k: int) -> int:
    """Resident working set of one (block_m, block_n) grid cell: the fp8
    a/b tiles at the 128-padded K, the lane-broadcast per-tensor scale,
    the bias, and the f32 accumulator / out tiles. Mirrored jax-free in
    ``tune.space.fp8_matmul_vmem_bytes`` (sync-tested)."""
    kp = _ceil_to(k, _LANES)
    return (block_m * kp                  # a fp8 tile
            + kp * block_n                # b fp8 tile
            + _LANES * 4                  # lane-broadcast tensor scale
            + block_n * 4                 # bias
            + 2 * block_m * block_n * 4)  # f32 acc + out tile


def _dequant(acc: jax.Array, scale: jax.Array) -> jax.Array:
    """f32 accumulator -> dequantized f32 via the combined per-tensor
    scale (rank-0 rescale; both operands' scales fold into one scalar)."""
    return acc * scale


def _matmul_kernel(aq_ref, bq_ref, s_ref, b_ref, o_ref):
    acc = jax.lax.dot_general(
        aq_ref[...], bq_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # the combined scale arrives lane-broadcast (1, 128); every lane holds
    # the same scalar
    y = _dequant(acc, s_ref[0, 0])
    # bias arrives as a (1, block_n) row: Mosaic refuses a rank-1 block
    # whose extent is not XLA's 1-D tile (1024 for f32)
    o_ref[...] = (y + b_ref[...]).astype(o_ref.dtype)


def _resolve_blocks(a_shape, b_shape, dtypes, block_m, block_n):
    """Trace-time (host-side) block resolution through the tune cache —
    lookup only, never a measurement. Explicit ints win (the tuner's bench
    closures pass them, so tuning cannot recurse)."""
    if block_m is not None and block_n is not None:
        return int(block_m), int(block_n)
    from jimm_tpu.tune import best_config
    cfg = best_config("fp8_matmul", (tuple(a_shape), tuple(b_shape)),
                      tuple(dtypes),
                      default={"block_m": DEFAULT_BLOCK_M,
                               "block_n": DEFAULT_BLOCK_N})
    return (int(block_m if block_m is not None else cfg["block_m"]),
            int(block_n if block_n is not None else cfg["block_n"]))


def _pad2(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    return x if pr == 0 and pc == 0 else jnp.pad(x, ((0, pr), (0, pc)))


def _fp8_gemm(a_q: jax.Array, b_q: jax.Array, scale: jax.Array,
              bias: jax.Array | None, block_m: int | None,
              block_n: int | None) -> jax.Array:
    """One fp8 x fp8 -> f32 Pallas matmul ``(M, K) @ (K, N)`` with the
    fused dequant + bias epilogue. Operand formats may differ (the
    backward contracts e5m2 gradients against e4m3 residuals)."""
    m, k = a_q.shape
    kb, n = b_q.shape
    if kb != k:
        raise ValueError(f"a_q K {k} != b_q K {kb}")
    bm, bn = _resolve_blocks(a_q.shape, b_q.shape,
                             (a_q.dtype, b_q.dtype), block_m, block_n)
    bm = max(_FP8_SUBLANES,
             min(_ceil_to(bm, _FP8_SUBLANES), _ceil_to(m, _FP8_SUBLANES)))
    bn = max(_LANES, min(_ceil_to(bn, _LANES), _ceil_to(n, _LANES)))
    mp, np_, kp = _ceil_to(m, bm), _ceil_to(n, bn), _ceil_to(k, _LANES)
    s = jnp.broadcast_to(
        jnp.asarray(scale, jnp.float32).reshape(1, 1), (1, _LANES))
    b = (jnp.zeros((1, np_), jnp.float32) if bias is None
         else jnp.pad(bias.astype(jnp.float32),
                      ((0, np_ - bias.shape[0]),))[None, :])
    # zero padding contributes zero products to the fp8 dot
    out = pl.pallas_call(
        _matmul_kernel,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, _LANES), lambda i, j: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        compiler_params=_SEMANTICS,
        interpret=_interpret(),
    )(_pad2(a_q, mp, kp), _pad2(b_q, kp, np_), s, b)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# scaling helpers — the sanctioned homes for every fp8 cast (JL016)
# ---------------------------------------------------------------------------

def quantize_tensor(x: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Per-tensor symmetric fp8 quantization at an explicit fp32 scale,
    saturating at the format max (no infs from a stale delayed scale)."""
    fmax = float(jnp.finfo(dtype).max)
    xf = x.astype(jnp.float32) / scale
    return jnp.clip(xf, -fmax, fmax).astype(dtype)


def tensor_amax(x: jax.Array) -> jax.Array:
    """The per-tensor amax observation feeding delayed scaling."""
    return jnp.max(jnp.abs(x.astype(jnp.float32)))


def dynamic_scale(x: jax.Array, dtype) -> jax.Array:
    """Per-tensor scale from this tensor's own amax: ``amax / format_max``
    (1.0 for all-zero tensors, so dequantization stays finite)."""
    amax = tensor_amax(x)
    fmax = float(jnp.finfo(dtype).max)
    return jnp.where(amax > 0, amax / fmax, 1.0)


def delayed_scale(amax_history: jax.Array, dtype) -> jax.Array:
    """Per-tensor scale from a rolling amax history (max over the window,
    one matmul pass behind the live tensor — Transformer-Engine-style
    delayed scaling)."""
    amax = jnp.max(amax_history)
    fmax = float(jnp.finfo(dtype).max)
    return jnp.where(amax > 0, amax / fmax, 1.0)


def update_amax_history(amax_history: jax.Array,
                        amax: jax.Array) -> jax.Array:
    """Roll the delayed-scaling window: drop the oldest observation,
    append the newest."""
    return jnp.concatenate(
        [amax_history[1:], jnp.reshape(amax, (1,)).astype(jnp.float32)])


# ---------------------------------------------------------------------------
# the custom VJP: e4m3 forward, e5m2 backward
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fp8_matmul(x, w, bias, x_scale, w_scale, block_m, block_n):
    x_q = quantize_tensor(x, x_scale, jnp.float8_e4m3fn)
    w_q = quantize_tensor(w, w_scale, jnp.float8_e4m3fn)
    return _fp8_gemm(x_q, w_q, x_scale * w_scale, bias, block_m, block_n)


def _fp8_matmul_fwd(x, w, bias, x_scale, w_scale, block_m, block_n):
    x_q = quantize_tensor(x, x_scale, jnp.float8_e4m3fn)
    w_q = quantize_tensor(w, w_scale, jnp.float8_e4m3fn)
    y = _fp8_gemm(x_q, w_q, x_scale * w_scale, bias, block_m, block_n)
    # residuals are the fp8 tensors themselves — the backward contracts
    # against exactly what the forward multiplied (straight-through
    # estimator through the quantizer), at 1 byte/element
    # zero-size sentinels carry the primal dtypes to the backward (dtype
    # objects are not valid pytree leaves for traced residuals)
    return y, (x_q, w_q, x_scale, w_scale,
               jnp.zeros((0,), x.dtype), jnp.zeros((0,), w.dtype),
               None if bias is None else jnp.zeros((0,), bias.dtype))


def _fp8_matmul_bwd(block_m, block_n, res, dy):
    x_q, w_q, x_scale, w_scale, x_sent, w_sent, b_sent = res
    x_dtype, w_dtype = x_sent.dtype, w_sent.dtype
    b_dtype = None if b_sent is None else b_sent.dtype
    dy_scale = dynamic_scale(dy, jnp.float8_e5m2)
    dy_q = quantize_tensor(dy, dy_scale, jnp.float8_e5m2)
    # dx = dy @ w^T : e5m2 x e4m3 contraction, dequant by both scales.
    # Cotangents land back in the primal dtypes — a bf16 model under remat
    # would otherwise see f32 cotangents meet bf16 recomputed values and
    # fail stablehlo verification at lowering.
    dx = _fp8_gemm(dy_q, w_q.T, dy_scale * w_scale, None, block_m,
                   block_n).astype(x_dtype)
    # dw = x^T @ dy
    dw = _fp8_gemm(x_q.T, dy_q, x_scale * dy_scale, None, block_m,
                   block_n).astype(w_dtype)
    dbias = (None if b_dtype is None
             else jnp.sum(dy.astype(jnp.float32), axis=0).astype(b_dtype))
    # scales are statistics, not parameters — no gradient flows to them
    return (dx, dw, dbias,
            jnp.zeros_like(x_scale), jnp.zeros_like(w_scale))


_fp8_matmul.defvjp(_fp8_matmul_fwd, _fp8_matmul_bwd)


def fp8_matmul(x: jax.Array, w: jax.Array, bias: jax.Array | None = None,
               *, x_scale: jax.Array | None = None,
               w_scale: jax.Array | None = None,
               block_m: int | None = None,
               block_n: int | None = None) -> jax.Array:
    """Differentiable fp8 matmul ``x @ w + bias`` (f32 output).

    Forward quantizes both operands to e4m3 at the given per-tensor
    scales; the backward quantizes the incoming gradient to e5m2 with a
    dynamic scale and contracts it against the saved fp8 residuals.

    Args:
        x: ``(M, K)`` activations (any float dtype).
        w: ``(K, N)`` weights (any float dtype).
        bias: optional ``(N,)`` bias added in f32 after dequantization.
        x_scale, w_scale: fp32 per-tensor scales; ``None`` falls back to
            dynamic scaling from the live tensor (the policy module passes
            delayed scales here instead).
        block_m, block_n: grid tile extents; ``None`` resolves through
            ``tune.best_config("fp8_matmul", ...)``.
    """
    xs = (dynamic_scale(x, jnp.float8_e4m3fn) if x_scale is None
          else jnp.asarray(x_scale, jnp.float32))
    ws = (dynamic_scale(w, jnp.float8_e4m3fn) if w_scale is None
          else jnp.asarray(w_scale, jnp.float32))
    return _fp8_matmul(x, w, bias, xs, ws, block_m, block_n)
