"""Pallas TPU int8 matmul with fused dequant + bias + activation.

The low-precision serving fast path's workhorse: an int8 x int8 -> int32
MXU matmul (``preferred_element_type=jnp.int32`` keeps the product in the
MXU's native int32 accumulator) whose epilogue dequantizes, adds the bias,
and applies the activation inside the same grid cell — one pass over the
output tile, no materialized int32 intermediate in HBM.

Quantization scheme (matches ``jimm_tpu.weights.quantize`` and
``jimm_tpu.quant``): symmetric, zero-point-free. Weights carry one fp32
scale per output channel; activations are quantized dynamically per row
(:func:`quantize_rows`). Dequantization is then a rank-1 rescale of the
int32 accumulator — exactly ``acc * x_scale[:, None] * w_scale[None, :]``
— confined to the :func:`_dequant` helper (the JL012 lint rule bans f32
upcasts anywhere else in quantized ops paths, so a stray ``astype`` can't
silently demote the int8 path back to f32 compute).

Shape robustness follows the LayerNorm rewrite: rows pad to the int8
32-sublane tile, K and N pad to 128 lanes (zero padding contributes zero
to the dot; padded output rows/cols are sliced off by the wrapper). Block
sizes resolve through ``jimm_tpu.tune.best_config`` ("int8_matmul") at
trace time — lookup only; explicit ints win so the tuner's bench closures
cannot recurse. Off-TPU the kernel runs in the Pallas interpreter so CPU
tests and the CPU-tiny serving smoke exercise the same code path.
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
#: int8 Mosaic tiles are (32, 128) — row blocks align to 32 sublanes
_INT8_SUBLANES = 32

_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"))

#: VMEM budget for one grid cell's resident tiles (mirrors the flash /
#: retrieval kernels' budget; sync-tested against tune.space)
_VMEM_BUDGET = 8 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _per_cell_vmem_bytes(block_m: int, block_n: int, k: int) -> int:
    """Resident working set of one (block_m, block_n) grid cell: the int8
    x/w tiles at the 128-padded K, the lane-broadcast row scales, the
    column scale + bias rows, and the int32 accumulator / f32 epilogue / out
    tiles. Mirrored jax-free in ``tune.space.int8_matmul_vmem_bytes``
    (sync-tested)."""
    kp = _ceil_to(k, _LANES)
    return (block_m * kp                  # x_q int8 tile
            + kp * block_n                # w_q int8 tile
            + block_m * _LANES * 4        # lane-broadcast x_scale
            + 2 * block_n * 4             # w_scale + bias
            + 3 * block_m * block_n * 4)  # int32 acc + f32 y + out tile


def _dequant(acc: jax.Array, x_scale: jax.Array,
             w_scale: jax.Array) -> jax.Array:
    """int32 accumulator -> f32 via the symmetric per-row ``(bm,)`` /
    per-column ``(1, bn)`` scales. The ONE sanctioned f32 upcast in this
    kernel (JL012)."""
    return acc.astype(jnp.float32) * x_scale[:, None] * w_scale


def _apply_activation(y: jax.Array, activation: str | None) -> jax.Array:
    if activation is None:
        return y
    if activation == "relu":
        return jnp.maximum(y, 0.0)
    if activation == "gelu":
        return jax.nn.gelu(y, approximate=False)
    raise ValueError(f"unknown fused activation {activation!r}; "
                     f"supported: None, 'relu', 'gelu'")


def _matmul_kernel(xq_ref, xs_ref, wq_ref, ws_ref, b_ref, o_ref, *,
                   activation: str | None):
    acc = jax.lax.dot_general(
        xq_ref[...], wq_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    # x_scale arrives lane-broadcast (block_m, 128) like the flash m/l
    # stats; max is an exact collapse over equal lanes
    x_scale = jnp.max(xs_ref[...], axis=1)
    # w_scale and bias arrive as (1, block_n) rows: Mosaic refuses a rank-1
    # block whose extent is not XLA's 1-D tile (1024 for f32)
    y = _dequant(acc, x_scale, ws_ref[...])
    y = y + b_ref[...]
    o_ref[...] = _apply_activation(y, activation).astype(o_ref.dtype)


def _resolve_blocks(x_shape, w_shape, dtypes, block_m, block_n):
    """Trace-time (host-side) block resolution through the tune cache —
    lookup only, never a measurement. Explicit ints win (the tuner's bench
    closures pass them, so tuning cannot recurse)."""
    if block_m is not None and block_n is not None:
        return int(block_m), int(block_n)
    from jimm_tpu.tune import best_config
    cfg = best_config("int8_matmul", (tuple(x_shape), tuple(w_shape)),
                      tuple(dtypes),
                      default={"block_m": DEFAULT_BLOCK_M,
                               "block_n": DEFAULT_BLOCK_N})
    return (int(block_m if block_m is not None else cfg["block_m"]),
            int(block_n if block_n is not None else cfg["block_n"]))


def _pad2(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    return x if pr == 0 and pc == 0 else jnp.pad(x, ((0, pr), (0, pc)))


def _pad1(v: jax.Array, cols: int) -> jax.Array:
    pc = cols - v.shape[0]
    return v if pc == 0 else jnp.pad(v, ((0, pc),))


def _dequant_operands(x_scale: jax.Array, w_scale: jax.Array,
                      bias: jax.Array | None, mp: int, np_: int):
    """Pad/normalize the f32 dequant-side operands (scales + bias) to the
    grid extents: row scales lane-broadcast to ``(mp, 128)``, column scales
    and bias as ``(1, np_)`` rows. Zero-padded scale rows dequantize padded
    output rows to exact zeros, sliced off by the wrapper."""
    xs = jnp.broadcast_to(
        _pad1(x_scale.astype(jnp.float32), mp)[:, None], (mp, _LANES))
    ws = _pad1(w_scale.astype(jnp.float32), np_)[None, :]
    b = (jnp.zeros((1, np_), jnp.float32) if bias is None
         else _pad1(bias.astype(jnp.float32), np_)[None, :])
    return xs, ws, b


def int8_matmul(x_q: jax.Array, x_scale: jax.Array, w_q: jax.Array,
                w_scale: jax.Array, bias: jax.Array | None = None, *,
                activation: str | None = None,
                block_m: int | None = None, block_n: int | None = None,
                out_dtype=jnp.float32) -> jax.Array:
    """Fused dequantizing matmul: ``(x_q * x_scale[:, None]) @
    (w_q * w_scale[None, :]) + bias`` with an optional fused activation.

    Args:
        x_q: ``(M, K)`` int8 activations (see :func:`quantize_rows`).
        x_scale: ``(M,)`` fp32 per-row activation scales.
        w_q: ``(K, N)`` int8 weights (per-output-channel symmetric).
        w_scale: ``(N,)`` fp32 per-column weight scales.
        bias: optional ``(N,)`` bias added in f32 after dequantization.
        activation: ``None`` / ``"relu"`` / ``"gelu"`` fused epilogue.
        block_m, block_n: grid tile extents; ``None`` resolves through
            ``tune.best_config("int8_matmul", ...)``.
    """
    m, k = x_q.shape
    kw, n = w_q.shape
    if kw != k:
        raise ValueError(f"x_q K {k} != w_q K {kw}")
    bm, bn = _resolve_blocks(x_q.shape, w_q.shape,
                             (x_q.dtype, w_q.dtype), block_m, block_n)
    bm = max(_INT8_SUBLANES,
             min(_ceil_to(bm, _INT8_SUBLANES), _ceil_to(m, _INT8_SUBLANES)))
    bn = max(_LANES, min(_ceil_to(bn, _LANES), _ceil_to(n, _LANES)))
    mp, np_, kp = _ceil_to(m, bm), _ceil_to(n, bn), _ceil_to(k, _LANES)
    # zero K-padding contributes zero products to the int8 dot
    xs, ws, b = _dequant_operands(x_scale, w_scale, bias, mp, np_)
    out = pl.pallas_call(
        partial(_matmul_kernel, activation=activation),
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, _LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        compiler_params=_SEMANTICS,
        interpret=_interpret(),
    )(_pad2(x_q, mp, kp), xs, _pad2(w_q, kp, np_), ws, b)
    return out[:m, :n]


def quantize_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Dynamic symmetric per-row int8 activation quantization:
    ``(x_q int8, scale f32)`` with ``scale = max|row| / 127`` (1.0 for
    all-zero rows, so dequantization stays finite)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    x_q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return x_q.astype(jnp.int8), scale


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _quantized_linear(x, w_q, w_scale, bias, activation, block_m, block_n):
    x_q, x_scale = quantize_rows(x)
    return int8_matmul(x_q, x_scale, w_q, w_scale, bias,
                       activation=activation, block_m=block_m,
                       block_n=block_n)


def _quantized_linear_fwd(x, w_q, w_scale, bias, activation, block_m,
                          block_n):
    y = _quantized_linear(x, w_q, w_scale, bias, activation, block_m,
                          block_n)
    # zero-size sentinels carry the primal dtypes (dtype objects are not
    # valid pytree leaves for traced residuals)
    return y, (w_q, w_scale, jnp.zeros((0,), x.dtype),
               None if bias is None else jnp.zeros((0,), bias.dtype))


def _quantized_linear_bwd(activation, block_m, block_n, res, dy):
    if activation is not None:
        raise NotImplementedError(
            "gradients through a fused int8 activation epilogue are not "
            "supported; run with activation=None when training")
    w_q, w_scale, x_sent, b_sent = res
    # straight-through past the per-row activation quantizer: dx contracts
    # the incoming gradient against the dequantized frozen weights in full
    # precision (the serving fast path trains nothing at int8 — fp8 is the
    # training format, ops/fp8_matmul.py)
    w_deq = w_q.astype(jnp.float32) * w_scale[None, :].astype(jnp.float32)
    dx = (dy.astype(jnp.float32) @ w_deq.T).astype(x_sent.dtype)
    # int8 weights + their scales are quantization artifacts, not trainable
    # parameters — zero gradient keeps an optimizer from mutating them
    dbias = (None if b_sent is None
             else jnp.sum(dy.astype(jnp.float32), axis=0)
             .astype(b_sent.dtype))
    return dx, jnp.zeros_like(w_q), jnp.zeros_like(w_scale), dbias


_quantized_linear.defvjp(_quantized_linear_fwd, _quantized_linear_bwd)


def quantized_linear(x: jax.Array, w_q: jax.Array, w_scale: jax.Array,
                     bias: jax.Array | None = None, *,
                     activation: str | None = None,
                     block_m: int | None = None,
                     block_n: int | None = None) -> jax.Array:
    """One W8A8 linear layer over float ``(M, K)`` input: quantize the
    activations per row, run the fused kernel, return f32 output.

    Differentiable (``activation=None`` only): the backward is the
    straight-through estimator — ``dx = dy @ dequant(w_q).T`` in f32, cast
    back to ``x.dtype``; the int8 weights and their scales receive zero
    gradient (they are frozen quantization artifacts)."""
    return _quantized_linear(x, w_q, w_scale, bias, activation, block_m,
                             block_n)
