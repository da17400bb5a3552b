"""Pallas TPU fused LayerNorm (forward + custom-VJP backward).

XLA's LayerNorm backward materializes several row-stat intermediates (an
early SigLIP train-step profile had it at ~340 GB/s; the benchmark has no
reading of either path, PERF.md). This kernel computes dx and the dscale/dbias
row-partials in ONE pass over (rows, features) tiles: each tensor is read
exactly once.

Shape robustness: every BlockSpec dimension is a multiple of the Mosaic
tile (sublanes x 128 lanes) — features are zero-padded up to the lane
multiple with the statistics masked to the real width, rows are padded to a
sublane-aligned block multiple, and the per-row mean/rstd are stored
lane-broadcast like the flash-attention stats. Nothing relies on the
"block equals array" escape hatch, which older kernels leaned on and which
stricter Mosaic versions reject (``ln_impl="fused"`` once failed to compile
for SigLIP-B/16-256 that way).

The row-block size resolves through `jimm_tpu.tune.best_config` when not
given explicitly: a tuned value if the persistent cache has one for this
(shape, dtype, backend), else ``DEFAULT_BLOCK_ROWS`` — lookup only, never
a measurement (docs/tuning.md).

Semantics match ``flax.nnx.LayerNorm`` (biased variance over the feature
axis, fp32 statistics, ``(x - mean) * rsqrt(var + eps) * scale + bias``),
verified to ~1e-5 in `tests/test_layer_norm.py` — including feature dims
not divisible by 128 and row counts not divisible by 8. Off-TPU the
kernels run in the Pallas interpreter so CPU tests exercise the same code
path.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 256
_LANES = 128
_SUBLANES = 8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _fwd_kernel(x_ref, g_ref, b_ref, o_ref, mu_ref, rstd_ref, *, eps: float,
                f_real: int):
    x = x_ref[...].astype(jnp.float32)              # (br, fp), tail cols 0
    fp = x.shape[1]
    # padded feature columns arrive zeroed from the host, so the raw sum is
    # already exact; the centered tail (0 - mu) must be masked before the
    # variance or every pad lane would contribute mu^2
    mu = jnp.sum(x, axis=1) / f_real
    xc = x - mu[:, None]
    if f_real != fp:
        in_f = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < f_real
        xc = jnp.where(in_f, xc, 0.0)
    var = jnp.sum(xc * xc, axis=1) / f_real
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd[:, None]
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] = (xhat * g[None, :] + b[None, :]).astype(o_ref.dtype)
    # stats are lane-broadcast (like flash attention's m/l) so their blocks
    # are full Mosaic tiles instead of (br, 1) lane slivers
    mu_ref[...] = jnp.broadcast_to(mu[:, None], mu_ref.shape)
    rstd_ref[...] = jnp.broadcast_to(rstd[:, None], rstd_ref.shape)


def _bwd_kernel(x_ref, g_ref, mu_ref, rstd_ref, do_ref, dx_ref, dg_ref,
                db_ref, *, f_real: int):
    x = x_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)            # tail cols/rows 0
    # all lanes equal -> max is an exact lane collapse
    mu = jnp.max(mu_ref[...], axis=1, keepdims=True)
    rstd = jnp.max(rstd_ref[...], axis=1, keepdims=True)
    xhat = (x - mu) * rstd
    if f_real != x.shape[1]:
        # pad cols hold x=0 so xhat=-mu*rstd there; zero them so the m2
        # moment and the dscale partial only see real features (do is
        # already zero in the tail, belt and suspenders for m2's product)
        in_f = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < f_real
        xhat = jnp.where(in_f, xhat, 0.0)
    g = g_ref[...].astype(jnp.float32)
    dy = do * g[None, :]
    m1 = jnp.sum(dy, axis=1, keepdims=True) / f_real
    m2 = jnp.sum(dy * xhat, axis=1, keepdims=True) / f_real
    dx_ref[...] = (rstd * (dy - m1 - xhat * m2)).astype(dx_ref.dtype)
    # dscale/dbias accumulate into ONE (8, fp) block revisited by every grid
    # step (TPU grids run sequentially, so read-modify-write is ordered).
    # Mosaic requires the sublane dim divisible by 8, so the partial lives
    # in row 0 of an 8-row block; the wrapper sums the zero rows away.
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    row0 = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, 1), 0) == 0
    dg_ref[...] += jnp.where(row0, jnp.sum(do * xhat, axis=0)[None, :], 0.0)
    db_ref[...] += jnp.where(row0, jnp.sum(do, axis=0)[None, :], 0.0)


def _pad2(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    return x if pr == 0 and pc == 0 else jnp.pad(x, ((0, pr), (0, pc)))


def _pad1(v: jax.Array, cols: int) -> jax.Array:
    pc = cols - v.shape[0]
    return v if pc == 0 else jnp.pad(v, ((0, pc),))


def _sublanes(*dtypes) -> int:
    """Row-block alignment: 16 when any 16-bit operand is in play (bf16
    Mosaic tiles are (16, 128)), else the fp32 minimum of 8."""
    if any(jnp.dtype(d).itemsize == 2 for d in dtypes):
        return 16
    return _SUBLANES


def _rows_blocks(n_rows: int, block_rows: int,
                 sublanes: int = _SUBLANES) -> tuple[int, int, int]:
    """(block_rows, n_blocks, padded_rows): the row block is clamped to the
    (sublane-aligned) row count and rounded UP to a sublane multiple, and
    odd row counts are PADDED to a block multiple (padded rows normalize
    garbage-but-finite values the wrappers slice off; zero-padded ``do``
    rows contribute nothing to the dscale/dbias partial sums) rather than
    shrinking the tile — a (1, F) tile per row would be orders of magnitude
    slower."""
    br = min(block_rows, _ceil_to(n_rows, sublanes))
    br = max(sublanes, _ceil_to(br, sublanes))
    padded = _ceil_to(n_rows, br)
    return br, padded // br, padded


def _resolve_block_rows(shape: tuple[int, ...], dtype,
                        block_rows: int | None) -> int:
    """Trace-time (host-side) block resolution through the tune cache —
    lookup only, never a measurement. Explicit ``block_rows`` wins (the
    tuner's own bench closures pass it, so tuning cannot recurse)."""
    if block_rows is not None:
        return int(block_rows)
    from jimm_tpu.tune import best_config
    cfg = best_config("layer_norm", (tuple(shape),), (dtype,),
                      default={"block_rows": DEFAULT_BLOCK_ROWS})
    return int(cfg["block_rows"])


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-6,
               block_rows: int | None = None) -> jax.Array:
    """Fused LayerNorm over the last axis of ``(rows, features)`` input."""
    o, _ = _ln_fwd(x, scale, bias, eps, block_rows)
    return o


def _ln_fwd_impl(x, scale, bias, eps, block_rows):
    r, f = x.shape
    br = _resolve_block_rows((r, f), x.dtype, block_rows)
    br, n_b, rp = _rows_blocks(r, br, _sublanes(x.dtype))
    fp = _ceil_to(f, _LANES)
    o, mu, rstd = pl.pallas_call(
        partial(_fwd_kernel, eps=eps, f_real=f),
        grid=(n_b,),
        in_specs=[
            pl.BlockSpec((br, fp), lambda i: (i, 0)),
            pl.BlockSpec((fp,), lambda i: (0,)),
            pl.BlockSpec((fp,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((br, fp), lambda i: (i, 0)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, fp), x.dtype),
            jax.ShapeDtypeStruct((rp, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((rp, _LANES), jnp.float32),
        ],
        interpret=_interpret(),
    )(_pad2(x, rp, fp), _pad1(scale, fp), _pad1(bias, fp))
    # stats residuals saved as (r,) — one lane of the broadcast, unpadded
    return o[:r, :f], (x, scale, mu[:r, 0], rstd[:r, 0])


def _ln_fwd(x, scale, bias, eps, block_rows):
    return _ln_fwd_impl(x, scale, bias, eps, block_rows)


def _ln_bwd(eps, block_rows, res, do):
    x, scale, mu, rstd = res
    r, f = x.shape
    br = _resolve_block_rows((r, f), x.dtype, block_rows)
    br, n_b, rp = _rows_blocks(r, br, _sublanes(x.dtype, do.dtype))
    fp = _ceil_to(f, _LANES)
    # zero-padded do rows/cols zero their dscale/dbias contributions; padded
    # dx rows/cols are garbage-but-finite and sliced off
    stats = (rp, _LANES)
    dx, dg_part, db_part = pl.pallas_call(
        partial(_bwd_kernel, f_real=f),
        grid=(n_b,),
        in_specs=[
            pl.BlockSpec((br, fp), lambda i: (i, 0)),
            pl.BlockSpec((fp,), lambda i: (0,)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, fp), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, fp), lambda i: (i, 0)),
            pl.BlockSpec((_SUBLANES, fp), lambda i: (0, 0)),
            pl.BlockSpec((_SUBLANES, fp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, fp), x.dtype),
            jax.ShapeDtypeStruct((_SUBLANES, fp), jnp.float32),
            jax.ShapeDtypeStruct((_SUBLANES, fp), jnp.float32),
        ],
        interpret=_interpret(),
    )(_pad2(x, rp, fp), _pad1(scale, fp),
      _pad2(jnp.broadcast_to(mu[:, None], (r, _LANES)), *stats),
      _pad2(jnp.broadcast_to(rstd[:, None], (r, _LANES)), *stats),
      _pad2(do, rp, fp))
    dg = jnp.sum(dg_part, axis=0)[:f].astype(scale.dtype)
    db = jnp.sum(db_part, axis=0)[:f].astype(scale.dtype)
    return dx[:r, :f], dg, db


layer_norm.defvjp(_ln_fwd, _ln_bwd)
