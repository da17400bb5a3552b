"""A sparse expert layer as one chip of an expert-parallel group runs it
(DeepSeek-V3's ``noaux_tc`` router with always-on shared experts).

    s   = sigmoid(x W_r)                      float32, over ALL num_experts
    top = top_k(s + b)                        b: selection only, no gradient
    w_e = scale * s_e / (sum_{e in top} s_e + 1e-20)
    y   = sum_{e in top, e held here} w_e * D_e(silu(G_e x) * U_e x)
          + Shared(x)                         one SwiGLU, shared_experts wide

The router is whole and the weights are normalised over all ``top_k`` chosen
experts; the sum runs over the chosen experts this chip holds (``first_expert
.. first_expert + held_experts``). What the other chips' experts would add is
their part of the layer's result: on one chip the layer runs without the
exchange, and nothing stands in for it.

No assignment is dropped. The (token, slot) assignments are sorted by held
expert (the others sort to the end), and the held ones go through the three
grouped products in chunks of ``chunk_rows`` rows, four thirds of the expected
load ``tokens * top_k * held / num_experts`` and no fewer than 4096: the first chunk always runs, as
straight-line code; the later ones sit behind `_overflow`, a loop that runs
only the chunks that start before the last held assignment and, with a
derivative rule of its own, costs nothing forward or backward while the first
chunk holds them all. So every shape is static, the work follows the actual
counts, a balanced router pays for one chunk and a router that sends every
token to held experts for all of them.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from flax import nnx
from jax.ad_checkpoint import checkpoint_name

from jimm_tpu.configs import TransformerConfig
from jimm_tpu.ops.activations import get_activation
from jimm_tpu.parallel.sharding import logical


class RouterBias(nnx.Variable):
    """The router's selection-only bias: no gradient, no optimizer state;
    `Kanana.update_router_bias` moves it after each step."""


#: a chunk's floor. A third over the expected load holds a large load (16,384
#: rows hold 12,288 expected ones whatever the seed), not a small one: a router
#: at its random start sent 400-2,300 assignments a layer to 8 held experts
#: that expect 1,024, and every step that ran a second chunk was 3 % longer,
#: which spread five seeds' throughput by 3.6 % (PERF.md, PR 34). A chunk's
#: rows cost a gather, two masks and a scatter; its matmul tiles follow the
#: actual counts, so room costs little
_MIN_CHUNK_ROWS = 4096


def chunk_rows(tokens: int, top_k: int, held: int, num_experts: int) -> int:
    """Rows of one chunk of the grouped products: 4/3 of the expected load
    and no fewer than `_MIN_CHUNK_ROWS`, a multiple of 512, at most every
    assignment."""
    expected = tokens * top_k * held / num_experts
    rows = math.ceil(max(expected * 4 / 3, _MIN_CHUNK_ROWS) / 512) * 512
    return min(rows, tokens * top_k)


def row_tile(tokens: int, top_k: int, num_experts: int) -> int:
    """The grouped products' tile of rows: every group's rows end in a tile
    of their own, so no tile larger than a group is expected to be, between
    the MXU's 128 and `_GMM_TILING`'s 512."""
    tile = _GMM_TILING[0]
    while tile > 128 and tile > tokens * top_k // num_experts:
        tile //= 2
    return tile


def routing_counts(chosen: jax.Array, num_experts: int) -> jax.Array:
    """How many assignments each expert drew: ``chosen (..., T, top_k)``
    expert ids -> ``(..., num_experts)`` int32."""
    flat = chosen.reshape(-1, chosen.shape[-2] * chosen.shape[-1])
    counts = jax.vmap(lambda ids: jnp.zeros((num_experts,), jnp.int32)
                      .at[ids].add(1))(flat)
    return counts.reshape(*chosen.shape[:-2], num_experts)


#: megablox tile sizes (rows, contraction, columns), the fastest of five
#: tried on the v5e at (16384, 2048) x (16, 2048, 768) and back: 4.67 ms for
#: the three products forward and backward, `jax.lax.ragged_dot` (XLA's own
#: Mosaic kernels) 6.12, the default 128^3 tiling 36.7 (PERF.md, PR 32). The
#: tile of rows follows the groups (`row_tile`): every group's rows end
#: in a tile of their own, so 8 groups of 128 rows each of (1536, 3072) x
#: (8, 3072, 3072) take 4.36 ms in tiles of 512 rows, 2.58 at 256 and 2.16 at
#: 128, where reading the weights alone takes 1.66 (PERF.md, PR 34)
_GMM_TILING = (512, 1024, 768)

#: where the groups are as small as the MXU's 128 rows the products are bound
#: by reading the experts' weights, and a group larger than expected reads
#: them once more for each further tile of rows: with the whole contraction
#: in one tile (up to 3072) an expert's weights stay in VMEM over its row
#: tiles. One layer's three products forward and backward over 8 experts of
#: 3072 x 3072, at 1,024 | 1,660 | 3,300 rows: 2.73 | 4.1-4.3 | 5.83 ms
#: against `_GMM_TILING`'s contraction and columns 2.75 | 5.2 | 7.97, so a
#: row costs 1.4 us instead of 2.3 (PERF.md, PR 34)
_SMALL_GROUP_TILING = (128, 3072, 512)


def tiling(tile_m: int, itemsize: int) -> tuple[int, int, int]:
    """The grouped products' tiles for rows in tiles of ``tile_m``."""
    tile_k, tile_n = (_SMALL_GROUP_TILING if tile_m == _SMALL_GROUP_TILING[0]
                      else _GMM_TILING)[1:]
    if itemsize > 2:  # float32 tiles of that size overflow VMEM
        tile_k //= 2
    return tile_m, tile_k, tile_n


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array,
                   tile_m: int | None = None) -> jax.Array:
    """``lhs[rows of group g] @ rhs[g]`` for rows sorted by group (megablox
    ``gmm``: its tiles follow the groups' actual sizes). The rows past the
    last group form one more group that ``rhs`` has no weights for, which the
    kernel leaves out: what it returns there is not defined, and the caller
    masks it."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    rows = lhs.shape[0]
    rest = rows - jnp.sum(sizes, keepdims=True)
    tile_m, tile_k, tile_n = tiling(tile_m or _GMM_TILING[0],
                                    lhs.dtype.itemsize)
    if rows % tile_m:
        tile_m = rows
    return gmm(lhs, rhs, jnp.concatenate([sizes, rest]).astype(jnp.int32),
               lhs.dtype, (tile_m, tile_k, tile_n),
               interpret=jax.default_backend() != "tpu")


class SparseMoe(nnx.Module):
    def __init__(self, cfg: TransformerConfig, rngs: nnx.Rngs, *,
                 dtype=None, param_dtype=jnp.float32):
        from jimm_tpu.nn.transformer import Mlp
        m = cfg.moe
        if not 0 <= m.first_expert <= m.num_experts - m.held_experts:
            raise ValueError(f"experts {m.first_expert}.."
                             f"{m.first_expert + m.held_experts} are not "
                             f"among the router's {m.num_experts}")
        self.moe = m
        self.dtype = dtype
        self.act = get_activation(cfg.act)
        per_expert = jax.nn.initializers.variance_scaling(
            1.0, "fan_avg", "uniform", in_axis=-2, out_axis=-1,
            batch_axis=(0,))

        def experts(din, dout, names):
            return nnx.Param(logical(per_expert, "expert", *names)(
                rngs.params(), (m.held_experts, din, dout), param_dtype))

        self.router = nnx.Param(logical(
            nnx.initializers.xavier_uniform(), "embed", None)(
                rngs.params(), (cfg.width, m.num_experts), param_dtype))
        self.router_bias = RouterBias(jnp.zeros((m.num_experts,), jnp.float32))
        self.gate = experts(cfg.width, m.expert_dim, ("embed", "mlp"))
        self.up = experts(cfg.width, m.expert_dim, ("embed", "mlp"))
        self.down = experts(m.expert_dim, cfg.width, ("mlp", "embed"))
        self.shared = Mlp(cfg.width, m.shared_experts * m.expert_dim, cfg.act,
                          rngs, gated=True, use_bias=False, dtype=dtype,
                          param_dtype=param_dtype)

    def route(self, xt: jax.Array) -> tuple[jax.Array, jax.Array]:
        """``(chosen experts (T, top_k) int32, their weights (T, top_k)
        float32)`` for tokens ``xt (T, width)``."""
        m = self.moe
        scores = jax.nn.sigmoid(jnp.dot(
            xt.astype(jnp.float32), self.router[...].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(self.router_bias[...]), m.top_k)
        # kept under every remat policy (`Transformer._remat_policy`): a
        # recomputed forward rounds its own way and breaks the router's
        # near-ties differently (1 % of the choices), and the backward would
        # then run other routes than the forward whose loss it differentiates
        chosen = checkpoint_name(chosen, "moe_chosen")
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = m.routed_scale * picked / (
            jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        return chosen, weights

    def __call__(self, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        """``(y, chosen)``: the layer's result on ``x (B, S, width)`` and the
        ``top_k`` experts each token chose, ``(B * S, top_k)`` int32 ids among
        all ``num_experts``."""
        m = self.moe
        dtype = self.dtype or x.dtype
        tokens = x.shape[0] * x.shape[1]
        xt = x.reshape(tokens, -1).astype(dtype)
        rows = chunk_rows(tokens, m.top_k, m.held_experts, m.num_experts)
        with jax.named_scope("moe"):
            with jax.named_scope("moe_route"):
                chosen, weights = self.route(xt)
                counts = routing_counts(chosen, m.num_experts)
                local = chosen.reshape(-1) - m.first_expert
                held = (local >= 0) & (local < m.held_experts)
                # assignments by held expert; the others sort to the end
                order = jnp.argsort(jnp.where(held, local, m.held_experts))
                order = jnp.pad(order, (0, -order.shape[0] % rows))
                ends = jnp.cumsum(jax.lax.dynamic_slice_in_dim(
                    counts, m.first_expert, m.held_experts))
            static = (m.top_k, self.act, rows,
                      row_tile(tokens, m.top_k, m.num_experts))
            operands = (order, ends, xt, weights.reshape(-1),
                        *(p[...].astype(dtype)
                          for p in (self.gate, self.up, self.down)))
            # a balanced router's held assignments fit the first chunk; what
            # is left over goes through `_overflow`, which costs nothing
            # while there is none
            routed = _chunk(static, 0, *operands)
            if order.shape[0] > rows:
                routed = routed + _overflow(static, *operands)
            with jax.named_scope("moe_shared"):
                y = routed.astype(dtype).reshape(x.shape) + self.shared(x)
        return y, chosen


def _chunk(static, lo, order, ends, xt, flat_weights, gate, up, down):
    """What the held assignments ``lo .. lo + rows`` of the sorted ``order``
    add to the layer's result, ``(tokens, width)`` float32: gather, the three
    grouped products, weighted scatter."""
    top_k, act, rows, tile_m = static
    with jax.named_scope("moe_route"):
        sel = jax.lax.dynamic_slice_in_dim(order, lo, rows)
        tok = sel // top_k
        live = lo + jnp.arange(rows) < ends[-1]
        starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
        sizes = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)
        # masked going in and coming out: what a grouped product gives a row
        # of no group, forward or backward, must reach no token
        xs = jnp.where(live[:, None], xt[tok], 0)
    with jax.named_scope("moe_experts"):
        h = act(grouped_matmul(xs, gate, sizes, tile_m)) \
            * grouped_matmul(xs, up, sizes, tile_m)
        ys = grouped_matmul(h, down, sizes, tile_m)
    with jax.named_scope("moe_route"):
        w = jnp.where(live, flat_weights[sel], 0.0)
        ys = jnp.where(live[:, None], ys.astype(jnp.float32) * w[:, None], 0.0)
        return jnp.zeros(xt.shape, jnp.float32).at[tok].add(ys)


def _later_chunks(static, order, ends, *operands):
    """`_chunk` summed over the chunks after the first, each skipped where it
    starts past the last held assignment."""
    rows = static[2]

    def one(total, lo):
        return jax.lax.cond(
            lo < ends[-1],
            lambda: total + _chunk(static, lo, order, ends, *operands),
            lambda: total), None

    return jax.lax.scan(one, jnp.zeros(operands[0].shape, jnp.float32),
                        jnp.arange(rows, order.shape[0], rows))[0]


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _overflow(static, order, ends, *operands):
    """`_later_chunks` where the held assignments overflow the first chunk,
    zeros (and no work, forward or backward) where they do not. Its own
    derivative rule, so that the usual case carries no residuals of the loop:
    the backward runs the loop again where it ran at all."""
    return jax.lax.cond(
        ends[-1] > static[2],
        lambda: _later_chunks(static, order, ends, *operands),
        lambda: jnp.zeros(operands[0].shape, jnp.float32))


def _overflow_fwd(static, order, ends, *operands):
    return _overflow(static, order, ends, *operands), (order, ends, operands)


#: `_overflow`'s backward differentiates `_later_chunks` as it stands while
#: the copies of the expert weights that keeps, one a later chunk (the loop's
#: residuals: a `cond` inside a `scan` hands its operands on per iteration),
#: stay under this; over it, it walks the chunks itself (`_later_chunks_bwd`).
#: XLA plans a branch's buffers whether or not the branch ever runs: 16
#: experts of 2048 x 768 in 5 later chunks are 0.75 GB of plan, 8 of 3072 x
#: 3072 in 21 are 9.1 GB, more than a 16 GB chip has left beside 1.6 B
#: parameters' state (PERF.md, PR 34)
_LOOP_RESIDUAL_LIMIT = 1 << 30


def _later_chunks_bwd(static, order, ends, operands, g):
    """The cotangents `_later_chunks` hands its ``operands`` for ``g``, one
    chunk at a time: each chunk that ran is run again and differentiated
    inside the loop, so nothing is kept from one chunk to the next but the
    sums."""
    rows = static[2]

    def one(sums, lo):
        def add():
            grads = jax.vjp(lambda *ops: _chunk(static, lo, order, ends, *ops),
                            *operands)[1](g)
            return tuple(s + d for s, d in zip(sums, grads, strict=True))
        return jax.lax.cond(lo < ends[-1], add, lambda: sums), None

    return jax.lax.scan(one, tuple(jnp.zeros_like(o) for o in operands),
                        jnp.arange(rows, order.shape[0], rows))[0]


def _overflow_bwd(static, residuals, g):
    order, ends, operands = residuals
    later = len(range(static[2], order.shape[0], static[2]))
    kept = later * sum(o.size * o.dtype.itemsize for o in operands[2:])
    if kept > _LOOP_RESIDUAL_LIMIT:
        def ran():
            return _later_chunks_bwd(static, order, ends, operands, g)
    else:
        def ran():
            return jax.vjp(partial(_later_chunks, static, order, ends),
                           *operands)[1](g)
    grads = jax.lax.cond(
        ends[-1] > static[2], ran,
        lambda: tuple(jnp.zeros_like(o) for o in operands))
    return (None, None, *grads)


_overflow.defvjp(_overflow_fwd, _overflow_bwd)
