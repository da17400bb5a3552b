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
load ``tokens * top_k * held / num_experts``: the first chunk always runs, as
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

from jimm_tpu.configs import TransformerConfig
from jimm_tpu.ops.activations import get_activation
from jimm_tpu.parallel.sharding import logical


class RouterBias(nnx.Variable):
    """The router's selection-only bias: no gradient, no optimizer state;
    `Kanana.update_router_bias` moves it after each step."""


def chunk_rows(tokens: int, top_k: int, held: int, num_experts: int) -> int:
    """Rows of one chunk of the grouped products: 4/3 of the expected load,
    a multiple of 512, at most every assignment."""
    expected = tokens * top_k * held / num_experts
    rows = math.ceil(expected * 4 / 3 / 512) * 512
    return min(rows, tokens * top_k)


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
#: Mosaic kernels) 6.12, the default 128^3 tiling 36.7 (PERF.md, PR 32)
_GMM_TILING = (512, 1024, 768)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array
                   ) -> jax.Array:
    """``lhs[rows of group g] @ rhs[g]`` for rows sorted by group (megablox
    ``gmm``: its tiles follow the groups' actual sizes). The rows past the
    last group form one more group that ``rhs`` has no weights for, which the
    kernel leaves out: what it returns there is not defined, and the caller
    masks it."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    rows = lhs.shape[0]
    rest = rows - jnp.sum(sizes, keepdims=True)
    tile_m, tile_k, tile_n = _GMM_TILING
    if rows % tile_m:
        tile_m = rows
    if lhs.dtype.itemsize > 2:  # float32 tiles of that size overflow VMEM
        tile_k //= 2
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
            static = (m.top_k, self.act, rows)
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
    top_k, act, rows = static
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
        h = act(grouped_matmul(xs, gate, sizes)) \
            * grouped_matmul(xs, up, sizes)
        ys = grouped_matmul(h, down, sizes)
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


def _overflow_bwd(static, residuals, g):
    order, ends, operands = residuals
    grads = jax.lax.cond(
        ends[-1] > static[2],
        lambda: jax.vjp(partial(_later_chunks, static, order, ends),
                        *operands)[1](g),
        lambda: tuple(jnp.zeros_like(o) for o in operands))
    return (None, None, *grads)


_overflow.defvjp(_overflow_fwd, _overflow_bwd)
