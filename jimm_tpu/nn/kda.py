"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692): a linear-attention
token mixer whose state is written by a gated delta rule. Per head ``h`` of
``N``, ``d_k = d_v = D``:

    q~, k~, v = SiLU(conv(x W_q)), SiLU(conv(x W_k)), SiLU(conv(x W_v))
        conv: causal, depthwise, ``taps`` wide, no bias:
        y_t = sum_{i < taps} w_i u_{t - taps + 1 + i},  u_{<0} = 0
    q_t = q~_t / ||q~_t||_2 * D^-1/2        k_t = k~_t / ||k~_t||_2
        the norm over a head's D channels
    g_t = -exp(A_log_h) * softplus((x W_f1 W_f2)_t + dt_bias)   in R^D, <= 0
    b_t = sigmoid((x W_b)_t)_h                                  in (0, 1)
    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
        S in R^(D x D), S_0 = 0, float32
    o_t = S_t^T q_t
    y_t = [RMSNorm_D(o_t) * sigmoid((x W_g1 W_g2)_t)] W_o
        the norm per head with one learned D-vector

No position signal, no softmax, no key/value cache that grows: the state is
``N x D x D`` whatever the length. The recurrence is `ops/delta_rule.py`'s
`chunk_kda` (scope ``kda_scan``); what stands before it is ``kda_proj``, what
follows ``kda_out``, all inside ``kda``. Training only: the recurrent-state
cache of generation and a reset of the state at a document boundary are not
built.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax import nnx
from jax.ad_checkpoint import checkpoint_name

from jimm_tpu.configs import TransformerConfig
from jimm_tpu.nn.transformer import _linear
from jimm_tpu.ops.delta_rule import chunk_kda
from jimm_tpu.parallel.sharding import logical

#: under the square root of the L2 norm on q and k
_L2_EPS = 1e-6


def causal_conv(u: jax.Array, taps: jax.Array) -> jax.Array:
    """Depthwise causal convolution over ``(B, S, channels)`` with ``taps
    (width, channels)``: ``y_t = sum_i taps_i u_{t - width + 1 + i}``."""
    width, s = taps.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(taps[i] * padded[:, i:i + s] for i in range(width))


class KimiDeltaAttention(nnx.Module):
    def __init__(self, cfg: TransformerConfig, rngs: nnx.Rngs, *,
                 dtype=None, param_dtype=jnp.float32):
        a = cfg.kda
        self.num_heads, self.head_dim, self.chunk = \
            a.num_heads, a.head_dim, a.chunk
        self.eps = cfg.ln_eps
        self.dtype = dtype
        inner = a.num_heads * a.head_dim

        def lin(din, dout, names):
            return _linear(din, dout, names, rngs, use_bias=False,
                           dtype=dtype, param_dtype=param_dtype)

        def taps():
            # a depthwise Conv1d's default start: U(-1, 1) / sqrt(taps)
            bound = 1 / math.sqrt(a.conv_taps)
            return nnx.Param(logical(
                lambda key, shape, dt: jax.random.uniform(
                    key, shape, dt, -bound, bound), None, "heads")(
                        rngs.params(), (a.conv_taps, inner), param_dtype))

        self.q, self.k, self.v = (lin(cfg.width, inner, ("embed", "heads"))
                                  for _ in range(3))
        self.q_conv, self.k_conv, self.v_conv = taps(), taps(), taps()
        self.f_a = lin(cfg.width, a.gate_rank, ("embed", None))
        self.f_b = lin(a.gate_rank, inner, (None, "heads"))
        self.b = lin(cfg.width, a.num_heads, ("embed", None))
        # exp(A_log) starts at U(1, 16) a head
        self.A_log = nnx.Param(jnp.log(jax.random.uniform(
            rngs.params(), (a.num_heads,), jnp.float32, 1.0, 16.0))
            .astype(param_dtype))
        self.dt_bias = nnx.Param(jnp.zeros((inner,), param_dtype))
        self.g_a = lin(cfg.width, a.gate_rank, ("embed", None))
        self.g_b = lin(a.gate_rank, inner, (None, "heads"))
        self.o_norm = nnx.Param(jnp.ones((a.head_dim,), param_dtype))
        self.out = lin(inner, cfg.width, ("heads", "embed"))

    def __call__(self, x: jax.Array, mask: jax.Array | None = None,
                 rope: tuple[jax.Array, jax.Array] | None = None
                 ) -> jax.Array:
        """``rope`` is not read (the layer takes no position signal); a
        ``mask`` has no meaning for a recurrence and is refused."""
        if mask is not None:
            raise ValueError("Kimi Delta Attention takes no attention mask")
        b, s, _ = x.shape
        n, d = self.num_heads, self.head_dim
        f32 = jnp.float32
        with jax.named_scope("kda"):
            with jax.named_scope("kda_proj"):
                def mixed(proj, taps):
                    y = causal_conv(proj(x).astype(f32), taps[...].astype(f32))
                    return jax.nn.silu(y).reshape(b, s, n, d)

                def unit(t):
                    return t * jax.lax.rsqrt(
                        jnp.sum(t * t, axis=-1, keepdims=True) + _L2_EPS)

                q = unit(mixed(self.q, self.q_conv)) * d ** -0.5
                k = unit(mixed(self.k, self.k_conv))
                v = mixed(self.v, self.v_conv)
                g = -jnp.exp(self.A_log[...].astype(f32))[:, None] \
                    * jax.nn.softplus(
                        (self.f_b(self.f_a(x)).astype(f32)
                         + self.dt_bias[...].astype(f32)).reshape(b, s, n, d))
                beta = jax.nn.sigmoid(self.b(x).astype(f32))
            with jax.named_scope("kda_scan"):
                # q, k, v go in and o comes out in the model's dtype (the
                # scan casts a slab of chunks at a time; float32 inside), the
                # gate in float32. ``kda_o``: kept by the remat policies that
                # keep the scan's states, so the backward needs no second scan
                dtype = self.dtype or x.dtype
                o = checkpoint_name(chunk_kda(
                    q.astype(dtype), k.astype(dtype), v.astype(dtype), g,
                    beta, chunk=self.chunk).astype(dtype), "kda_o")
            with jax.named_scope("kda_out"):
                o = o.astype(f32)
                o = o * jax.lax.rsqrt(
                    jnp.mean(o * o, axis=-1, keepdims=True) + self.eps) \
                    * self.o_norm[...].astype(f32)
                gate = jax.nn.sigmoid(self.g_b(self.g_a(x)).astype(f32))
                y = o.reshape(b, s, n * d) * gate
                return self.out(y.astype(dtype))
