"""The Mamba-2 mixer (Dao & Gu, arXiv:2405.21060), as granite-4.0-h's
state-space layers have it. ``H`` heads of ``P`` channels (``d_inner = H
P``), ``B`` and ``C`` ``N`` wide in ``G`` groups of heads:

    [z | xBC | dt] = u W_in                  no bias
    xBC = SiLU(conv(xBC) + b_conv)           causal, depthwise, ``taps`` wide
    x, B, C = split(xBC, [H P, G N, G N])
    dt_h = softplus(dt_h + dt_bias_h);  A_h = -exp(A_log_h)
    S_t,h = exp(dt_t,h A_h) S_t-1,h + dt_t,h x_t,h B_t^T     S in R^(P x N)
    y_t,h = S_t,h C_t + D_h x_t,h
    out = RMSNorm_(H P)(y * SiLU(z)) W_out   gated before it normalises

No position signal: the convolution and the recurrence carry the order. The
recurrence is `ops/ssd.py`'s `chunk_ssd` (scope ``ssm_scan``); what stands
before it is ``ssm_proj``, the ``D`` term, the gated norm and ``W_out``
``ssm_out``, all inside ``ssm``. Training only: the state cache of generation
and a reset of the state at a document boundary are not built.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax import nnx
from jax.ad_checkpoint import checkpoint_name

from jimm_tpu.configs import TransformerConfig
from jimm_tpu.nn.kda import causal_conv
from jimm_tpu.ops.ssd import chunk_ssd
from jimm_tpu.parallel.sharding import logical


class Mamba2(nnx.Module):
    def __init__(self, cfg: TransformerConfig, rngs: nnx.Rngs, *,
                 dtype=None, param_dtype=jnp.float32):
        m = cfg.mamba
        self.heads, self.head_dim, self.state, self.groups, self.chunk = \
            m.num_heads, m.head_dim, m.state, m.groups, m.chunk
        self.eps = cfg.ln_eps
        self.dtype = dtype
        inner = m.num_heads * m.head_dim
        self.conv_dim = inner + 2 * m.groups * m.state

        def lin(din, dout, names):
            return nnx.Linear(
                din, dout, use_bias=False, dtype=dtype,
                param_dtype=param_dtype,
                kernel_init=logical(nnx.initializers.normal(0.02), *names),
                rngs=rngs)

        def conv_start(shape, names):
            # a depthwise Conv1d's default start, taps and bias alike:
            # U(-1, 1) / sqrt(taps)
            bound = 1 / math.sqrt(m.conv_taps)
            return nnx.Param(logical(
                lambda key, shape, dt: jax.random.uniform(
                    key, shape, dt, -bound, bound), *names)(
                        rngs.params(), shape, param_dtype))

        self.in_proj = lin(cfg.width, inner + self.conv_dim + m.num_heads,
                           ("embed", "heads"))
        self.conv = conv_start((m.conv_taps, self.conv_dim), (None, "heads"))
        self.conv_bias = conv_start((self.conv_dim,), ("heads",))
        # exp(A_log) = 1 .. H: head h forgets h times as fast as head 1
        self.A_log = nnx.Param(jnp.log(jnp.arange(
            1, m.num_heads + 1, dtype=jnp.float32)).astype(param_dtype))
        self.dt_bias = nnx.Param(jnp.ones((m.num_heads,), param_dtype))
        self.D = nnx.Param(jnp.ones((m.num_heads,), param_dtype))
        self.norm = nnx.Param(jnp.ones((inner,), param_dtype))
        self.out_proj = lin(inner, cfg.width, ("heads", "embed"))

    def __call__(self, u: jax.Array, mask: jax.Array | None = None,
                 rope: tuple[jax.Array, jax.Array] | None = None
                 ) -> jax.Array:
        """``rope`` is not read (the layer takes no position signal); a
        ``mask`` has no meaning for a recurrence and is refused."""
        if mask is not None:
            raise ValueError("a Mamba-2 layer takes no attention mask")
        b, s, _ = u.shape
        h, p, g, n = self.heads, self.head_dim, self.groups, self.state
        inner = h * p
        f32 = jnp.float32
        dtype = self.dtype or u.dtype
        with jax.named_scope("ssm"):
            with jax.named_scope("ssm_proj"):
                zxd = self.in_proj(u)
                z, xbc, dt = (zxd[..., :inner],
                              zxd[..., inner:inner + self.conv_dim],
                              zxd[..., inner + self.conv_dim:])
                xbc = jax.nn.silu(
                    causal_conv(xbc.astype(f32), self.conv[...].astype(f32))
                    + self.conv_bias[...].astype(f32))
                x = xbc[..., :inner].reshape(b, s, h, p)
                B = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
                C = xbc[..., inner + g * n:].reshape(b, s, g, n)
                dt = jax.nn.softplus(dt.astype(f32)
                                     + self.dt_bias[...].astype(f32))
                A = -jnp.exp(self.A_log[...].astype(f32))
            with jax.named_scope("ssm_scan"):
                # x, B, C go in and y comes out in the model's dtype, dt and A
                # in float32 (float32 inside). ``ssm_y``: kept by the remat
                # policies that keep the scan's states, so the backward needs
                # no second scan
                y = checkpoint_name(chunk_ssd(
                    x.astype(dtype), dt, A, B.astype(dtype), C.astype(dtype),
                    chunk=self.chunk).astype(dtype), "ssm_y")
            with jax.named_scope("ssm_out"):
                y = y.astype(f32) + self.D[...].astype(f32)[:, None] * x
                y = y.reshape(b, s, inner) * jax.nn.silu(z.astype(f32))
                y = y * jax.lax.rsqrt(
                    jnp.mean(y * y, axis=-1, keepdims=True) + self.eps) \
                    * self.norm[...].astype(f32)
                return self.out_proj(y.astype(dtype))
