"""LayerNorm module with a swappable kernel.

Drop-in for ``nnx.LayerNorm`` (same ``scale``/``bias`` param names, so
checkpoint mappings are unchanged) that can route through the fused Pallas
kernel (`jimm_tpu/ops/layer_norm.py`) — one pass over HBM for the backward
instead of XLA's multi-fusion LN bwd."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import nnx

from jimm_tpu.ops.layer_norm import layer_norm
from jimm_tpu.parallel.sharding import logical


class FusedLayerNorm(nnx.Module):
    def __init__(self, dim: int, *, epsilon: float, rngs: nnx.Rngs,
                 dtype=None, param_dtype=jnp.float32):
        self.epsilon = epsilon
        self.dtype = dtype
        self.scale = nnx.Param(
            logical(nnx.initializers.ones_init(), "embed")(
                rngs.params(), (dim,), param_dtype))
        self.bias = nnx.Param(
            logical(nnx.initializers.zeros_init(), "embed")(
                rngs.params(), (dim,), param_dtype))

    def __call__(self, x: jax.Array) -> jax.Array:
        shape = x.shape
        dtype = self.dtype or x.dtype
        x2 = x.reshape(-1, shape[-1]).astype(dtype)
        out = layer_norm(x2, self.scale[...].astype(dtype),
                         self.bias[...].astype(dtype), self.epsilon)
        return out.reshape(shape)
