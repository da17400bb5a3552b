"""Latent attention (MLA, DeepSeek-V2/V3): keys and values are rebuilt from
one low-rank latent per token, and position lives in a rotary part kept
apart from it.

    q          = x W_q            -> (B, S, N, d_n + d_r) = [q_n | q_r]
    [c | k_r]  = x W_kva          -> r + d_r      (ONE rotary key for all heads)
    c          = RMS_r(c)
    [k_n | v]  = c W_kvb          -> (B, S, N, d_n + d_v)
    q_r, k_r   = rotary(q_r), rotary(k_r)         pairs (2i, 2i + 1); with no
                                                  rope tables: left as they are
    k          = [k_n | k_r broadcast over the heads]
    o          = softmax(causal(q k^T / sqrt(d_n + d_r))) v
    out        = o W_o            (N * d_v -> width)

q and k are ``d_n + d_r`` wide and v ``d_v``: `ops/attention.py` takes the two
widths as they are (the tiled flash kernels keep v, o and their gradients at
v's own tile). Training only: the latent cache and the absorbed decode path
(W_kvb folded into q and o) are not built.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import nnx

from jimm_tpu.configs import TransformerConfig
from jimm_tpu.nn.transformer import _linear
from jimm_tpu.ops.attention import dot_product_attention
from jimm_tpu.parallel.sharding import logical


def apply_rope_pairs(x: jax.Array, rope: tuple[jax.Array, jax.Array]
                     ) -> jax.Array:
    """Rotate ``(B, S, N, D)``: the interleaved pairing, element ``2i`` with
    ``2i + 1`` by ``t * theta**(-2i / D)`` (`rope_tables`), in float32, back
    in ``x``'s dtype."""
    cos, sin = (t[None, :, None, :] for t in rope)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class LatentAttention(nnx.Module):
    def __init__(self, cfg: TransformerConfig, rngs: nnx.Rngs, *,
                 dtype=None, param_dtype=jnp.float32):
        m = cfg.mla
        self.num_heads = cfg.num_heads
        self.dims = (m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim)
        self.rank = m.kv_lora_rank
        self.impl = cfg.attn_impl
        n, (d_n, d_r, d_v) = cfg.num_heads, self.dims

        def lin(din, dout, names):
            return _linear(din, dout, names, rngs, use_bias=False,
                           dtype=dtype, param_dtype=param_dtype)

        self.q = lin(cfg.width, n * (d_n + d_r), ("embed", "heads"))
        self.kv_a = lin(cfg.width, m.kv_lora_rank + d_r, ("embed", None))
        self.kv_norm = nnx.RMSNorm(
            m.kv_lora_rank, epsilon=cfg.ln_eps, dtype=dtype,
            param_dtype=param_dtype,
            scale_init=logical(nnx.initializers.ones_init(), None), rngs=rngs)
        self.kv_b = lin(m.kv_lora_rank, n * (d_n + d_v), (None, "heads"))
        self.out = lin(n * d_v, cfg.width, ("heads", "embed"))

    def __call__(self, x: jax.Array, mask: jax.Array | None = None,
                 rope: tuple[jax.Array, jax.Array] | None = None
                 ) -> jax.Array:
        b, s, _ = x.shape
        n, (d_n, d_r, d_v) = self.num_heads, self.dims
        with jax.named_scope("mla"):
            q = self.q(x).reshape(b, s, n, d_n + d_r)
            latent, k_r = jnp.split(self.kv_a(x), [self.rank], axis=-1)
            kv = self.kv_b(self.kv_norm(latent)).reshape(b, s, n, d_n + d_v)
            k_n, v = kv[..., :d_n], kv[..., d_n:]
            if rope is None:  # no position signal: the dims stay, unturned
                k_r = k_r[:, :, None, :]
            else:
                q_r = apply_rope_pairs(q[..., d_n:], rope)
                k_r = apply_rope_pairs(k_r[:, :, None, :], rope)
                q = jnp.concatenate([q[..., :d_n], q_r], axis=-1)
            k = jnp.concatenate(
                [k_n, jnp.broadcast_to(k_r, (b, s, n, d_r))], axis=-1)
            o = dot_product_attention(q, k, v, is_causal=True, mask=mask,
                                      impl=self.impl)
            return self.out(o.reshape(b, s, n * d_v))
