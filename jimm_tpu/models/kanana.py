"""kanana-2-30b-a3b (kakaocorp; ``model_type`` deepseek_v3): a decoder with
latent attention in every layer, a dense SwiGLU in the first layer and a
128-expert top-6 mixture with two shared experts in the rest.

    x = E[tokens]
    x = x + MLA(RMS(x)); x = x + SwiGLU(RMS(x))        the dense layer(s)
    x = x + MLA(RMS(x)); x = x + MoE(RMS(x))           the sparse layers
    z = RMS_f(x) W_head                                head untied from E

`nn/mla.py` and `nn/moe.py` hold the two mechanisms; the block is
`nn/transformer.py::Block` under `MoEDecoderConfig.encoder()`, two stacks of
it (dense, then sparse). The model is ONE chip's share of an expert-parallel
group: it holds ``held_experts`` of each sparse layer's experts and a slice of
the vocabulary, and computes its own experts' part of each layer's result.
Training goes through `train/trainer.py::make_lm_train_step`. Not built: the
exchange across chips, generation (a latent cache, the absorbed decode path),
checkpoint loading, the sequence-wise balance loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import nnx

from jimm_tpu.configs import KananaConfig
from jimm_tpu.nn.transformer import Transformer, _norm
from jimm_tpu.parallel.sharding import (ShardingRules, TENSOR_PARALLEL,
                                        logical, logical_constraint,
                                        shard_model)


class Kanana(nnx.Module):
    def __init__(self, config: KananaConfig | None = None, *,
                 rngs: nnx.Rngs | None = None,
                 mesh: jax.sharding.Mesh | None = None,
                 rules: ShardingRules | str = TENSOR_PARALLEL,
                 dtype=None, param_dtype=jnp.float32):
        cfg = config or KananaConfig()
        self.config = cfg
        d = cfg.decoder
        if not 0 < d.dense_layers < d.depth:
            raise ValueError(f"depth {d.depth} needs at least one dense and "
                             f"one sparse layer ({d.dense_layers} dense)")
        rngs = rngs if rngs is not None else nnx.Rngs(0)
        self.embed = nnx.Embed(
            d.vocab_size, d.width, dtype=dtype, param_dtype=param_dtype,
            embedding_init=logical(nnx.initializers.normal(0.02),
                                   "vocab", "embed"),
            rngs=rngs)
        self.dense = Transformer(d.encoder(sparse=False), rngs, dtype=dtype,
                                 param_dtype=param_dtype)
        self.sparse = Transformer(d.encoder(sparse=True), rngs, dtype=dtype,
                                  param_dtype=param_dtype)
        self.norm = _norm(d.encoder(sparse=False), rngs, dtype=dtype,
                          param_dtype=param_dtype)
        self.head = nnx.Linear(
            d.width, d.vocab_size, use_bias=False, dtype=dtype,
            param_dtype=param_dtype,
            kernel_init=logical(nnx.initializers.normal(0.02),
                                "embed", "vocab"),
            rngs=rngs)
        if mesh is not None:
            shard_model(self, mesh, rules)

    def hidden_states(self, tokens: jax.Array) -> tuple[jax.Array, jax.Array]:
        """``(B, S)`` int ids -> the last layer's output ``(B, S, width)``
        (before the final norm) and the experts each token chose in each
        sparse layer, ``(sparse layers, B * S, top_k)`` int32."""
        with jax.named_scope("embed"):
            x = logical_constraint(self.embed(tokens), "batch", "seq", None)
        with jax.named_scope("decoder_stack"):
            return self.sparse(self.dense(x))

    def __call__(self, tokens: jax.Array) -> jax.Array:
        """Logits ``(B, S, vocab)``, whole: for sizes where that fits."""
        return self.head(self.norm(self.hidden_states(tokens)[0]))

    def update_router_bias(self, counts: jax.Array) -> None:
        """Auxiliary-loss-free balancing: each sparse layer's selection bias
        moves by ``bias_update_rate`` toward the experts that drew fewer than
        the mean of this step's ``counts (sparse layers, num_experts)``."""
        bias = self.sparse.blocks.mlp.router_bias
        load = counts.astype(jnp.float32)
        bias[...] = bias[...] + self.config.bias_update_rate * jnp.sign(
            jnp.mean(load, axis=-1, keepdims=True) - load)
