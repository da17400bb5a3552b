"""Ouro: a looped decoder language model (ByteDance, "Scaling Latent
Reasoning via Looped Language Models", 2025).

    x = E[tokens]
    for r in 1..R:                      # the SAME n blocks every pass
        x = blocks(x); x = RMS_f(x)     # nn/transformer.py::_apply_loops
        h_r = x                         # feeds the next pass
        g_r = h_r . w_g + b_g           # exit gate
        z_r = h_r W_head                # logits, head untied from E

The block is `nn/transformer.py::Block` under `DecoderConfig.encoder()`:
RMSNorm before and after each sub-layer, rotary q/k, causal attention,
SwiGLU, no biases. Training goes through `train/trainer.py::
make_lm_train_step`, which never holds the ``(R, B, S, vocab)`` logits
(`train/losses.py::blocked_cross_entropy`). Not built: generation (a KV cache
per pass and layer, early exit) and checkpoint loading.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import nnx

from jimm_tpu.configs import OuroConfig
from jimm_tpu.nn.transformer import Transformer
from jimm_tpu.parallel.sharding import (ShardingRules, TENSOR_PARALLEL,
                                        logical, logical_constraint,
                                        shard_model)


class Ouro(nnx.Module):
    def __init__(self, config: OuroConfig | None = None, *,
                 rngs: nnx.Rngs | None = None,
                 mesh: jax.sharding.Mesh | None = None,
                 rules: ShardingRules | str = TENSOR_PARALLEL,
                 dtype=None, param_dtype=jnp.float32):
        cfg = config or OuroConfig()
        self.config = cfg
        d = cfg.decoder
        rngs = rngs if rngs is not None else nnx.Rngs(0)
        self.embed = nnx.Embed(
            d.vocab_size, d.width, dtype=dtype, param_dtype=param_dtype,
            embedding_init=logical(nnx.initializers.normal(0.02),
                                   "vocab", "embed"),
            rngs=rngs)
        self.decoder = Transformer(d.encoder(), rngs, dtype=dtype,
                                   param_dtype=param_dtype)
        self.gate = nnx.Linear(
            d.width, 1, dtype=dtype, param_dtype=param_dtype,
            kernel_init=logical(nnx.initializers.normal(0.02), "embed", None),
            bias_init=logical(nnx.initializers.zeros_init(), None),
            rngs=rngs)
        self.head = nnx.Linear(
            d.width, d.vocab_size, use_bias=False, dtype=dtype,
            param_dtype=param_dtype,
            kernel_init=logical(nnx.initializers.normal(0.02),
                                "embed", "vocab"),
            rngs=rngs)
        if mesh is not None:
            shard_model(self, mesh, rules)

    def hidden_states(self, tokens: jax.Array) -> jax.Array:
        """``(B, S)`` int ids -> the pass outputs ``h_r``, ``(R, B, S, width)``."""
        with jax.named_scope("embed"):
            x = logical_constraint(self.embed(tokens), "batch", "seq", None)
        with jax.named_scope("loop_stack"):
            return self.decoder(x)

    def exit_gates(self, hidden: jax.Array) -> jax.Array:
        """The exit gates' logits ``g_r`` of ``hidden_states``' output,
        ``(R, B, S)`` float32; ``sigmoid`` of them is ``lam_r``."""
        return self.gate(hidden)[..., 0].astype(jnp.float32)

    def __call__(self, tokens: jax.Array) -> tuple[jax.Array, jax.Array]:
        """``(logits (R, B, S, vocab), gate logits (R, B, S))``: every pass's
        output whole, for inspection at sizes where that fits."""
        hidden = self.hidden_states(tokens)
        return self.head(hidden), self.exit_gates(hidden)
