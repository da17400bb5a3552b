"""kanana-2-30b-a3b (kakaocorp; ``model_type`` deepseek_v3): a decoder with
latent attention in every layer, a dense SwiGLU in the first layer and a
128-expert top-6 mixture with two shared experts in the rest.

    x = E[tokens]
    x = x + MLA(RMS(x)); x = x + SwiGLU(RMS(x))        the dense layer(s)
    x = x + MLA(RMS(x)); x = x + MoE(RMS(x))           the sparse layers
    z = RMS_f(x) W_head                                head untied from E

`nn/mla.py` and `nn/moe.py` hold the two mechanisms; the block is
`nn/transformer.py::Block`, one scanned stack of it a run of like layers
(`MoEDecoderConfig.runs`: same token mixer, same kind of FFN; here ``dense``,
then ``sparse``; a family whose layers differ in their mixer, as
`models/kimi_linear.py`, has a run per kind and place; a stack with no
sparse layer, as `models/granite.py`, has dense runs alone). The model is ONE
chip's share of an expert-parallel
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
    #: the head is the embedding's transpose and no module of its own
    #: (`models/granite.py`)
    tied_head = False

    def __init__(self, config: KananaConfig | None = None, *,
                 rngs: nnx.Rngs | None = None,
                 mesh: jax.sharding.Mesh | None = None,
                 rules: ShardingRules | str = TENSOR_PARALLEL,
                 dtype=None, param_dtype=jnp.float32):
        cfg = config or KananaConfig()
        self.config = cfg
        d = cfg.decoder
        if d.moe is not None and not 0 < d.dense_layers < d.depth:
            raise ValueError(f"depth {d.depth} needs at least one dense and "
                             f"one sparse layer ({d.dense_layers} dense)")
        rngs = rngs if rngs is not None else nnx.Rngs(0)
        self.embed = nnx.Embed(
            d.vocab_size, d.width, dtype=dtype, param_dtype=param_dtype,
            embedding_init=logical(nnx.initializers.normal(0.02),
                                   "vocab", "embed"),
            rngs=rngs)
        # a run of like layers is one scanned stack, an attribute under the
        # run's name, walked in layer order
        runs = d.runs()
        self.run_names = tuple(name for name, _ in runs)
        for name, block in runs:
            setattr(self, name, Transformer(block, rngs, dtype=dtype,
                                            param_dtype=param_dtype))
        self.norm = _norm(runs[0][1], rngs, dtype=dtype,
                          param_dtype=param_dtype)
        if not self.tied_head:
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
            return self.decode(x)

    def sparse_runs(self) -> list[Transformer]:
        """The runs that hold expert layers, in layer order."""
        return [run for run in (getattr(self, name)
                                for name in self.run_names)
                if run.cfg.moe is not None]

    def decode(self, x: jax.Array) -> tuple[jax.Array, jax.Array | None]:
        """``x`` through every run in order: the last layer's output and the
        sparse layers' routing choices, in layer order (None in a stack
        without a sparse layer)."""
        chosen = []
        for name in self.run_names:
            run = getattr(self, name)
            if run.cfg.moe is None:
                x = run(x)
            else:
                x, picked = run(x)
                chosen.append(picked)
        return x, jnp.concatenate(chosen) if chosen else None

    def router_bias(self) -> jax.Array:
        """The sparse layers' selection biases ``(sparse layers,
        num_experts)``, in layer order."""
        return jnp.concatenate([run.blocks.mlp.router_bias[...]
                                for run in self.sparse_runs()])

    def __call__(self, tokens: jax.Array) -> jax.Array:
        """Logits ``(B, S, vocab)``, whole: for sizes where that fits."""
        return self.head(self.norm(self.hidden_states(tokens)[0]))

    def update_router_bias(self, counts: jax.Array) -> None:
        """Auxiliary-loss-free balancing: each sparse layer's selection bias
        moves by ``bias_update_rate`` toward the experts that drew fewer than
        the mean of this step's ``counts (sparse layers, num_experts)``."""
        load = counts.astype(jnp.float32)
        step = self.config.bias_update_rate * jnp.sign(
            jnp.mean(load, axis=-1, keepdims=True) - load)
        first = 0
        for run in self.sparse_runs():
            bias = run.blocks.mlp.router_bias
            bias[...] = bias[...] + step[first:first + run.cfg.depth]
            first += run.cfg.depth
