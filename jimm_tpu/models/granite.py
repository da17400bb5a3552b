"""granite-4.0-h-micro (ibm-granite; ``model_type`` granitemoehybrid): a
dense decoder whose token mixer is Mamba-2 (a selective state-space layer,
`nn/mamba2.py`) on nine layers of ten and grouped-query attention with NO
position signal on the tenth, a SwiGLU in every layer, muP-style multipliers
and a tied head.

    x = 12 * E[tokens]                                 embedding_multiplier
    x = x + 0.22 * Mixer_l(RMS(x)); x = x + 0.22 * SwiGLU(RMS(x))
        Mixer_l = Mamba-2 on published layers 0-4, 6-14, ..., 36-39
                  attention on 5, 15, 25, 35: 32 heads over 8 of 64, no
                  rotary, softmax(q k^T / 64)
    z = RMS_f(x) E^T / 8                               tied, logits_scaling

A Mamba-2 layer and an attention layer hold different parameters, so they
cannot ride one scan: the rest is `models/kanana.py::Kanana` with a run of the
one `Block` per stretch of like layers (`MoEDecoderConfig.runs`, no sparse
layer; the preset's published layers 0-9 are Mamba-2 x 5, attention x 1,
Mamba-2 x 4). Training goes through `train/trainer.py::make_lm_train_step`
(`dense_lm_loss_fn`). Not built: generation (a state cache beside a key/value
cache), a reset of the state at a document boundary, checkpoint loading.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jimm_tpu.configs import GraniteConfig
from jimm_tpu.models.kanana import Kanana
from jimm_tpu.parallel.sharding import logical_constraint


class Granite(Kanana):
    tied_head = True

    def __init__(self, config: GraniteConfig | None = None, **kw):
        super().__init__(config or GraniteConfig(), **kw)

    def hidden_states(self, tokens: jax.Array) -> jax.Array:
        """``(B, S)`` int ids -> the last layer's output ``(B, S, width)``
        (before the final norm), from the embedding times
        ``embedding_multiplier``."""
        with jax.named_scope("embed"):
            x = self.embed(tokens)
            x = logical_constraint(
                x * jnp.asarray(self.config.embedding_multiplier, x.dtype),
                "batch", "seq", None)
        with jax.named_scope("decoder_stack"):
            return self.decode(x)[0]

    def head_input(self, normed: jax.Array) -> jax.Array:
        """What meets the embedding's transpose: the final norm's output
        over ``logits_scaling``."""
        return normed / jnp.asarray(self.config.logits_scaling, normed.dtype)

    def __call__(self, tokens: jax.Array) -> jax.Array:
        """Logits ``(B, S, vocab)``, whole: for sizes where that fits."""
        h = self.head_input(self.norm(self.hidden_states(tokens)))
        return h @ self.embed.embedding[...].astype(h.dtype).T
