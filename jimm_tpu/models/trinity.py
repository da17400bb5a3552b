"""Trinity-Large-Preview (arcee-ai; ``model_type`` afmoe): a decoder with
grouped-query attention in every layer (48 heads of 128 over 8 key/value
heads, an RMSNorm on each head of q and k, a sigmoid gate on the output), a
4096-token window with rotary on three layers of four and full attention with
NO position signal on the fourth, sandwich RMSNorms, dense SwiGLUs in the
first layers and a 256-expert top-4 mixture with one shared expert in the
rest.

    x = E[tokens] * sqrt(width)
    x = x + RMS'(Attn(RMS(x))); x = x + RMS'(SwiGLU(RMS(x)))   the dense layer(s)
    x = x + RMS'(Attn(RMS(x))); x = x + RMS'(MoE(RMS(x)))      the sparse layers
    z = RMS_f(x) W_head                                        head untied from E

`nn/transformer.py::Attention` under `GQAConfig` and `nn/moe.py` hold the two
mechanisms; the rest is `models/kanana.py::Kanana` (a run of the one `Block`
per kind of layer: dense, then sparse; final norm, untied head, the routers'
bias update), of which this is
the same one-chip share of an expert-parallel group. The gains of RMS' start
at 1 / sqrt(60) (`post_norm_gain`: the depth-scaled sandwich with a constant
of 1, the model's own not being public): with gains of 1 the averaged
attention outputs, rescaled to unit size, push every token's router input the
same way and a random router sends most tokens to the same few experts. Not
built: the exchange across chips, generation (a
cache for window and full layers), checkpoint loading, the balance loss.
"""

from __future__ import annotations

import math

import jax

from jimm_tpu.configs import TrinityConfig
from jimm_tpu.models.kanana import Kanana
from jimm_tpu.parallel.sharding import logical_constraint


class Trinity(Kanana):
    def __init__(self, config: TrinityConfig | None = None, **kw):
        super().__init__(config or TrinityConfig(), **kw)

    def hidden_states(self, tokens: jax.Array) -> tuple[jax.Array, jax.Array]:
        """`Kanana.hidden_states` on an embedding scaled by ``sqrt(width)``."""
        with jax.named_scope("embed"):
            x = self.embed(tokens)
            x = logical_constraint(x * math.sqrt(x.shape[-1]),
                                   "batch", "seq", None)
        with jax.named_scope("decoder_stack"):
            return self.decode(x)
