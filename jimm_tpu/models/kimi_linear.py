"""Kimi-Linear-48B-A3B-Instruct (moonshotai; ``model_type`` kimi_linear): a
decoder whose token mixer is Kimi Delta Attention (a gated delta-rule linear
attention, `nn/kda.py`) on three layers of four and latent attention with NO
position signal on the fourth (``mla_use_nope``; `nn/mla.py` with no rope
tables), a dense SwiGLU in the first layer and a 256-expert top-8 mixture with
one shared expert in the rest.

    x = E[tokens]                                      not scaled
    x = x + Mixer_l(RMS(x)); x = x + FFN_l(RMS(x))     pre-norm, eps 1e-5
        Mixer_l = KDA on published layers 1-3, 5-7, ..., 25, 26
                  MLA (no rotary on either 64-wide part) on 4, 8, ..., 24, 27
        FFN_l   = SwiGLU (9216) on layer 1, MoE on layers 2-27
    z = RMS_f(x) W_head                                head untied from E

The mixers' equations are in `nn/kda.py` and `nn/mla.py`, the expert layer's
in `nn/moe.py`. A KDA layer and a latent-attention layer hold different
parameters, so they cannot ride one scan: the rest is
`models/kanana.py::Kanana` with a run of the one `Block` per stretch of like
layers (`MoEDecoderConfig.runs`; the preset's published layers 1-5 are (KDA,
dense) x 1, (KDA, sparse) x 2, (MLA, sparse) x 1, (KDA, sparse) x 1), of which
this is the same one-chip share of an expert-parallel group. Not built: the
exchange across chips, generation (a recurrent-state cache beside a latent
cache), a reset of the state at a document boundary, checkpoint loading.
"""

from __future__ import annotations

from jimm_tpu.configs import KimiLinearConfig
from jimm_tpu.models.kanana import Kanana


class KimiLinear(Kanana):
    def __init__(self, config: KimiLinearConfig | None = None, **kw):
        super().__init__(config or KimiLinearConfig(), **kw)
