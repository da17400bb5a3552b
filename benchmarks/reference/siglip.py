"""Plain float32 reference: SigLIP's two towers, its logits, the sigmoid loss.

Straight ``jax.numpy`` over the blocks of :mod:`benchmarks.reference.vit`.
Follows "Sigmoid Loss for Language Image Pre-Training" (Zhai et al., 2023) and
SigLIP 2 (arXiv:2502.14786, fixed-resolution variants) as HF ``SiglipModel``
implements google/siglip-base-patch16-256 and
google/siglip2-so400m-patch16-256:

- vision: patch projection, learned position embeddings (no class token),
  pre-LayerNorm blocks with tanh-GELU, a final LayerNorm, then the attention
  pooling head: one learned probe attends over the sequence, and the result is
  ``a + mlp(ln(a))`` taken at the probe's position;
- text: token + position embeddings, the same blocks without a mask, a final
  LayerNorm, the last position's state, a biased linear projection;
- logits ``exp(logit_scale) * <img, txt> + logit_bias`` over L2-normalised
  embeddings; loss ``-1/n * sum_ij log sigmoid(z_ij * logit_ij)`` with
  ``z = +1`` on the diagonal and ``-1`` elsewhere (the paper's equation 1).

Departures: as in ``vit.py`` (random weights from the seed; the convolution
written as a matmul; parameters read from the model under test). The probe's
attention is written with the same separate q/k/v projections the program
stores; HF fuses them into one ``in_proj`` of the same numbers.

Tolerances: see ``vit.py``. The loss is a sum of n*n terms near |bias| = 10,
where bfloat16 is spaced 0.06 apart, hence its own bound.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import vit
from benchmarks.reference.vit import TOLERANCE, params_from_state  # noqa: F401


def encode_image(params, images, sizes):
    """Unnormalised image embedding, (B, width)."""
    p = params["vision"]
    x = vit.embed_patches(images, p, sizes) + p["pos_embed"]
    x = vit.encoder(x, p["encoder"], sizes)
    x = vit.layer_norm(x, p["ln_post"], sizes["layer_norm_eps"])
    head = p["head"]
    probe = jnp.broadcast_to(head["probe"], (x.shape[0], 1, x.shape[-1]))
    a = vit.attention(probe, x, head["attn"], sizes["num_attention_heads"])
    a = a + vit.mlp(vit.layer_norm(a, head["ln"], sizes["layer_norm_eps"]),
                    head["mlp"], sizes["hidden_act"])
    return a[:, 0]


def encode_text(params, tokens, sizes):
    """Unnormalised text embedding, (B, projection)."""
    p = params["text"]
    x = p["token_embed"]["embedding"][tokens] + p["pos_embed"][:tokens.shape[1]]
    x = vit.encoder(x, p["encoder"], sizes)
    x = vit.layer_norm(x, p["ln_final"], sizes["layer_norm_eps"])
    return vit.linear(x[:, -1], params["text_projection"])


def logits(params, img, txt):
    img = img / jnp.linalg.norm(img, axis=-1, keepdims=True)
    txt = txt / jnp.linalg.norm(txt, axis=-1, keepdims=True)
    return jnp.exp(params["logit_scale"]) * img @ txt.T + params["logit_bias"]


def loss(params, images, tokens, vision_sizes, text_sizes):
    z = logits(params, encode_image(params, images, vision_sizes),
               encode_text(params, tokens, text_sizes))
    n = z.shape[0]
    sign = 2.0 * jnp.eye(n) - 1.0
    return -jnp.sum(jax.nn.log_sigmoid(sign * z)) / n


GRAD_LEAVES = {
    "last_block_mlp_fc2": "vision/encoder/blocks/-1/mlp/fc2/kernel",
    "final_layer_norm": "vision/ln_post/scale",
    "logit_scale": "logit_scale",
}
