"""Plain float32 reference: the ViT encoder, its classifier, cross-entropy.

Straight ``jax.numpy``: a Python loop over layers, no scan, no kernels, no
rematerialisation, no sharding. Follows "An Image is Worth 16x16 Words"
(Dosovitskiy et al., 2021) as google/vit-large-patch16-384 implements it
(HF ``ViTModel``): non-overlapping patch projection, a class token, learned
position embeddings, pre-LayerNorm blocks ``x + attn(ln1(x))``,
``x + mlp(ln2(x))`` with biased q/k/v/out projections and exact (erf) GELU,
a final LayerNorm, and a linear classifier on the class token.

Departures from the published model, each deliberate:
- weights are random from the seed, not the checkpoint (speed and agreement
  do not need trained weights);
- the patch projection is written as a matmul over flattened patches, which
  is what a stride-16 16x16 convolution computes;
- parameters come from the ``nnx`` state of the model under test through
  :func:`params_from_state`, cast to float32, so both sides hold the same
  numbers (to the rounding of the model's own bfloat16 storage).

Callers wrap these functions in ``jax.default_matmul_precision("highest")``:
on a TPU a float32 matmul otherwise runs in bfloat16 passes.

Tolerances, and why. The model under test keeps parameters and activations
in bfloat16 (8 significant bits, 0.4 % per rounding) through 24 or more
blocks; the reference is float32 throughout. Outputs are compared as
``max|a - b| / max|b|`` and gradients as ``||a - b|| / ||b||`` (Frobenius).
The bounds below are about three times what the chip showed at the published
widths (PERF.md, Findings, PR 22) and an order of magnitude under what a
dropped bias, a wrong epsilon, a missing residual, a skipped normalisation
or a float16-range overflow produces (all O(1)).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: see the module docstring for the reasons
TOLERANCE = {"outputs": 6e-2, "loss": 3e-2, "grads": 2.5e-1}


def layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def linear(x, p):
    return x @ p["kernel"] + p["bias"]


def attention(x_q, x_kv, p, num_heads):
    b, sq, w = x_q.shape
    sk = x_kv.shape[1]
    d = w // num_heads
    q = linear(x_q, p["q"]).reshape(b, sq, num_heads, d)
    k = linear(x_kv, p["k"]).reshape(b, sk, num_heads, d)
    v = linear(x_kv, p["v"]).reshape(b, sk, num_heads, d)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(jnp.float32(d))
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bnqk,bknd->bqnd", probs, v).reshape(b, sq, w)
    return linear(o, p["out"])


def gelu(x, kind):
    return jax.nn.gelu(x, approximate=(kind != "gelu"))


def mlp(x, p, act):
    return linear(gelu(linear(x, p["fc1"]), act), p["fc2"])


def block(x, p, num_heads, eps, act):
    h = layer_norm(x, p["ln1"], eps)
    x = x + attention(h, h, p["attn"], num_heads)
    return x + mlp(layer_norm(x, p["ln2"], eps), p["mlp"], act)


def patchify(images, patch):
    """(B, H, W, C) -> (B, N, patch*patch*C), rows of (row, column, channel)
    as a (patch, patch, C, width) convolution kernel flattens."""
    b, h, w, c = images.shape
    x = images.reshape(b, h // patch, patch, w // patch, patch, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (h // patch) * (w // patch), patch * patch * c)


def encoder(x, p, sizes):
    for layer in p["blocks"]:
        x = block(x, layer, sizes["num_attention_heads"],
                  sizes["layer_norm_eps"], sizes["hidden_act"])
    return x


def embed_patches(images, p, sizes):
    kernel = p["patch_embed"]["conv"]["kernel"]
    flat = kernel.reshape(-1, kernel.shape[-1])
    return (patchify(images, sizes["patch_size"]) @ flat
            + p["patch_embed"]["conv"]["bias"])


def vit_tower(images, p, sizes):
    """Pooled class-token feature, (B, width)."""
    x = embed_patches(images, p, sizes)
    cls = jnp.broadcast_to(p["cls_token"], (x.shape[0], 1, x.shape[-1]))
    x = jnp.concatenate([cls, x], axis=1) + p["pos_embed"]
    x = encoder(x, p["encoder"], sizes)
    return layer_norm(x, p["ln_post"], sizes["layer_norm_eps"])[:, 0]


def logits(params, images, sizes):
    return linear(vit_tower(images, params["vision"], sizes),
                  params["classifier"])


def loss(params, images, labels, sizes):
    """Mean softmax cross-entropy with integer labels."""
    logp = jax.nn.log_softmax(logits(params, images, sizes), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


# ---------------------------------------------------------------------------
# The name map: nnx state of the model under test -> reference parameters
# ---------------------------------------------------------------------------

def unstack_blocks(stacked: dict) -> list[dict]:
    """The program stacks its layers on a leading axis (scan over layers);
    the reference wants a list of layers."""
    depth = jax.tree.leaves(stacked)[0].shape[0]
    return [jax.tree.map(lambda a, i=i: a[i], stacked) for i in range(depth)]


def params_from_state(pure: dict, device=None) -> dict:
    """``nnx.to_pure_dict(nnx.state(model, nnx.Param))`` -> float32 reference
    parameters under the same names (``vision/encoder/blocks`` becomes a list
    of layers; everything else keeps its path), gathered onto ``device``."""

    def to_f32(a):
        a = a.astype(jnp.float32)
        return jax.device_put(a, device) if device is not None else a

    def convert(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k == "blocks":
                    out[k] = unstack_blocks(convert(v))
                else:
                    out[k] = convert(v)
            return out
        return to_f32(node)

    return convert(pure)


def sizes_from_config(config: dict) -> dict:
    """The vision sizes the reference needs, from a configuration file."""
    return config.get("vision_config", config)


#: the three leaves whose gradients are compared, by path in the model's
#: state; -1 picks the last layer of a stack
GRAD_LEAVES = {
    "last_block_mlp_fc2": "vision/encoder/blocks/-1/mlp/fc2/kernel",
    "final_layer_norm": "vision/ln_post/scale",
    "classifier": "classifier/kernel",
}
