"""Plain float32 reference: the Ouro looped language model, its exit
distribution, and the stage-one training loss.

Straight ``jax.numpy``: explicit Python loops over passes and layers, a
materialised causal mask, no scan, no kernels, no sharding. Follows "Scaling
Latent Reasoning via Looped Language Models" (ByteDance Seed, 2025) and the
family's published modelling code for ByteDance/Ouro-2.6B:

    x = E[tokens]
    for r in 1..R:                                # the SAME n layers every pass
        for l in 1..n:
            a = x + RMS_l2(Attn_l(RMS_l1(x)))     # a norm before AND after each
            x = a + RMS_l4(SwiGLU_l(RMS_l3(a)))   #   sub-layer, then the residual
        x = RMS_f(x); h_r = x                     # closes the pass, feeds the next
        g_r = h_r . w_g + b_g                     # exit gate
        z_r = h_r W_head                          # logits, head untied from E
    Attn: q, k, v projections without bias, rotary positions (base theta, the
          rotate-half pairing (i, i + D/2) over the whole head) on q and k,
          softmax(causal(q k^T / sqrt(D))) v, output projection
    SwiGLU: W_down(silu(W_gate x) * (W_up x))
    RMS(x) = x / sqrt(mean(x^2) + eps) * w
    lam_r = sigmoid(g_r); p_r = lam_r prod_{j<r}(1 - lam_j) for r < R;
    p_R = prod_{j<R}(1 - lam_j)
    loss = mean over positions of [sum_r p_r CE(z_r, next token) - beta H(p)]

Departures from the published description, each deliberate:
- weights are random from the seed, not the checkpoint;
- only the stage-one objective (the entropy-regularised expected loss) is
  built, not the later gate-only stage; ``beta`` comes from the model under
  test (0.1);
- parameters come from the ``nnx`` state of the model under test through
  :func:`params_from_state`, cast to float32, so both sides hold the same
  numbers (to the rounding of the model's own bfloat16 storage);
- every matmul goes through :data:`matmul` and :func:`hidden_states` /
  :func:`loss` take ``wrap``, applied to ``block`` and to one pass's
  cross-entropy. Both are the identity here. The comparison on the chip passes
  ``jax.checkpoint`` as ``wrap``: the backward of 32 block applications at 4096
  tokens with materialised (16, 4096, 4096) scores does not fit 16 GB
  otherwise (it changes what is kept, not what is computed); the
  low-precision reading of PERF.md swaps ``matmul``.

It shares no code with ``jimm_tpu``. Callers wrap these functions in
``jax.default_matmul_precision("highest")``.

Tolerances, and why. The model keeps parameters and activations in bfloat16
(8 significant bits) through R x n = 32 block applications at the timed
size; every pass ends in an RMSNorm, so the hidden states are O(1) and errors
do not grow with the residual stream's norm. ``hidden`` and ``logits`` are
``max|a - b| / max|b|`` over all passes, ``gates`` is ``max|a - b| / max(1,
max|b|)`` of the gate logits (O(0.1) at initialisation, so a purely relative
measure would divide by little), ``loss`` is relative, and a gradient leaf is
``||a - b|| / ||b||``, each leaf under its own limit: the backward of a
middle layer's shared ``W_q`` runs through all four passes and reads several
times the head's. Each limit lies between what the chip showed for the
bfloat16 model over its seeds and what the same comparison reads with every
matmul operand of this reference rounded to float8 (e4m3), which fails by
``hidden``, ``logits`` and more (PERF.md section 6, PR 27, has both
readings); a missing pass, post-norm, between-pass norm or rotary step is
O(1) in ``hidden``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: see the module docstring; PERF.md, Findings, PR 27 has the two readings
#: each limit lies between
TOLERANCE = {"hidden": 1.5e-1, "gates": 1e-1, "logits": 1.5e-1, "loss": 2e-4,
             "grads": {"embedding": 1e-1, "middle_layer_q": 1.2e-1,
                       "gate": 1.5e-1, "head": 6e-2}}

#: every matmul of the reference (the low-precision reading swaps it)
matmul = jnp.matmul


def _identity(fn):
    return fn


def rms_norm(x, p, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["scale"]


def rotate(x, theta):
    """Rotary positions on (B, S, N, D): pair i turns with pair i + D/2 by
    ``position * theta**(-2i / D)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(angle) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angle)


def attention(x, p, sizes):
    b, s, w = x.shape
    n = sizes["num_attention_heads"]
    d = w // n
    q = rotate(matmul(x, p["q"]["kernel"]).reshape(b, s, n, d),
               sizes["rope_theta"])
    k = rotate(matmul(x, p["k"]["kernel"]).reshape(b, s, n, d),
               sizes["rope_theta"])
    v = matmul(x, p["v"]["kernel"]).reshape(b, s, n, d)
    scores = matmul(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1)) \
        / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    o = matmul(probs, v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    return matmul(o.reshape(b, s, w), p["out"]["kernel"])


def swiglu(x, p):
    return matmul(jax.nn.silu(matmul(x, p["gate"]["kernel"]))
                  * matmul(x, p["fc1"]["kernel"]), p["fc2"]["kernel"])


def block(x, p, sizes):
    eps = sizes["rms_norm_eps"]
    a = x + rms_norm(attention(rms_norm(x, p["ln1"], eps), p["attn"], sizes),
                     p["ln1_post"], eps)
    return a + rms_norm(swiglu(rms_norm(a, p["ln2"], eps), p["mlp"]),
                        p["ln2_post"], eps)


def hidden_states(params, tokens, sizes, wrap=_identity):
    """The pass outputs ``h_1 .. h_R``, a list of (B, S, width)."""
    one_block = wrap(lambda x, p: block(x, p, sizes))
    x = params["embed"]["embedding"][tokens]
    out = []
    for _ in range(sizes["total_ut_steps"]):
        for layer in params["decoder"]["blocks"]:
            x = one_block(x, layer)
        x = rms_norm(x, params["decoder"]["norm"], sizes["rms_norm_eps"])
        out.append(x)
    return out


def gate_logits(params, h):
    """(B, S): the exit gate on one pass's output."""
    return matmul(h, params["gate"]["kernel"])[..., 0] + params["gate"]["bias"][0]


def logits(params, h):
    return matmul(h, params["head"]["kernel"])


def cross_entropy(params, h, targets):
    """Per-position softmax cross-entropy of one pass, (B, S)."""
    logp = jax.nn.log_softmax(logits(params, h), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def exit_distribution(gates):
    """``p_r`` from the gate logits of the R passes (a list): R arrays that
    sum to one at every position."""
    lam = [jax.nn.sigmoid(g) for g in gates]
    stayed = jnp.ones_like(lam[0])
    p = []
    for r in range(len(lam) - 1):
        p.append(lam[r] * stayed)
        stayed = stayed * (1.0 - lam[r])
    return p + [stayed]


def loss_of_hidden(params, hs, targets, sizes, wrap=_identity):
    """The objective from the pass outputs ``hs`` and the next tokens."""
    one_pass = wrap(lambda h: cross_entropy(params, h, targets))
    p = exit_distribution([gate_logits(params, h) for h in hs])
    expected = sum(p_r * one_pass(h) for p_r, h in zip(p, hs, strict=True))
    entropy = -sum(_p_log_p(p_r) for p_r in p)
    return jnp.mean(expected - sizes["exit_beta"] * entropy)


def loss(params, tokens, sizes, wrap=_identity):
    """Stage-one objective on (B, S + 1) ids: inputs are the first S, targets
    the ids shifted by one."""
    hs = hidden_states(params, tokens[:, :-1], sizes, wrap)
    return loss_of_hidden(params, hs, tokens[:, 1:], sizes, wrap)


def _p_log_p(p):
    """``p log p`` with ``0 log 0 = 0``: a gate saturated in float32 gives a
    pass the mass 0 exactly."""
    positive = p > 0
    return jnp.where(positive, p * jnp.log(jnp.where(positive, p, 1.0)), 0.0)


# ---------------------------------------------------------------------------
# The name map: nnx state of the model under test -> reference parameters
# ---------------------------------------------------------------------------

def params_from_state(pure: dict, device=None) -> dict:
    """``nnx.to_pure_dict(nnx.state(model, nnx.Param))`` -> float32 reference
    parameters under the same names (``decoder/blocks``, stacked on a leading
    layer axis by the program, becomes a list of layers), on ``device``."""

    def to_f32(a):
        a = a.astype(jnp.float32)
        return jax.device_put(a, device) if device is not None else a

    out = jax.tree.map(to_f32, pure)
    stacked = out["decoder"]["blocks"]
    depth = jax.tree.leaves(stacked)[0].shape[0]
    out["decoder"] = {**out["decoder"], "blocks": [
        jax.tree.map(lambda a, i=i: a[i], stacked) for i in range(depth)]}
    return out


#: the four leaves whose gradients are compared, by path in the model's state;
#: ``{mid}`` is a middle layer (depth // 2). ``W_q`` there is shared by the R
#: passes: its gradient is wrong if one pass's contribution is dropped.
GRAD_LEAVES = {
    "embedding": "embed/embedding",
    "middle_layer_q": "decoder/blocks/{mid}/attn/q/kernel",
    "gate": "gate/kernel",
    "head": "head/kernel",
}
