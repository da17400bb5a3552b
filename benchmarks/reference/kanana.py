"""Plain float32 reference: kanana-2-30b-a3b (``model_type`` deepseek_v3) as
ONE chip of an eight-way expert-parallel group holds it, its next-token loss
and its gradients.

Straight ``jax.numpy``: explicit Python loops over layers and over the held
experts, attention by explicit scores, no sort, no grouped product, no scan,
no kernels, no sharding. From the published ``config.json``, the DeepSeek-V2
paper (latent attention) and the DeepSeek-V3 paper (the sigmoid router with a
selection-only bias):

    x = E[tokens]
    layer l:   a = x + MLA_l(RMS_l1(x));   x = a + FFN_l(RMS_l2(a))
               FFN_l = SwiGLU (width 6144) for l < first_k_dense_replace,
                       MoE otherwise
    z = RMS_f(x) W_head                      head untied from E
    loss = mean over positions of CE(z, next token)

    MLA:  q = x W_q -> (B, S, N, 192) = [q_n (128) | q_r (64)]
          [c (512) | k_r (64)] = x W_kva;  c = RMS_512(c)
          [k_n (128) | v (128)] = c W_kvb -> (B, S, N, 256)
          q_r, k_r = rotary(q_r), rotary(k_r): pair (2i, 2i + 1) turns by
              position * theta**(-2i / 64); ONE k_r for all heads
          k = [k_n | k_r];  o = softmax(causal(q k^T / sqrt(192))) v
          out = o W_o                        (N * 128 -> hidden)
    MoE:  s = sigmoid(x W_r)                 128 wide
          top = the 6 largest of s + b       b takes no gradient
          w_e = 2.448 * s_e / (sum_{e in top} s_e + 1e-20)
          y = sum_{e in top, e held here} w_e * D_e(silu(G_e x) * U_e x)
              + Shared(x)                    one SwiGLU of width 2 * 768
    SwiGLU: W_down(silu(W_gate x) * (W_up x))
    RMS(x) = x / sqrt(mean(x^2) + eps) * w

The share: this chip holds experts ``first_expert .. first_expert + held`` of
each sparse layer and a slice of the vocabulary. The router is whole and the
weights are normalised over all six chosen experts; the sum runs over the
chosen experts that are held, every token through every held expert with a
weight that is zero where it was not chosen. What the other chips' experts
would add is left out, here as in the program, and that partial result goes on
to the next layer (`tests/benchmark/test_moe_lm.py` adds the eight shares up
to the uncut layer).

Departures from the published model, each deliberate: random weights from the
seed; no sequence-wise balance loss (alpha 1e-4 in the V3 paper), no
multi-token prediction (the config has none), no checkpoint, no generation;
``b`` is an input (the program moves it after each step, this file does not).
Parameters come from the ``nnx`` state of the model under test through
:func:`params_from_state`, cast to float32. Every matmul goes through
:data:`matmul` (the low-precision control swaps it), :func:`hidden_states` and
:func:`loss_of_hidden` take ``wrap``, applied to a layer and to the
cross-entropy, and :func:`hidden_states` takes ``attend``, the attention core:
all identities of what is computed. The comparison on the chip passes
``jax.checkpoint`` and :func:`in_blocks`, which computes the same scores per
sequence, head group and block of query rows: (2, 32, 8192, 8192) float32
scores are 17 GB otherwise.

It shares no code with ``jimm_tpu``. Callers wrap these functions in
``jax.default_matmul_precision("highest")``.

Tolerances, and why. The model keeps parameters and activations in bfloat16
through 6 layers; the last output passes the final RMSNorm, so ``hidden`` and
``logits`` (``max|a - b| / max|b|``) are O(1) quantities. ``loss`` is
relative; a gradient leaf is ``||a - b|| / ||b||``. ``routing`` is, per sparse
layer, the share of (token, slot) choices on which the two sides differ (the
chosen sets compared as sorted ids): a bfloat16 hidden state moves a score
s + b by about 1e-3 and flips the sixth choice where two scores lie that
close, a few percent of the tokens, more in later layers; the limit is on the
worst layer. Every other number is compared with the reference computed for
the model's own choices (:func:`moe`, ``forced``), so that it reads the
arithmetic and not how often a sixth choice flips. Each limit lies between what the chip showed for the bfloat16
model over its seeds and what the same comparison reads with every matmul
operand of this reference rounded to float8 (e4m3) (PERF.md section 6, PR 32,
has both readings).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: see the module docstring. Beside each limit: the largest the chip showed for
#: the bfloat16 model | the float8 control's smallest (PERF.md, Findings, PR
#: 32). ``loss`` is the accepted LM cell's limit: the two losses agree to 1e-5
#: in either precision (a mean over 16,384 positions), so it separates nothing
#: and guards only a loss that is wrong
TOLERANCE = {"hidden": 5e-2,    # 0.0200 | 0.083
             "logits": 5e-2,    # 0.0202 | 0.092
             "loss": 2e-4,      # 1.3e-5 | 1.5e-5
             "routing": 3e-2,   # 0.0178 | 0.053
             "grads": {"embedding": 4e-2,          # 0.0221 | 0.073
                       "middle_layer_kvb": 3.6e-2,  # 0.0186 | 0.071
                       "router": 5.5e-2,           # 0.0292 | 0.105
                       "expert_down": 4.4e-2,      # 0.0226 | 0.085
                       "head": 3.2e-2}}            # 0.0180 | 0.057

#: every matmul of the reference (the low-precision reading swaps it)
matmul = jnp.matmul


def _identity(fn):
    return fn


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotate_pairs(x, theta):
    """Rotary positions on (B, S, N, D): element 2i turns with element
    2i + 1 by ``position * theta**(-2i / D)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def causal_attention(q, k, v, first_row=0):
    """``softmax(causal(q k^T / sqrt(D))) v`` by explicit scores. q is
    (B, Sq, N, D), the rows ``first_row ..`` of a sequence whose keys and
    values are k (B, S, N, D) and v (B, S, N, Dv)."""
    scores = matmul(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1)) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    rows = first_row + jnp.arange(q.shape[1])
    mask = jnp.arange(k.shape[1])[None, :] <= rows[:, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return matmul(probs, v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)


def in_blocks(attend, heads: int, rows: int, wrap=_identity):
    """``attend`` computed per sequence, per group of ``heads`` heads and per
    block of ``rows`` query rows (every row still sees all its keys at once):
    the same numbers, never more than (heads, rows, S) scores alive."""
    one = wrap(attend)

    def blocked(q, k, v):
        b, s, n, _ = q.shape
        groups, blocks = n // heads, s // rows

        def split(x):
            return x.reshape(b, s, groups, heads, -1).transpose(0, 2, 1, 3, 4) \
                .reshape(b * groups, s, heads, -1)

        def one_group(args):
            qg, kg, vg = args

            def one_block(args):
                r, qb = args
                return one(qb[None], kg[None], vg[None], r * rows)[0]

            out = jax.lax.map(one_block, (
                jnp.arange(blocks), qg.reshape(blocks, rows, heads, -1)))
            return out.reshape(s, heads, -1)

        o = jax.lax.map(one_group, (split(q), split(k), split(v)))
        return o.reshape(b, groups, s, heads, -1).transpose(0, 2, 1, 3, 4) \
            .reshape(b, s, n, -1)

    return blocked


def mla(x, p, sizes, attend=causal_attention):
    b, s, _ = x.shape
    n = sizes["num_attention_heads"]
    d_n, d_r = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    d_v, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    theta = sizes["rope_theta"]
    q = matmul(x, p["q"]["kernel"]).reshape(b, s, n, d_n + d_r)
    kv_a = matmul(x, p["kv_a"]["kernel"])
    latent = rms_norm(kv_a[..., :rank], p["kv_norm"]["scale"],
                      sizes["rms_norm_eps"])
    kv = matmul(latent, p["kv_b"]["kernel"]).reshape(b, s, n, d_n + d_v)
    q = jnp.concatenate([q[..., :d_n], rotate_pairs(q[..., d_n:], theta)], -1)
    k_r = rotate_pairs(kv_a[..., rank:][:, :, None, :], theta)
    k = jnp.concatenate([kv[..., :d_n],
                         jnp.broadcast_to(k_r, (b, s, n, d_r))], -1)
    o = attend(q, k, kv[..., d_n:])
    return matmul(o.reshape(b, s, n * d_v), p["out"]["kernel"])


def swiglu(x, gate, up, down):
    return matmul(jax.nn.silu(matmul(x, gate)) * matmul(x, up), down)


def route(x, p, sizes, forced=None):
    """``(chosen (T, k) expert ids among ALL experts, their weights (T, k),
    this router's own choice)`` for tokens x (T, hidden). ``forced`` puts
    another choice in the place of the router's own (see :func:`moe`)."""
    scores = jax.nn.sigmoid(matmul(x, p["router"]))
    _, own = jax.lax.top_k(scores + p["router_bias"],
                           sizes["num_experts_per_tok"])
    chosen = own if forced is None else forced
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = sizes["routed_scaling_factor"] * picked \
        / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights, own


def moe(x, p, sizes, forced=None):
    """``(y, own)``: the held experts' part of the layer's result plus the
    shared experts, and the experts each token chose by this router. With
    ``forced (T, k)`` the result is computed for those choices instead (the
    scores, and so the weights, stay this router's): a top-k is
    discontinuous, so the comparison on the chip hands over the choices of the
    model under test and compares the two sides' own choices apart from the
    arithmetic."""
    xt = x.reshape(-1, x.shape[-1])
    chosen, weights, own = route(xt, p, sizes, forced)
    y = jnp.zeros_like(xt)
    for e in range(p["gate"].shape[0]):  # every token through every held expert
        w_e = jnp.sum(jnp.where(chosen == sizes["first_expert"] + e,
                                weights, 0.0), axis=-1)
        y = y + w_e[:, None] * swiglu(xt, p["gate"][e], p["up"][e],
                                      p["down"][e])
    shared = p["shared"]
    y = y + swiglu(xt, shared["gate"]["kernel"], shared["fc1"]["kernel"],
                   shared["fc2"]["kernel"])
    return y.reshape(x.shape), own


def layer(x, p, sizes, attend=causal_attention, forced=None):
    """One layer, dense or sparse by what ``p`` holds: ``(x, the router's
    own choices or None)``."""
    eps = sizes["rms_norm_eps"]
    a = x + mla(rms_norm(x, p["ln1"]["scale"], eps), p["attn"], sizes, attend)
    h = rms_norm(a, p["ln2"]["scale"], eps)
    if "router" in p["mlp"]:
        m, chosen = moe(h, p["mlp"], sizes, forced)
        return a + m, chosen
    mlp = p["mlp"]
    return a + swiglu(h, mlp["gate"]["kernel"], mlp["fc1"]["kernel"],
                      mlp["fc2"]["kernel"]), None


def hidden_states(params, tokens, sizes, wrap=_identity,
                  attend=causal_attention, forced=None):
    """``(the final-normed output (B, S, hidden), [the router's own choices
    (T, k) of each sparse layer])``; ``forced``, one ``(T, k)`` per sparse
    layer, as in :func:`moe`."""
    one_layer = wrap(lambda x, p, forced: layer(x, p, sizes, attend, forced))
    x = params["embed"]["embedding"][tokens]
    for p in params["dense"]["blocks"]:
        x, _ = one_layer(x, p, None)
    routing = []
    for i, p in enumerate(params["sparse"]["blocks"]):
        x, own = one_layer(x, p, None if forced is None else forced[i])
        routing.append(own)
    return rms_norm(x, params["norm"]["scale"], sizes["rms_norm_eps"]), routing


def logits(params, h):
    return matmul(h, params["head"]["kernel"])


def cross_entropy(params, h, targets):
    """Per-position softmax cross-entropy, (B, S)."""
    logp = jax.nn.log_softmax(logits(params, h), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def loss_of_hidden(params, h, targets, wrap=_identity):
    return jnp.mean(wrap(lambda h: cross_entropy(params, h, targets))(h))


def loss(params, tokens, sizes, wrap=_identity, attend=causal_attention):
    """Mean next-token cross-entropy on (B, S + 1) ids: inputs are the first
    S, targets the ids shifted by one."""
    h, _ = hidden_states(params, tokens[:, :-1], sizes, wrap, attend)
    return loss_of_hidden(params, h, tokens[:, 1:], wrap)


# ---------------------------------------------------------------------------
# The name map: nnx state of the model under test -> reference parameters
# ---------------------------------------------------------------------------

def params_from_state(pure: dict, router_bias, device=None) -> dict:
    """``nnx.to_pure_dict(nnx.state(model, nnx.Param))`` and the routers'
    selection biases ``(sparse layers, experts)`` -> float32 reference
    parameters under the same names (a stack's ``blocks``, stacked on a
    leading layer axis by the program, becomes a list of layers), on
    ``device``."""

    def to_f32(a):
        a = jnp.asarray(a).astype(jnp.float32)
        return jax.device_put(a, device) if device is not None else a

    out = jax.tree.map(to_f32, pure)
    for stack in ("dense", "sparse"):
        stacked = out[stack]["blocks"]
        depth = jax.tree.leaves(stacked)[0].shape[0]
        out[stack] = {**out[stack], "blocks": [
            jax.tree.map(lambda a, i=i: a[i], stacked) for i in range(depth)]}
    for p, bias in zip(out["sparse"]["blocks"], to_f32(router_bias),
                       strict=True):
        p["mlp"]["router_bias"] = bias
    return out


#: the leaves whose gradients are compared, by path in the model's state;
#: ``{mid}`` is the middle sparse layer
GRAD_LEAVES = {
    "embedding": "embed/embedding",
    "middle_layer_kvb": "sparse/blocks/{mid}/attn/kv_b/kernel",
    "router": "sparse/blocks/{mid}/mlp/router",
    "expert_down": "sparse/blocks/{mid}/mlp/down",
    "head": "head/kernel",
}
