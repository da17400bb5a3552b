"""Plain float32 reference: Trinity-Large-Preview (``model_type`` afmoe) as
ONE chip of a 32-way expert-parallel group holds it, its next-token loss and
its gradients.

Straight ``jax.numpy``: explicit Python loops over layers and over the held
experts, attention by explicit scores under an explicit boolean mask, key and
value heads repeated by ``jnp.repeat``, rotary written from its equations, no
sort, no grouped product, no scan, no kernels, no sharding. From the published
``config.json``:

    x = E[tokens] * sqrt(hidden)                            (mup_enabled)
    layer l:   a = x + RMS_l1'(Attn_l(RMS_l1(x)))
               x = a + RMS_l2'(FFN_l(RMS_l2(a)))            four norms a layer
               FFN_l = SwiGLU (width 12288) for l < num_dense_layers,
                       MoE otherwise
    z = RMS_f(x) W_head                                     head untied from E
    loss = mean over positions of CE(z, next token)

    Attn: q = x W_q -> (B, S, 48, 128);  k, v = x W_k, x W_v -> (B, S, 8, 128)
          g = x W_gate -> (B, S, 6144)
          q, k = RMS_128(q), RMS_128(k)     one learned 128-vector each
          layer l is a full_attention layer iff (l + 1) % 4 == 0, else a
          sliding_attention layer. Sliding only: q, k = rotary(q), rotary(k),
              element i of a head turns with element i + 64 by
              position * 10000**(-2i / 128)
          query head h reads key/value head h // 6
          key j is visible to query i iff j <= i and, sliding only,
              i - j < 4096
          o = softmax(where(visible, q k^T / sqrt(128), -inf)) v
          out = (o.reshape(B, S, 6144) * sigmoid(g)) W_o
          A full layer takes NO rotary and no other position signal.
    MoE:  s = sigmoid(x W_r)                 256 wide
          top = the 4 largest of s + b       b takes no gradient
          w_e = 2.448 * s_e / (sum_{e in top} s_e + 1e-20)
          y = sum_{e in top, e held here} w_e * D_e(silu(G_e x) * U_e x)
              + Shared(x)                    one SwiGLU of width 3072
    SwiGLU: W_down(silu(W_gate x) * (W_up x))
    RMS(x) = x / sqrt(mean(x^2) + eps) * w

The share: this chip holds published layers ``first_layer ..`` (5-9: the last
dense layer and four sparse ones), experts ``first_expert .. first_expert +
held`` of each sparse layer and a slice of the vocabulary. The router is whole
and the weights are normalised over all four chosen experts; the sum runs over
the chosen experts that are held, every token through every held expert with a
weight that is zero where it was not chosen. What the other chips' experts
would add is left out, here as in the program, and that partial result goes on
to the next layer (`tests/benchmark/test_gqa_moe_lm.py` adds the 32 shares up
to the uncut layer).

Departures from the published model, each deliberate: random weights from the
seed (the program starts the gains of the sandwich's output norms at
1 / sqrt(60): its reading of "depth-scaled", the constants not being public;
this file takes the gains it is given); no balance loss (load_balance_coeff
5e-5); ``b`` is an input (the program moves it after each step by the sign
rule, not by the model's own momentum rule, whose constants are not public;
this file does not move it); no checkpoint, no generation.

Parameters come from the ``nnx`` state of the model under test through
:func:`params_from_state`, cast to float32. Every matmul goes through
:data:`matmul` (the low-precision control swaps it); :data:`IGNORE_WINDOW` and
:data:`ROPE_ON_FULL` are the two mechanism controls' switches (a reference with
either on must be refused). :func:`hidden_states` and :func:`loss_of_hidden`
take ``wrap``, applied to a layer and to the cross-entropy, and
:func:`hidden_states` takes ``attend``, the attention core: all identities of
what is computed. The comparison on the chip passes ``jax.checkpoint`` and
:func:`in_blocks`, which computes the same scores per sequence, key/value head
and block of query rows: (1, 48, 8192, 8192) float32 scores are 12.9 GB
otherwise.

It shares no code with ``jimm_tpu``. Callers wrap these functions in
``jax.default_matmul_precision("highest")``.

Tolerances, and why. The model keeps parameters and activations in bfloat16
through 5 layers. ``hidden`` (after the final RMSNorm) and every gradient leaf
are ``||a - b|| / ||b||``; ``logits`` is ``max|a - b| / max|b|``; ``loss`` is
relative. ``routing`` is the mean over the sparse layers of the share of
(token, slot) choices on which the two sides' own routers differ: a bfloat16
hidden state moves a score s + b by about 1e-3 and flips the fourth choice
where two scores lie that close, one choice in a hundred. Every other number
is compared with the reference computed for the model's own choices
(:func:`moe`, ``forced``), so that it reads the arithmetic and not how often a
fourth choice flips. Each limit lies between the largest the chip showed for
the bfloat16 model over its seeds and what the same comparison reads with every
matmul operand of this reference rounded to float8 (e4m3) (PERF.md section 6,
PR 34, has both readings, and the two mechanism controls'). The float8 reading
stands close over the bfloat16 one in ``hidden`` and ``head`` (the scaled
embedding, which no matmul touches, carries most of the final state), so those
two limits have little room; both are norms over tens of millions of numbers
and moved by 2 % over the seeds. The float8 reference is refused by ``logits``
and the other gradients with room to spare, the window-ignoring one and the one
with rotary on the full layer by ``hidden`` (0.066 and 0.0126) and most others.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Beside each limit: the bfloat16 model's largest of 12 seeds | float8 | window
#: ignored | rotary on the full layer (PERF.md, PR 34). float8 is refused by
#: ``logits``, ``router``, ``expert_down``; the others lie under the other two
TOLERANCE = {"hidden": 1.2e-2,   # 0.0058 | 0.0062 | 0.048 | 0.026
             "logits": 1.7e-2,   # 0.0063 | 0.047 | 0.081 | 0.065
             "loss": 2e-4,       # 1.8e-5 | 6.7e-5: it separates nothing
             "routing": 1.25e-2,  # 0.0068 | 0.0069 | 0.043 | 0.025
             "grads": {"embedding": 1.9e-2,        # 0.0067 | 0.012 | 0.079 | 0.054
                       "window_layer_k": 3.3e-2,   # 0.0122 | 0.016 | 0.230 | 0.094
                       "full_layer_gate": 2.3e-2,  # 0.0098 | 0.013 | 0.061 | 0.267
                       "router": 2.5e-2,           # 0.0116 | 0.071 | 0.092 | 0.055
                       "expert_down": 2e-2,        # 0.0084 | 0.048 | 0.069 | 0.053
                       "head": 1.3e-2}}            # 0.0069 | 0.0073 | 0.048 | 0.026

#: what a ``--rehearse`` run is held to: a bfloat16 model 64 wide with 32
#: tokens reads other numbers than the cell's (fewer numbers under each norm,
#: one flipped choice in 128), and a rehearsal checks the plumbing, not the chip
REHEARSAL_TOLERANCE = {"hidden": 5e-2, "logits": 5e-2, "loss": 2e-4,
                       "routing": 3e-2,
                       "grads": dict.fromkeys(TOLERANCE["grads"], 6e-2)}

#: every matmul of the reference (the low-precision reading swaps it)
matmul = jnp.matmul
#: the mechanism controls: a reference that ignores the window on the sliding
#: layers, and one that applies rotary on the full layers too
IGNORE_WINDOW = False
ROPE_ON_FULL = False


def _identity(fn):
    return fn


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotate_half(x, theta):
    """Rotary positions on (B, S, N, D): element i turns with element
    i + D/2 by ``position * theta**(-2i / D)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def visible(rows, keys, window):
    """The boolean mask ``(len(rows), len(keys))``: key j is visible to query
    i iff ``j <= i`` and, under a ``window``, ``i - j < window``."""
    i, j = rows[:, None], keys[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (i - j < window)
    return mask


def attention(q, k, v, window=None, first_row=0):
    """``softmax(where(visible, q k^T / sqrt(D), -inf)) v`` by explicit
    scores. q is (B, Sq, N, D), the rows ``first_row ..`` of a sequence whose
    keys and values are k, v (B, S, N_kv, D); query head h reads key/value
    head ``h // (N / N_kv)``."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = matmul(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1)) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    mask = visible(first_row + jnp.arange(q.shape[1]), jnp.arange(k.shape[1]),
                   window)
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return matmul(probs, v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)


def in_blocks(attend, rows: int, wrap=_identity):
    """``attend`` computed per sequence, per key/value head (with the query
    heads that read it) and per block of ``rows`` query rows (every row still
    sees all its keys at once): the same numbers, never more than
    (group, rows, S) scores alive."""
    one = wrap(attend, static_argnums=(3,)) if wrap is not _identity \
        else attend

    def blocked(q, k, v, window=None):
        b, s, n, d = q.shape
        n_kv = k.shape[2]
        group, blocks = n // n_kv, s // rows

        def per_head(x, heads):
            # (B, S, n_kv * heads, D) -> (B * n_kv, S, heads, D)
            return x.reshape(b, s, n_kv, heads, -1).transpose(0, 2, 1, 3, 4) \
                .reshape(b * n_kv, s, heads, -1)

        def one_head(args):
            qg, kg, vg = args

            def one_block(args):
                r, qb = args
                return one(qb[None], kg[None], vg[None], window, r * rows)[0]

            out = jax.lax.map(one_block, (
                jnp.arange(blocks), qg.reshape(blocks, rows, group, d)))
            return out.reshape(s, group, -1)

        o = jax.lax.map(one_head, (per_head(q, group), per_head(k, 1),
                                   per_head(v, 1)))
        return o.reshape(b, n_kv, s, group, -1).transpose(0, 2, 1, 3, 4) \
            .reshape(b, s, n, -1)

    return blocked


def gqa(x, p, sizes, full: bool, attend=attention):
    """One layer's attention; ``full``: a full_attention layer."""
    b, s, _ = x.shape
    n, n_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    q = matmul(x, p["q"]["kernel"]).reshape(b, s, n, d)
    k = matmul(x, p["k"]["kernel"]).reshape(b, s, n_kv, d)
    v = matmul(x, p["v"]["kernel"]).reshape(b, s, n_kv, d)
    gate = matmul(x, p["gate"]["kernel"])
    q = rms_norm(q, p["q_norm"]["scale"], eps)
    k = rms_norm(k, p["k_norm"]["scale"], eps)
    if not full or ROPE_ON_FULL:
        q, k = (rotate_half(t, sizes["rope_theta"]) for t in (q, k))
    window = None if full or IGNORE_WINDOW else sizes["sliding_window"]
    o = attend(q, k, v, window)
    return matmul(o.reshape(b, s, n * d) * jax.nn.sigmoid(gate),
                  p["out"]["kernel"])


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
    weights = sizes["route_scale"] * picked \
        / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights, own


def moe(x, p, sizes, forced=None):
    """``(y, own)``: the held experts' part of the layer's result plus the
    shared expert, and the experts each token chose by this router. With
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


def layer(x, p, sizes, full: bool, attend=attention, forced=None):
    """One layer, dense or sparse by what ``p`` holds: ``(x, the router's
    own choices or None)``. Four norms: before and after each sub-layer."""
    eps = sizes["rms_norm_eps"]
    a = x + rms_norm(
        gqa(rms_norm(x, p["ln1"]["scale"], eps), p["attn"], sizes, full,
            attend), p["ln1_post"]["scale"], eps)
    h = rms_norm(a, p["ln2"]["scale"], eps)
    chosen = None
    if "router" in p["mlp"]:
        m, chosen = moe(h, p["mlp"], sizes, forced)
    else:
        mlp = p["mlp"]
        m = swiglu(h, mlp["gate"]["kernel"], mlp["fc1"]["kernel"],
                   mlp["fc2"]["kernel"])
    return a + rms_norm(m, p["ln2_post"]["scale"], eps), chosen


def is_full(index: int, sizes) -> bool:
    """Whether held layer ``index`` (published layer ``first_layer + index``)
    is a full_attention layer."""
    every = sizes["global_attn_every_n_layers"]
    return (sizes["first_layer"] + index + 1) % every == 0


def hidden_states(params, tokens, sizes, wrap=_identity, attend=attention,
                  forced=None):
    """``(the final-normed output (B, S, hidden), [the router's own choices
    (T, k) of each sparse layer])``; ``forced``, one ``(T, k)`` per sparse
    layer, as in :func:`moe`."""
    def one_layer(x, p, full, forced):
        fn = wrap(lambda x, p, forced: layer(x, p, sizes, full, attend,
                                             forced))
        return fn(x, p, forced)

    x = params["embed"]["embedding"][tokens] \
        * jnp.sqrt(jnp.float32(sizes["hidden_size"]))
    index = 0
    for p in params["dense"]["blocks"]:
        x, _ = one_layer(x, p, is_full(index, sizes), None)
        index += 1
    routing = []
    for i, p in enumerate(params["sparse"]["blocks"]):
        x, own = one_layer(x, p, is_full(index, sizes),
                           None if forced is None else forced[i])
        routing.append(own)
        index += 1
    return rms_norm(x, params["norm"]["scale"], sizes["rms_norm_eps"]), routing


def logits(params, h):
    return matmul(h, params["head"]["kernel"])


def cross_entropy(params, h, targets):
    """Per-position softmax cross-entropy, (B, S)."""
    logp = jax.nn.log_softmax(logits(params, h), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def loss_of_hidden(params, h, targets, wrap=_identity):
    return jnp.mean(wrap(lambda h: cross_entropy(params, h, targets))(h))


def loss(params, tokens, sizes, wrap=_identity, attend=attention):
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
#: ``{window}`` is a sparse windowed layer (its W_k is a grouped leaf: the
#: gradient sums six query heads), ``{full}`` the sparse stack's full layer
GRAD_LEAVES = {
    "embedding": "embed/embedding",
    "window_layer_k": "sparse/blocks/{window}/attn/k/kernel",
    "full_layer_gate": "sparse/blocks/{full}/attn/gate/kernel",
    "router": "sparse/blocks/{full}/mlp/router",
    "expert_down": "sparse/blocks/{window}/mlp/down",
    "head": "head/kernel",
}
