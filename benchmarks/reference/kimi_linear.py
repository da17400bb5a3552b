"""Plain float32 reference: Kimi-Linear-48B-A3B-Instruct (``model_type``
kimi_linear) as ONE chip of a 16-way expert-parallel group holds its first
layers, its next-token loss and its gradients.

Straight ``jax.numpy``: explicit Python loops over layers and over the held
experts, the linear-attention state advanced TOKEN BY TOKEN (no chunks, no
triangular solve, no WY form), latent attention by explicit scores under an
explicit causal mask, no sort, no grouped product, no kernels, no sharding.
From the published ``config.json``, the Kimi Linear report (arXiv:2510.26692)
and the family's open ``fla`` layer:

    x = E[tokens]                                        the embedding unscaled
    layer l:   a = x + Mixer_l(RMS_l1(x))
               x = a + FFN_l(RMS_l2(a))                  pre-norm, eps 1e-5
               Mixer_l = KDA for l in kda_layers, MLA for l in full_attn_layers
               FFN_l = SwiGLU (width 9216) for l = 1, MoE for l = 2..27
    z = RMS_f(x) W_head                                  head untied from E
    loss = mean over positions of CE(z, next token)

    KDA, per head h of 32, d_k = d_v = 128:
      q~, k~, v = SiLU(conv4(x W_q)), SiLU(conv4(x W_k)), SiLU(conv4(x W_v))
          conv4: causal, depthwise, 4 taps, no bias:
          y_t = sum_{i=0..3} w_i u_{t-3+i},  u_{<0} = 0
      q_t = q~_t / ||q~_t||_2 * d_k^-1/2     k_t = k~_t / ||k~_t||_2
          the norm over a head's 128 channels, sqrt(sum + 1e-6)
      g_t = -exp(A_log_h) * softplus((x W_f1 W_f2)_t + dt_bias)  in R^128, <= 0
      b_t = sigmoid((x W_b)_t)_h                                 in (0, 1)
      S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
          S in R^(128 x 128), S_0 = 0
      o_t = S_t^T q_t
      y_t = [RMSNorm_128(o_t) * sigmoid((x W_g1 W_g2)_t)] W_o
          the norm per head with one learned 128-vector
    MLA (no q low-rank), 32 heads, q / k 128 + 64, v 128, latent 512:
      q = x W_q -> (B, S, 32, 192);  [c | k_s] = x W_kva -> 512 + 64
      [k_n | v] = RMS_512(c) W_kvb -> (B, S, 32, 128 + 128)
      k = [k_n | k_s broadcast over the heads]
      o = softmax(causal(q k^T / sqrt(192))) v;  out = o W_o
      NO rotary on either 64-wide part (mla_use_nope): no position signal
    MoE:  s = sigmoid(x W_r)                 256 wide
          top = the 8 largest of s + b       b takes no gradient
          w_e = 2.446 * s_e / (sum_{e in top} s_e + 1e-20)
          y = sum_{e in top, e held here} w_e * D_e(silu(G_e x) * U_e x)
              + Shared(x)                    one SwiGLU of width 1024
    SwiGLU: W_down(silu(W_gate x) * (W_up x))
    RMS(x) = x / sqrt(mean(x^2) + eps) * w

The share: this chip holds published layers 1-5, experts ``first_expert ..
first_expert + held`` of each sparse layer and a slice of the vocabulary. The
router is whole and the weights are normalised over all eight chosen experts;
the sum runs over the chosen experts that are held. What the other chips'
experts would add is left out, here as in the program
(`tests/benchmark/test_hybrid_lm.py` adds the 16 shares up to the uncut layer).

Departures from the published description, each deliberate: random weights
from the seed (``A_log`` from log U(1, 16), ``dt_bias`` zeros, the taps from
U(-1/2, 1/2): what the program's initialisers give; this file takes what it is
handed); the gates' low rank is the head width (not in ``config.json``); ``b``
is an input (the program moves it after each step by the repo's sign rule;
this file does not move it); no checkpoint, no generation, no reset of the
state at a document boundary.

Parameters come from the ``nnx`` state of the model under test through
:func:`params_from_state`, cast to float32. Every matmul goes through
:data:`matmul` (the low-precision control swaps it), the state's reads and
writes included; :data:`STATE_BF16` is the mechanism control's switch: the
carried state rounded to bfloat16 after every token (a reference with it on
must be refused). :func:`hidden_states` and :func:`loss_of_hidden` take
``wrap``, applied to a layer, to a block of the recurrence and to the
cross-entropy, and :func:`hidden_states` takes ``attend``, the latent
attention's core: all identities of what is computed. The comparison on the
chip passes ``jax.checkpoint`` and :func:`in_blocks`: (1, 32, 16384, 16384)
float32 scores are 34 GB otherwise, and the recurrence's 16,384 states a layer
as many.

It shares no code with ``jimm_tpu``. Callers wrap these functions in
``jax.default_matmul_precision("highest")``.

Tolerances, and why: see :data:`TOLERANCE`. ``hidden`` (after the final
RMSNorm) and every gradient leaf are ``||a - b|| / ||b||``; ``logits`` is
``max|a - b| / max|b|``; ``loss`` is relative; ``routing`` is the mean over the
sparse layers of the share of (token, slot) choices on which the two sides' own
routers differ; ``scan`` is ``||a - b|| / ||b||`` of the program's chunked
scan alone against :func:`delta_rule` on seeded inputs at the timed shape
(``parity_hybrid_lm.scan_error``); ``update`` and ``moment`` are
``||a - b|| / ||b||`` of the change that the timed program's own step makes
to the parameters and to Adam's first moment on ``GRAD_LEAVES`` against
:func:`adamw_step` from this file's gradients (``parity_hybrid_lm.timed_step``:
a state left as it was reads 1). Every other number is compared with the
reference computed for the model's own choices (:func:`moe`, ``forced``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Beside each limit: the bfloat16 model's largest over fourteen seeds | the
#: float8 reference's smallest over three seeds (my chip runs, PR 38, both
#: sessions; ``kda_dt_bias`` six | two seeds, ``update`` and ``moment`` five |
#: one: they came later; PERF.md section 6 has every reading). float8 reads
#: about twice the bfloat16 model in every number but ``loss``, ``scan`` and
#: ``update``; each such limit stands near the geometric mean of its two
#: readings, about 1.45 times the model's. From seed to seed the model's
#: readings move by under 2 % (``logits``, a maximum, by 20 %).
#: ``loss`` separates NOTHING and no limit can: the model read 1.0e-6 to
#: 4.3e-5, float8 8.1e-6, 3.2e-5 and 7.3e-5 on its three seeds, the
#: bfloat16-state reference 7.5e-6 and 6.9e-5: all inside one range, the noise
#: of a loss of 10.4 summed from bfloat16 logits (a limit of 3.5e-5 would have
#: refused two sound runs of the fourteen and passed two of the three float8
#: ones). It keeps the limit of the harness's accepted cells (``kanana.py``,
#: ``trinity.py``, ``ouro.py``: 2e-4), 118 times the first reading (1.7e-6),
#: and what it cannot see is ``update``'s and ``moment``'s to see: the change
#: the TIMED step makes to the parameters (the eight leaves pooled) and to Adam's
#: first moment (the worst leaf) against the reference's AdamW from the
#: reference's gradients (``parity_hybrid_lm.timed_step``); a state left as it
#: was reads 1 in both. ``update`` reads over a tenth because a bfloat16 weight
#: moves by whole units in its last place (16-30 % of a matrix's weights move
#: in a step at rate 1e-4): what is compared is which ones were rounded up.
#: ``scan`` reads float32 rounding (the chunked form against the recurrence);
#: a reference whose recurrence keeps its state in bfloat16 reads 1.67e-3
#: there at the timed shape and is refused by it alone
TOLERANCE = {"hidden": 3.6e-2,    # 0.0250 | 0.0492
             "logits": 5e-2,      # 0.0342 | 0.0707
             "loss": 2e-4,        # 4.3e-5 | 8.1e-6
             "routing": 2.9e-2,   # 0.0207 | 0.0379
             "scan": 1e-4,        # 8.8e-7 | (the state control) 1.67e-3
             "update": 0.145,     # 0.1233 | 0.1675
             "moment": 7.5e-2,    # 0.0459 | 0.1195
             "grads": {"embedding": 5.5e-2,     # 0.0356 | 0.0863
                       "kda_q": 5.5e-2,         # 0.0372 | 0.0862
                       "kda_dt_bias": 5.4e-2,   # 0.0368 | 0.0782
                       "kda_conv": 5.5e-2,      # 0.0376 | 0.0853
                       "mla_kvb": 4e-2,         # 0.0261 | 0.0574
                       "router": 7.5e-2,        # 0.0457 | 0.1122
                       "expert_down": 5.5e-2,   # 0.0335 | 0.0903
                       "head": 3.7e-2}}         # 0.0258 | 0.0497

#: what a ``--rehearse`` run is held to. The driver rehearses in float32: a
#: bfloat16 model 64 wide with 32 tokens read 0.05-0.09 in ``hidden`` and
#: 0.1-1.4 on the gradient leaves on the CPU (every normalisation of a small
#: linear-attention layer amplifies rounding noise, and five layers compound
#: it), which checks nothing, where the float32 model reads 1e-5
REHEARSAL_TOLERANCE = {"hidden": 1e-3, "logits": 1e-3, "loss": 1e-4,
                       "routing": 1e-2, "scan": 1e-4,
                       "update": 5e-2, "moment": 5e-2,
                       "grads": dict.fromkeys(TOLERANCE["grads"], 5e-3)}

#: every matmul of the reference (the low-precision control swaps it)
matmul = jnp.matmul
#: the mechanism control: the recurrence's carried state rounded to bfloat16
#: after every token
STATE_BF16 = False
#: tokens of one block of the recurrence (``wrap`` is applied to a block:
#: under ``jax.checkpoint`` the backward keeps one state a block, not a token)
STATE_BLOCK = 128


def _identity(fn):
    return fn


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def conv4(u, taps):
    """Causal depthwise convolution of (B, S, C) with taps (width, C):
    ``y_t = sum_i taps_i u_{t - width + 1 + i}``, zeros before the start.

    Written with one pad and ``width`` slices ON PURPOSE. Written as ``width``
    shifts, each ``concatenate([zeros, u[:, :-back]])``, it is the same
    function, and XLA:TPU (libtpu 0.0.34) compiled it wrongly INSIDE the whole
    layer's program at 16,384 tokens: tokens 8192-8194 read other inputs
    (``||a - b|| / ||b||`` of the layer's output 0.83, 0.75, 0.52 there, 3e-7
    everywhere else; compiled alone the same function equals numpy to the
    bit; PERF.md, PR 38). The comparison then blamed the program for the
    reference's fault."""
    width, s = taps.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    y = jnp.zeros_like(u)
    for i in range(width):
        y = y + taps[i] * padded[:, i:i + s]
    return y


def delta_rule(q, k, v, g, b, wrap=_identity):
    """The recurrence, one token at a time. q, k, g (B, S, H, D), v
    (B, S, H, Dv), b (B, S, H) -> o (B, S, H, Dv)."""
    batch, s, h, d = k.shape

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None]
        held = matmul(k_t[..., None, :], state)[..., 0, :]        # k^T S
        state = state + k_t[..., :, None] \
            * (b_t[..., None] * (v_t - held))[..., None, :]
        if STATE_BF16:
            # not a cast there and back: XLA may drop that pair as "excess
            # precision" (on the chip it did: the control read what the plain
            # reference reads, to the last digit; PERF.md, PR 38)
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, matmul(q_t[..., None, :], state)[..., 0, :]  # S^T q

    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    block = wrap(block)
    size = STATE_BLOCK if s % STATE_BLOCK == 0 else s
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(s // size, size, *x.shape[:1],
                                             *x.shape[2:])
               for x in (q, k, v, g, b))
    _, o = jax.lax.scan(block, jnp.zeros((batch, h, d, v.shape[-1]),
                                         jnp.float32), xs)
    return jnp.moveaxis(o.reshape(s, batch, h, -1), 0, 1)


def kda(x, p, sizes, wrap=_identity):
    bsz, s, _ = x.shape
    lin = sizes["linear_attn_config"]
    n, d = lin["num_heads"], lin["head_dim"]

    def mixed(name):
        y = conv4(matmul(x, p[name]["kernel"]), p[f"{name}_conv"])
        return jax.nn.silu(y).reshape(bsz, s, n, d)

    def unit(t):
        return t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q = unit(mixed("q")) * d ** -0.5
    k = unit(mixed("k"))
    v = mixed("v")
    f = matmul(matmul(x, p["f_a"]["kernel"]), p["f_b"]["kernel"])
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        (f + p["dt_bias"]).reshape(bsz, s, n, d))
    b = jax.nn.sigmoid(matmul(x, p["b"]["kernel"]))
    o = delta_rule(q, k, v, g, b, wrap)
    o = rms_norm(o, p["o_norm"], sizes["rms_norm_eps"])
    gate = jax.nn.sigmoid(
        matmul(matmul(x, p["g_a"]["kernel"]), p["g_b"]["kernel"]))
    return matmul(o.reshape(bsz, s, n * d) * gate, p["out"]["kernel"])


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
    """Latent attention with NO position signal: the 64 "rotary" dims stay in
    the projections and in the 192-wide q and k, unrotated."""
    b, s, _ = x.shape
    n = sizes["num_attention_heads"]
    d_n, d_r = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    d_v, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    q = matmul(x, p["q"]["kernel"]).reshape(b, s, n, d_n + d_r)
    kv_a = matmul(x, p["kv_a"]["kernel"])
    latent = rms_norm(kv_a[..., :rank], p["kv_norm"]["scale"],
                      sizes["rms_norm_eps"])
    kv = matmul(latent, p["kv_b"]["kernel"]).reshape(b, s, n, d_n + d_v)
    k_s = kv_a[..., rank:][:, :, None, :]
    k = jnp.concatenate([kv[..., :d_n],
                         jnp.broadcast_to(k_s, (b, s, n, d_r))], -1)
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
                           sizes["num_experts_per_token"])
    chosen = own if forced is None else forced
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = sizes["routed_scaling_factor"] * picked \
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


def layer(x, p, sizes, attend=causal_attention, forced=None, wrap=_identity):
    """One layer: its mixer and its FFN by what ``p`` holds. ``(x, the
    router's own choices or None)``."""
    eps = sizes["rms_norm_eps"]
    h = rms_norm(x, p["ln1"]["scale"], eps)
    a = x + (kda(h, p["attn"], sizes, wrap) if "A_log" in p["attn"]
             else mla(h, p["attn"], sizes, attend))
    h = rms_norm(a, p["ln2"]["scale"], eps)
    if "router" in p["mlp"]:
        m, chosen = moe(h, p["mlp"], sizes, forced)
        return a + m, chosen
    mlp = p["mlp"]
    return a + swiglu(h, mlp["gate"]["kernel"], mlp["fc1"]["kernel"],
                      mlp["fc2"]["kernel"]), None


def run_names(params) -> list[str]:
    """The runs of like layers, in layer order (``run<first layer>``)."""
    return sorted((k for k in params if k.startswith("run")),
                  key=lambda k: int(k[3:]))


def layers(params) -> list[dict]:
    return [p for name in run_names(params) for p in params[name]["blocks"]]


def hidden_states(params, tokens, sizes, wrap=_identity,
                  attend=causal_attention, forced=None):
    """``(the final-normed output (B, S, hidden), [the router's own choices
    (T, k) of each sparse layer])``; ``forced``, one ``(T, k)`` per sparse
    layer, as in :func:`moe`."""
    def one_layer(x, p, forced):
        return wrap(lambda x, p, forced: layer(x, p, sizes, attend, forced,
                                               wrap))(x, p, forced)

    x = params["embed"]["embedding"][tokens]
    routing = []
    for p in layers(params):
        sparse = "router" in p["mlp"]
        x, own = one_layer(x, p, forced[len(routing)]
                           if sparse and forced is not None else None)
        if sparse:
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
# The optimizer's step, in numpy on the host
# ---------------------------------------------------------------------------

#: the step the cell's program states: `jimm-tpu train`'s AdamW under a clip of
#: the gradients' global norm, the family's learning rate ramped linearly over
#: ``warmup_steps`` (or over the whole run less one step if that is shorter)
#: and then on a cosine to zero at the run's last step; decay on matrices only
OPTIMIZER = {"lr": 1e-4, "warmup_steps": 20, "b1": 0.9, "b2": 0.999,
             "eps": 1e-8, "weight_decay": 1e-4, "clip_norm": 1.0}


def learning_rate(count: int, steps: int) -> float:
    """The rate of the update numbered ``count`` (from 0) of a run of
    ``steps`` updates."""
    import math
    warm = min(OPTIMIZER["warmup_steps"], max(steps - 1, 0))
    if count < warm:
        return OPTIMIZER["lr"] * count / warm
    span = max(steps - warm, 1)
    return OPTIMIZER["lr"] * 0.5 * (
        1.0 + math.cos(math.pi * min(count - warm, span) / span))


def adamw_step(p, m, v, g, *, count: int, steps: int, grad_norm: float):
    """``(the change of p, the new first moment)`` of update ``count`` of a
    run of ``steps``, float32 numpy: p a parameter, m and v its moments as the
    optimizer holds them, g its gradient, ``grad_norm`` the global norm of
    ALL gradients (the clip's one number)."""
    import numpy as np
    o = OPTIMIZER
    g = g * np.float32(min(1.0, o["clip_norm"] / max(grad_norm, 1e-30)))
    m = o["b1"] * m + (1.0 - o["b1"]) * g
    v = o["b2"] * v + (1.0 - o["b2"]) * g * g
    m_hat = m / (1.0 - o["b1"] ** (count + 1))
    v_hat = v / (1.0 - o["b2"] ** (count + 1))
    step = m_hat / (np.sqrt(v_hat) + o["eps"])
    if p.ndim > 1:
        step = step + o["weight_decay"] * p
    return (-learning_rate(count, steps) * step).astype(np.float32), \
        m.astype(np.float32)


# ---------------------------------------------------------------------------
# The name map: nnx state of the model under test -> reference parameters
# ---------------------------------------------------------------------------

def params_from_state(pure: dict, router_bias, device=None) -> dict:
    """``nnx.to_pure_dict(nnx.state(model, nnx.Param))`` and the routers'
    selection biases ``(sparse layers, experts)``, in layer order -> float32
    reference parameters under the same names (a run's ``blocks``, stacked on
    a leading layer axis by the program, becomes a list of layers), on
    ``device``."""

    def to_f32(a):
        a = jnp.asarray(a).astype(jnp.float32)
        return jax.device_put(a, device) if device is not None else a

    out = jax.tree.map(to_f32, pure)
    for name in run_names(out):
        stacked = out[name]["blocks"]
        depth = jax.tree.leaves(stacked)[0].shape[0]
        out[name] = {**out[name], "blocks": [
            jax.tree.map(lambda a, i=i: a[i], stacked) for i in range(depth)]}
    sparse = [p for p in layers(out) if "router" in p["mlp"]]
    for p, bias in zip(sparse, to_f32(router_bias), strict=True):
        p["mlp"]["router_bias"] = bias
    return out


#: the leaves whose gradients are compared, by path in the model's state
#: (of the gate, ``dt_bias`` and not ``A_log``: the gradient on ``A_log`` is
#: 32 numbers, and its distance read 0.026-0.074 over eight seeds of the
#: bfloat16 model against float8's 0.087: no limit fits between; ``dt_bias``
#: is 4096 numbers, each a sum over every token);
#: ``{kda}`` is the run of the first sparse KDA layers, ``{mla}`` the run of
#: the first latent-attention layer (`parity_hybrid_lm.layer_kinds`)
GRAD_LEAVES = {
    "embedding": "embed/embedding",
    "kda_q": "{kda}/blocks/0/attn/q/kernel",
    "kda_dt_bias": "{kda}/blocks/0/attn/dt_bias",
    "kda_conv": "{kda}/blocks/0/attn/k_conv",
    "mla_kvb": "{mla}/blocks/0/attn/kv_b/kernel",
    "router": "{mla}/blocks/0/mlp/router",
    "expert_down": "{kda}/blocks/0/mlp/down",
    "head": "head/kernel",
}
