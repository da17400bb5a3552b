"""Plain float32 reference: granite-4.0-h-micro (``model_type``
granitemoehybrid) as ONE stage of a four-stage pipeline holds its first
layers, its next-token loss and its gradients.

Straight ``jax.numpy``: explicit Python loops over layers, the state-space
recurrence advanced TOKEN BY TOKEN (no chunks, no segment sums), attention by
explicit scores under an explicit causal mask with the key/value heads
repeated, no kernels, no sharding. From the published ``config.json`` and the
Mamba-2 paper (arXiv:2405.21060):

    x = 12 * E[tokens]                                   embedding_multiplier
    layer l:   a = x + 0.22 * Mixer_l(RMS_l1(x))         residual_multiplier
               x = a + 0.22 * SwiGLU(RMS_l2(a))          pre-norm, eps 1e-5
               Mixer_l = Mamba-2 where layer_types[l] is "mamba", else
               attention
    z = RMS_f(x) E^T / 8                                 tied, logits_scaling
    loss = mean over positions of CE(z, next token)

    Mamba-2, 64 heads of P = 64, N = 128, one group:
      [z | xBC | dt] = x W_in                            no bias
      xBC = SiLU(conv4(xBC) + b)   conv4: causal, depthwise, 4 taps:
          y_t = sum_{i=0..3} w_i u_{t-3+i},  u_{<0} = 0
      x, B, C = split(xBC, [4096, 128, 128])
      dt_h = softplus(dt_h + dt_bias_h);  A_h = -exp(A_log_h)
      S_t,h = exp(dt_t,h A_h) S_t-1,h + dt_t,h x_t,h B_t^T      S_0 = 0
      y_t,h = S_t,h C_t + D_h x_t,h
      out = RMSNorm_4096(y * SiLU(z)) W_out
    attention, 32 heads over 8 of 64, no bias, NO rotary:
      o = softmax(causal(q k^T * 1/64)) v;  out = o W_o   attention_multiplier
    SwiGLU: W_down(silu(W_gate x) * (W_up x))
    RMS(x) = x / sqrt(mean(x^2) + eps) * w

The share: this chip holds published layers ``first_layer ..`` (0-9: Mamba-2
x 5, attention, Mamba-2 x 4) and, unlike a real first stage, the final norm
and the head. Nothing crosses to the other stages, here as in the program.

Departures from the published description, each deliberate: random weights
from the seed (``A_log`` log(1 .. 64), ``dt_bias`` and ``D`` ones, the taps
and their bias from U(-1/2, 1/2): what the program's initialisers give; this
file takes what it is handed); no checkpoint, no generation, no reset of the
state at a document boundary. In the optimizer's step (:func:`adamw_step`,
the one ``kimi_linear.py`` holds) the decay is on matrices alone, while the
program's decay mask reads a STACKED leaf's rank and so decays a scanned run's
vectors too (``A_log``, ``dt_bias``, ``D``, the norms: 1e-8 of a weight a
step at the family's rate, under the comparison's sight).

Parameters come from the ``nnx`` state of the model under test through
:func:`params_from_state`, cast to float32. Every matmul goes through
:data:`matmul` (the low-precision control swaps it), the recurrence's read of
its state included; :data:`STATE_BF16` is the mechanism control's switch: the
carried state rounded to bfloat16 after every token (a reference with it on
must be refused). :func:`hidden_states` and :func:`loss_of_hidden` take
``wrap``, applied to a layer, to a block of the recurrence and to the
cross-entropy's blocks of rows, and :func:`hidden_states` takes ``attend``,
the attention's core: all identities of what is computed. The comparison on
the chip passes ``jax.checkpoint`` and ``kimi_linear.in_blocks``: (1, 32,
16384, 16384) float32 scores are 34 GB otherwise, the recurrence's 16,384
states a layer as many, and the logits 6.6 GB.

It shares no code with ``jimm_tpu``. Callers wrap these functions in
``jax.default_matmul_precision("highest")``.

Tolerances, and why: see :data:`TOLERANCE`. ``hidden`` (after the final
RMSNorm) and every gradient leaf are ``||a - b|| / ||b||``; ``logits`` is
``max|a - b| / max|b|``; ``loss`` is relative; ``scan`` and ``scan_memory``
are ``||a - b|| / ||b||`` of the program's chunked scan alone against
:func:`ssm_scan` on seeded inputs at the timed shape, ``scan_grads`` the same
of its five inputs' gradients (``parity_granite.scan_errors``); ``update`` and
``moment`` are ``||a - b|| / ||b||`` of the change that the timed program's
own step makes to the parameters and to Adam's first moment on
``GRAD_LEAVES`` against :func:`adamw_step` from this file's gradients
(``parity_hybrid_lm.timed_step``: a state left as it was reads 1).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.reference.kimi_linear import (OPTIMIZER, adamw_step,  # noqa: F401
                                              conv4, in_blocks,
                                              learning_rate, rms_norm)

#: Beside each limit: the bfloat16 model's largest over its seeds | the
#: control's reading (float8 unless named; read on a TPU v5e; PERF.md
#: section 6 has every reading). A reading this table does not name is
#: reported and decides nothing (``parity_granite.check_train``'s
#: ``not_held``): where float8 reads less than three times the sound runs'
#: largest, its limit would sit at the edge of one or the other. That leaves
#: out ``logits`` (0.2215 | 0.352), ``moment`` (0.684 | 1.020) and nine of
#: the ten gradient leaves (sound 0.37-0.67 | float8 0.73-1.14): the
#: bfloat16 model's gradients sit 0.16-0.67 from the reference because the
#: model at its start amplifies the backward's rounding, and the same program
#: in float32 reads 1e-5 on every leaf (``witness_granite.py``). The scan's
#: own backward is held instead, alone and in float32 (``scan_grads``).
#: ``update`` is held between its reading and 1, what a state left as it was
#: reads. ``scan`` reads float32 rounding at the model's start, where no
#: state outlives a chunk; ``scan_memory`` and ``scan_grads`` at a trained
#: model's steps, where the state handed from chunk to chunk carries most of
#: the output; a recurrence that keeps its state in bfloat16 reads 9.9e-4 on
#: ``scan``
TOLERANCE = {"hidden": 0.1,           # 0.0499 | 0.278
             "loss": 5e-5,            # 5.3e-6 | 2.5e-4
             "scan": 1e-4,            # 1.24e-7 | (the state control) 9.9e-4
             # largest of two seeds | the state control (a TPU v5e; the CPU
             # reads 2.5e-7 sound: the chip's float32 arithmetic, which the
             # start's ``scan`` never reaches, is coarser where a state
             # lives across chunks)
             "scan_memory": 3e-3,     # 3.0e-4 | 0.0747
             "scan_grads": {"x": 4e-4,       # 4.3e-5 | 2.0e-3
                            "dt": 3e-3,      # 3.5e-4 | 0.0359
                            "A": 1.5e-2,     # 1.7e-3 | 0.0766
                            "B": 4e-4,       # 4.3e-5 | 3.3e-3
                            "C": 2e-3},      # 1.5e-4 | 0.0410
             "update": 0.5,           # 0.192 | (a state left as it was) 1
             "grads": {"attn_q": 0.5}}  # 0.232 | 0.728

#: what a ``--rehearse`` run is held to: the driver rehearses in float32,
#: where the model reads 1e-6 to 1e-5 (a bfloat16 model 64 wide with 32
#: tokens checks nothing: its rounding noise, amplified by ten normalised
#: layers, is of the size of what a limit should see); every reading held
REHEARSAL_TOLERANCE = {"hidden": 1e-3, "logits": 1e-3, "loss": 1e-4,
                       "scan": 1e-4, "scan_memory": 1e-4,
                       "scan_grads": dict.fromkeys(TOLERANCE["scan_grads"],
                                                   1e-4),
                       "update": 5e-2, "moment": 5e-2,
                       "grads": dict.fromkeys(
                           ("embedding", "A_log", "dt_bias", "D", "in_proj",
                            "conv", "norm", "out_proj", "attn_q",
                            "swiglu_down"), 5e-3)}

#: every matmul of the reference (the low-precision control swaps it)
matmul = jnp.matmul
#: the mechanism control: the recurrence's carried state rounded to bfloat16
#: after every token
STATE_BF16 = False
#: tokens of one block of the recurrence (``wrap`` is applied to a block:
#: under ``jax.checkpoint`` the backward keeps one state a block, not a token)
STATE_BLOCK = 128
#: rows of logits alive at once: (16384, 100352) float32 logits are 6.6 GB
LOGITS_ROWS = 1024


def _identity(fn):
    return fn


def ssm_scan(x, dt, A, B, C, wrap=_identity):
    """The recurrence, one token at a time, without the ``D`` term. x
    (batch, S, H, P), dt (batch, S, H), A (H,), B and C (batch, S, G, N) ->
    y (batch, S, H, P); head h reads group h // (H / G)."""
    batch, s, h, p = x.shape
    g, n = B.shape[2:]

    def token(state, xs):
        # state (batch, G, H / G, P, N)
        x_t, dt_t, B_t, C_t = xs
        x_t = x_t.reshape(batch, g, h // g, p)
        dt_t = dt_t.reshape(batch, g, h // g)
        decay = jnp.exp(dt_t * A.reshape(g, h // g))
        state = decay[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., :, None] * B_t[:, :, None, None, :]
        if STATE_BF16:
            # not a cast there and back: XLA may drop that pair as "excess
            # precision" (kimi_linear.py has the reading)
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        y = matmul(state, C_t[:, :, None, :, None])[..., 0]       # S C
        return state, y.reshape(batch, h, p)

    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    block = wrap(block)
    size = STATE_BLOCK if s % STATE_BLOCK == 0 else s
    xs = tuple(jnp.moveaxis(t, 1, 0).reshape(s // size, size, *t.shape[:1],
                                             *t.shape[2:])
               for t in (x, dt, B, C))
    _, y = jax.lax.scan(block, jnp.zeros((batch, g, h // g, p, n),
                                         jnp.float32), xs)
    return jnp.moveaxis(y.reshape(s, batch, h, p), 0, 1)


def mamba(u, p, sizes, wrap=_identity):
    b, s, _ = u.shape
    h, hp = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    g, n = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    inner = h * hp
    zxd = matmul(u, p["in_proj"]["kernel"])
    z, xbc, dt = (zxd[..., :inner], zxd[..., inner:-h], zxd[..., -h:])
    xbc = jax.nn.silu(conv4(xbc, p["conv"]) + p["conv_bias"])
    x = xbc[..., :inner].reshape(b, s, h, hp)
    B = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
    C = xbc[..., inner + g * n:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssm_scan(x, dt, -jnp.exp(p["A_log"]), B, C, wrap)
    y = (y + p["D"][:, None] * x).reshape(b, s, inner) * jax.nn.silu(z)
    y = rms_norm(y, p["norm"], sizes["rms_norm_eps"])
    return matmul(y, p["out_proj"]["kernel"])


def causal_attention(q, k, v, first_row=0, *, scale):
    """``softmax(causal(q k^T * scale)) v`` by explicit scores. q is
    (B, Sq, N, D), the rows ``first_row ..`` of a sequence whose keys and
    values are k and v (B, S, N, D)."""
    scores = matmul(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1)) * scale
    rows = first_row + jnp.arange(q.shape[1])
    mask = jnp.arange(k.shape[1])[None, :] <= rows[:, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return matmul(probs, v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)


def plain_attend(sizes):
    """The attention core at the configuration's ``attention_multiplier``."""
    return partial(causal_attention, scale=sizes["attention_multiplier"])


def attention(u, p, sizes, attend):
    b, s, _ = u.shape
    n, n_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["hidden_size"] // n
    q = matmul(u, p["q"]["kernel"]).reshape(b, s, n, d)
    k, v = (jnp.repeat(matmul(u, p[name]["kernel"]).reshape(b, s, n_kv, d),
                       n // n_kv, axis=2) for name in ("k", "v"))
    return matmul(attend(q, k, v).reshape(b, s, n * d), p["out"]["kernel"])


def swiglu(x, gate, up, down):
    return matmul(jax.nn.silu(matmul(x, gate)) * matmul(x, up), down)


def layer(x, p, sizes, attend, wrap=_identity):
    """One layer: its mixer by what ``p`` holds, then its SwiGLU."""
    eps, m = sizes["rms_norm_eps"], sizes["residual_multiplier"]
    h = rms_norm(x, p["ln1"]["scale"], eps)
    a = x + m * (mamba(h, p["attn"], sizes, wrap) if "A_log" in p["attn"]
                 else attention(h, p["attn"], sizes, attend))
    h = rms_norm(a, p["ln2"]["scale"], eps)
    mlp = p["mlp"]
    return a + m * swiglu(h, mlp["gate"]["kernel"], mlp["fc1"]["kernel"],
                          mlp["fc2"]["kernel"])


def run_names(params) -> list[str]:
    """The runs of like layers, in layer order (``run<first layer>``)."""
    return sorted((k for k in params if k.startswith("run")),
                  key=lambda k: int(k[3:]))


def layers(params) -> list[dict]:
    return [p for name in run_names(params) for p in params[name]["blocks"]]


def hidden_states(params, tokens, sizes, wrap=_identity, attend=None):
    """The final-normed output (B, S, hidden)."""
    attend = attend or plain_attend(sizes)
    x = params["embed"]["embedding"][tokens] * sizes["embedding_multiplier"]
    for p in layers(params):
        x = wrap(lambda x, p: layer(x, p, sizes, attend, wrap))(x, p)
    return rms_norm(x, params["norm"]["scale"], sizes["rms_norm_eps"])


def logits(params, h, sizes):
    return matmul(h / sizes["logits_scaling"], params["embed"]["embedding"].T)


def cross_entropy(params, h, targets, sizes):
    """Per-position softmax cross-entropy, ``h``'s leading shape."""
    logp = jax.nn.log_softmax(logits(params, h, sizes), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def loss_of_hidden(params, h, targets, sizes, wrap=_identity):
    """The mean of :func:`cross_entropy` over every position, taken
    :data:`LOGITS_ROWS` rows at a time (``wrap`` applied to a block)."""
    width = h.shape[-1]
    rows, t = h.reshape(-1, width), targets.reshape(-1)
    size = LOGITS_ROWS if rows.shape[0] % LOGITS_ROWS == 0 else rows.shape[0]
    one = wrap(lambda args: cross_entropy(params, *args, sizes))
    return jnp.mean(jax.lax.map(one, (rows.reshape(-1, size, width),
                                      t.reshape(-1, size))))


def loss(params, tokens, sizes, wrap=_identity, attend=None):
    """Mean next-token cross-entropy on (B, S + 1) ids: inputs are the first
    S, targets the ids shifted by one."""
    h = hidden_states(params, tokens[:, :-1], sizes, wrap, attend)
    return loss_of_hidden(params, h, tokens[:, 1:], sizes, wrap)


# ---------------------------------------------------------------------------
# The name map: nnx state of the model under test -> reference parameters
# ---------------------------------------------------------------------------

def params_from_state(pure: dict, device=None) -> dict:
    """``nnx.to_pure_dict(nnx.state(model, nnx.Param))`` -> float32
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
    return out


#: the leaves whose gradients are compared, by path in the model's state:
#: every parameter of a Mamba-2 layer, the attention layer's ``W_q``, a
#: SwiGLU, the tied embedding. ``{mamba}`` is the first run of Mamba-2 layers
#: (its first layer is published layer 0: every later layer lies between it
#: and the loss), ``{attn}`` the attention layer's run
#: (`parity_granite.layer_kinds`)
GRAD_LEAVES = {
    "embedding": "embed/embedding",
    "A_log": "{mamba}/blocks/0/attn/A_log",
    "dt_bias": "{mamba}/blocks/0/attn/dt_bias",
    "D": "{mamba}/blocks/0/attn/D",
    "in_proj": "{mamba}/blocks/0/attn/in_proj/kernel",
    "conv": "{mamba}/blocks/0/attn/conv",
    "norm": "{mamba}/blocks/0/attn/norm",
    "out_proj": "{mamba}/blocks/0/attn/out_proj/kernel",
    "attn_q": "{attn}/blocks/0/attn/q/kernel",
    "swiglu_down": "{mamba}/blocks/0/mlp/fc2/kernel",
}
