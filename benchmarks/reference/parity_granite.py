"""The comparison that decides ``correct`` for the train cell of a dense
hybrid language model (Mamba-2 state-space layers beside grouped-query
attention ones, a SwiGLU in every layer, a tied head): the model as the cell
configured it (bfloat16, its own chunked scan and attention kernels, its own
loss and gradient from ``jimm_tpu/train/trainer.py``) against the plain
float32 reference, on ONE seeded batch at the timed sizes, outside the timed
window, of the timed run's own model (its weights as the last step left them).

``parity_hybrid_lm.py``'s frame, less the routing (no layer has experts):
final hidden state, logits in blocks, loss, the gradient on the reference's
``GRAD_LEAVES``, all of ONE differentiated pass of the model;

- the sizes read off the model are the configuration file's own keys
  (``mamba_*`` beside the attention's), and a gradient leaf is named by the
  run of its KIND (``{mamba}``: the first run of Mamba-2 layers, ``{attn}``:
  the attention layer's);
- ``scan``: the program's chunked scan ALONE (``ops/ssd.py::chunk_ssd``, the
  function the timed step calls) against the reference's token-by-token
  recurrence, on seeded x, B, C at the timed shape and a ``dt`` drawn through
  the timed model's own ``dt_bias`` and ``A_log``: float32 inside, so it reads
  rounding in the seventh digit where the bfloat16 model's other numbers read
  the third, and a scan that kept its state in bfloat16 reads far above;
- ``scan_memory`` and ``scan_grads``: the same at a trained model's steps
  (:data:`DT_RANGE`), where the state each chunk hands the next carries most
  of the output, forward and the gradients of all five inputs: the chunk to
  chunk walk and the scan's own backward, which at the model's start (a head
  forgets within a token) nothing reads;
- ``update`` and ``moment``: the TIMED program itself, called once more
  (``parity_hybrid_lm.timed_step``);
- a reading that the limits (``TOLERANCE`` of the reference's module) do not
  name is reported under ``not_held`` and decides nothing;
- room, at the timed size: ``parity_hybrid_lm``'s moves (the optimizer's state
  to the host before the model's pass, the model's weights after it, both back
  where they were before the step); the reference's attention runs per
  ``ATTEND_HEADS`` heads and ``ATTEND_ROWS`` query rows, its recurrence token
  by token in blocks of ``STATE_BLOCK`` tokens, ``jax.checkpoint`` around
  each.
"""

from __future__ import annotations

import importlib

from benchmarks import harness
from benchmarks.reference.parity import _get, _model_leaf, _rel_norm, _with
from benchmarks.reference.parity_gqa_moe_lm import to_host
from benchmarks.reference.parity_hybrid_lm import (placements, put_back,
                                                   timed_step)
from benchmarks.reference.parity_moe_lm import LOGITS_BLOCK

#: the reference's attention at the timed size: heads and query rows a block
#: ((4, 1024, 16384) float32 scores are 268 MB)
ATTEND_HEADS, ATTEND_ROWS = 4, 1024

#: configuration-file key -> how the built model's config gives it
_SIZES = {
    "hidden_size": lambda c: c.decoder.width,
    "shared_intermediate_size": lambda c: c.decoder.mlp_dim,
    "num_attention_heads": lambda c: c.decoder.num_heads,
    "num_key_value_heads": lambda c: c.decoder.gqa.kv_heads,
    "mamba_n_heads": lambda c: c.decoder.mamba.num_heads,
    "mamba_d_head": lambda c: c.decoder.mamba.head_dim,
    "mamba_d_state": lambda c: c.decoder.mamba.state,
    "mamba_n_groups": lambda c: c.decoder.mamba.groups,
    "mamba_d_conv": lambda c: c.decoder.mamba.conv_taps,
    "mamba_chunk_size": lambda c: c.decoder.mamba.chunk,
    "vocab_size": lambda c: c.decoder.vocab_size,
    "num_layers": lambda c: c.decoder.depth,
    "first_layer": lambda c: c.decoder.first_layer,
    "rms_norm_eps": lambda c: c.decoder.ln_eps,
    "hidden_act": lambda c: c.decoder.act,
    "residual_multiplier": lambda c: c.decoder.residual_scale,
    "attention_multiplier": lambda c: c.decoder.attn_scale,
    "embedding_multiplier": lambda c: c.embedding_multiplier,
    "logits_scaling": lambda c: c.logits_scaling,
    "layer_types": lambda c: list(c.decoder.mixers),
}


def sizes_of(model) -> dict:
    """Reference sizes read off the model under test (so that a rehearsal at
    ``--tiny`` compares like with like; at the published widths they equal
    the configuration file, which :func:`check_sizes` asserts)."""
    return {key: read(model.config) for key, read in _SIZES.items()}


def check_sizes(run: harness.Run, model) -> list[str]:
    """Where the model the program built differs from the configuration
    file (nothing, unless this is a rehearsal)."""
    built = sizes_of(model)
    wrong = [f"{key}: file {run.config[key]} != built {built[key]}"
             for key in _SIZES if run.config[key] != built[key]]
    if not model.tied_head or not run.config["tie_word_embeddings"]:
        wrong.append("the head is not the embedding")
    if model.config.decoder.gqa.window is not None:
        wrong.append("the attention layer has a window")
    seq = run.cell["traffic_params"]["seq_len"]
    if model.config.decoder.seq_len != seq:
        wrong.append(f"seq_len: cell {seq} != built "
                     f"{model.config.decoder.seq_len}")
    return wrong


def layer_kinds(model) -> dict:
    """The runs that ``GRAD_LEAVES``' ``{mamba}`` and ``{attn}`` name: the
    first run of Mamba-2 layers and the first run of attention layers."""
    runs = model.config.decoder.runs()
    return {"mamba": next(name for name, c in runs if c.mamba is not None),
            "attn": next(name for name, c in runs if c.gqa is not None)}


#: the range of a trained Mamba-2 head's step ``dt`` (the paper's
#: ``dt_min``, ``dt_max``): at the family's start a head loses 1.3 to 84 nats
#: a token and no state outlives a chunk; in this range the low heads keep
#: theirs over many chunks
DT_RANGE = (1e-3, 1e-1)


def scan_inputs(model, seed: int, memory: bool) -> tuple:
    """Seeded ``(x, dt, A, B, C)`` at the model's shape (one sequence), ``A``
    the timed model's own. ``dt`` goes through a bias: the model's own
    ``dt_bias`` where ``memory`` is false; else head ``h``'s step
    :data:`DT_RANGE` log-spaced over the heads (head 0 at 1e-3, whose state
    loses 0.26 nats a chunk of 256 at ``A = -1``), so that the state handed
    from chunk to chunk carries most of ``y``."""
    import jax
    import jax.numpy as jnp

    d = model.config.decoder
    m = d.mamba
    attn = getattr(model, layer_kinds(model)["mamba"]).blocks.attn
    a_log, dt_bias = (t[...][0].astype(jnp.float32)
                      for t in (attn.A_log, attn.dt_bias))
    if memory:
        step = jnp.geomspace(*DT_RANGE, m.num_heads)
        dt_bias = jnp.log(jnp.expm1(step))            # softplus^-1
    keys = jax.random.split(jax.random.key(seed + 2), 4)
    heads = (1, d.seq_len, m.num_heads)
    groups = (1, d.seq_len, m.groups, m.state)
    return (jax.nn.silu(jax.random.normal(keys[0], (*heads, m.head_dim))),
            jax.nn.softplus(jax.random.normal(keys[1], heads) + dt_bias),
            -jnp.exp(a_log),
            jax.nn.silu(jax.random.normal(keys[2], groups)),
            jax.nn.silu(jax.random.normal(keys[3], groups)))


def scan_errors(ref, model, seed: int, wrap) -> dict:
    """``||a - b|| / ||b||`` of the program's chunked scan alone against the
    reference's recurrence (:func:`scan_inputs`): ``scan`` at the model's own
    ``dt``, ``scan_memory`` in :data:`DT_RANGE`, and there ``scan_grads``, the
    gradients of all five inputs for a seeded cotangent (the scan's own
    backward: the state's cotangent handed back from chunk to chunk)."""
    import jax
    import jax.numpy as jnp

    from jimm_tpu.ops.ssd import chunk_ssd
    chunk = model.config.decoder.mamba.chunk

    def program(*a):
        return chunk_ssd(*a, chunk=chunk)

    def reference(*a):
        return ref.ssm_scan(*a, wrap)

    def pulled(fn):
        # the inputs' gradients of <fn(inputs), w>: a vector-Jacobian product
        return jax.jit(jax.grad(lambda w, *a: jnp.sum(fn(*a) * w),
                                argnums=range(1, 6)))

    out = {}
    for name, memory in (("scan", False), ("scan_memory", True)):
        inputs = scan_inputs(model, seed, memory)
        got = jax.device_get(jax.jit(program)(*inputs))
        with jax.default_matmul_precision("highest"):
            want = jax.device_get(jax.jit(reference)(*inputs))
        out[name] = _rel_norm(got, want)
    w = jax.random.normal(jax.random.key(seed + 3), got.shape)
    got = jax.device_get(pulled(program)(w, *inputs))
    with jax.default_matmul_precision("highest"):
        want = jax.device_get(pulled(reference)(w, *inputs))
    out["scan_grads"] = {name: _rel_norm(a, b) for name, a, b
                         in zip(("x", "dt", "A", "B", "C"), got, want)}
    return out


def grad_leaves(ref, model) -> dict:
    """``GRAD_LEAVES`` with their runs named for ``model``."""
    return {name: path.format(**layer_kinds(model))
            for name, path in ref.GRAD_LEAVES.items()}


def model_side(model, tokens, leaves: dict) -> tuple:
    """The model's own differentiated pass on ``tokens``: its final hidden
    state after the norm, its loss, the norm over ALL its gradients (which
    the optimizer's clip divides by) and its gradients on ``leaves``."""
    import jax
    import jax.numpy as jnp
    from flax import nnx

    from jimm_tpu.train.trainer import dense_lm_forward

    @nnx.jit
    def run(model, tokens):
        (loss, normed), grads = nnx.value_and_grad(
            lambda m: dense_lm_forward(m, tokens), has_aux=True)(model)
        pure = nnx.to_pure_dict(grads)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g in jax.tree.leaves(pure)))
        return (normed, loss, norm,
                {name: _model_leaf(pure, path)
                 for name, path in leaves.items()})

    return jax.block_until_ready(run(model, tokens))


def reference_wrap(ref, sizes: dict, seq_len: int) -> tuple:
    """``(wrap, attend)`` for the reference at ``seq_len`` tokens: above
    ``ATTEND_ROWS`` its attention by blocks and ``jax.checkpoint`` around
    each layer, block of the recurrence and block of logits."""
    import jax
    attend, wrap = ref.plain_attend(sizes), lambda fn: fn
    if seq_len > ATTEND_ROWS:
        wrap = jax.checkpoint
        attend = ref.in_blocks(attend, ATTEND_HEADS, ATTEND_ROWS, wrap)
    return wrap, attend


def reference_side(ref, params, tokens, sizes: dict, leaves: dict, wrap,
                   attend) -> tuple:
    """The reference's final hidden state, loss and gradients on ``leaves``,
    on the device. Inputs are arguments, not closed over (a constant in the
    program would make every seed another program and a compile-cache
    miss). Called under ``jax.default_matmul_precision("highest")``."""
    import jax

    def loss_of_leaves(selected, params, tokens):
        for name, path in leaves.items():
            params = _with(params, path, selected[name])
        h = ref.hidden_states(params, tokens[:, :-1], sizes, wrap, attend)
        return ref.loss_of_hidden(params, h, tokens[:, 1:], sizes, wrap), h

    @jax.jit
    def run(params, tokens):
        selected = {name: _get(params, path) for name, path in leaves.items()}
        (value, h), grads = jax.value_and_grad(
            loss_of_leaves, has_aux=True)(selected, params, tokens)
        return h, value, grads

    return run(params, tokens)


def hidden_by_token(got, want) -> dict:
    """Where the hidden state's distance sits: ``||a - b|| / ||b||`` token by
    token (a norm over everything hides one token that is wholly wrong)."""
    import numpy as np
    per_token = np.linalg.norm(np.asarray(got, np.float32) - want, axis=-1) \
        / np.maximum(np.linalg.norm(want, axis=-1), 1e-30)
    worst = np.unravel_index(np.argmax(per_token), per_token.shape)
    return {"max": float(per_token.max()), "at": [int(i) for i in worst],
            "median": float(np.median(per_token)),
            "over_0.2": int((per_token > 0.2).sum())}


def check_train(run: harness.Run, result) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    ref = importlib.import_module(f"benchmarks.reference.{run.config['family']}")
    model = result.model
    d = model.config.decoder
    sizes = sizes_of(model)
    wrong_sizes = [] if run.rehearse else check_sizes(run, model)
    was_at = {}
    if not run.rehearse:
        was_at = {"optimizer": placements(result.optimizer),
                  "model": placements(model)}
        to_host(result.optimizer)
    batch = result.batch[0].shape[0]
    tokens = jax.random.randint(jax.random.key(run.seed + 1),
                                (batch, d.seq_len + 1), 0, d.vocab_size,
                                jnp.int32)
    leaves = grad_leaves(ref, model)
    got_hidden, got_loss, grad_norm, got_grads = model_side(model, tokens,
                                                            leaves)

    # the reference: one device, float32, highest matmul precision
    device = jax.devices()[0]
    params = ref.params_from_state(
        nnx.to_pure_dict(nnx.state(model, nnx.Param)), device=device)
    tokens = jax.device_put(tokens, device)
    embedding = jnp.copy(model.embed.embedding[...])
    if not run.rehearse:
        to_host(model)
    wrap, attend = reference_wrap(ref, sizes, d.seq_len)

    @jax.jit
    def logits_error(got_hidden, embedding, want_hidden, params):
        """``(max|a - b|, max|b|)`` of the logits, block by block: the
        model's as its loss takes them (the matmul in its own dtype)."""
        width = got_hidden.shape[-1]
        got = got_hidden.reshape(-1, width)
        want = want_hidden.reshape(-1, width)
        block = min(LOGITS_BLOCK, got.shape[0])
        n = got.shape[0] // block * block  # a ragged tail is left out

        def one(args):
            g, w = args
            a = (model.head_input(g) @ embedding.astype(g.dtype).T
                 ).astype(jnp.float32)
            b = ref.logits(params, w, sizes)
            return jnp.max(jnp.abs(a - b)), jnp.max(jnp.abs(b))

        diff, size = jax.lax.map(one, (got[:n].reshape(-1, block, width),
                                       want[:n].reshape(-1, block, width)))
        return jnp.max(diff), jnp.max(size)

    with jax.default_matmul_precision("highest"):
        want_hidden, want_loss, want_grads = reference_side(
            ref, params, tokens, sizes, leaves, wrap, attend)
        logit_diff, logit_size = jax.device_get(logits_error(
            got_hidden, embedding, want_hidden, params))
    (got_hidden, got_loss, got_grads, want_hidden, want_loss,
     want_grads) = jax.device_get((got_hidden, got_loss, got_grads,
                                   want_hidden, want_loss, want_grads))
    del params
    if not run.rehearse:
        put_back(model, was_at["model"])
    scans = scan_errors(ref, model, run.seed, wrap)
    if not run.rehearse:
        put_back(result.optimizer, was_at["optimizer"])
    step = timed_step(ref, result, tokens, leaves, want_grads,
                      float(grad_norm))

    tol = ref.REHEARSAL_TOLERANCE if run.rehearse else ref.TOLERANCE
    errors = {
        "hidden": _rel_norm(got_hidden, want_hidden),
        "logits": (float(logit_diff / max(logit_size, 1e-30))
                   if np.isfinite(logit_diff) else float("inf")),
        "loss": abs(float(got_loss) - float(want_loss))
        / max(1.0, abs(float(want_loss))),
        **scans,
        "update": step["update"],
        "moment": step["moment"],
        "grads": {name: _rel_norm(got_grads[name], want_grads[name])
                  for name in leaves}}
    # a reading the limits do not name is reported and decides nothing
    nested = ("grads", "scan_grads")
    ok = (not wrong_sizes
          and all(errors[k] <= limit for k, limit in tol.items()
                  if k not in nested)
          and all(errors[k][name] <= limit for k in nested
                  for name, limit in tol.get(k, {}).items()))
    held = {k for k in tol if k not in nested} | {
        f"{k}/{name}" for k in nested for name in tol.get(k, {})}
    every = {k for k in errors if k not in nested} | {
        f"{k}/{name}" for k in nested for name in errors[k]}
    return {"ok": bool(ok), "errors": errors, "tolerance": tol,
            "not_held": sorted(every - held),
            "hidden_by_token": hidden_by_token(got_hidden, want_hidden), "timed_step": step,
            "loss_model": float(got_loss), "loss_reference": float(want_loss),
            "tokens": [batch, d.seq_len], "grad_leaves": leaves,
            "sizes_differ_from_file": wrong_sizes}
