"""The comparison that decides ``correct`` for a language-model train cell:
the model as the cell configured it (bfloat16, its own attention path, its
own loss from ``jimm_tpu/train/losses.py`` and the gradient ``trainer.py``
takes) against the plain float32 reference, on ONE seeded batch at the timed
sizes, outside the timed window.

Compared: the pass outputs ``h_r``, the exit gates' logits, the logits of
every pass (both sides in blocks of positions, so neither side ever holds the
``(R, S, vocab)`` set), the loss, and the gradient on the reference's
``GRAD_LEAVES``. The reference runs on one device with ``jax.checkpoint``
around each block application and each pass's cross-entropy (its ``wrap``
hook): what is computed does not change, and its backward then fits beside
the model's resident state.
"""

from __future__ import annotations

import importlib

from benchmarks import harness
from benchmarks.reference.parity import (_get, _model_leaf, _rel_max,
                                         _rel_norm, _with)

#: positions per block of the logits comparison
LOGITS_BLOCK = 1024


def sizes_of(model) -> dict:
    """Reference sizes read off the model under test (so that a rehearsal at
    ``--tiny`` compares like with like; at the published widths they equal
    the configuration file, which :func:`check_sizes` asserts)."""
    d = model.config.decoder
    return {"hidden_size": d.width, "intermediate_size": d.mlp_dim,
            "num_attention_heads": d.num_heads, "head_dim": d.width // d.num_heads,
            "vocab_size": d.vocab_size, "num_layers": d.depth,
            "total_ut_steps": d.loops, "rms_norm_eps": d.ln_eps,
            "rope_theta": d.rope_theta, "hidden_act": d.act,
            "exit_beta": model.config.exit_beta}


def check_sizes(run: harness.Run, model) -> list[str]:
    """Where the model the program built differs from the configuration
    file (nothing, unless this is a rehearsal)."""
    built = sizes_of(model)
    wrong = [f"{key}: file {run.config[key]} != built {built[key]}"
             for key in ("hidden_size", "intermediate_size", "head_dim",
                         "num_attention_heads", "vocab_size", "num_layers",
                         "total_ut_steps", "rms_norm_eps", "rope_theta",
                         "hidden_act")
             if run.config[key] != built[key]]
    seq = run.cell["traffic_params"]["seq_len"]
    if model.config.decoder.seq_len != seq:
        wrong.append(f"seq_len: cell {seq} != built "
                     f"{model.config.decoder.seq_len}")
    return wrong


def check_train(run: harness.Run, result) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    from jimm_tpu.train.trainer import lm_loss_fn

    ref = importlib.import_module(f"benchmarks.reference.{run.config['family']}")
    model = result.model
    d = model.config.decoder
    sizes = sizes_of(model)
    wrong_sizes = [] if run.rehearse else check_sizes(run, model)
    batch = result.batch[0].shape[0]
    tokens = jax.random.randint(jax.random.key(run.seed + 1),
                                (batch, d.seq_len + 1), 0, d.vocab_size,
                                jnp.int32)
    leaves = {name: path.format(mid=d.depth // 2)
              for name, path in ref.GRAD_LEAVES.items()}

    @nnx.jit
    def model_side(model, tokens):
        hidden = model.hidden_states(tokens[:, :-1])
        (loss, _), grads = nnx.value_and_grad(
            lambda m: lm_loss_fn(m, tokens), has_aux=True)(model)
        pure = nnx.to_pure_dict(grads)
        return (hidden, model.exit_gates(hidden), loss,
                {name: _model_leaf(pure, path)
                 for name, path in leaves.items()})

    got_hidden, got_gates, got_loss, got_grads = model_side(model, tokens)

    # the reference: one device, float32, highest matmul precision; inputs
    # are arguments, not closed over (a constant in the program would make
    # every seed another program and a compile-cache miss)
    device = jax.devices()[0]
    params = ref.params_from_state(
        nnx.to_pure_dict(nnx.state(model, nnx.Param)), device=device)
    tokens = jax.device_put(tokens, device)

    def loss_of_leaves(selected, params, tokens):
        for name, path in leaves.items():
            params = _with(params, path, selected[name])
        hs = ref.hidden_states(params, tokens[:, :-1], sizes, jax.checkpoint)
        return ref.loss_of_hidden(params, hs, tokens[:, 1:], sizes,
                                  jax.checkpoint), hs

    @jax.jit
    def reference_side(params, tokens):
        selected = {name: _get(params, path) for name, path in leaves.items()}
        (value, hs), grads = jax.value_and_grad(loss_of_leaves, has_aux=True)(
            selected, params, tokens)
        return (jnp.stack(hs), jnp.stack([ref.gate_logits(params, h)
                                          for h in hs]), value, grads)

    @jax.jit
    def logits_error(got_hidden, head_kernel, want_hidden, params):
        """``(max|a - b|, max|b|)`` of every pass's logits, block by block:
        the model's as its loss takes them (the matmul in its own dtype)."""
        width = got_hidden.shape[-1]
        got = got_hidden.reshape(-1, width)
        want = want_hidden.reshape(-1, width)
        block = min(LOGITS_BLOCK, got.shape[0])
        n = got.shape[0] // block * block  # a ragged tail is left out

        def one(args):
            g, w = args
            a = (g @ head_kernel.astype(g.dtype)).astype(jnp.float32)
            b = ref.logits(params, w)
            return jnp.max(jnp.abs(a - b)), jnp.max(jnp.abs(b))

        diff, size = jax.lax.map(one, (got[:n].reshape(-1, block, width),
                                       want[:n].reshape(-1, block, width)))
        return jnp.max(diff), jnp.max(size)

    with jax.default_matmul_precision("highest"):
        want_hidden, want_gates, want_loss, want_grads = reference_side(
            params, tokens)
        logit_diff, logit_size = jax.device_get(logits_error(
            got_hidden, model.head.kernel[...], want_hidden, params))
    (got_hidden, got_gates, got_loss, got_grads, want_hidden, want_gates,
     want_loss, want_grads) = jax.device_get(
        (got_hidden, got_gates, got_loss, got_grads, want_hidden, want_gates,
         want_loss, want_grads))

    tol = ref.TOLERANCE
    gate_diff = np.max(np.abs(np.asarray(got_gates, np.float32) - want_gates))
    errors = {
        "hidden": _rel_max(got_hidden, want_hidden),
        "gates": (float(gate_diff / max(1.0, np.max(np.abs(want_gates))))
                  if np.isfinite(gate_diff) else float("inf")),
        "logits": (float(logit_diff / max(logit_size, 1e-30))
                   if np.isfinite(logit_diff) else float("inf")),
        "loss": abs(float(got_loss) - float(want_loss))
        / max(1.0, abs(float(want_loss))),
        "grads": {name: _rel_norm(got_grads[name], want_grads[name])
                  for name in leaves}}
    ok = (not wrong_sizes
          and all(errors[k] <= tol[k]
                  for k in ("hidden", "gates", "logits", "loss"))
          and all(e <= tol["grads"][name]
                  for name, e in errors["grads"].items()))
    return {"ok": bool(ok), "errors": errors, "tolerance": tol,
            "loss_model": float(got_loss), "loss_reference": float(want_loss),
            "tokens": [batch, d.seq_len], "grad_leaves": leaves,
            "sizes_differ_from_file": wrong_sizes}
