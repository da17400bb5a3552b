"""The comparison that decides ``correct`` for a sparse language model's
train cell: the model as the cell configured it (bfloat16, its own attention
and expert paths, its own loss and gradient from ``jimm_tpu/train/trainer.py``)
against the plain float32 reference, on ONE seeded batch at the timed sizes,
outside the timed window, of the timed run's own model (its weights and router
biases as the last step left them).

Compared: the final hidden state (after the final norm), the logits (both
sides in blocks of positions), the loss and the gradient on the reference's
``GRAD_LEAVES``, all of ONE differentiated pass of the model and with the
reference computed for the routing choices that pass made; and per sparse
layer the share of (token, slot) choices on which the reference's own router,
given the same input, differs from the model's. The reference runs on one
device with ``jax.checkpoint`` around each layer and the cross-entropy (its
``wrap`` hook) and its attention per sequence, group of ``ATTEND_HEADS`` heads
and block of ``ATTEND_ROWS`` query rows (its ``attend`` hook): what is
computed does not change, and it then fits beside the model's resident state.
"""

from __future__ import annotations

import importlib

from benchmarks import harness
from benchmarks.reference.parity import (_get, _model_leaf, _rel_max,
                                         _rel_norm, _with)

#: positions per block of the logits comparison
LOGITS_BLOCK = 1024
#: the reference's attention at the timed size: heads and query rows a block
ATTEND_HEADS, ATTEND_ROWS = 4, 1024

#: configuration-file key -> how the built model's config gives it
_SIZES = {
    "hidden_size": lambda d: d.width,
    "intermediate_size": lambda d: d.mlp_dim,
    "num_attention_heads": lambda d: d.num_heads,
    "kv_lora_rank": lambda d: d.mla.kv_lora_rank,
    "qk_nope_head_dim": lambda d: d.mla.qk_nope_dim,
    "qk_rope_head_dim": lambda d: d.mla.qk_rope_dim,
    "v_head_dim": lambda d: d.mla.v_head_dim,
    "vocab_size": lambda d: d.vocab_size,
    "num_layers": lambda d: d.depth,
    "first_k_dense_replace": lambda d: d.dense_layers,
    "n_routed_experts": lambda d: d.moe.held_experts,
    "num_experts_per_tok": lambda d: d.moe.top_k,
    "n_shared_experts": lambda d: d.moe.shared_experts,
    "moe_intermediate_size": lambda d: d.moe.expert_dim,
    "routed_scaling_factor": lambda d: d.moe.routed_scale,
    "rms_norm_eps": lambda d: d.ln_eps,
    "rope_theta": lambda d: d.rope_theta,
    "hidden_act": lambda d: d.act,
}


def sizes_of(model) -> dict:
    """Reference sizes read off the model under test (so that a rehearsal at
    ``--tiny`` compares like with like; at the published widths they equal
    the configuration file, which :func:`check_sizes` asserts)."""
    d = model.config.decoder
    return {**{key: read(d) for key, read in _SIZES.items()},
            "first_expert": d.moe.first_expert,
            "router_width": d.moe.num_experts}


def check_sizes(run: harness.Run, model) -> list[str]:
    """Where the model the program built differs from the configuration
    file (nothing, unless this is a rehearsal)."""
    built = sizes_of(model)
    wrong = [f"{key}: file {run.config[key]} != built {built[key]}"
             for key in _SIZES if run.config[key] != built[key]]
    published = run.config["published"]["n_routed_experts"]
    if published != built["router_width"]:
        wrong.append(f"router width: published {published} != built "
                     f"{built['router_width']}")
    seq = run.cell["traffic_params"]["seq_len"]
    if model.config.decoder.seq_len != seq:
        wrong.append(f"seq_len: cell {seq} != built "
                     f"{model.config.decoder.seq_len}")
    return wrong


def routing_differs(got, want):
    """Per layer, the share of (token, slot) choices of ``got (L, T, k)``
    that are not among ``want (L, T, k)``'s for the same token."""
    import jax.numpy as jnp
    found = jnp.any(got[..., :, None] == want[..., None, :], axis=-1)
    return 1.0 - jnp.mean(found.astype(jnp.float32), axis=(1, 2))


def check_train(run: harness.Run, result) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    from jimm_tpu.train.trainer import moe_lm_forward

    ref = importlib.import_module(f"benchmarks.reference.{run.config['family']}")
    model = result.model
    d = model.config.decoder
    sizes = sizes_of(model)
    wrong_sizes = [] if run.rehearse else check_sizes(run, model)
    batch = result.batch[0].shape[0]
    tokens = jax.random.randint(jax.random.key(run.seed + 1),
                                (batch, d.seq_len + 1), 0, d.vocab_size,
                                jnp.int32)
    leaves = {name: path.format(mid=(d.depth - d.dense_layers) // 2)
              for name, path in ref.GRAD_LEAVES.items()}

    # hidden state, routing choices, loss and gradients of ONE pass: a forward
    # compiled apart from the differentiated one breaks the router's
    # near-ties another way (1-2 % of the choices), and the reference,
    # forced to those, would be held against gradients of other routes
    @nnx.jit
    def model_side(model, tokens):
        (loss, (normed, chosen)), grads = nnx.value_and_grad(
            lambda m: moe_lm_forward(m, tokens), has_aux=True)(model)
        pure = nnx.to_pure_dict(grads)
        return (normed, chosen, loss,
                {name: _model_leaf(pure, path)
                 for name, path in leaves.items()})

    got_hidden, got_chosen, got_loss, got_grads = model_side(model, tokens)

    # the reference: one device, float32, highest matmul precision; inputs
    # are arguments, not closed over (a constant in the program would make
    # every seed another program and a compile-cache miss)
    device = jax.devices()[0]
    params = ref.params_from_state(
        nnx.to_pure_dict(nnx.state(model, nnx.Param)),
        model.sparse.blocks.mlp.router_bias[...], device=device)
    tokens = jax.device_put(tokens, device)
    attend = ref.causal_attention
    if d.seq_len > ATTEND_ROWS:
        attend = ref.in_blocks(attend, min(ATTEND_HEADS, d.num_heads),
                               ATTEND_ROWS, jax.checkpoint)

    def loss_of_leaves(selected, params, tokens, forced):
        for name, path in leaves.items():
            params = _with(params, path, selected[name])
        h, routing = ref.hidden_states(params, tokens[:, :-1], sizes,
                                       jax.checkpoint, attend, forced)
        return ref.loss_of_hidden(params, h, tokens[:, 1:],
                                  jax.checkpoint), (h, jnp.stack(routing))

    @jax.jit
    def reference_side(params, tokens, forced):
        selected = {name: _get(params, path) for name, path in leaves.items()}
        (value, (h, routing)), grads = jax.value_and_grad(
            loss_of_leaves, has_aux=True)(selected, params, tokens, forced)
        return h, routing, value, grads

    @jax.jit
    def logits_error(got_hidden, head_kernel, want_hidden, params):
        """``(max|a - b|, max|b|)`` of the logits, block by block: the
        model's as its loss takes them (the matmul in its own dtype)."""
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
        # the reference computes for the model's own routing choices and
        # reports its own beside them (reference/kanana.py::moe)
        want_hidden, want_chosen, want_loss, want_grads = reference_side(
            params, tokens, jax.device_put(got_chosen, device))
        logit_diff, logit_size = jax.device_get(logits_error(
            got_hidden, model.head.kernel[...], want_hidden, params))
    routing = jax.device_get(routing_differs(got_chosen, want_chosen))
    (got_hidden, got_loss, got_grads, want_hidden, want_loss,
     want_grads) = jax.device_get((got_hidden, got_loss, got_grads,
                                   want_hidden, want_loss, want_grads))

    tol = ref.TOLERANCE
    errors = {
        "hidden": _rel_max(got_hidden, want_hidden),
        "logits": (float(logit_diff / max(logit_size, 1e-30))
                   if np.isfinite(logit_diff) else float("inf")),
        "loss": abs(float(got_loss) - float(want_loss))
        / max(1.0, abs(float(want_loss))),
        "routing": float(np.max(routing)),
        "grads": {name: _rel_norm(got_grads[name], want_grads[name])
                  for name in leaves}}
    ok = (not wrong_sizes
          and all(errors[k] <= tol[k]
                  for k in ("hidden", "logits", "loss", "routing"))
          and all(e <= tol["grads"][name]
                  for name, e in errors["grads"].items()))
    return {"ok": bool(ok), "errors": errors, "tolerance": tol,
            "routing_differs_per_layer": [float(r) for r in routing],
            "loss_model": float(got_loss), "loss_reference": float(want_loss),
            "tokens": [batch, d.seq_len], "grad_leaves": leaves,
            "sizes_differ_from_file": wrong_sizes}
