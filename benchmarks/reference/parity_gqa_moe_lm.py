"""The comparison that decides ``correct`` for the train cell of a sparse
language model with grouped-query, window-and-full attention: the model as the
cell configured it (bfloat16, its own attention kernels and expert path, its
own loss and gradient from ``jimm_tpu/train/trainer.py``) against the plain
float32 reference, on ONE seeded batch at the timed sizes, outside the timed
window, of the timed run's own model (its weights and router biases as the
last step left them).

``parity_moe_lm.py``'s frame (that file hard-wires latent attention's sizes and
is not this PR's to edit): final hidden state, logits in blocks, loss, the
gradient on the reference's ``GRAD_LEAVES``, all of ONE differentiated pass of
the model and with the reference computed for the routing choices that pass
made, and per sparse layer the share of choices on which the reference's own
router differs (their mean is held to the limit). The hidden state is compared
by norm (``||a - b|| / ||b||``), not by its largest single difference. What
differs here:

- the sizes read off the model are grouped-query attention's (heads of their
  own width, key/value heads, window, which layers are full);
- the gradient leaves are named by the KIND of their layer (a windowed layer's
  ``W_k``, a grouped leaf; the full layer's ``W_gate``);
- room: 1.6 B parameters are 9.6 GB of bfloat16 weights and Adam moments on a
  16 GB chip, the reference's float32 copy of the weights is 6.4 GB more and
  its program asks for 7.2 GB. So, outside the timed window, the optimizer's
  state goes to the host before the model's pass and the model's weights after
  it (they come back when the reference is done; a run with both on the chip
  was refused, 5.2 GB free of 7.2), and the reference's attention runs per
  key/value head and block of ``ATTEND_ROWS`` query rows (its ``attend`` hook)
  with ``jax.checkpoint`` around each layer.
"""

from __future__ import annotations

import importlib

from benchmarks import harness
from benchmarks.reference.parity import (_get, _model_leaf, _rel_norm,
                                         _with)
from benchmarks.reference.parity_moe_lm import LOGITS_BLOCK, routing_differs

#: the reference's attention at the timed size: query rows a block
ATTEND_ROWS = 1024

#: configuration-file key -> how the built model's config gives it
_SIZES = {
    "hidden_size": lambda d: d.width,
    "intermediate_size": lambda d: d.mlp_dim,
    "num_attention_heads": lambda d: d.num_heads,
    "num_key_value_heads": lambda d: d.gqa.kv_heads,
    "head_dim": lambda d: d.gqa.head_dim,
    "sliding_window": lambda d: d.gqa.window,
    "global_attn_every_n_layers": lambda d: d.gqa.full_every,
    "vocab_size": lambda d: d.vocab_size,
    "num_layers": lambda d: d.depth,
    "dense_layers_held": lambda d: d.dense_layers,
    "first_layer": lambda d: d.first_layer,
    "num_experts": lambda d: d.moe.held_experts,
    "num_experts_per_tok": lambda d: d.moe.top_k,
    "num_shared_experts": lambda d: d.moe.shared_experts,
    "moe_intermediate_size": lambda d: d.moe.expert_dim,
    "route_scale": lambda d: d.moe.routed_scale,
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
    published = run.config["published"]["num_experts"]
    if published != built["router_width"]:
        wrong.append(f"router width: published {published} != built "
                     f"{built['router_width']}")
    seq = run.cell["traffic_params"]["seq_len"]
    if model.config.decoder.seq_len != seq:
        wrong.append(f"seq_len: cell {seq} != built "
                     f"{model.config.decoder.seq_len}")
    return wrong


def layer_kinds(ref, sizes: dict) -> dict:
    """Index within the sparse stack of its first windowed layer and of its
    first full-attention layer (``GRAD_LEAVES``' ``{window}``, ``{full}``)."""
    dense = sizes["dense_layers_held"]
    full = [ref.is_full(dense + i, sizes)
            for i in range(sizes["num_layers"] - dense)]
    return {"window": full.index(False), "full": full.index(True)}


def to_host(module) -> None:
    """A module's arrays off the device (it stays usable: a later use, or
    :func:`to_device`, places them again). Makes room for the reference."""
    import jax
    import numpy as np
    from flax import nnx
    def moves(x) -> bool:  # a random key has no numpy form, and no size
        return isinstance(x, jax.Array) and not jax.dtypes.issubdtype(
            x.dtype, jax.dtypes.prng_key)

    state = nnx.state(module)
    leaves = [x for x in jax.tree.leaves(state) if moves(x)]
    if not leaves:
        return
    nnx.update(module, jax.tree.map(
        lambda x: np.asarray(x) if moves(x) else x, state))
    for x in leaves:
        x.delete()


def to_device(module, device) -> None:
    import jax
    from flax import nnx
    nnx.update(module, jax.device_put(nnx.state(module), device))


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
    if not run.rehearse:
        to_host(result.optimizer)
    batch = result.batch[0].shape[0]
    tokens = jax.random.randint(jax.random.key(run.seed + 1),
                                (batch, d.seq_len + 1), 0, d.vocab_size,
                                jnp.int32)
    leaves = {name: path.format(**layer_kinds(ref, sizes))
              for name, path in ref.GRAD_LEAVES.items()}

    # hidden state, routing choices, loss and gradients of ONE pass
    # (parity_moe_lm.py has why)
    @nnx.jit
    def model_side(model, tokens):
        (loss, (normed, chosen)), grads = nnx.value_and_grad(
            lambda m: moe_lm_forward(m, tokens), has_aux=True)(model)
        pure = nnx.to_pure_dict(grads)
        return (normed, chosen, loss,
                {name: _model_leaf(pure, path)
                 for name, path in leaves.items()})

    got_hidden, got_chosen, got_loss, got_grads = jax.block_until_ready(
        model_side(model, tokens))

    # the reference: one device, float32, highest matmul precision; inputs
    # are arguments, not closed over (a constant in the program would make
    # every seed another program and a compile-cache miss)
    device = jax.devices()[0]
    params = ref.params_from_state(
        nnx.to_pure_dict(nnx.state(model, nnx.Param)),
        model.sparse.blocks.mlp.router_bias[...], device=device)
    tokens = jax.device_put(tokens, device)
    head_kernel = jnp.copy(model.head.kernel[...])
    if not run.rehearse:
        to_host(model)
    attend = ref.attention
    if d.seq_len > ATTEND_ROWS:
        attend = ref.in_blocks(attend, ATTEND_ROWS, jax.checkpoint)

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
        want_hidden, want_chosen, want_loss, want_grads = reference_side(
            params, tokens, jax.device_put(got_chosen, device))
        logit_diff, logit_size = jax.device_get(logits_error(
            got_hidden, head_kernel, want_hidden, params))
    routing = jax.device_get(routing_differs(got_chosen, want_chosen))
    (got_hidden, got_loss, got_grads, want_hidden, want_loss,
     want_grads) = jax.device_get((got_hidden, got_loss, got_grads,
                                   want_hidden, want_loss, want_grads))
    del params
    if not run.rehearse:
        to_device(model, device)

    tol = ref.REHEARSAL_TOLERANCE if run.rehearse else ref.TOLERANCE
    errors = {
        # a norm, not a maximum: over 25 M numbers it reads the same from seed
        # to seed, where the largest single difference moved by 40 %
        "hidden": _rel_norm(got_hidden, want_hidden),
        "logits": (float(logit_diff / max(logit_size, 1e-30))
                   if np.isfinite(logit_diff) else float("inf")),
        "loss": abs(float(got_loss) - float(want_loss))
        / max(1.0, abs(float(want_loss))),
        # the mean over the sparse layers: one layer's share moved by a
        # quarter from seed to seed, their mean by a twentieth
        "routing": float(np.mean(routing)),
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
