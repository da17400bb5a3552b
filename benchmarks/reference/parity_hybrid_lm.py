"""The comparison that decides ``correct`` for the train cell of a hybrid
language model (linear-attention layers beside latent-attention ones, a
mixture of experts behind both): the model as the cell configured it
(bfloat16, its own chunked scan, attention kernels and expert path, its own
loss and gradient from ``jimm_tpu/train/trainer.py``) against the plain float32
reference, on ONE seeded batch at the timed sizes, outside the timed window, of
the timed run's own model (its weights and router biases as the last step left
them).

``parity_gqa_moe_lm.py``'s frame (that file hard-wires grouped-query
attention's sizes and the two-stack tree and is not this PR's to edit): final
hidden state, logits in blocks, loss, the gradient on the reference's
``GRAD_LEAVES``, all of ONE differentiated pass of the model and with the
reference computed for the routing choices that pass made, and per sparse layer
the share of choices on which the reference's own router differs (their mean
is held to the limit). What differs here:

- the sizes read off the model are those of both mixers (``linear_attn_config``
  beside the latent attention's widths), and the layer kinds are read from the
  model's runs;
- the model is a sequence of runs of like layers, so a gradient leaf is named
  by the run of its KIND (``{kda}``: the first sparse run of KDA layers,
  ``{mla}``: the first run of latent-attention layers), and the routers' biases
  come from every sparse run in layer order;
- ``scan``: the program's chunked scan ALONE (``ops/delta_rule.py::chunk_kda``,
  the function the timed step calls) against the reference's token-by-token
  recurrence, on seeded q, k, v, b at the timed shape and a gate drawn with the
  timed model's own ``A_log``: float32 inside, so it reads rounding in the
  sixth digit where the bfloat16 model's other numbers read the third, and a
  scan that kept its state in bfloat16 reads the second;
- ``update`` and ``moment``: the TIMED program itself, ``result.step_fn`` as
  the window ran it, called once more on the seeded batch with the run's
  optimizer, and the change it made to the parameters and to Adam's first
  moment on ``GRAD_LEAVES`` held against the reference's AdamW step
  (``ref.adamw_step``, numpy on the host) from the REFERENCE's gradients and
  the moments as the optimizer held them (:func:`timed_step`). A state left
  unchanged reads 1 in both;
- room, at the timed size: the optimizer's state goes to the host before the
  model's pass and the model's weights after it (``parity_gqa_moe_lm``'s
  moves), both come back where they were before the step, the reference's
  latent attention runs per ``ATTEND_HEADS`` heads and ``ATTEND_ROWS`` query
  rows, its recurrence token by token in blocks of ``STATE_BLOCK`` tokens,
  ``jax.checkpoint`` around each.
"""

from __future__ import annotations

import importlib

from benchmarks import harness
from benchmarks.reference.parity import (_get, _model_leaf, _rel_norm,
                                         _with)
from benchmarks.reference.parity_gqa_moe_lm import to_host
from benchmarks.reference.parity_moe_lm import LOGITS_BLOCK, routing_differs

#: the reference's latent attention at the timed size: heads and query rows a
#: block ((4, 1024, 16384) float32 scores are 268 MB)
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
    "num_experts": lambda d: d.moe.held_experts,
    "num_experts_per_token": lambda d: d.moe.top_k,
    "num_shared_experts": lambda d: d.moe.shared_experts,
    "moe_intermediate_size": lambda d: d.moe.expert_dim,
    "routed_scaling_factor": lambda d: d.moe.routed_scale,
    "rms_norm_eps": lambda d: d.ln_eps,
    "hidden_act": lambda d: d.act,
}
#: the nested group's keys that are sizes of the layer
_KDA_SIZES = {
    "num_heads": lambda k: k.num_heads,
    "head_dim": lambda k: k.head_dim,
    "short_conv_kernel_size": lambda k: k.conv_taps,
}


def sizes_of(model) -> dict:
    """Reference sizes read off the model under test (so that a rehearsal at
    ``--tiny`` compares like with like; at the published widths they equal
    the configuration file, which :func:`check_sizes` asserts)."""
    d = model.config.decoder
    mixers = d.mixers
    return {**{key: read(d) for key, read in _SIZES.items()},
            "linear_attn_config": {
                **{key: read(d.kda) for key, read in _KDA_SIZES.items()},
                "kda_layers": [i + 1 for i, m in enumerate(mixers)
                               if m == "kda"],
                "full_attn_layers": [i + 1 for i, m in enumerate(mixers)
                                     if m == "mla"]},
            "gate_rank": d.kda.gate_rank,
            "first_layer": d.first_layer,
            "first_expert": d.moe.first_expert,
            "router_width": d.moe.num_experts}


def check_sizes(run: harness.Run, model) -> list[str]:
    """Where the model the program built differs from the configuration
    file (nothing, unless this is a rehearsal)."""
    built = sizes_of(model)
    wrong = [f"{key}: file {run.config[key]} != built {built[key]}"
             for key in _SIZES if run.config[key] != built[key]]
    group = "linear_attn_config"
    wrong += [f"{group}.{key}: file {run.config[group][key]} != built "
              f"{built[group][key]}" for key in built[group]
              if run.config[group][key] != built[group][key]]
    published = run.config["published"]["num_experts"]
    if published != built["router_width"]:
        wrong.append(f"router width: published {published} != built "
                     f"{built['router_width']}")
    if run.config["assumed"]["gate_rank"] != built["gate_rank"]:
        wrong.append(f"gate rank: assumed {run.config['assumed']['gate_rank']}"
                     f" != built {built['gate_rank']}")
    seq = run.cell["traffic_params"]["seq_len"]
    if model.config.decoder.seq_len != seq:
        wrong.append(f"seq_len: cell {seq} != built "
                     f"{model.config.decoder.seq_len}")
    return wrong


def layer_kinds(model) -> dict:
    """The run that ``GRAD_LEAVES``' ``{kda}`` and ``{mla}`` name: the first
    SPARSE run of KDA layers and the first run of latent-attention layers."""
    runs = model.config.decoder.runs()
    return {"kda": next(name for name, c in runs
                        if c.kda is not None and c.moe is not None),
            "mla": next(name for name, c in runs if c.mla is not None)}


def scan_error(ref, model, seed: int, wrap) -> float:
    """``||a - b|| / ||b||`` of the program's chunked scan against the
    reference's recurrence at the model's shape (one sequence)."""
    import jax
    import jax.numpy as jnp

    from jimm_tpu.ops.delta_rule import chunk_kda
    d = model.config.decoder
    a_log = getattr(model, layer_kinds(model)["kda"]).blocks.attn.A_log[...][0]
    shape = (1, d.seq_len, d.kda.num_heads, d.kda.head_dim)
    keys = jax.random.split(jax.random.key(seed + 2), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    inputs = (unit(jax.random.normal(keys[0], shape)) * shape[-1] ** -0.5,
              unit(jax.random.normal(keys[1], shape)),
              jax.random.normal(keys[2], shape),
              -jnp.exp(a_log.astype(jnp.float32))[:, None]
              * jax.nn.softplus(jax.random.normal(keys[3], shape)),
              jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3])))
    got = jax.jit(lambda *a: chunk_kda(*a, chunk=d.kda.chunk))(*inputs)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: ref.delta_rule(*a, wrap))(*inputs)
    return _rel_norm(jax.device_get(got), jax.device_get(want))


def placements(module) -> list:
    """Where each array of a module lives, in the order of ``nnx.state``'s
    leaves: what :func:`put_back` needs after ``to_host``."""
    import jax
    from flax import nnx
    return [x.sharding for x in jax.tree.leaves(nnx.state(module))
            if isinstance(x, jax.Array) and not jax.dtypes.issubdtype(
                x.dtype, jax.dtypes.prng_key)]


def put_back(module, shardings: list) -> None:
    """The arrays ``to_host`` moved, placed again exactly where they were, so
    that the step compiled for them runs as compiled (a plain ``device_put``
    to the device gives another kind of sharding and another compile)."""
    import jax
    import numpy as np
    from flax import nnx
    leaves, tree = jax.tree.flatten(nnx.state(module))
    where = iter(shardings)
    nnx.update(module, tree.unflatten(
        [jax.device_put(x, next(where)) if isinstance(x, np.ndarray) else x
         for x in leaves]))


def adam_state(optimizer) -> dict:
    """The optimizer's ``{"count", "mu", "nu"}``, wherever its chain holds
    them."""
    from flax import nnx

    def find(node):
        if not isinstance(node, dict):
            return None
        if "mu" in node and "nu" in node:
            return node
        return next((hit for hit in map(find, node.values())
                     if hit is not None), None)

    return find(nnx.to_pure_dict(nnx.state(optimizer)))


def timed_step(ref, result, tokens, leaves: dict, want_grads: dict,
               grad_norm: float) -> dict:
    """``result.step_fn``, the compiled step the window timed, once more on
    the seeded batch, and what it did to ``leaves`` against the reference's
    AdamW step from the reference's gradients.

    The run's schedule ends at zero after its last step, so every counter of
    the optimizer is first set back by one: the step taken here is the run's
    last one again (its rate, its bias correction), from the state the run
    left. ``update`` pools the leaves (``||all changes - all reference
    changes|| / ||all reference changes||``: a bfloat16 parameter far above
    the rate's size does not move at all, on either side, and a leaf of such
    has no norm to divide by); ``moment`` is the worst leaf. The reference's
    new value is rounded to the dtype the program stores it in, as the
    program's is."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    from jimm_tpu.parallel import use_sharding
    model, optimizer = result.model, result.optimizer
    steps = int(optimizer.step[...])
    nnx.update(optimizer, jax.tree.map(
        lambda x: x - 1 if x.ndim == 0 and jnp.issubdtype(x.dtype, jnp.integer)
        else x, nnx.state(optimizer)))

    def held() -> dict:
        adam = adam_state(optimizer)
        trees = {"p": nnx.to_pure_dict(nnx.state(model, nnx.Param)),
                 "m": adam["mu"], "v": adam["nu"]}
        return {kind: {name: np.array(_model_leaf(tree, path))
                       for name, path in leaves.items()}
                for kind, tree in trees.items()}

    before = held()
    with use_sharding(result.mesh, result.rules):
        metrics = jax.block_until_ready(result.step_fn(
            model, optimizer,
            jax.device_put(tokens, result.batch[0].sharding)))
    after = held()

    def f32(x):
        return np.asarray(x, np.float32)

    got, want = {"p": {}, "m": {}}, {"p": {}, "m": {}}
    for name in leaves:
        p, m = before["p"][name], before["m"][name]
        change, moment = ref.adamw_step(
            f32(p), f32(m), f32(before["v"][name]), f32(want_grads[name]),
            count=steps - 1, steps=steps, grad_norm=grad_norm)
        want["p"][name] = f32((f32(p) + change).astype(p.dtype)) - f32(p)
        want["m"][name] = f32(moment.astype(m.dtype)) - f32(m)
        got["p"][name] = f32(after["p"][name]) - f32(p)
        got["m"][name] = f32(after["m"][name]) - f32(m)

    def pooled(kind) -> float:
        off = sum(float(np.sum(np.square(got[kind][n] - want[kind][n])))
                  for n in leaves)
        size = sum(float(np.sum(np.square(want[kind][n]))) for n in leaves)
        return float(np.sqrt(off) / max(np.sqrt(size), 1e-30))

    by_leaf = {kind: {n: _rel_norm(got[kind][n], want[kind][n])
                      for n in leaves} for kind in ("p", "m")}
    return {"update": pooled("p"), "moment": max(by_leaf["m"].values()),
            "update_by_leaf": by_leaf["p"], "moment_by_leaf": by_leaf["m"],
            "moved_share": {n: float(np.mean(got["p"][n] != 0))
                            for n in leaves},
            "steps": steps, "rate": ref.learning_rate(steps - 1, steps),
            "grad_norm": grad_norm, "loss": float(metrics["loss"])}


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
    was_at = {}
    if not run.rehearse:
        was_at = {"optimizer": placements(result.optimizer),
                  "model": placements(model)}
        to_host(result.optimizer)
    batch = result.batch[0].shape[0]
    tokens = jax.random.randint(jax.random.key(run.seed + 1),
                                (batch, d.seq_len + 1), 0, d.vocab_size,
                                jnp.int32)
    leaves = {name: path.format(**layer_kinds(model))
              for name, path in ref.GRAD_LEAVES.items()}

    # hidden state, routing choices, loss and gradients of ONE pass
    # (parity_moe_lm.py has why)
    @nnx.jit
    def model_side(model, tokens):
        (loss, (normed, chosen)), grads = nnx.value_and_grad(
            lambda m: moe_lm_forward(m, tokens), has_aux=True)(model)
        pure = nnx.to_pure_dict(grads)
        # the norm over ALL gradients, which the optimizer's clip divides by
        # (the reference holds eight leaves, so this one number is the model's)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g in jax.tree.leaves(pure)))
        return (normed, chosen, loss, norm,
                {name: _model_leaf(pure, path)
                 for name, path in leaves.items()})

    got_hidden, got_chosen, got_loss, grad_norm, got_grads = \
        jax.block_until_ready(model_side(model, tokens))

    # the reference: one device, float32, highest matmul precision; inputs
    # are arguments, not closed over (a constant in the program would make
    # every seed another program and a compile-cache miss)
    device = jax.devices()[0]
    params = ref.params_from_state(
        nnx.to_pure_dict(nnx.state(model, nnx.Param)),
        model.router_bias(), device=device)
    tokens = jax.device_put(tokens, device)
    head_kernel = jnp.copy(model.head.kernel[...])
    if not run.rehearse:
        to_host(model)
    attend, wrap = ref.causal_attention, lambda fn: fn
    if d.seq_len > ATTEND_ROWS:
        wrap = jax.checkpoint
        attend = ref.in_blocks(attend, ATTEND_HEADS, ATTEND_ROWS, wrap)

    def loss_of_leaves(selected, params, tokens, forced):
        for name, path in leaves.items():
            params = _with(params, path, selected[name])
        h, routing = ref.hidden_states(params, tokens[:, :-1], sizes,
                                       wrap, attend, forced)
        return ref.loss_of_hidden(params, h, tokens[:, 1:],
                                  wrap), (h, jnp.stack(routing))

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
        put_back(model, was_at["model"])
    scan = scan_error(ref, model, run.seed, wrap)
    if not run.rehearse:
        put_back(result.optimizer, was_at["optimizer"])
    step = timed_step(ref, result, tokens, leaves, want_grads,
                      float(grad_norm))

    # where the hidden state's distance sits: ``||a - b|| / ||b||`` token by
    # token (a norm over everything hides one token that is wholly wrong)
    per_token = np.linalg.norm(
        np.asarray(got_hidden, np.float32) - want_hidden, axis=-1) \
        / np.maximum(np.linalg.norm(want_hidden, axis=-1), 1e-30)
    worst = np.unravel_index(np.argmax(per_token), per_token.shape)
    by_token = {"max": float(per_token.max()),
                "at": [int(i) for i in worst],
                "median": float(np.median(per_token)),
                "over_0.2": int((per_token > 0.2).sum()),
                "worst_positions": [int(i) for i in np.argsort(
                    per_token.reshape(-1))[-8:]]}

    tol = ref.REHEARSAL_TOLERANCE if run.rehearse else ref.TOLERANCE
    errors = {
        "hidden": _rel_norm(got_hidden, want_hidden),
        "logits": (float(logit_diff / max(logit_size, 1e-30))
                   if np.isfinite(logit_diff) else float("inf")),
        "loss": abs(float(got_loss) - float(want_loss))
        / max(1.0, abs(float(want_loss))),
        "routing": float(np.mean(routing)),
        "scan": scan,
        "update": step["update"],
        "moment": step["moment"],
        "grads": {name: _rel_norm(got_grads[name], want_grads[name])
                  for name in leaves}}
    ok = (not wrong_sizes
          and all(errors[k] <= tol[k]
                  for k in ("hidden", "logits", "loss", "routing", "scan",
                            "update", "moment"))
          and all(e <= tol["grads"][name]
                  for name, e in errors["grads"].items()))
    return {"ok": bool(ok), "errors": errors, "tolerance": tol,
            "routing_differs_per_layer": [float(r) for r in routing],
            "hidden_by_token": by_token, "timed_step": step,
            "loss_model": float(got_loss), "loss_reference": float(want_loss),
            "tokens": [batch, d.seq_len], "grad_leaves": leaves,
            "sizes_differ_from_file": wrong_sizes}
