"""The comparison that decides ``correct``: the model as the cell configures
it (bfloat16, its own attention path, its own loss from
``jimm_tpu/train/losses.py`` and ``trainer.py``, its mesh) against the plain
float32 reference, on eight samples drawn from the seed, outside the timed
window. Outputs (embeddings, or logits for ViT), the loss, and the gradient
on three named leaves. On several chips the reference runs on one device with
the parameters gathered onto it.
"""

from __future__ import annotations

import importlib

from benchmarks import harness

SAMPLES = 8


def _get(tree, path: str):
    node = tree
    for key in path.split("/"):
        node = node[int(key)] if isinstance(node, list) else node[key]
    return node


def _with(tree, path: str, value):
    """A copy of ``tree`` with the leaf at ``path`` replaced."""
    key, _, rest = path.partition("/")
    if isinstance(tree, list):
        i = int(key) % len(tree)
        return [(_with(v, rest, value) if rest else value) if j == i else v
                for j, v in enumerate(tree)]
    return {**tree, key: _with(tree[key], rest, value) if rest else value}


def _model_leaf(pure: dict, path: str):
    """The same leaf in the model's own (stacked) state: ``blocks/-1/...``
    indexes the leading layer axis."""
    keys = path.split("/")
    if "blocks" in keys:
        i = keys.index("blocks")
        layer = int(keys[i + 1])
        leaf = _get(pure, "/".join(keys[:i + 1] + keys[i + 2:]))
        return leaf[layer]
    return _get(pure, path)


def samples(seed: int, model):
    """Eight seeded inputs at the sizes the model under test was built with."""
    import jax
    import jax.numpy as jnp
    cfg = model.config
    v = cfg.vision
    k_img, k_second = jax.random.split(jax.random.key(seed + 1))
    images = jax.random.normal(
        k_img, (SAMPLES, v.image_size, v.image_size, v.channels), jnp.float32)
    if hasattr(cfg, "text"):
        second = jax.random.randint(
            k_second, (SAMPLES, cfg.text.context_length), 0,
            cfg.text.vocab_size, jnp.int32)
    else:
        second = jax.random.randint(k_second, (SAMPLES,), 0, cfg.num_classes,
                                    jnp.int32)
    return images, second


def _sizes(model_cfg_part) -> dict:
    """Reference sizes read off the model under test (so that a rehearsal at
    ``--tiny`` compares like with like; at the published widths they equal
    the configuration file, which :func:`check_sizes` asserts)."""
    return {"num_attention_heads": model_cfg_part.num_heads,
            "layer_norm_eps": model_cfg_part.ln_eps,
            "hidden_act": "gelu" if model_cfg_part.act == "gelu" else "gelu_tanh",
            "patch_size": getattr(model_cfg_part, "patch_size", None),
            "hidden_size": model_cfg_part.width,
            "intermediate_size": model_cfg_part.mlp_dim,
            "num_hidden_layers": model_cfg_part.depth}


def check_sizes(run: harness.Run, model) -> list[str]:
    """Where the model the program built differs from the configuration
    file's published sizes (nothing, unless this is a rehearsal)."""
    wrong = []
    pairs = [(run.config.get("vision_config", run.config), model.config.vision)]
    if "text_config" in run.config:
        pairs.append((run.config["text_config"], model.config.text))
    for published, built in pairs:
        got = _sizes(built)
        for key in ("hidden_size", "intermediate_size", "num_hidden_layers",
                    "num_attention_heads", "layer_norm_eps"):
            if published[key] != got[key]:
                wrong.append(f"{key}: file {published[key]} != built {got[key]}")
        if "image_size" in published and (
                published["image_size"] != built.image_size
                or published["patch_size"] != built.patch_size):
            wrong.append("image or patch size")
        if "vocab_size" in published and (
                published["vocab_size"] != built.vocab_size
                or published["max_position_embeddings"] != built.context_length):
            wrong.append("vocabulary or context length")
    if "num_labels" in run.config and (
            run.config["num_labels"] != model.config.num_classes):
        wrong.append(f"num_labels: file {run.config['num_labels']} != built "
                     f"{model.config.num_classes}")
    return wrong


def _rel_max(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    if not np.all(np.isfinite(a)):
        return float("inf")
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _rel_norm(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    if not np.all(np.isfinite(a)):
        return float("inf")
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _reference_params(model, ref, device):
    """The model's parameters as the reference wants them."""
    from flax import nnx
    pure = nnx.to_pure_dict(nnx.state(model, nnx.Param))
    return ref.params_from_state(pure, device=device)


def check_train(run: harness.Run, result) -> dict:
    """Model against reference: outputs, loss, three gradient leaves."""
    import jax
    import optax
    from flax import nnx

    from jimm_tpu.parallel import shard_batch, use_sharding
    from jimm_tpu.train.trainer import contrastive_loss_fn

    family = run.config["family"]
    ref = importlib.import_module(f"benchmarks.reference.{family}")
    model, mesh, rules = result.model, result.mesh, result.rules
    wrong_sizes = [] if run.rehearse else check_sizes(run, model)
    images, second = samples(run.seed, model)
    leaves = ref.GRAD_LEAVES
    loss_kind = run.cell["traffic_params"].get("loss")

    def pick(grads):
        pure = nnx.to_pure_dict(grads)
        return {name: _model_leaf(pure, path) for name, path in leaves.items()}

    @nnx.jit
    def model_side(model, images, second):
        if family == "vit":
            def loss_fn(m):
                logits = m(images)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, second).mean(), logits
            (loss, out), grads = nnx.value_and_grad(
                loss_fn, has_aux=True)(model)
            return (out,), loss, pick(grads)
        out = (model.encode_image(images), model.encode_text(second))
        loss, grads = nnx.value_and_grad(
            lambda m: contrastive_loss_fn(m, images, second, kind=loss_kind,
                                          mesh=mesh, axis_name="data"))(model)
        return out, loss, pick(grads)

    with use_sharding(mesh, rules):
        batch = ((images, second) if mesh is None
                 else shard_batch((images, second), mesh, rules))
        got_out, got_loss, got_grads = jax.device_get(
            model_side(model, *batch))

    # the reference: one device, float32, highest matmul precision
    device = jax.devices()[0]
    params = _reference_params(model, ref, device)
    images, second = jax.device_put((images, second), device)
    # inputs are arguments, not closed over: a constant in the program would
    # make every seed another program and a compile-cache miss
    if family == "vit":
        sizes = (_sizes(model.config.vision),)

        def outputs(p, images, second):
            return (ref.logits(p, images, *sizes),)
    else:
        sizes = (_sizes(model.config.vision), _sizes(model.config.text))

        def outputs(p, images, second):
            return (ref.encode_image(p, images, sizes[0]),
                    ref.encode_text(p, second, sizes[1]))

    def loss_of_leaves(selected, params, images, second):
        for name, path in leaves.items():
            params = _with(params, path, selected[name])
        return ref.loss(params, images, second, *sizes)

    @jax.jit
    def reference_side(params, images, second):
        selected = {name: _get(params, path) for name, path in leaves.items()}
        value, grads = jax.value_and_grad(loss_of_leaves)(
            selected, params, images, second)
        return outputs(params, images, second), value, grads

    with jax.default_matmul_precision("highest"):
        want_out, want_loss, want_grads = jax.device_get(
            reference_side(params, images, second))

    tol = ref.TOLERANCE
    errors = {"outputs": max(_rel_max(g, w)
                             for g, w in zip(got_out, want_out, strict=True)),
              "loss": abs(float(got_loss) - float(want_loss))
              / max(1.0, abs(float(want_loss))),
              "grads": {name: _rel_norm(got_grads[name], want_grads[name])
                        for name in leaves}}
    ok = (not wrong_sizes and errors["outputs"] <= tol["outputs"]
          and errors["loss"] <= tol["loss"]
          and all(e <= tol["grads"] for e in errors["grads"].values()))
    return {"ok": bool(ok), "errors": errors, "tolerance": tol,
            "loss_model": float(got_loss), "loss_reference": float(want_loss),
            "samples": SAMPLES, "sizes_differ_from_file": wrong_sizes}
