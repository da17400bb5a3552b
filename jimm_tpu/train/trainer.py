"""Training loop machinery: optimizer factory, jitted step builders for
classification and contrastive training.

The reference ships one MNIST example loop (`examples/vit_training.py`) and
nothing for its dual-tower models. Here training is library code: steps are
built once per (model, loss) pair, jitted with donated state, and work on any
mesh/rules combination (replicated, DP, TP, FSDP, FSDP+TP) because sharding
comes from the logical-rules context — not from the step code.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import optax
from flax import nnx

from jimm_tpu.train.losses import (blocked_cross_entropy, clip_softmax_loss,
                                   expected_exit_loss, ring_clip_infonce_loss,
                                   ring_sigmoid_loss, sigmoid_pairwise_loss)


@dataclass(frozen=True)
class OptimizerConfig:
    learning_rate: float = 1e-3
    weight_decay: float = 1e-4
    warmup_steps: int = 0
    total_steps: int | None = None  # cosine decay horizon; None = constant
    b1: float = 0.9
    b2: float = 0.999
    grad_clip_norm: float | None = 1.0
    min_lr_ratio: float = 0.0
    #: dtype for Adam's first moment (optax ``mu_dtype``); "bfloat16" halves
    #: that buffer's HBM footprint and read/write traffic on the (bandwidth-
    #: bound) update. None = accumulate in the param dtype.
    moment_dtype: str | None = None


def make_schedule(cfg: OptimizerConfig) -> optax.Schedule:
    if cfg.total_steps is None:
        if cfg.warmup_steps:
            return optax.linear_schedule(0.0, cfg.learning_rate,
                                         cfg.warmup_steps)
        return optax.constant_schedule(cfg.learning_rate)
    # short runs (smoke tests, debug) can have total_steps <= warmup_steps;
    # optax requires decay_steps > warmup_steps, so clamp the warmup — but
    # loudly, since in a long run this usually means a units typo
    warmup = min(cfg.warmup_steps, max(cfg.total_steps - 1, 0))
    if warmup != cfg.warmup_steps:
        import warnings
        warnings.warn(
            f"warmup_steps={cfg.warmup_steps} >= total_steps="
            f"{cfg.total_steps}; clamping warmup to {warmup}",
            stacklevel=2)
    return optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=cfg.learning_rate,
        warmup_steps=warmup, decay_steps=cfg.total_steps,
        end_value=cfg.learning_rate * cfg.min_lr_ratio)


def make_optimizer(model: nnx.Module, cfg: OptimizerConfig) -> nnx.Optimizer:
    """AdamW with warmup-cosine schedule and global-norm clipping; weight
    decay is masked off 1-D params (LayerNorm/bias) and scalars."""
    schedule = make_schedule(cfg)

    def decay_mask(params):
        return jax.tree.map(lambda p: jnp.ndim(p) > 1, params)

    chain = []
    if cfg.grad_clip_norm:
        chain.append(optax.clip_by_global_norm(cfg.grad_clip_norm))
    chain.append(optax.adamw(schedule, b1=cfg.b1, b2=cfg.b2,
                             weight_decay=cfg.weight_decay, mask=decay_mask,
                             mu_dtype=cfg.moment_dtype))
    return nnx.Optimizer(model, optax.chain(*chain), wrt=nnx.Param)


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

def make_classifier_train_step(*, donate: bool = False) -> Callable:
    """Cross-entropy classification step (ref `examples/vit_training.py:81-102`
    semantics: value_and_grad over model, accuracy metric, optimizer update).
    ``donate=True`` donates model+optimizer buffers so params/m/v update in
    place (same HBM rationale as ``make_contrastive_train_step``)."""

    @partial(nnx.jit, donate_argnums=(0, 1) if donate else ())
    def train_step(model: nnx.Module, optimizer: nnx.Optimizer,
                   images: jax.Array, labels: jax.Array) -> dict[str, jax.Array]:
        def loss_fn(model):
            logits = model(images)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
            return loss, logits

        # named_scope (not obs.span — this is traced code) tags the emitted
        # ops so profile.op_stats and obs trace lanes share one vocabulary
        with jax.named_scope("fwd_bwd"):
            (loss, logits), grads = nnx.value_and_grad(
                loss_fn, has_aux=True)(model)
        with jax.named_scope("optimizer_update"):
            optimizer.update(model, grads)
        accuracy = jnp.mean(jnp.argmax(logits, axis=-1) == labels)
        return {"loss": loss, "accuracy": accuracy}

    return train_step


def make_classifier_eval_step() -> Callable:
    @nnx.jit
    def eval_step(model: nnx.Module, images: jax.Array, labels: jax.Array
                  ) -> dict[str, jax.Array]:
        logits = model(images)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        accuracy = jnp.mean(jnp.argmax(logits, axis=-1) == labels)
        return {"loss": loss, "accuracy": accuracy}

    return eval_step


def contrastive_loss_fn(model, images: jax.Array, text: jax.Array, *,
                        kind: str, mesh=None, axis_name: str = "data"
                        ) -> jax.Array:
    """Shared loss dispatch for CLIP/SigLIP models.

    - ``"clip"``: symmetric softmax InfoNCE (needs ``logit_scale``).
    - ``"clip_ring"``: ppermute-ring InfoNCE over ``axis_name`` — streaming
      logsumexp, never materializes the global logit matrix.
    - ``"siglip"``: dense sigmoid all-pairs (oracle / single chip).
    - ``"siglip_ring"``: ppermute-ring sigmoid over ``axis_name`` —
      the north-star loss.

    ``images`` is either a ``(B, H, W, C)`` array or a NaFlex triple
    ``(patches, spatial_shapes, mask)`` (see
    `SigLIP.encode_image_naflex`) — the latter trains SigLIP2 on
    variable-resolution batches, which the reference cannot.
    """
    if isinstance(images, (tuple, list)):
        img = model.encode_image_naflex(*images)
    else:
        img = model.encode_image(images)
    txt = model.encode_text(text)
    scale = model.logit_scale[...]
    if kind == "clip":
        return clip_softmax_loss(img, txt, scale)
    if kind == "clip_ring":
        return ring_clip_infonce_loss(img, txt, scale, mesh=mesh,
                                      axis_name=axis_name)
    bias = model.logit_bias[...]
    if kind == "siglip":
        return sigmoid_pairwise_loss(img, txt, scale, bias)
    if kind == "siglip_ring":
        return ring_sigmoid_loss(img, txt, scale, bias, mesh=mesh,
                                 axis_name=axis_name)
    raise ValueError(f"unknown contrastive loss kind {kind!r}")


def make_contrastive_train_step(kind: str = "siglip_ring", *, mesh=None,
                                axis_name: str = "data",
                                donate: bool = False) -> Callable:
    """``donate=True`` donates the model+optimizer state buffers to XLA so
    params/m/v update in place instead of double-buffering — saves HBM
    capacity and write bandwidth on the hot training path."""
    loss = partial(contrastive_loss_fn, kind=kind, mesh=mesh,
                   axis_name=axis_name)

    @partial(nnx.jit, donate_argnums=(0, 1) if donate else ())
    def train_step(model: nnx.Module, optimizer: nnx.Optimizer,
                   images: jax.Array, text: jax.Array) -> dict[str, jax.Array]:
        def loss_fn(model):
            return loss(model, images, text)

        with jax.named_scope("fwd_bwd"):
            loss_val, grads = nnx.value_and_grad(loss_fn)(model)
        with jax.named_scope("optimizer_update"):
            optimizer.update(model, grads)
        return {"loss": loss_val}

    return train_step


def lm_loss_fn(model, tokens: jax.Array
               ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """The looped language model's loss on ``(B, S + 1)`` token ids: inputs
    are the first S, targets the ids shifted by one; every pass's logits are
    taken in blocks (`blocked_cross_entropy`). Returns the loss and the
    per-pass means of `expected_exit_loss`."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    hidden = model.hidden_states(inputs)
    with jax.named_scope("exit_head"):
        ce = blocked_cross_entropy(hidden, model.head.kernel[...], targets)
        return expected_exit_loss(ce, model.exit_gates(hidden),
                                  beta=model.config.exit_beta)


def _exit_metrics(model, per_pass: dict) -> dict[str, jax.Array]:
    """Per pass ``r``: ``loss_exit<r>`` (mean cross-entropy of that pass's
    logits) and ``exit_p<r>`` (mean exit mass)."""
    metrics = {}
    for r in range(per_pass["ce"].shape[0]):
        metrics[f"loss_exit{r + 1}"] = per_pass["ce"][r]
        metrics[f"exit_p{r + 1}"] = per_pass["p"][r]
    return metrics


def moe_lm_forward(model, tokens: jax.Array
                   ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """The sparse decoder on ``(B, S + 1)`` token ids: the mean next-token
    cross-entropy (the logits taken in blocks) and, of the same pass, the
    final hidden state after its norm and the experts each token chose
    ``(layers, B * S, top_k)``. A second pass compiled apart from this one
    rounds differently and breaks the router's near-ties another way, so
    whoever compares gradients with routing takes both from here."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    hidden, chosen = model.hidden_states(inputs)
    with jax.named_scope("lm_head"):
        normed = model.norm(hidden)
        ce = blocked_cross_entropy(normed, model.head.kernel[...], targets)
    return jnp.mean(ce), (normed, chosen)


def moe_lm_loss_fn(model, tokens: jax.Array
                   ) -> tuple[jax.Array, jax.Array]:
    """The sparse decoder's loss (`moe_lm_forward`) and the sparse layers'
    routing counts ``(layers, num_experts)``."""
    from jimm_tpu.nn.moe import routing_counts
    loss, (_, chosen) = moe_lm_forward(model, tokens)
    return loss, routing_counts(
        chosen, model.config.decoder.moe.num_experts)


def _routing_metrics(model, counts: jax.Array) -> dict[str, jax.Array]:
    """Moves the routers' selection biases by this step's ``counts`` and
    reports the step's routing: ``moe_held_rows`` (assignments to the experts
    held here, summed over the sparse layers), ``moe_load_max_over_mean``
    (over the held experts, worst layer) and ``router_bias_absmax``."""
    model.update_router_bias(counts)
    moe = model.config.decoder.moe
    held = counts[:, moe.first_expert:moe.first_expert + moe.held_experts] \
        .astype(jnp.float32)
    bias = model.router_bias()
    return {"moe_held_rows": jnp.sum(held),
            "moe_load_max_over_mean": jnp.max(
                jnp.max(held, axis=-1)
                / jnp.maximum(jnp.mean(held, axis=-1), 1.0)),
            "router_bias_absmax": jnp.max(jnp.abs(bias))}


def dense_lm_forward(model, tokens: jax.Array
                     ) -> tuple[jax.Array, jax.Array]:
    """A decoder without a sparse layer and with a tied head
    (`models/granite.py`) on ``(B, S + 1)`` token ids: the mean next-token
    cross-entropy of ``model.head_input(RMS_f(h)) E^T`` (the logits taken in
    blocks) and, of the same pass, the final hidden state after its norm."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    hidden = model.hidden_states(inputs)
    with jax.named_scope("lm_head"):
        normed = model.norm(hidden)
        ce = blocked_cross_entropy(model.head_input(normed),
                                   model.embed.embedding[...], targets,
                                   vocab_major=True)
    return jnp.mean(ce), normed


def dense_lm_loss_fn(model, tokens: jax.Array) -> tuple[jax.Array, None]:
    """The dense decoder's loss (`dense_lm_forward`), no auxiliary."""
    return dense_lm_forward(model, tokens)[0], None


#: a language-model family's step: its loss ``(model, tokens) -> (loss, aux)``
#: and what follows the optimizer's update, ``(model, aux) -> metrics``
LM_STEPS: dict[str, tuple[Callable, Callable]] = {
    "ouro": (lm_loss_fn, _exit_metrics),
    "kanana": (moe_lm_loss_fn, _routing_metrics),
    "trinity": (moe_lm_loss_fn, _routing_metrics),
    "kimi": (moe_lm_loss_fn, _routing_metrics),
    "granite": (dense_lm_loss_fn, lambda model, aux: {}),
}


def make_lm_train_step(family: str = "ouro", *, donate: bool = False
                       ) -> Callable:
    """Next-token step of a language-model ``family`` (`LM_STEPS`): the
    looped decoder's metrics carry `_exit_metrics`, the sparse decoder's
    `_routing_metrics`, the dense decoder's none but the loss. ``donate`` as
    in ``make_contrastive_train_step``."""
    loss_fn, after_update = LM_STEPS[family]

    @partial(nnx.jit, donate_argnums=(0, 1) if donate else ())
    def train_step(model: nnx.Module, optimizer: nnx.Optimizer,
                   tokens: jax.Array) -> dict[str, jax.Array]:
        with jax.named_scope("fwd_bwd"):
            (loss, aux), grads = nnx.value_and_grad(
                lambda m: loss_fn(m, tokens), has_aux=True)(model)
        with jax.named_scope("optimizer_update"):
            optimizer.update(model, grads)
        return {"loss": loss, **after_update(model, aux)}

    return train_step
