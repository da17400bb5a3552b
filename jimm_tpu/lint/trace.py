"""Layer-2 checks (``--trace``): lower registered model entry points on tiny
shapes — no real execution, only ``jit(...).lower()`` (plus XLA compilation
for the FSDP check, still host-side) — and assert TPU-correctness properties
on the emitted program text:

- **JLT101** donation materialized: a donated train step's StableHLO carries
  ``tf.aliasing_output`` on the model/optimizer state inputs. Donation that
  silently fails to alias (dtype/layout mismatch, struct change) doubles HBM
  on the hot path without any runtime error.
- **JLT102** no full-parameter all-gather under FSDP: the compiled module
  must not gather an entire stacked parameter onto every device — good FSDP
  lowering moves per-layer slices (or uses reduce-scatter/all-reduce).
- **JLT103** stable program across the declared batch buckets: the op
  histogram of the lowered module must be identical for every batch size in
  :data:`BATCH_BUCKETS`, otherwise each bucket compiles a structurally
  different program (cache-key churn and recompiles at runtime).

Tiny configs keep tracing cheap (~seconds); the properties they certify are
shape-independent program structure, not numerics.
"""

from __future__ import annotations

import re

from jimm_tpu.lint.core import ERROR, WARNING, Finding

#: batch sizes the data pipeline is allowed to present to a jitted step;
#: JLT103 asserts one program structure covers them all
BATCH_BUCKETS = (2, 4)

_TINY_VISION = dict(image_size=16, patch_size=8, width=32, depth=2,
                    num_heads=2, mlp_dim=64)

_ALLGATHER_RE = re.compile(
    r"=\s*([a-z]+[0-9]+)\[([0-9,]*)\][^=]*\ball-gather")

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1}


def _tiny_vit():
    from flax import nnx

    from jimm_tpu import VisionTransformer, ViTConfig, VisionConfig
    cfg = ViTConfig(vision=VisionConfig(**_TINY_VISION), num_classes=4)
    return VisionTransformer(cfg, rngs=nnx.Rngs(0))


def _tiny_siglip():
    from flax import nnx

    from jimm_tpu import SigLIP, SigLIPConfig, TextConfig, VisionConfig
    cfg = SigLIPConfig(
        vision=VisionConfig(**_TINY_VISION),
        text=TextConfig(vocab_size=64, context_length=8, width=32, depth=2,
                        num_heads=2, mlp_dim=64, causal=False,
                        pooling="last", proj_bias=True),
        projection_dim=32)
    return SigLIP(cfg, rngs=nnx.Rngs(0))


def _vit_batch(batch: int):
    import jax.numpy as jnp
    images = jnp.zeros((batch, 16, 16, 3), jnp.float32)
    labels = jnp.zeros((batch,), jnp.int32)
    return images, labels


def _siglip_batch(batch: int):
    import jax.numpy as jnp
    images = jnp.zeros((batch, 16, 16, 3), jnp.float32)
    text = jnp.zeros((batch, 8), jnp.int32)
    return images, text


def _vit_step_body(model, optimizer, images, labels):
    import optax
    from flax import nnx

    def loss_fn(model):
        logits = model(images)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    loss, grads = nnx.value_and_grad(loss_fn)(model)
    optimizer.update(model, grads)
    return loss


def _siglip_step_body(model, optimizer, images, text):
    from flax import nnx

    from jimm_tpu.train import contrastive_loss_fn

    def loss_fn(model):
        return contrastive_loss_fn(model, images, text, kind="siglip")

    loss, grads = nnx.value_and_grad(loss_fn)(model)
    optimizer.update(model, grads)
    return loss


#: registered entry points: name -> (model builder, batch builder, step body,
#: forward fn)
ENTRY_POINTS = {
    "vit_classifier": (_tiny_vit, _vit_batch, _vit_step_body,
                       lambda m, b: m(b[0])),
    "siglip_contrastive": (_tiny_siglip, _siglip_batch, _siglip_step_body,
                           lambda m, b: m.encode_image(b[0])),
}


def _trace_path(entry: str) -> str:
    return f"<trace:{entry}>"


# ---------------------------------------------------------------------------
# JLT101 — donation must materialize as input/output aliasing
# ---------------------------------------------------------------------------

def _check_donation(entry: str, build_model, build_batch,
                    step_body) -> list[Finding]:
    import jax
    from flax import nnx

    from jimm_tpu.train import OptimizerConfig, make_optimizer

    model = build_model()
    optimizer = make_optimizer(model, OptimizerConfig())
    graphdef, state = nnx.split((model, optimizer))
    batch = build_batch(BATCH_BUCKETS[0])

    def pure_step(state, *batch):
        model, optimizer = nnx.merge(graphdef, state)
        loss = step_body(model, optimizer, *batch)
        return nnx.state((model, optimizer)), loss

    lowered = jax.jit(pure_step, donate_argnums=(0,)).lower(state, *batch)
    text = lowered.as_text()
    if "tf.aliasing_output" not in text:
        return [Finding(
            "JLT101", ERROR, _trace_path(entry), 0,
            "donate_argnums on the train-step state produced no "
            "tf.aliasing_output attribute in the lowered StableHLO — "
            "donation is silently not materializing, params/m/v will "
            "double-buffer in HBM")]
    return []


# ---------------------------------------------------------------------------
# JLT102 — no full-parameter all-gather under FSDP
# ---------------------------------------------------------------------------

def _check_fsdp_allgather(entry: str, build_model, build_batch,
                          forward) -> list[Finding]:
    import jax
    import jax.numpy as jnp
    from flax import nnx

    from jimm_tpu.parallel import FSDP, create_sharded, make_mesh, \
        use_sharding

    ndev = len(jax.devices())
    if ndev < 2:
        return [Finding(
            "JLT102", WARNING, _trace_path(entry), 0,
            f"skipped: FSDP all-gather check needs >= 2 devices, "
            f"have {ndev}")]
    mesh = make_mesh({"data": ndev})
    with use_sharding(mesh, FSDP):
        model = create_sharded(build_model, mesh, FSDP)
        graphdef, state = nnx.split(model)
        batch = build_batch(BATCH_BUCKETS[0])

        def fwd(state, batch):
            model = nnx.merge(graphdef, state)
            return forward(model, batch)

        compiled = jax.jit(fwd).lower(state, batch).compile()
    text = compiled.as_text()

    # threshold: the largest single (stacked) parameter's full byte size —
    # per-layer FSDP gathers move 1/depth of it, a "full parameter" gather
    # moves at least all of it
    # shape/dtype arithmetic instead of .nbytes: abstract arrays (lazy
    # sharded init) raise NotImplementedError on the property
    def leaf_nbytes(leaf):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            return 0
        elems = 1
        for d in shape:
            elems *= int(d)
        try:
            itemsize = jnp.dtype(dtype).itemsize
        except TypeError:
            itemsize = 4
        return elems * itemsize

    largest = max(
        leaf_nbytes(leaf)
        for leaf in jax.tree_util.tree_leaves(state))
    findings = []
    for dtype, dims in _ALLGATHER_RE.findall(text):
        elems = 1
        for d in dims.split(","):
            if d:
                elems *= int(d)
        nbytes = elems * _DTYPE_BYTES.get(dtype, 4)
        if nbytes >= largest:
            findings.append(Finding(
                "JLT102", ERROR, _trace_path(entry), 0,
                f"compiled FSDP forward all-gathers {nbytes} bytes "
                f"({dtype}[{dims}]) >= largest stacked parameter "
                f"({largest} bytes) — a full-parameter gather defeats "
                f"FSDP's memory scaling"))
    return findings


# ---------------------------------------------------------------------------
# JLT103 — one program structure across batch buckets
# ---------------------------------------------------------------------------

_OP_RE = re.compile(r"\bstablehlo\.[a-z_]+")


def _op_histogram(text: str) -> dict[str, int]:
    hist: dict[str, int] = {}
    for op in _OP_RE.findall(text):
        hist[op] = hist.get(op, 0) + 1
    return hist


def _check_bucket_stability(entry: str, build_model, build_batch,
                            forward) -> list[Finding]:
    import jax
    from flax import nnx

    model = build_model()
    graphdef, state = nnx.split(model)

    def fwd(state, batch):
        model = nnx.merge(graphdef, state)
        return forward(model, batch)

    jitted = jax.jit(fwd)
    hists = {}
    for batch in BATCH_BUCKETS:
        text = jitted.lower(state, build_batch(batch)).as_text()
        hists[batch] = _op_histogram(text)
    base_batch = BATCH_BUCKETS[0]
    base = hists[base_batch]
    findings = []
    for batch, hist in hists.items():
        if hist != base:
            diff = {op for op in set(base) | set(hist)
                    if base.get(op, 0) != hist.get(op, 0)}
            findings.append(Finding(
                "JLT103", ERROR, _trace_path(entry), 0,
                f"lowered program structure differs between batch "
                f"{base_batch} and batch {batch} (ops: {sorted(diff)}) — "
                f"each bucket will compile a different program "
                f"(cache-key churn, runtime recompiles)"))
    return findings


# ---------------------------------------------------------------------------

def run_trace_checks() -> list[Finding]:
    """Run every trace check over every registered entry point. Exceptions
    inside a check become JLT000 error findings — a broken lowering path is
    itself a finding, not a linter crash."""
    import jax

    # must land before the XLA backend initializes; refused after
    try:
        jax.config.update("jax_num_cpu_devices", 8)
    except RuntimeError:
        pass

    findings: list[Finding] = []
    for entry, (build_model, build_batch, step_body,
                forward) in ENTRY_POINTS.items():
        for check in (
                lambda: _check_donation(entry, build_model, build_batch,
                                        step_body),
                lambda: _check_fsdp_allgather(entry, build_model,
                                              build_batch, forward),
                lambda: _check_bucket_stability(entry, build_model,
                                                build_batch, forward)):
            try:
                findings.extend(check())
            except Exception as e:  # noqa: BLE001 — surface, don't crash
                findings.append(Finding(
                    "JLT000", ERROR, _trace_path(entry), 0,
                    f"trace check raised {type(e).__name__}: {e}"))
    return findings
