"""Model configuration dataclasses and named presets.

The reference (`/root/reference`) derives hyperparameters ad-hoc from HF
`config.json` keys or shape inference scattered through each model's
`from_pretrained` (e.g. `src/jimm/models/vit.py:131-164`). Here every model is
driven by one frozen dataclass so presets, checkpoint inference, and CLI flags
all land in the same place.

Parity-critical defaults are documented per field with the reference citation.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Literal

Pooling = Literal["cls", "map", "last", "eot", "none"]
Activation = Literal["gelu", "gelu_tanh", "quick_gelu", "silu"]
AttnImpl = Literal["auto", "xla", "flash", "flash_masked", "flash_bias",
                   "flash_int8", "sigmoid", "ring", "ulysses", "saveable"]
#: Training precision policy (`jimm_tpu/quant/policy.py`): "bf16" trains
#: as built, "fp8_hybrid" swaps eligible Linears for e4m3-fwd/e5m2-grad
#: fp8 matmuls, "int8_qk" switches attention to the int8-QK flash kernel.
Precision = Literal["bf16", "fp8_hybrid", "int8_qk"]
#: "dots" + optional "+ln"/"+act"/"+attn" save-list extensions
RematPolicy = str


def remat_policy_parts(policy: str) -> list[str]:
    """Validate a remat policy string; return its ``+``-separated parts.
    Canonical validator shared by the CLI/bench parse layer and the
    execution point (`nn/transformer.py:_remat_policy`)."""
    parts = policy.split("+")
    if policy != "none" and (parts[0] != "dots"
                             or not set(parts[1:]) <= {"ln", "act", "attn"}):
        raise ValueError(f"unknown remat_policy {policy!r}; expected 'none' "
                         "or 'dots' with optional '+ln', '+act', '+attn' "
                         "suffixes (e.g. 'dots+ln+act')")
    return parts


def parse_remat(spec: str) -> dict[str, Any]:
    """CLI ``--remat`` spec -> `with_runtime` kwargs. ``none`` = remat off,
    ``full`` = remat with full recompute, ``dots[+ln][+act][+attn]`` = remat
    with that save-list. Raises ValueError on a malformed spec, so tools can
    fail at parse time instead of deep inside the first jit trace."""
    if spec in ("none", "full"):
        return {"remat": spec != "none", "remat_policy": "none"}
    remat_policy_parts(spec)
    return {"remat": True, "remat_policy": spec}


def check_pp_schedule(M: int, V: int, *, n_stages: int | None = None,
                      local_batch: int | None = None,
                      prefix: str = "") -> None:
    """Microbatch scheduling constraints — the ONE implementation behind
    both the parse-time validation (``validate_pipeline``) and the
    trace-time checks in `parallel/pipeline.py`, so semantics and messages
    cannot drift apart."""
    if M < 1:
        raise ValueError(prefix + f"n_microbatches must be >= 1, got {M}")
    if V < 1:
        raise ValueError(prefix + f"n_virtual must be >= 1, got {V}")
    if n_stages is not None and V > 1 and M % n_stages:
        raise ValueError(prefix + f"interleaved schedule needs microbatches "
                         f"{M} divisible by {n_stages} stages")
    if local_batch is not None and local_batch % M:
        raise ValueError(prefix + f"local batch {local_batch} not divisible "
                         f"by {M} microbatches")


def validate_pipeline(tower, *, n_stages: int, local_batch: int | None = None,
                      tower_name: str | None = None) -> None:
    """Surface the pipeline constraints at config/CLI parse time (a user
    used to reach them minutes into a compile). The same
    function runs inside `nn/transformer.py`'s pipeline dispatch, and the
    microbatch checks are shared with `parallel/pipeline.py` via
    ``check_pp_schedule`` — one implementation, both paths."""
    if not getattr(tower, "pipeline", False):
        return
    M, V = tower.pp_microbatches, tower.pp_virtual
    prefix = f"{tower_name} tower: " if tower_name else ""
    check_pp_schedule(M, V, prefix=prefix)
    if n_stages < 1:
        raise ValueError(prefix + "pipeline=True needs an ambient mesh with "
                         "a 'stage' axis (use use_sharding(mesh, PIPELINE))")
    if tower.depth % (n_stages * V):
        raise ValueError(prefix + f"depth {tower.depth} not divisible by "
                         f"{n_stages} stages x {V} virtual chunks")
    if V > 1 and tower.pp_stages and tower.pp_stages != n_stages:
        raise ValueError(prefix + f"model was built for "
                         f"pp_stages={tower.pp_stages} but the mesh has "
                         f"{n_stages} stages")
    check_pp_schedule(M, V, n_stages=n_stages, local_batch=local_batch,
                      prefix=prefix)


def normalize_act(name: str | None, default: str = "gelu") -> str:
    """HF ``hidden_act`` -> canonical Activation name."""
    if name is None:
        return default
    return {"gelu": "gelu", "gelu_new": "gelu_tanh",
            "gelu_pytorch_tanh": "gelu_tanh",
            "quick_gelu": "quick_gelu"}.get(name, name)


def act_to_hf(name: str) -> str:
    """Canonical Activation name -> HF ``hidden_act``."""
    return {"gelu": "gelu", "gelu_tanh": "gelu_pytorch_tanh",
            "quick_gelu": "quick_gelu"}.get(name, name)


#: Tower fields that select execution strategy, not architecture — safe to
#: override when loading a checkpoint (`from_pretrained(..., runtime=...)`)
RUNTIME_FIELDS = frozenset({
    "attn_impl", "ln_impl", "fused_qkv", "remat", "remat_policy", "scan_unroll",
    "dropout", "pipeline", "pp_microbatches", "pp_virtual", "pp_stages",
    "precision",
})


def with_runtime(cfg, **fields):
    """Return ``cfg`` with runtime (non-architecture) fields replaced in the
    vision — and, if present, text — tower. Rejects architecture fields so a
    checkpoint's shapes can never be silently contradicted.

    Flat fields apply to both towers; ``vision=dict(...)`` / ``text=dict(...)``
    target one tower (needed when the towers' depths admit different
    pipeline splits, e.g. CLIP-L's 24-deep vision vs 12-deep text)."""
    per_tower = {t: dict(fields.pop(t, None) or {})
                 for t in ("vision", "text")}
    bad = (set(fields) | set(per_tower["vision"]) | set(per_tower["text"])
           ) - RUNTIME_FIELDS
    if bad:
        raise ValueError(f"not runtime-overridable: {sorted(bad)} "
                         f"(allowed: {sorted(RUNTIME_FIELDS)})")
    cfg = dataclasses.replace(cfg, vision=dataclasses.replace(
        cfg.vision, **fields, **per_tower["vision"]))
    if hasattr(cfg, "text"):
        cfg = dataclasses.replace(cfg, text=dataclasses.replace(
            cfg.text, **fields, **per_tower["text"]))
    elif per_tower["text"]:
        raise ValueError("config has no text tower to override")
    return cfg


@dataclass(frozen=True)
class MLAConfig:
    """Latent attention: q heads of ``qk_nope_dim + qk_rope_dim``, keys and
    values rebuilt from a ``kv_lora_rank``-wide latent and ONE rotary key of
    ``qk_rope_dim`` shared by all heads, values ``v_head_dim`` wide."""

    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class MoEConfig:
    """A sparse expert layer as ONE chip of an expert-parallel group sees
    it: the router is whole (``num_experts`` wide, ``top_k`` chosen), the
    chip holds experts ``first_expert .. first_expert + held_experts`` and
    computes their part of the result."""

    num_experts: int = 128
    top_k: int = 6
    expert_dim: int = 768
    shared_experts: int = 2
    routed_scale: float = 2.448
    held_experts: int = 128
    first_expert: int = 0


@dataclass(frozen=True)
class GQAConfig:
    """Grouped-query attention with heads of a width of their own:
    ``num_heads`` query heads of ``head_dim`` read ``kv_heads`` key/value
    heads; ``qk_norm``: an RMSNorm over each head of q and of k (one learned
    ``head_dim``-vector each, shared by the heads); ``gate``: the output is
    multiplied by ``sigmoid(x W_gate)`` before the output projection.
    ``window``: a layer sees the newest ``window`` keys of each query (its own
    counted) and takes the stack's rotary; every ``full_every``-th layer of
    the model, counted from ``first_layer`` (this stack's first layer's index
    in the whole model), sees everything to its left and takes NO position
    signal. ``full_every`` 0: every layer is windowed."""

    head_dim: int = 128
    kv_heads: int = 8
    qk_norm: bool = True
    gate: bool = True
    window: int | None = 4096
    full_every: int = 4
    first_layer: int = 0

    def full_layers(self, depth: int) -> tuple[bool, ...]:
        """Which of a stack's ``depth`` layers are full-attention layers."""
        return tuple(bool(self.full_every)
                     and (self.first_layer + i + 1) % self.full_every == 0
                     for i in range(depth))


@dataclass(frozen=True)
class KDAConfig:
    """Kimi Delta Attention (`nn/kda.py`): ``num_heads`` heads of ``head_dim``
    (keys and values alike), a causal depthwise convolution of ``conv_taps``
    taps on q, k and v, the forget gate and the output gate each through a
    ``gate_rank``-wide bottleneck, the recurrence in chunks of ``chunk``
    tokens (`ops/delta_rule.py`)."""

    num_heads: int = 32
    head_dim: int = 128
    conv_taps: int = 4
    gate_rank: int = 128
    chunk: int = 64


@dataclass(frozen=True)
class Mamba2Config:
    """A Mamba-2 state-space mixer (`nn/mamba2.py`): ``num_heads`` heads of
    ``head_dim`` channels, a state of ``head_dim x state`` a head, ``B`` and
    ``C`` ``state`` wide in each of ``groups`` groups of heads, a causal
    depthwise convolution of ``conv_taps`` taps with a bias, the scan in
    chunks of ``chunk`` tokens (`ops/ssd.py`)."""

    num_heads: int = 64
    head_dim: int = 64
    state: int = 128
    groups: int = 1
    conv_taps: int = 4
    chunk: int = 256


@dataclass(frozen=True)
class TransformerConfig:
    """Shared encoder-stack hyperparameters (vision or text tower)."""

    width: int = 768
    depth: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072  # read from config, NOT hardcoded 4x (ref limitation, SURVEY §2.4)
    act: Activation = "gelu"
    ln_eps: float = 1e-6
    dropout: float = 0.0
    causal: bool = False
    attn_impl: AttnImpl = "auto"
    #: Pipeline-parallel forward: shard the stacked ``layers`` axis over a
    #: ``stage`` mesh axis and circulate microbatches via ppermute
    #: (`jimm_tpu/parallel/pipeline.py`). Requires depth % n_stages == 0 and
    #: (local) batch % pp_microbatches == 0.
    pipeline: bool = False
    pp_microbatches: int = 4
    #: Interleaved pipeline schedule: each stage holds this many
    #: NON-contiguous layer chunks (circular placement) and microbatches lap
    #: the ring pp_virtual times — bubble shrinks ~pp_virtual-fold
    #: (`jimm_tpu/parallel/pipeline.py`). Needs depth % (stages*virtual) == 0
    #: and (for >1) pp_microbatches % stages == 0.
    pp_virtual: int = 1
    #: Known pipeline-stage count. With ``pp_virtual > 1`` and this set, the
    #: stacked blocks are STORED in circular schedule order from
    #: construction (loaders/exporters reorder at the stacking edge), so the
    #: forward avoids re-permuting — a cross-stage all-to-all — every step.
    #: 0 = unknown: the forward permutes per call (correct, slower).
    pp_stages: int = 0
    remat: bool = False
    #: What the backward pass may keep from the forward when ``remat`` is on:
    #: "none" recomputes everything (min memory, ~1/3 extra FLOPs); "dots"
    #: saves matmul outputs and recomputes only cheap elementwise ops
    #: (ln/act/softmax) — the usual best MFU/memory trade on TPU.
    remat_policy: RematPolicy = "none"
    #: LayerNorm kernel: "xla" (nnx.LayerNorm) or "fused" (one-pass Pallas
    #: fwd/bwd, `jimm_tpu/ops/layer_norm.py`).
    ln_impl: Literal["xla", "fused"] = "xla"
    #: Compute q/k/v as one (H, 3H) matmul (call-time kernel concat;
    #: checkpoints unchanged).
    fused_qkv: bool = False
    #: `lax.scan` unroll factor for the layer loop. >1 trades compile time
    #: for schedule freedom: XLA turns the per-layer stacked-gradient
    #: dynamic-update-slices into statically-indexed updates it can fuse.
    scan_unroll: int = 1
    #: Training precision policy, applied to the built model by
    #: `quant.policy.apply_precision_policy` (trainer/CLI plumbing) — the
    #: config field records intent, so that a run's logs and reports carry
    #: it; construction itself never reads it.
    precision: Precision = "bf16"
    # -- the decoder family's block (`DecoderConfig.encoder`); every default
    # below is the ViT / CLIP / SigLIP block, whose programs do not change
    #: "rms": ``x / sqrt(mean(x^2) + ln_eps) * w``, no bias, no mean
    norm: Literal["layer", "rms"] = "layer"
    #: a second norm on each sub-layer's OUTPUT, before the residual add
    #: ("sandwich"): ``x + norm(attn(norm(x)))``
    post_norm: bool = False
    #: what the scales of those second norms start at (the first norms' start
    #: at 1): a depth-scaled sandwich gives 1 / sqrt(layers of the model)
    post_norm_gain: float = 1.0
    #: rotary positions on q and k with this base, the rotate-half pairing
    #: (i, i + head_dim/2) over the whole head; None = positions come from
    #: the embedding
    rope_theta: float | None = None
    #: ``fc2(act(gate(x)) * fc1(x))`` in place of ``fc2(act(fc1(x)))``
    gated_mlp: bool = False
    #: biases on the q/k/v/out and MLP projections
    use_bias: bool = True
    #: Looped stack: the same ``depth`` blocks run ``loops`` times (weights
    #: shared between passes), a final norm closes every pass and its output
    #: feeds the next, and the call returns the ``loops`` pass outputs
    #: stacked on a leading axis. 0 = a plain stack: one pass, no norm, the
    #: carry returned.
    loops: int = 0
    #: latent attention (`nn/mla.py`) in place of `Attention`; None = the
    #: heads above
    mla: MLAConfig | None = None
    #: a sparse expert layer (`nn/moe.py`) in place of `Mlp`; the block then
    #: returns its routing choices beside the carry
    moe: MoEConfig | None = None
    #: grouped-query attention in `Attention` (heads of their own width,
    #: fewer key/value heads, qk-norm, an output gate, windowed layers beside
    #: full ones); None = ``num_heads`` heads of ``width / num_heads`` each way
    gqa: GQAConfig | None = None
    #: Kimi Delta Attention (`nn/kda.py`) in place of `Attention`: a
    #: linear-attention mixer with a recurrent state, no position signal
    kda: KDAConfig | None = None
    #: a Mamba-2 state-space mixer (`nn/mamba2.py`) in place of `Attention`
    mamba: Mamba2Config | None = None
    #: what each sub-layer's output is multiplied by before the residual add
    residual_scale: float = 1.0
    #: the grouped-query attention's softmax scale; None = ``1/sqrt(head_dim)``
    attn_scale: float | None = None

    @property
    def head_dim(self) -> int:
        if self.gqa is not None:
            return self.gqa.head_dim
        return self.width // self.num_heads

    @property
    def rope_dim(self) -> int:
        """How many dims of a head the rotary tables turn."""
        return self.mla.qk_rope_dim if self.mla is not None else self.head_dim


@dataclass(frozen=True)
class VisionConfig:
    """Vision tower. Mirrors `src/jimm/common/vit.py:104-248` behavior.

    - ``pre_norm``: CLIP applies an extra LayerNorm after embeddings and skips
      embedding dropout (ref `common/vit.py:181-190,238-241`).
    - ``patch_bias``: CLIP's patch conv has no bias (ref `models/clip.py:66`).
    - ``pooling``: "cls" (ViT/CLIP) or "map" (SigLIP MAP head,
      ref `common/vit.py:12-101`) or "none" (return full sequence).
    """

    image_size: int = 224
    patch_size: int = 16
    channels: int = 3
    #: frames per clip for temporal (video) towers: each frame patchifies
    #: independently and the T * grid^2 tokens flatten into ONE sequence
    #: (pos table covers the full flattened length) — long-sequence work
    #: that the seq-parallel mesh axis shards across chips. 1 = image.
    num_frames: int = 1
    width: int = 768
    depth: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    act: Activation = "gelu"
    ln_eps: float = 1e-6
    dropout: float = 0.0
    pooling: Pooling = "cls"
    pre_norm: bool = False
    patch_bias: bool = True
    attn_impl: AttnImpl = "auto"
    pipeline: bool = False
    pp_microbatches: int = 4
    pp_virtual: int = 1
    pp_stages: int = 0
    remat: bool = False
    remat_policy: RematPolicy = "none"
    ln_impl: Literal["xla", "fused"] = "xla"
    fused_qkv: bool = False
    scan_unroll: int = 1
    precision: Precision = "bf16"

    @property
    def grid(self) -> int:
        return self.image_size // self.patch_size

    @property
    def num_patches(self) -> int:
        return self.grid * self.grid * self.num_frames

    @property
    def seq_len(self) -> int:
        return self.num_patches + (1 if self.pooling == "cls" else 0)

    def encoder(self) -> TransformerConfig:
        return TransformerConfig(
            width=self.width, depth=self.depth, num_heads=self.num_heads,
            mlp_dim=self.mlp_dim, act=self.act, ln_eps=self.ln_eps,
            dropout=self.dropout, causal=False, attn_impl=self.attn_impl,
            pipeline=self.pipeline, pp_microbatches=self.pp_microbatches,
            pp_virtual=self.pp_virtual, pp_stages=self.pp_stages,
            remat=self.remat, remat_policy=self.remat_policy,
            ln_impl=self.ln_impl, fused_qkv=self.fused_qkv,
            scan_unroll=self.scan_unroll, precision=self.precision,
        )


@dataclass(frozen=True)
class TextConfig:
    """Text tower. CLIP: causal + EOT-argmax pooling (ref `models/clip.py:92-104,
    164-166`). SigLIP: bidirectional + last-token pooling (ref
    `models/siglip.py:79-91,151-152`)."""

    vocab_size: int = 49408
    context_length: int = 77
    width: int = 512
    depth: int = 12
    num_heads: int = 8
    mlp_dim: int = 2048
    act: Activation = "quick_gelu"
    ln_eps: float = 1e-5
    dropout: float = 0.0
    causal: bool = True
    pooling: Pooling = "eot"
    proj_bias: bool = False  # CLIP text_projection is bias-free; SigLIP head has bias
    # recorded at load, re-emitted at export; HF CLIP pools at this token's
    # first occurrence (argmax-equivalent when EOT is the max id)
    eos_token_id: int | None = None
    attn_impl: AttnImpl = "auto"
    pipeline: bool = False
    pp_microbatches: int = 4
    pp_virtual: int = 1
    pp_stages: int = 0
    remat: bool = False
    remat_policy: RematPolicy = "none"
    ln_impl: Literal["xla", "fused"] = "xla"
    fused_qkv: bool = False
    scan_unroll: int = 1
    precision: Precision = "bf16"

    def encoder(self) -> TransformerConfig:
        return TransformerConfig(
            width=self.width, depth=self.depth, num_heads=self.num_heads,
            mlp_dim=self.mlp_dim, act=self.act, ln_eps=self.ln_eps,
            dropout=self.dropout, causal=self.causal, attn_impl=self.attn_impl,
            pipeline=self.pipeline, pp_microbatches=self.pp_microbatches,
            pp_virtual=self.pp_virtual, pp_stages=self.pp_stages,
            remat=self.remat, remat_policy=self.remat_policy,
            ln_impl=self.ln_impl, fused_qkv=self.fused_qkv,
            scan_unroll=self.scan_unroll, precision=self.precision,
        )


@dataclass(frozen=True)
class DecoderConfig:
    """Causal decoder stack of a language model: RMSNorm around every
    sub-layer, rotary positions, SwiGLU, no biases, and the whole stack run
    ``loops`` times with shared weights (1 = a plain decoder)."""

    vocab_size: int = 49152
    #: tokens of a training sequence (the data path draws ``seq_len + 1``
    #: ids: inputs and the targets shifted by one)
    seq_len: int = 4096
    width: int = 2048
    depth: int = 48
    num_heads: int = 16
    mlp_dim: int = 5632
    act: Activation = "silu"
    ln_eps: float = 1e-6
    rope_theta: float = 1e6
    loops: int = 4
    # runtime fields, as the towers have them; the pipelined path and the
    # fused LayerNorm kernel are not built for this block
    dropout: float = 0.0
    attn_impl: AttnImpl = "auto"
    remat: bool = False
    remat_policy: RematPolicy = "none"
    fused_qkv: bool = False
    scan_unroll: int = 1
    precision: Precision = "bf16"

    def encoder(self) -> TransformerConfig:
        return TransformerConfig(
            width=self.width, depth=self.depth, num_heads=self.num_heads,
            mlp_dim=self.mlp_dim, act=self.act, ln_eps=self.ln_eps,
            dropout=self.dropout, causal=True, attn_impl=self.attn_impl,
            remat=self.remat, remat_policy=self.remat_policy,
            fused_qkv=self.fused_qkv,
            scan_unroll=self.scan_unroll, precision=self.precision,
            norm="rms", post_norm=True, rope_theta=self.rope_theta,
            gated_mlp=True, use_bias=False, loops=self.loops,
        )


@dataclass(frozen=True)
class MoEDecoderConfig:
    """Causal decoder stack with latent attention (``mla``) or grouped-query
    attention (``gqa``) in every layer, a dense SwiGLU in the first
    ``dense_layers`` and a sparse expert layer (`MoEConfig`) in the rest
    (``moe`` None: a dense SwiGLU in every layer): pre-norm RMS blocks
    (``post_norm``: a second norm on each sub-layer's output, its scale
    starting at ``post_norm_gain``; ``residual_scale``: each sub-layer's output
    multiplied before the add), rotary on the rotary dims alone, no biases.
    ``mixers`` names each published layer's token mixer where they differ
    (``kda``, ``mla``, ``mamba``; any other name is the attention above).
    ``depth`` counts every held layer; ``first_layer`` is the index, in the
    whole published model, of the first layer held here
    (`GQAConfig.full_layers` counts from it)."""

    vocab_size: int = 16032
    seq_len: int = 8192
    width: int = 2048
    depth: int = 48
    dense_layers: int = 1
    num_heads: int = 32
    mlp_dim: int = 6144
    act: Activation = "silu"
    ln_eps: float = 1e-6
    rope_theta: float | None = 1e6
    mla: MLAConfig | None = field(default_factory=MLAConfig)
    moe: MoEConfig | None = field(
        default_factory=lambda: MoEConfig(held_experts=16))
    gqa: GQAConfig | None = None
    kda: KDAConfig | None = None
    mamba: Mamba2Config | None = None
    mixers: tuple[str, ...] = ()
    post_norm: bool = False
    post_norm_gain: float = 1.0
    residual_scale: float = 1.0
    attn_scale: float | None = None
    first_layer: int = 0
    # runtime fields, as `DecoderConfig` has them
    dropout: float = 0.0
    attn_impl: AttnImpl = "auto"
    remat: bool = False
    remat_policy: RematPolicy = "none"
    scan_unroll: int = 1
    precision: Precision = "bf16"

    @property
    def full_layers(self) -> tuple[bool, ...]:
        """Which of the ``depth`` held layers are full-attention layers
        (none without ``gqa``)."""
        if self.gqa is None:
            return (False,) * self.depth
        return dataclasses.replace(
            self.gqa, first_layer=self.first_layer).full_layers(self.depth)

    @property
    def held_mixers(self) -> tuple[str | None, ...]:
        """The token mixer of each of the ``depth`` held layers (None: the
        one kind the config has)."""
        if not self.mixers:
            return (None,) * self.depth
        held = self.mixers[self.first_layer:self.first_layer + self.depth]
        if len(held) < self.depth:
            raise ValueError(f"layers {self.first_layer}.."
                             f"{self.first_layer + self.depth} are not among "
                             f"the {len(self.mixers)} of `mixers`")
        return held

    def runs(self) -> tuple[tuple[str, TransformerConfig], ...]:
        """The held layers as runs of like layers (same token mixer, same
        kind of FFN), in layer order: ``(name, the run's block)`` each. A
        stack of one mixer is the two runs ``dense`` and ``sparse``; a mixed
        one names its runs by their first layer (``run0``, ``run1``, ...)."""
        kinds = [(mixer, self.moe is not None and i >= self.dense_layers)
                 for i, mixer in enumerate(self.held_mixers)]
        starts = [i for i, kind in enumerate(kinds)
                  if i == 0 or kind != kinds[i - 1]]
        out = []
        for lo, hi in zip(starts, [*starts[1:], self.depth]):
            mixer, sparse = kinds[lo]
            name = f"run{lo}" if self.mixers else (
                "sparse" if sparse else "dense")
            out.append((name, self.encoder(sparse=sparse, first=lo,
                                           depth=hi - lo, mixer=mixer)))
        return tuple(out)

    def encoder(self, *, sparse: bool, first: int | None = None,
                depth: int | None = None, mixer: str | None = None
                ) -> TransformerConfig:
        """The block of a run of ``depth`` like layers from held layer
        ``first`` on; by default the dense stack's, or the sparse stack's."""
        if first is None:
            first = self.dense_layers if sparse else 0
            depth = (self.depth - self.dense_layers if sparse
                     else self.dense_layers)
        gqa = self.gqa and dataclasses.replace(
            self.gqa, first_layer=self.first_layer + first)
        return TransformerConfig(
            width=self.width, depth=depth,
            num_heads=self.num_heads, mlp_dim=self.mlp_dim, act=self.act,
            ln_eps=self.ln_eps, dropout=self.dropout, causal=True,
            attn_impl=self.attn_impl, remat=self.remat,
            remat_policy=self.remat_policy, scan_unroll=self.scan_unroll,
            precision=self.precision, norm="rms", rope_theta=self.rope_theta,
            gated_mlp=True, use_bias=False,
            mla=self.mla if mixer in (None, "mla") else None,
            kda=self.kda if mixer == "kda" else None,
            mamba=self.mamba if mixer == "mamba" else None,
            moe=self.moe if sparse else None,
            gqa=None if mixer == "mamba" else gqa,
            post_norm=self.post_norm, post_norm_gain=self.post_norm_gain,
            residual_scale=self.residual_scale, attn_scale=self.attn_scale,
        )


@dataclass(frozen=True)
class ViTConfig:
    """ViT image classifier (ref `models/vit.py:16-103`): post-norm backbone,
    CLS pooling, LN eps 1e-12 (ref `models/vit.py:73`), optional linear head."""

    vision: VisionConfig = field(default_factory=lambda: VisionConfig(ln_eps=1e-12))
    num_classes: int = 1000
    do_classification: bool = True


@dataclass(frozen=True)
class CLIPConfig:
    """CLIP dual tower (ref `models/clip.py:15-188`): pre-norm QuickGELU vision
    tower without patch bias, causal text tower, bias-free projections,
    learned ``logit_scale``."""

    vision: VisionConfig = field(default_factory=lambda: VisionConfig(
        width=768, depth=12, num_heads=12, mlp_dim=3072, act="quick_gelu",
        ln_eps=1e-5, pooling="cls", pre_norm=True, patch_bias=False,
        patch_size=32))
    text: TextConfig = field(default_factory=TextConfig)
    projection_dim: int = 512
    logit_scale_init: float = 2.6592  # ln(1/0.07), OpenAI CLIP init


@dataclass(frozen=True)
class SigLIPConfig:
    """SigLIP dual tower (ref `models/siglip.py:15-174`): MAP-pooled vision
    tower (gelu_tanh, eps 1e-6), bidirectional text tower with last-token
    pooling and biased projection, ``logit_scale`` AND ``logit_bias``."""

    vision: VisionConfig = field(default_factory=lambda: VisionConfig(
        image_size=256, patch_size=16, width=768, depth=12, num_heads=12,
        mlp_dim=3072, act="gelu_tanh", ln_eps=1e-6, pooling="map",
        pre_norm=False, patch_bias=True))
    text: TextConfig = field(default_factory=lambda: TextConfig(
        vocab_size=32000, context_length=64, width=768, depth=12, num_heads=12,
        mlp_dim=3072, act="gelu_tanh", ln_eps=1e-6, causal=False,
        pooling="last", proj_bias=True))
    # SigLIP projects both towers to the (shared) text width, not a separate dim
    projection_dim: int = 768
    logit_scale_init: float = 2.3026  # ln(10), SigLIP paper init
    logit_bias_init: float = -10.0


@dataclass(frozen=True)
class OuroConfig:
    """Ouro looped language model (ByteDance, 2025): token embedding, the
    looped `DecoderConfig` stack, and after each pass an exit gate
    (``Linear(width -> 1)``) and the untied head. Trained on the expected
    loss over the exit distribution less ``exit_beta`` times its entropy
    (`train/losses.py::expected_exit_loss`)."""

    decoder: DecoderConfig = field(default_factory=DecoderConfig)
    exit_beta: float = 0.1


@dataclass(frozen=True)
class KananaConfig:
    """kanana-2-30b-a3b (kakaocorp, ``model_type`` deepseek_v3) as one chip of
    an eight-way expert-parallel group holds it: token embedding, the
    `MoEDecoderConfig` stack, a final RMSNorm and the untied head, trained on
    the mean next-token cross-entropy. After each step the routers'
    selection biases move by ``bias_update_rate`` toward balance (the
    auxiliary-loss-free balancing of the DeepSeek-V3 paper)."""

    decoder: MoEDecoderConfig = field(default_factory=MoEDecoderConfig)
    bias_update_rate: float = 1e-3


def _trinity_decoder() -> MoEDecoderConfig:
    return MoEDecoderConfig(
        vocab_size=25024, seq_len=8192, width=3072, depth=55, dense_layers=1,
        first_layer=5, num_heads=48, mlp_dim=12288, ln_eps=1e-5,
        rope_theta=1e4, mla=None, gqa=GQAConfig(), post_norm=True,
        post_norm_gain=60 ** -0.5,  # depth-scaled: 60 published layers
        moe=MoEConfig(num_experts=256, top_k=4, expert_dim=3072,
                      shared_experts=1, routed_scale=2.448, held_experts=8))


@dataclass(frozen=True)
class TrinityConfig:
    """Trinity-Large-Preview (arcee-ai, ``model_type`` afmoe) from its last
    dense layer on (published layers 5-59: one dense layer, then the sparse
    ones), as one chip of a 32-way expert-parallel group holds it (8 of 256
    routed experts a layer, an eighth of the vocabulary): grouped-query
    attention with qk-norm and an output gate, a 4096-token window with rotary
    on three layers of four and position-free full attention on the fourth,
    sandwich RMSNorms, the embedding scaled by ``sqrt(width)``. Trained like
    `KananaConfig`'s model; ``bias_update_rate`` as there."""

    decoder: MoEDecoderConfig = field(default_factory=_trinity_decoder)
    bias_update_rate: float = 1e-3


#: Kimi-Linear-48B-A3B's 27 published layers (``linear_attn_config``:
#: ``full_attn_layers`` 4, 8, 12, 16, 20, 24, 27 counted from 1)
_KIMI_LINEAR_MIXERS = tuple(
    "mla" if i + 1 in (4, 8, 12, 16, 20, 24, 27) else "kda" for i in range(27))


def _kimi_linear_decoder() -> MoEDecoderConfig:
    return MoEDecoderConfig(
        vocab_size=20480, seq_len=16384, width=2304, depth=5, dense_layers=1,
        num_heads=32, mlp_dim=9216, ln_eps=1e-5, rope_theta=None,
        mla=MLAConfig(), kda=KDAConfig(), mixers=_KIMI_LINEAR_MIXERS,
        moe=MoEConfig(num_experts=256, top_k=8, expert_dim=1024,
                      shared_experts=1, routed_scale=2.446, held_experts=16))


@dataclass(frozen=True)
class KimiLinearConfig:
    """Kimi-Linear-48B-A3B-Instruct (moonshotai, ``model_type`` kimi_linear)
    as one chip of a 16-way expert-parallel group holds its first five
    layers: Kimi Delta Attention (`KDAConfig`) on three layers of four and
    latent attention with NO position signal on the fourth, a dense SwiGLU in
    the first layer and a 256-expert top-8 mixture with one shared expert in
    the rest (16 of 256 routed experts a layer, an eighth of the vocabulary).
    Trained like `KananaConfig`'s model; ``bias_update_rate`` as there."""

    decoder: MoEDecoderConfig = field(default_factory=_kimi_linear_decoder)
    bias_update_rate: float = 1e-3


#: granite-4.0-h-micro's 40 published layers (``layer_types``: attention on
#: 5, 15, 25, 35 counted from 0, Mamba-2 on the rest)
_GRANITE_MIXERS = tuple("attention" if i % 10 == 5 else "mamba"
                        for i in range(40))


def _granite_decoder() -> MoEDecoderConfig:
    return MoEDecoderConfig(
        vocab_size=100352, seq_len=16384, width=2048, depth=10, num_heads=32,
        mlp_dim=8192, ln_eps=1e-5, rope_theta=None, mla=None, moe=None,
        gqa=GQAConfig(head_dim=64, kv_heads=8, qk_norm=False, gate=False,
                      window=None, full_every=1),
        mamba=Mamba2Config(), mixers=_GRANITE_MIXERS, residual_scale=0.22,
        attn_scale=0.015625)


@dataclass(frozen=True)
class GraniteConfig:
    """granite-4.0-h-micro (ibm-granite, ``model_type`` granitemoehybrid)
    as one stage of a four-stage pipeline holds its first ten layers: Mamba-2
    (`Mamba2Config`) on nine layers of ten and position-free grouped-query
    attention (32 heads over 8, softmax scale 1/64) on the tenth, a dense
    SwiGLU in every layer, each sub-layer's output times 0.22 before the add.
    The embedding is multiplied by ``embedding_multiplier`` and is also the
    head: the logits are ``RMS_f(h) E^T / logits_scaling``. Trained on the
    mean next-token cross-entropy."""

    decoder: MoEDecoderConfig = field(default_factory=_granite_decoder)
    embedding_multiplier: float = 12.0
    logits_scaling: float = 8.0


def _vit(size: str, patch: int, image: int, classes: int = 1000) -> ViTConfig:
    w, d, h, m = {
        "T": (192, 12, 3, 768),
        "S": (384, 12, 6, 1536),
        "B": (768, 12, 12, 3072),
        "L": (1024, 24, 16, 4096),
        "H": (1280, 32, 16, 5120),
        "g": (1408, 40, 16, 6144),
        "G": (1664, 48, 16, 8192),
    }[size]
    return ViTConfig(
        vision=VisionConfig(image_size=image, patch_size=patch, width=w,
                            depth=d, num_heads=h, mlp_dim=m, ln_eps=1e-12),
        num_classes=classes)


def _vit_temporal(size: str, patch: int, image: int, frames: int,
                  classes: int = 1000) -> ViTConfig:
    """Temporal ViT: frames flattened into one sequence (T * grid^2
    tokens) — the video workload the sequence-parallel mesh axis exists
    for. No architectural surgery beyond the longer pos table; attention
    is full spatio-temporal. MAP pooling on purpose: a CLS token would
    make the sequence odd and lock out every even ring size, while
    T * grid^2 divides cleanly across the ``seq`` axis."""
    base = _vit(size, patch, image, classes)
    return dataclasses.replace(
        base, vision=dataclasses.replace(base.vision, num_frames=frames,
                                         pooling="map"))


def _siglip(size: str, patch: int, image: int, vocab: int = 32000,
            ctx: int = 64) -> SigLIPConfig:
    w, d, h, m = {
        "B": (768, 12, 12, 3072),
        "L": (1024, 24, 16, 4096),
        "So400m": (1152, 27, 16, 4304),  # non-4x MLP: loadable here, not in ref
    }[size]
    return SigLIPConfig(
        vision=VisionConfig(image_size=image, patch_size=patch, width=w, depth=d,
                            num_heads=h, mlp_dim=m, act="gelu_tanh", ln_eps=1e-6,
                            pooling="map"),
        text=TextConfig(vocab_size=vocab, context_length=ctx, width=w, depth=d,
                        num_heads=h, mlp_dim=m, act="gelu_tanh", ln_eps=1e-6,
                        causal=False, pooling="last", proj_bias=True),
        projection_dim=w)


def _clip(vision_size: str, patch: int, image: int = 224) -> CLIPConfig:
    vw, vd, vh, vm, proj = {
        "B": (768, 12, 12, 3072, 512),
        "L": (1024, 24, 16, 4096, 768),
    }[vision_size]
    tw, td, th, tm = {"B": (512, 12, 8, 2048), "L": (768, 12, 12, 3072)}[vision_size]
    return CLIPConfig(
        vision=VisionConfig(image_size=image, patch_size=patch, width=vw,
                            depth=vd, num_heads=vh, mlp_dim=vm, act="quick_gelu",
                            ln_eps=1e-5, pooling="cls", pre_norm=True,
                            patch_bias=False),
        text=TextConfig(vocab_size=49408, context_length=77, width=tw, depth=td,
                        num_heads=th, mlp_dim=tm, act="quick_gelu", ln_eps=1e-5,
                        causal=True, pooling="eot", proj_bias=False),
        projection_dim=proj)


#: Named presets: the published shapes of each family.
PRESETS: dict[str, Any] = {
    # ViT
    "vit-tiny-patch16-224": _vit("T", 16, 224),
    "vit-small-patch16-224": _vit("S", 16, 224),
    "vit-base-patch16-224": _vit("B", 16, 224),
    "vit-base-patch32-384": _vit("B", 32, 384),
    "vit-large-patch16-384": _vit("L", 16, 384),
    "vit-huge-patch14-224": _vit("H", 14, 224),
    # Temporal ViT (video: frames flattened into sequence — 8 * 196 + 1 =
    # 1569 tokens; train/serve these across a seq-parallel mesh axis)
    "vit-temporal-small-patch16-224-f8": _vit_temporal("S", 16, 224, 8),
    "vit-temporal-base-patch16-224-f8": _vit_temporal("B", 16, 224, 8),
    # CLIP
    "clip-vit-base-patch32": _clip("B", 32),
    "clip-vit-base-patch16": _clip("B", 16),
    "clip-vit-large-patch14": _clip("L", 14),
    "clip-vit-large-patch14-336": _clip("L", 14, 336),
    # SigLIP
    "siglip-base-patch16-224": _siglip("B", 16, 224),
    "siglip-base-patch16-256": _siglip("B", 16, 256),
    "siglip-base-patch16-384": _siglip("B", 16, 384),
    "siglip-large-patch16-256": _siglip("L", 16, 256),
    "siglip-large-patch16-384": _siglip("L", 16, 384),
    "siglip-so400m-patch14-384": _siglip("So400m", 14, 384),
    "siglip2-base-patch16-256": _siglip("B", 16, 256, vocab=256000),
    "siglip2-large-patch16-512": _siglip("L", 16, 512, vocab=256000),
    # So400m towers are dimensionally identical to the v1 So400m release
    # (verified against google/siglip-so400m-patch14-384); v2 swaps the
    # tokenizer/vocab (Gemma 256k) and training recipe, not the shapes.
    # (giant-opt is deliberately absent: its asymmetric text tower can't be
    # verified offline — from_pretrained still loads it from the HF config.)
    "siglip2-so400m-patch14-384": _siglip("So400m", 14, 384, vocab=256000),
    "siglip2-so400m-patch16-256": _siglip("So400m", 16, 256, vocab=256000),
    # Ouro looped LM: the published 48 layers run 4 times (ByteDance/Ouro-2.6B)
    "ouro-2.6b": OuroConfig(),
    # kanana-2-30b-a3b-instruct-2601: latent attention, 128 experts top-6 and
    # two shared; the preset is ONE chip's share of an eight-way
    # expert-parallel layer (experts 0-15, an eighth of the vocabulary)
    "kanana-2-30b-a3b": KananaConfig(),
    # Trinity-Large-Preview: grouped-query attention (48 heads over 8), a
    # window on three layers of four, 256 experts top-4 and one shared; the
    # preset is ONE chip's share of 32-way expert parallelism from the last
    # dense layer on (experts 0-7, an eighth of the vocabulary)
    "trinity-large": TrinityConfig(),
    # Kimi-Linear-48B-A3B-Instruct: Kimi Delta Attention on three layers of
    # four, position-free latent attention on the fourth, 256 experts top-8
    # and one shared; the preset is published layers 1-5 as ONE chip of
    # 16-way expert parallelism holds them (experts 0-15, an eighth of the
    # vocabulary)
    "kimi-linear-48b-a3b": KimiLinearConfig(),
    # granite-4.0-h-micro: Mamba-2 on nine layers of ten, position-free
    # grouped-query attention on the tenth, dense SwiGLU, tied head; the
    # preset is published layers 0-9, one stage of a four-stage pipeline
    "granite-4.0-h-micro": GraniteConfig(),
}


def preset(name: str, **overrides: Any):
    """Fetch a named preset, optionally overriding top-level fields."""
    cfg = PRESETS[name]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg
