"""Profiling & observability: MFU, throughput, structured metric logging.

The reference's only observability is ``print`` per step
(ref `examples/vit_training.py:226`). The north star requires MFU as the
metric of record (`BASELINE.json`), so we compute achieved FLOP/s from XLA's
own cost analysis of the compiled step and divide by the chip's peak.
"""

from __future__ import annotations

import json
import math
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, IO

import jax

from jimm_tpu.obs.registry import MetricRegistry, get_registry

#: Peak dense bf16 TFLOP/s of one chip, keyed by ``device_kind`` exactly as
#: jax reports it, each with its source. A device that is not here has no
#: MFU: :func:`device_peak_tflops` raises rather than guess.
PEAK_TFLOPS: dict[str, tuple[float, str]] = {
    "TPU v5 lite": (197.0, 'Google Cloud documentation, "TPU v5e"'),
}


def device_peak_tflops(device: jax.Device | None = None) -> float:
    device = device or jax.devices()[0]
    try:
        return PEAK_TFLOPS[device.device_kind][0]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s recorded for device_kind "
            f"{device.device_kind!r} (platform {device.platform!r}); add it "
            f"to jimm_tpu.train.metrics.PEAK_TFLOPS with its source") from None


def compiled_flops(compiled) -> float | None:
    """Total FLOPs of one execution from XLA cost analysis (per-process);
    None when the analysis reports no flops."""
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return float(cost.get("flops", 0.0)) or None


def mfu(flops_per_step: float | None, step_time_s: float,
        n_devices: int | None = None,
        device: jax.Device | None = None) -> float:
    """Model FLOPs utilization in [0, 1]. ``flops_per_step`` is the global
    FLOP count of one step; peak scales with device count.

    Degenerate inputs — ``flops_per_step`` of ``None`` (cost analysis
    reported no flops, see :func:`compiled_flops`), a zero/negative/NaN
    step time, or a NaN FLOP count — return 0.0 instead of raising, and
    bump the ``jimm_train`` registry's ``mfu_degenerate_total`` counter so
    a bench that silently reports 0 MFU is still diagnosable.
    """
    if (flops_per_step is None or step_time_s is None
            or not math.isfinite(step_time_s) or step_time_s <= 0.0
            or not math.isfinite(flops_per_step) or flops_per_step < 0.0):
        get_registry("jimm_train").counter("mfu_degenerate_total").inc()
        return 0.0
    n = n_devices if n_devices is not None else jax.device_count()
    peak = device_peak_tflops(device) * 1e12 * n
    return flops_per_step / (step_time_s * peak)


@dataclass
class StepTimer:
    """Wall-clock timing of one stretch, with a device sync
    (``block_until_ready``) on each boundary it is given arrays for. A loop
    that reads every loss before the next launch wraps the whole step in one
    stretch. ``jimm-tpu train`` keeps a step in flight, so it times the
    step's call (no sync) and, after the next step's call, the wait for its
    loss, and logs their sum as ``step_time_s``: from call to arrival would
    span two programs."""

    t0: float = 0.0

    def start(self, *sync: jax.Array) -> None:
        jax.block_until_ready(sync)
        self.t0 = time.perf_counter()

    def stop(self, *sync: jax.Array) -> float:
        jax.block_until_ready(sync)
        return time.perf_counter() - self.t0


@dataclass
class MetricsLogger:
    """Structured metrics: console + JSONL file (one object per step) +
    optional TensorBoard scalars (``tensorboard_dir``; writes event files
    through the ``tensorboard`` package directly — no tensorflow).

    When ``registry`` is set (cmd_train passes the shared ``jimm_train``
    registry), every logged scalar is mirrored into it: ``step`` as the
    ``steps_logged_total`` counter, ``step_time_s`` into the
    ``step_time_seconds`` histogram, and every other numeric value as a
    last-value gauge — so the unified ``obs.snapshot()`` carries the same
    series the JSONL does. ``file_only`` fields (the loop's per-step
    ``phases``: structure, not scalars) go into the JSONL row and nowhere
    else.
    """

    path: str | Path | None = None
    print_every: int = 1
    tensorboard_dir: str | Path | None = None
    registry: MetricRegistry | None = None
    _file: IO | None = field(default=None, repr=False)
    _tb: Any = field(default=None, repr=False)
    _step: int = 0

    def log(self, step: int, *, file_only: dict[str, Any] | None = None,
            **metrics: Any) -> None:
        record = {"step": step, "time": time.time(), **metrics,
                  **(file_only or {})}
        if self.path is not None:
            if self._file is None:
                Path(self.path).parent.mkdir(parents=True, exist_ok=True)
                self._file = open(self.path, "a")
            self._file.write(json.dumps(record, default=float) + "\n")
            self._file.flush()
        if self.registry is not None:
            self._registry_log(metrics)
        if self.tensorboard_dir is not None:
            self._tb_log(step, metrics)
        if self.print_every and step % self.print_every == 0:
            parts = " ".join(f"{k}={float(v):.4g}" if isinstance(v, (int, float))
                             else f"{k}={v}" for k, v in metrics.items())
            print(f"step {step}: {parts}")  # jaxlint: disable=JL007 — the console sink IS the logger

    def _registry_log(self, metrics: dict[str, Any]) -> None:
        reg = self.registry
        reg.counter("steps_logged_total").inc()
        for k, v in metrics.items():
            try:
                value = float(v)
            except (TypeError, ValueError):
                continue  # non-numeric: JSONL-only, same as TensorBoard
            if k == "step_time_s":
                reg.histogram("step_time_seconds").observe(value)
            else:
                try:
                    reg.gauge(k).set(value)
                except Exception:  # jaxlint: disable=JL013 — best-effort mirror; a name clash with a counter must not fail the log call  # noqa: BLE001
                    pass

    def _tb_log(self, step: int, metrics: dict[str, Any]) -> None:
        if self._tb is None:
            try:
                from tensorboard.summary.writer.event_file_writer import (
                    EventFileWriter)
            except ImportError:
                self.tensorboard_dir = None  # optional dep absent: degrade
                import warnings
                warnings.warn("tensorboard not installed; scalar event "
                              "logging disabled", stacklevel=3)
                return
            Path(self.tensorboard_dir).mkdir(parents=True, exist_ok=True)
            self._tb = EventFileWriter(str(self.tensorboard_dir))
        from tensorboard.compat.proto.event_pb2 import Event
        from tensorboard.compat.proto.summary_pb2 import Summary
        values = []
        for k, v in metrics.items():
            try:
                # match the JSONL path's default=float coercion: np/jax
                # scalars must land in TensorBoard too, not just floats
                values.append(Summary.Value(tag=k, simple_value=float(v)))
            except (TypeError, ValueError):
                pass  # non-numeric (strings etc.) — JSONL-only
        if values:
            self._tb.add_event(Event(step=step, wall_time=time.time(),
                                     summary=Summary(value=values)))

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
            self._file = None
        if self._tb is not None:
            self._tb.close()
            self._tb = None


# ---------------------------------------------------------------------------
# Analytic model FLOPs (XLA cost analysis counts a scanned layer body once,
# so compiled_flops undercounts depth-L towers by ~L; MFU uses these instead)
# ---------------------------------------------------------------------------

def _tower_fwd_flops(width: int, depth: int, mlp_dim: int, seq: int) -> float:
    matmul_params = depth * (4 * width * width + 2 * width * mlp_dim)
    attn = depth * 4 * seq * seq * width  # qk^T and pv
    return 2 * matmul_params * seq + attn


def vision_fwd_flops(v) -> float:
    """Per-image forward FLOPs of a VisionConfig tower (+ patch conv, MAP)."""
    seq = v.seq_len
    total = _tower_fwd_flops(v.width, v.depth, v.mlp_dim, seq)
    total += 2 * (v.patch_size ** 2 * v.channels * v.width) * v.num_patches
    if v.pooling == "map":
        # probe cross-attention: k/v projections over seq + mlp on 1 token
        total += 2 * (2 * v.width ** 2) * seq + 2 * (2 * v.width * v.mlp_dim)
    return total


def text_fwd_flops(t) -> float:
    return _tower_fwd_flops(t.width, t.depth, t.mlp_dim, t.context_length)


def decoder_fwd_flops(d, loops: int | None = None) -> float:
    """Per-sequence forward FLOPs of a looped `DecoderConfig` language model:
    per token and block application the four attention projections, the three
    SwiGLU matmuls and causal attention (q k^T and p v over HALF of S^2: the
    masked half is not work the algorithm needs), times passes x layers; and
    per pass the gate and the untied head."""
    loops = d.loops if loops is None else loops
    block = 2 * (4 * d.width ** 2 + 3 * d.width * d.mlp_dim) \
        + 2 * d.seq_len * d.width
    per_pass = d.depth * block + 2 * d.width * (d.vocab_size + 1)
    return float(loops * per_pass * d.seq_len)


def moe_decoder_fwd_flops(d) -> float:
    """Per-sequence forward FLOPs of a `MoEDecoderConfig` language model as
    one chip holds it: per token and layer the latent attention's four
    projections and causal attention over half of S^2 (q k^T at the q/k
    width, p v at v's); the dense layers' SwiGLU; in a sparse layer the
    router, the shared experts and the routed experts at the expected
    ``top_k * held / num_experts`` applications a token (without ``moe``, a
    dense SwiGLU in every layer); the head."""
    a, e = d.mla, d.moe
    if d.gqa is not None:
        # grouped-query attention: q, gate and output at num_heads x head_dim,
        # k and v at kv_heads x head_dim; a windowed layer sees at most
        # ``window`` keys a query
        g = d.gqa
        inner = d.num_heads * g.head_dim
        proj = 2 * d.width * ((3 if g.gate else 2) * inner
                              + 2 * g.kv_heads * g.head_dim)
        seen = [d.seq_len / 2 if full or not g.window else
                min(g.window, d.seq_len) * (1 - min(g.window, d.seq_len)
                                            / (2 * d.seq_len))
                for full in d.full_layers]
        attn = proj + sum(seen) / d.depth * 4 * inner
    else:
        qk = a.qk_nope_dim + a.qk_rope_dim
        attn = 2 * (d.width * d.num_heads * qk
                    + d.width * (a.kv_lora_rank + a.qk_rope_dim)
                    + a.kv_lora_rank * d.num_heads
                    * (a.qk_nope_dim + a.v_head_dim)
                    + d.num_heads * a.v_head_dim * d.width) \
            + d.seq_len * d.num_heads * (qk + a.v_head_dim)
    mixers = d.held_mixers
    if "kda" in mixers:
        # Kimi Delta Attention in place of ``attn`` on its layers: the
        # projections (q, k, v, output, two low-rank gates, the write
        # strength) and the recurrence's three head_dim x head_dim products
        # a token and head
        k = d.kda
        inner = k.num_heads * k.head_dim
        kda = 2 * (4 * d.width * inner + 2 * k.gate_rank * (d.width + inner)
                   + d.width * k.num_heads) + 6 * inner * k.head_dim
        attn = (mixers.count("kda") * kda
                + (d.depth - mixers.count("kda")) * attn) / d.depth
    if "mamba" in mixers:
        # Mamba-2 in place of ``attn`` on its layers: its two projections,
        # the convolution, and the recurrence's write and read, head_dim x
        # state each a token and head
        m = d.mamba
        inner = m.num_heads * m.head_dim
        conv = inner + 2 * m.groups * m.state
        ssm = 2 * (d.width * (inner + conv + m.num_heads) + inner * d.width
                   + m.conv_taps * conv) + 4 * inner * m.state
        attn = (mixers.count("mamba") * ssm
                + (d.depth - mixers.count("mamba")) * attn) / d.depth
    dense_ffn = 2 * 3 * d.width * d.mlp_dim
    if e is None:
        return float((d.depth * (attn + dense_ffn)
                      + 2 * d.width * d.vocab_size) * d.seq_len)
    swiglu = 2 * 3 * d.width * e.expert_dim
    sparse = 2 * d.width * e.num_experts + swiglu * (
        e.shared_experts + e.top_k * e.held_experts / e.num_experts)
    per_token = (d.depth * attn + d.dense_layers * dense_ffn
                 + (d.depth - d.dense_layers) * sparse
                 + 2 * d.width * d.vocab_size)
    return float(per_token * d.seq_len)


def model_fwd_flops(cfg) -> float:
    """Per-sample forward FLOPs for a ViT/CLIP/SigLIP config, per sequence
    for a language model."""
    if hasattr(cfg, "decoder"):
        if hasattr(cfg.decoder, "moe"):
            return moe_decoder_fwd_flops(cfg.decoder)
        return decoder_fwd_flops(cfg.decoder)
    total = vision_fwd_flops(cfg.vision)
    if hasattr(cfg, "text"):
        total += text_fwd_flops(cfg.text)
        proj = getattr(cfg, "projection_dim", cfg.text.width)
        total += 2 * cfg.text.width * proj
        if hasattr(cfg.vision, "width") and cfg.vision.pooling == "cls":
            total += 2 * cfg.vision.width * proj  # CLIP visual projection
    return total


def train_step_flops(cfg, batch_size: int) -> float:
    """Model FLOPs (no remat recompute) of one training step: fwd + 2x bwd."""
    return 3.0 * model_fwd_flops(cfg) * batch_size
