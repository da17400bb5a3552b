"""Operations and bytes the algorithm needs, from shapes alone.

The yardstick: a copy of ``jimm_tpu/train/metrics.py::train_step_flops``
(model FLOPs of one training step, forward + 2x backward, recomputed
operations not counted) over the sizes in a configuration's own file, and
the operations and bytes of one flash-attention call. Kept here so that a
later change to the program cannot move a utilization.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``. A device that is not in
    ``peaks.json`` is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device_kind {device_kind!r}; "
                       f"add it to {PEAKS_FILE.name} with its source")
    return table[device_kind]


def _tower_fwd_flops(width: int, depth: int, mlp: int, seq: int) -> float:
    matmul_params = depth * (4 * width * width + 2 * width * mlp)
    attn = depth * 4 * seq * seq * width  # q k^T and p v
    return 2 * matmul_params * seq + attn


def vision_tower(config: dict) -> dict:
    """The vision sizes of a configuration file (HF keys), with the
    sequence length the tower runs at."""
    v = config.get("vision_config", config)
    patches = (v["image_size"] // v["patch_size"]) ** 2
    cls = config["family"] == "vit"  # ViT prepends a class token; SigLIP pools by attention
    return {**v, "num_patches": patches, "seq_len": patches + (1 if cls else 0),
            "map_head": config["family"] == "siglip"}


def vision_fwd_flops(config: dict) -> float:
    v = vision_tower(config)
    w, mlp = v["hidden_size"], v["intermediate_size"]
    total = _tower_fwd_flops(w, v["num_hidden_layers"], mlp, v["seq_len"])
    total += 2 * (v["patch_size"] ** 2 * v["num_channels"] * w) * v["num_patches"]
    if v["map_head"]:
        # probe cross-attention: k/v projections over the sequence, MLP on one token
        total += 2 * (2 * w * w) * v["seq_len"] + 2 * (2 * w * mlp)
    return total


def text_fwd_flops(config: dict) -> float:
    t = config["text_config"]
    total = _tower_fwd_flops(t["hidden_size"], t["num_hidden_layers"],
                             t["intermediate_size"],
                             t["max_position_embeddings"])
    proj = t.get("projection_size", t["hidden_size"])
    return total + 2 * t["hidden_size"] * proj


def model_fwd_flops(config: dict) -> float:
    """Forward FLOPs for one sample (one image, or one image-text pair)."""
    total = vision_fwd_flops(config)
    if "text_config" in config:
        total += text_fwd_flops(config)
    return total


def train_step_flops(config: dict, batch_size: int) -> float:
    """Model FLOPs of one training step: forward + 2x backward."""
    return 3.0 * model_fwd_flops(config) * batch_size


def flash_attention_cost(batch: int, seq: int, heads: int, head_dim: int,
                         *, backward: bool, bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of one full (unmasked) attention call.

    Forward: q k^T and p v, 2 matmuls of 2*S*S*D each per head. Backward:
    dv, dp, dq, dk, 4 matmuls; a flash kernel recomputes q k^T (in each of
    its two backward kernels) because it keeps no probabilities, and like any
    recomputation that is not counted. Bytes: q, k, v read and o written once
    (forward); q, k, v, o, do read and dq, dk, dv written once (backward);
    the (S,) row statistics are left out.
    """
    per_matmul = 2.0 * batch * heads * seq * seq * head_dim
    tensor = batch * heads * seq * head_dim * bytes_per_el
    if backward:
        return {"flops": 4 * per_matmul, "bytes": 8.0 * tensor}
    return {"flops": 2 * per_matmul, "bytes": 4.0 * tensor}


def roofline_least_seconds(flops: float, bytes_: float, device_kind: str
                           ) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    p = peaks(device_kind)
    t_compute = flops / (p["bf16_tflops"] * 1e12)
    t_memory = bytes_ / (p["hbm_gbps"] * 1e9)
    return ((t_compute, "compute") if t_compute >= t_memory
            else (t_memory, "memory"))
