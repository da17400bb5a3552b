"""Operations and bytes a looped decoder language model needs, from shapes
alone: the yardstick of the ``ouro_2_6b`` cells, kept apart from the
program's copy (``jimm_tpu/train/metrics.py::decoder_fwd_flops``) so that a
later change to the program cannot move a utilization.

Causal attention is counted at HALF of S^2: the masked half is not work the
algorithm needs. Recomputed operations (a remat policy's second forward) are
not counted either, so a kernel that runs twice reads a lower roofline share.
"""

from __future__ import annotations

from benchmarks import flops


def sizes(config: dict, seq_len: int) -> dict:
    """What the counts below need, from a configuration file's (HF) keys and
    the cell's sequence length."""
    return {"width": config["hidden_size"], "mlp": config["intermediate_size"],
            "heads": config["num_attention_heads"],
            "head_dim": config["head_dim"], "vocab": config["vocab_size"],
            "layers": config["num_layers"], "passes": config["total_ut_steps"],
            "seq": seq_len}


def fwd_flops_per_sequence(config: dict, seq_len: int) -> float:
    """Per token and block application: the four attention projections and
    the three SwiGLU matmuls (2 FLOPs a weight) and causal attention (q k^T
    and p v over half of S^2: 2 * S * width); times passes x layers; plus,
    per pass, the gate and the untied head."""
    s = sizes(config, seq_len)
    block = 2 * (4 * s["width"] ** 2 + 3 * s["width"] * s["mlp"]) \
        + 2 * s["seq"] * s["heads"] * s["head_dim"]
    per_pass = s["layers"] * block + 2 * s["width"] * (s["vocab"] + 1)
    return float(s["passes"] * per_pass * s["seq"])


def train_step_flops(config: dict, batch_size: int, seq_len: int) -> float:
    """Model FLOPs of one training step: forward + 2x backward."""
    return 3.0 * fwd_flops_per_sequence(config, seq_len) * batch_size


def causal_flash_cost(batch: int, seq: int, heads: int, head_dim: int, *,
                      backward: bool, bytes_per_el: int = 2) -> dict:
    """One causal attention call: the FLOPs of the unmasked call halved, the
    bytes unchanged (q, k, v, o and their gradients are read and written
    whole whatever the mask)."""
    full = flops.flash_attention_cost(batch, seq, heads, head_dim,
                                      backward=backward,
                                      bytes_per_el=bytes_per_el)
    return {"flops": full["flops"] / 2, "bytes": full["bytes"]}


def causal_flash_least_seconds(config: dict, batch_size: int, seq_len: int,
                               device_kind: str) -> float:
    """The least time the chip could take for a step's causal attention: one
    forward and one backward call per block application (passes x layers),
    each at the larger of FLOPs / peak and bytes / peak."""
    s = sizes(config, seq_len)
    least = 0.0
    for backward in (False, True):
        cost = causal_flash_cost(batch_size, s["seq"], s["heads"],
                                 s["head_dim"], backward=backward)
        seconds, _ = flops.roofline_least_seconds(
            cost["flops"], cost["bytes"], device_kind)
        least += seconds
    return least * s["passes"] * s["layers"]
