"""Operations and bytes a hybrid decoder language model needs (linear-attention
layers with a gated delta-rule state beside latent-attention ones, a mixture of
experts behind both), from shapes alone: the yardstick of the
``kimi_linear_48b_a3b`` cells, kept apart from the program's copy
(``jimm_tpu/train/metrics.py::moe_decoder_fwd_flops``) so that a later change
to the program cannot move a utilization.

By layer kind. A KDA layer: its projections (q, k, v, output, the two low-rank
gates, the write strength), its three convolutions, and the recurrence counted
in its RECURRENT form, three ``head_dim x head_dim`` products a token and head
(read ``k^T S``, write ``k u^T``, read ``S^T q``): what the chunked form adds
(the triangular solve, the intra-chunk products) is how one implementation
computes it, not work the layer needs. A latent-attention layer: its four
projections and causal attention at HALF of S^2 and the UNPADDED widths (q and
k ``qk_nope_head_dim + qk_rope_head_dim``, v ``v_head_dim``). The routed
experts are counted at the expected ``num_experts_per_token * held /
published`` applications a token, never at a buffer's capacity. Recomputed
operations (a remat policy's second forward) are not counted either.
"""

from __future__ import annotations

from benchmarks import flops
from benchmarks.flops_moe_lm import mla_flash_cost


def sizes(config: dict, seq_len: int) -> dict:
    """What the counts below need, from a configuration file's (HF) keys and
    the cell's sequence length."""
    lin = config["linear_attn_config"]
    return {"width": config["hidden_size"], "mlp": config["intermediate_size"],
            "heads": config["num_attention_heads"],
            "rank": config["kv_lora_rank"],
            "d_nope": config["qk_nope_head_dim"],
            "d_rope": config["qk_rope_head_dim"],
            "d_v": config["v_head_dim"],
            "kda_heads": lin["num_heads"], "kda_d": lin["head_dim"],
            "taps": lin["short_conv_kernel_size"],
            "gate_rank": config["assumed"]["gate_rank"],
            "vocab": config["vocab_size"], "layers": config["num_layers"],
            "dense": config["first_k_dense_replace"],
            "held": config["num_experts"],
            "experts": config["published"]["num_experts"],
            "top_k": config["num_experts_per_token"],
            "shared": config["num_shared_experts"],
            "expert_mlp": config["moe_intermediate_size"], "seq": seq_len}


def layer_mixers(config: dict) -> list[str]:
    """Per held layer (published layers ``first_layer + 1 ..``, counted from
    1 as the file's lists count them), ``"kda"`` or ``"mla"``."""
    full = set(config["linear_attn_config"]["full_attn_layers"])
    return ["mla" if config["first_layer"] + i + 1 in full else "kda"
            for i in range(config["num_layers"])]


def parameter_count(config: dict) -> dict:
    """Parameters this chip holds, by part."""
    s = sizes(config, 0)
    mixers = layer_mixers(config)
    inner = s["kda_heads"] * s["kda_d"]
    kda = (4 * s["width"] * inner + 3 * s["taps"] * inner
           + 2 * s["gate_rank"] * (s["width"] + inner)
           + s["width"] * s["kda_heads"] + s["kda_heads"] + inner + s["kda_d"])
    mla = (s["width"] * s["heads"] * (s["d_nope"] + s["d_rope"])
           + s["width"] * (s["rank"] + s["d_rope"]) + s["rank"]
           + s["rank"] * s["heads"] * (s["d_nope"] + s["d_v"])
           + s["heads"] * s["d_v"] * s["width"])
    expert = 3 * s["width"] * s["expert_mlp"]
    sparse = s["layers"] - s["dense"]
    return {
        "kda": mixers.count("kda") * kda,
        "mla": mixers.count("mla") * mla,
        "norms": s["layers"] * 2 * s["width"] + s["width"],
        "dense_ffn": s["dense"] * 3 * s["width"] * s["mlp"],
        "router": sparse * s["width"] * s["experts"],
        "shared_experts": sparse * s["shared"] * expert,
        "held_experts": sparse * s["held"] * expert,
        "embedding": s["vocab"] * s["width"],
        "head": s["vocab"] * s["width"],
    }


def fwd_flops_per_token(config: dict, seq_len: int) -> dict:
    """Forward FLOPs a token, by part (2 FLOPs a weight)."""
    s = sizes(config, seq_len)
    mixers = layer_mixers(config)
    n_kda, n_mla = mixers.count("kda"), mixers.count("mla")
    inner = s["kda_heads"] * s["kda_d"]
    d_qk = s["d_nope"] + s["d_rope"]
    sparse = s["layers"] - s["dense"]
    swiglu = 2 * 3 * s["width"] * s["expert_mlp"]
    return {
        "kda_projections": n_kda * 2 * (
            4 * s["width"] * inner + 3 * s["taps"] * inner
            + 2 * s["gate_rank"] * (s["width"] + inner)
            + s["width"] * s["kda_heads"]),
        "kda_recurrence": n_kda * 3 * 2 * inner * s["kda_d"],
        "mla_projections": n_mla * 2 * (
            s["width"] * s["heads"] * d_qk
            + s["width"] * (s["rank"] + s["d_rope"])
            + s["rank"] * s["heads"] * (s["d_nope"] + s["d_v"])
            + s["heads"] * s["d_v"] * s["width"]),
        "attention_core": n_mla * s["seq"] * s["heads"] * (d_qk + s["d_v"]),
        "dense_ffn": s["dense"] * 2 * 3 * s["width"] * s["mlp"],
        "shared_experts": sparse * swiglu * s["shared"],
        "router": sparse * 2 * s["width"] * s["experts"],
        "held_experts": sparse * swiglu * s["top_k"] * s["held"]
        / s["experts"],
        "head": 2 * s["width"] * s["vocab"],
    }


def train_step_flops(config: dict, batch_size: int, seq_len: int) -> float:
    """Model FLOPs of one training step: forward + 2x backward."""
    per_token = sum(fwd_flops_per_token(config, seq_len).values())
    return 3.0 * per_token * seq_len * batch_size


def mla_flash_least_seconds(config: dict, batch_size: int, seq_len: int,
                            device_kind: str) -> float:
    """The least time for a step's causal attention kernels: one forward and
    one backward call a LATENT-ATTENTION layer (``flops_moe_lm``'s cost of a
    call: half of S^2, the unpadded widths), each at the larger of FLOPs /
    peak and bytes / peak."""
    s = sizes(config, seq_len)
    least = 0.0
    for backward in (False, True):
        cost = mla_flash_cost(batch_size, s["seq"], s["heads"],
                              s["d_nope"] + s["d_rope"], s["d_v"],
                              backward=backward)
        least += flops.roofline_least_seconds(
            cost["flops"], cost["bytes"], device_kind)[0]
    return least * layer_mixers(config).count("mla")


def kda_scan_cost(batch: int, seq: int, heads: int, d: int, *,
                  backward: bool, bytes_per_el: int = 2) -> dict:
    """What ANY implementation of one layer's recurrence must do. Forward:
    the recurrent form's three ``d x d`` products a token and head, q, k, v,
    g (``d`` each) and b (1) read, o written. Backward: twice the products,
    the five inputs and ``do`` read, the five gradients written. At two bytes
    an element: a later kernel may take every operand in bfloat16."""
    tokens = batch * seq * heads
    products = 3 * 2.0 * d * d * tokens
    if backward:
        return {"flops": 2 * products,
                "bytes": tokens * ((5 * d + 1) + (4 * d + 1)) * bytes_per_el}
    return {"flops": products, "bytes": tokens * (5 * d + 1) * bytes_per_el}


def kda_scan_least_seconds(config: dict, batch_size: int, seq_len: int,
                           device_kind: str) -> float:
    """The least time for a step's delta-rule scans: one forward and one
    backward a KDA layer, each at the larger of FLOPs / peak and bytes /
    peak (bytes, at these shapes)."""
    s = sizes(config, seq_len)
    least = 0.0
    for backward in (False, True):
        cost = kda_scan_cost(batch_size, seq_len, s["kda_heads"], s["kda_d"],
                             backward=backward)
        least += flops.roofline_least_seconds(
            cost["flops"], cost["bytes"], device_kind)[0]
    return least * layer_mixers(config).count("kda")
