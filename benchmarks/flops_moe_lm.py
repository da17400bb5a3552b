"""Operations and bytes a sparse decoder language model with latent attention
needs, from shapes alone: the yardstick of the ``kanana_2_30b_a3b`` cells,
kept apart from the program's copy (``jimm_tpu/train/metrics.py::
moe_decoder_fwd_flops``) so that a later change to the program cannot move a
utilization.

Causal attention is counted at HALF of S^2 and at the UNPADDED head widths
(q and k ``qk_nope_head_dim + qk_rope_head_dim``, v ``v_head_dim``): lanes a
kernel pads to are not work the algorithm needs. The routed experts are
counted at the expected ``num_experts_per_tok * held / published`` applications
a token (each token through six of the published experts, this chip's share of
them), never at a buffer's capacity. Recomputed operations (a remat policy's
second forward) are not counted either.
"""

from __future__ import annotations

from benchmarks import flops


def sizes(config: dict, seq_len: int) -> dict:
    """What the counts below need, from a configuration file's (HF) keys and
    the cell's sequence length."""
    return {"width": config["hidden_size"], "mlp": config["intermediate_size"],
            "heads": config["num_attention_heads"],
            "rank": config["kv_lora_rank"],
            "d_nope": config["qk_nope_head_dim"],
            "d_rope": config["qk_rope_head_dim"],
            "d_v": config["v_head_dim"], "vocab": config["vocab_size"],
            "layers": config["num_layers"],
            "dense": config["first_k_dense_replace"],
            "held": config["n_routed_experts"],
            "experts": config["published"]["n_routed_experts"],
            "top_k": config["num_experts_per_tok"],
            "shared": config["n_shared_experts"],
            "expert_mlp": config["moe_intermediate_size"], "seq": seq_len}


def fwd_flops_per_token(config: dict, seq_len: int) -> dict:
    """Forward FLOPs a token, by part (2 FLOPs a weight; causal attention
    over half of S^2: ``S * heads * (d_qk + d_v)``)."""
    s = sizes(config, seq_len)
    d_qk = s["d_nope"] + s["d_rope"]
    sparse = s["layers"] - s["dense"]
    swiglu = 2 * 3 * s["width"] * s["expert_mlp"]
    return {
        "mla_projections": s["layers"] * 2 * (
            s["width"] * s["heads"] * d_qk
            + s["width"] * (s["rank"] + s["d_rope"])
            + s["rank"] * s["heads"] * (s["d_nope"] + s["d_v"])
            + s["heads"] * s["d_v"] * s["width"]),
        "attention_core": s["layers"] * s["seq"] * s["heads"]
        * (d_qk + s["d_v"]),
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


def mla_flash_cost(batch: int, seq: int, heads: int, d_qk: int, d_v: int, *,
                   backward: bool, bytes_per_el: int = 2) -> dict:
    """One causal attention call with q/k of ``d_qk`` and v of ``d_v``: the
    FLOPs of the unmasked call halved. Forward: q k^T (2 S^2 d_qk) and p v
    (2 S^2 d_v) a head; backward: dp and dv at d_v, dq and dk at d_qk (the
    recomputed q k^T is not counted). Bytes: q, k, v read and o written
    (forward); q, k, v, o, do read and dq, dk, dv written (backward)."""
    pair = 2.0 * batch * heads * seq * seq * (d_qk + d_v) / 2
    row = batch * heads * seq * bytes_per_el
    if backward:
        return {"flops": 2 * pair, "bytes": row * (4 * d_qk + 4 * d_v)}
    return {"flops": pair, "bytes": row * (2 * d_qk + 2 * d_v)}


def mla_flash_least_seconds(config: dict, batch_size: int, seq_len: int,
                            device_kind: str) -> float:
    """The least time the chip could take for a step's causal attention: one
    forward and one backward call a layer, each at the larger of FLOPs / peak
    and bytes / peak."""
    s = sizes(config, seq_len)
    least = 0.0
    for backward in (False, True):
        cost = mla_flash_cost(batch_size, s["seq"], s["heads"],
                              s["d_nope"] + s["d_rope"], s["d_v"],
                              backward=backward)
        least += flops.roofline_least_seconds(
            cost["flops"], cost["bytes"], device_kind)[0]
    return least * s["layers"]


def grouped_products_cost(rows: float, config: dict, *, backward: bool,
                          bytes_per_el: int = 2) -> dict:
    """The three grouped products of ONE sparse layer (gate, up, down) over
    ``rows`` assignment rows: 2 FLOPs a row and weight forward, twice that
    backward (the gradient on the rows and on the weights). Bytes: the rows
    in and out of each product and the held experts' weights once (forward);
    rows, their gradients and the weights read, the weights' gradients
    written (backward)."""
    w, f, held = (config["hidden_size"], config["moe_intermediate_size"],
                  config["n_routed_experts"])
    matmul = 2.0 * rows * 3 * w * f
    weights = held * 3 * w * f * bytes_per_el
    acts = rows * (2 * (w + f) + (f + w)) * bytes_per_el
    if backward:
        return {"flops": 2 * matmul, "bytes": 2 * acts + 2 * weights}
    return {"flops": matmul, "bytes": acts + weights}


def grouped_products_least_seconds(held_rows: float, config: dict,
                                   device_kind: str) -> float:
    """The least time for a step's grouped products, forward and backward,
    at ``held_rows`` assignments to held experts summed over the sparse
    layers (the step's own count, not a buffer's capacity)."""
    sparse = config["num_layers"] - config["first_k_dense_replace"]
    least = 0.0
    for backward in (False, True):
        cost = grouped_products_cost(held_rows / sparse, config,
                                     backward=backward)
        least += flops.roofline_least_seconds(
            cost["flops"], cost["bytes"], device_kind)[0]
    return least * sparse
