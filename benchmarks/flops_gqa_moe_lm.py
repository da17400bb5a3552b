"""Operations and bytes a sparse decoder language model with grouped-query,
window-and-full attention needs, from shapes alone: the yardstick of the
``trinity_large`` cells, kept apart from the program's copy
(``jimm_tpu/train/metrics.py::moe_decoder_fwd_flops``) so that a later change
to the program cannot move a utilization.

Causal attention is counted at the EXACT number of visible (query, key) pairs
of a sequence: ``S (S + 1) / 2`` on a full_attention layer, and on a
sliding_attention layer ``sum_i min(i + 1, window)`` (33,558,528 and
25,167,872 at 8192 tokens under a window of 4096). Key and value heads are
counted once, at their own number: nothing is repeated. The routed experts are
counted at the expected ``num_experts_per_tok * held / published`` applications
a token, never at a buffer's capacity. Recomputed operations (a remat policy's
second forward) are not counted either.
"""

from __future__ import annotations

from benchmarks import flops


def sizes(config: dict, seq_len: int) -> dict:
    """What the counts below need, from a configuration file's (HF) keys and
    the cell's sequence length."""
    return {"width": config["hidden_size"], "mlp": config["intermediate_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "d": config["head_dim"], "window": config["sliding_window"],
            "full_every": config["global_attn_every_n_layers"],
            "vocab": config["vocab_size"], "layers": config["num_layers"],
            "first_layer": config["first_layer"],
            "dense": config["dense_layers_held"],
            "held": config["num_experts"],
            "experts": config["published"]["num_experts"],
            "top_k": config["num_experts_per_tok"],
            "shared": config["num_shared_experts"],
            "expert_mlp": config["moe_intermediate_size"], "seq": seq_len}


def layer_is_full(config: dict) -> list[bool]:
    """Per held layer, whether it is a full_attention layer (published layer
    ``first_layer + i`` is one iff its index + 1 divides by
    ``global_attn_every_n_layers``)."""
    return [(config["first_layer"] + i + 1)
            % config["global_attn_every_n_layers"] == 0
            for i in range(config["num_layers"])]


def visible_pairs(seq_len: int, window: int | None) -> int:
    """(query, key) pairs of one sequence with ``j <= i`` and, under a
    ``window``, ``i - j < window``."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def pairs_by_layer(config: dict, seq_len: int) -> list[int]:
    return [visible_pairs(seq_len, None if full else config["sliding_window"])
            for full in layer_is_full(config)]


def parameter_count(config: dict) -> dict:
    """Parameters this chip holds, by part."""
    s = sizes(config, 0)
    inner, kv = s["heads"] * s["d"], s["kv_heads"] * s["d"]
    attn = s["width"] * (3 * inner + 2 * kv) + 2 * s["d"]  # q, gate, out; k, v
    norms = 4 * s["width"]
    expert = 3 * s["width"] * s["expert_mlp"]
    sparse = s["layers"] - s["dense"]
    return {
        "attention": s["layers"] * attn,
        "norms": s["layers"] * norms + s["width"],
        "dense_ffn": s["dense"] * 3 * s["width"] * s["mlp"],
        "router": sparse * s["width"] * s["experts"],
        "shared_experts": sparse * s["shared"] * expert,
        "held_experts": sparse * s["held"] * expert,
        "embedding": s["vocab"] * s["width"],
        "head": s["vocab"] * s["width"],
    }


def fwd_flops_per_sequence(config: dict, seq_len: int) -> dict:
    """Forward FLOPs a sequence, by part (2 FLOPs a weight and token; the
    attention core 4 * head_dim FLOPs a visible pair and query head)."""
    s = sizes(config, seq_len)
    inner, kv = s["heads"] * s["d"], s["kv_heads"] * s["d"]
    sparse = s["layers"] - s["dense"]
    swiglu = 2 * 3 * s["width"] * s["expert_mlp"]
    per_token = {
        "attention_projections": s["layers"] * 2 * s["width"]
        * (3 * inner + 2 * kv),
        "dense_ffn": s["dense"] * 2 * 3 * s["width"] * s["mlp"],
        "shared_experts": sparse * swiglu * s["shared"],
        "router": sparse * 2 * s["width"] * s["experts"],
        "held_experts": sparse * swiglu * s["top_k"] * s["held"]
        / s["experts"],
        "head": 2 * s["width"] * s["vocab"],
    }
    out = {k: v * seq_len for k, v in per_token.items()}
    out["attention_core"] = sum(pairs_by_layer(config, seq_len)) \
        * s["heads"] * 4 * s["d"]
    return out


def train_step_flops(config: dict, batch_size: int, seq_len: int) -> float:
    """Model FLOPs of one training step: forward + 2x backward."""
    return 3.0 * batch_size * sum(
        fwd_flops_per_sequence(config, seq_len).values())


def gqa_flash_cost(pairs: int, batch: int, seq: int, heads: int,
                   kv_heads: int, d: int, *, backward: bool,
                   bytes_per_el: int = 2) -> dict:
    """One causal attention call over ``pairs`` visible pairs a sequence.
    Forward: q k^T and p v, ``4 d`` FLOPs a pair and query head; backward:
    dp, dv, dq, dk, twice that (the recomputed q k^T is not counted). Bytes:
    q read and o written at ``heads``, k and v read ONCE at ``kv_heads``
    (forward); q, o, do read and dq written at ``heads``, k, v read and dk, dv
    written at ``kv_heads`` (backward)."""
    core = 4.0 * d * pairs * heads * batch
    rows, kv_rows = (batch * seq * n * d * bytes_per_el
                     for n in (heads, kv_heads))
    if backward:
        return {"flops": 2 * core, "bytes": 4 * rows + 4 * kv_rows}
    return {"flops": core, "bytes": 2 * rows + 2 * kv_rows}


def gqa_flash_least_seconds(config: dict, batch_size: int, seq_len: int,
                            device_kind: str) -> float:
    """The least time the chip could take for a step's attention kernels:
    one forward and one backward call a layer at the layer's own visible
    pairs, each at the larger of FLOPs / peak and bytes / peak."""
    s = sizes(config, seq_len)
    least = 0.0
    for pairs in pairs_by_layer(config, seq_len):
        for backward in (False, True):
            cost = gqa_flash_cost(pairs, batch_size, seq_len, s["heads"],
                                  s["kv_heads"], s["d"], backward=backward)
            least += flops.roofline_least_seconds(
                cost["flops"], cost["bytes"], device_kind)[0]
    return least


def grouped_products_cost(rows: float, config: dict, *, backward: bool,
                          bytes_per_el: int = 2) -> dict:
    """The three grouped products of ONE sparse layer (gate, up, down) over
    ``rows`` assignment rows: 2 FLOPs a row and weight forward, twice that
    backward (the gradient on the rows and on the weights). Bytes: the rows
    in and out of each product and the held experts' weights once (forward);
    rows, their gradients and the weights read, the weights' gradients
    written (backward)."""
    w, f, held = (config["hidden_size"], config["moe_intermediate_size"],
                  config["num_experts"])
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
    sparse = config["num_layers"] - config["dense_layers_held"]
    least = 0.0
    for backward in (False, True):
        cost = grouped_products_cost(held_rows / sparse, config,
                                     backward=backward)
        least += flops.roofline_least_seconds(
            cost["flops"], cost["bytes"], device_kind)[0]
    return least * sparse
