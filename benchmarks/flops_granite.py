"""Operations and bytes a dense hybrid decoder needs (Mamba-2 state-space
layers beside grouped-query attention ones, a SwiGLU in every layer, a tied
head), from shapes alone: the yardstick of the ``granite_4_0_h_micro`` cell,
kept apart from the program's copy (``jimm_tpu/train/metrics.py::
moe_decoder_fwd_flops``) so that a later change to the program cannot move a
utilization.

By layer kind. A Mamba-2 layer: its two projections (``W_in`` to ``z``,
``xBC`` and ``dt``; ``W_out``), its convolution, and the recurrence counted in
its RECURRENT form, two ``head_dim x state`` products a token and head (write
``x B^T``, read ``S C``): what the chunked form adds (the ``(L, L)`` products
inside a chunk) is how one implementation computes it, not work the layer
needs. An attention layer: its four projections (q and output at ``heads x
head_dim``, k and v at ``kv_heads x head_dim``) and causal attention at HALF
of S^2. The SwiGLU in every layer; the head (the tied embedding) once.
Recomputed operations (a remat policy's second forward) are not counted.
"""

from __future__ import annotations

from benchmarks import flops


def sizes(config: dict, seq_len: int) -> dict:
    """What the counts below need, from a configuration file's (HF) keys and
    the cell's sequence length."""
    heads = config["num_attention_heads"]
    return {"width": config["hidden_size"],
            "mlp": config["shared_intermediate_size"],
            "heads": heads, "kv_heads": config["num_key_value_heads"],
            "head_dim": config["hidden_size"] // heads,
            "ssm_heads": config["mamba_n_heads"],
            "ssm_head_dim": config["mamba_d_head"],
            "state": config["mamba_d_state"],
            "groups": config["mamba_n_groups"],
            "taps": config["mamba_d_conv"],
            "vocab": config["vocab_size"], "layers": config["num_layers"],
            "seq": seq_len}


def layer_mixers(config: dict) -> list[str]:
    """Per held layer (published layers ``first_layer ..``, counted from 0 as
    ``layer_types`` counts them), ``"mamba"`` or ``"attention"``."""
    first = config["first_layer"]
    return config["layer_types"][first:first + config["num_layers"]]


def _mamba_parts(s: dict) -> dict:
    inner = s["ssm_heads"] * s["ssm_head_dim"]
    conv = inner + 2 * s["groups"] * s["state"]
    return {"inner": inner, "conv": conv,
            "in_proj": s["width"] * (inner + conv + s["ssm_heads"]),
            "out_proj": inner * s["width"]}


def parameter_count(config: dict) -> dict:
    """Parameters this chip holds, by part."""
    s = sizes(config, 0)
    mixers = layer_mixers(config)
    m = _mamba_parts(s)
    mamba = (m["in_proj"] + m["out_proj"] + s["taps"] * m["conv"] + m["conv"]
             + 3 * s["ssm_heads"] + m["inner"])
    attention = 2 * s["width"] * s["heads"] * s["head_dim"] \
        + 2 * s["width"] * s["kv_heads"] * s["head_dim"]
    return {
        "mamba": mixers.count("mamba") * mamba,
        "attention": mixers.count("attention") * attention,
        "ffn": s["layers"] * 3 * s["width"] * s["mlp"],
        "norms": s["layers"] * 2 * s["width"] + s["width"],
        "embedding": s["vocab"] * s["width"],
    }


def fwd_flops_per_token(config: dict, seq_len: int) -> dict:
    """Forward FLOPs a token, by part (2 FLOPs a weight)."""
    s = sizes(config, seq_len)
    mixers = layer_mixers(config)
    n_mamba, n_attn = mixers.count("mamba"), mixers.count("attention")
    m = _mamba_parts(s)
    return {
        "mamba_projections": n_mamba * 2 * (
            m["in_proj"] + m["out_proj"] + s["taps"] * m["conv"]),
        "mamba_recurrence": n_mamba * 2 * 2 * m["inner"] * s["state"],
        "attention_projections": n_attn * 2 * (
            2 * s["width"] * s["heads"] * s["head_dim"]
            + 2 * s["width"] * s["kv_heads"] * s["head_dim"]),
        "attention_core": n_attn * s["seq"] * s["heads"] * 2 * s["head_dim"],
        "ffn": s["layers"] * 2 * 3 * s["width"] * s["mlp"],
        "head": 2 * s["width"] * s["vocab"],
    }


def train_step_flops(config: dict, batch_size: int, seq_len: int) -> float:
    """Model FLOPs of one training step: forward + 2x backward."""
    per_token = sum(fwd_flops_per_token(config, seq_len).values())
    return 3.0 * per_token * seq_len * batch_size


def ssm_scan_cost(batch: int, seq: int, heads: int, head_dim: int,
                  state: int, groups: int, *, backward: bool) -> dict:
    """What ANY implementation of one layer's recurrence must do. Forward:
    the recurrent form's two ``head_dim x state`` products a token and head;
    x (``head_dim`` a head) and B, C (``state`` a group each) read at two
    bytes, dt (one a head) at four, y written at two. Backward: twice the
    products, the inputs and dy read, dx, ddt, dB, dC written."""
    tokens = batch * seq
    products = 2 * 2.0 * head_dim * state * heads * tokens
    inputs = tokens * (heads * head_dim * 2 + heads * 4 + 2 * groups * state * 2)
    y = tokens * heads * head_dim * 2
    if backward:
        return {"flops": 2 * products, "bytes": 2 * inputs + y}
    return {"flops": products, "bytes": inputs + y}


def ssm_scan_least_seconds(config: dict, batch_size: int, seq_len: int,
                           device_kind: str) -> float:
    """The least time for a step's state-space scans: one forward and one
    backward a Mamba-2 layer, each at the larger of FLOPs / peak and bytes /
    peak (bytes, at these shapes)."""
    s = sizes(config, seq_len)
    least = 0.0
    for backward in (False, True):
        cost = ssm_scan_cost(batch_size, seq_len, s["ssm_heads"],
                             s["ssm_head_dim"], s["state"], s["groups"],
                             backward=backward)
        least += flops.roofline_least_seconds(
            cost["flops"], cost["bytes"], device_kind)[0]
    return least * layer_mixers(config).count("mamba")
