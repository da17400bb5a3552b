"""Per-layer metrics of the train cell of a sparse language model with
grouped-query, window-and-full attention, read from the profiler trace
(``benchmarks/trace/reduce.py``) and the step rows through what
``benchmarks/drivers/train_gqa_moe_lm.py`` observed. Off the TPU, and for a
program that has no such scope, kernel or counter (the parent of the PR that
brought them), every reader returns nothing.

The program's scopes ``attn``, ``attn_window``, ``attn_full``, ``moe``,
``moe_route``, ``moe_experts``, ``moe_shared`` are plain path components of an
operation's ``op_name`` whichever way it is run (forward, backward, a remat
policy's second forward), so one name finds all three. The names differ from ``moe_lm.py``'s because a
metric has one reader and the accepted entries list only the other sparse
cell (PERF.md section 7 (i), (ii)).
"""

from __future__ import annotations

import statistics

from benchmarks import flops_gqa_moe_lm

#: what ``reduce_profile(scopes=...)`` has to be told
INNER_SCOPES = ("attn", "moe", "moe_route", "moe_experts", "moe_shared")
OUTER_SCOPES = ("embed", "decoder_stack", "lm_head")
#: the kernels' op_name paths by layer kind
WINDOW_KERNELS, FULL_KERNELS = "attn_window/pallas_call", "attn_full/pallas_call"


def scope_names(scope: str) -> tuple[str, ...]:
    if scope in INNER_SCOPES:
        return (scope,)
    return f"jvp({scope})", f"transpose(jvp({scope}))"


def _trace(o: dict) -> dict | None:
    if o.get("platform") != "tpu" or "gqa_moe_lm_shape" not in o:
        return None
    return o.get("trace")


def _scope_ms(o: dict, scope: str) -> float | None:
    t = _trace(o)
    if t is None or not t.get("scoped_ops"):
        return None
    found = sum(t["scope_ms"].get(name, 0.0) for name in scope_names(scope))
    return found or None


def _kernels_ms(o: dict, kernels: tuple[str, ...]) -> float | None:
    t = _trace(o)
    if t is None or not o.get("flash_calls"):
        return None
    found = sum(t["kernel_ms"].get(k, 0.0) for k in kernels)
    return found or None


def gqa_attn_ms(o: dict) -> float | None:
    """Device time per step of attention in every layer: projections,
    qk-norm, rotary, the kernels, gate, output projection."""
    return _scope_ms(o, "attn")


def gqa_flash_ms(o: dict) -> float | None:
    """Device time per step of the attention kernels of both layer kinds."""
    return _kernels_ms(o, (WINDOW_KERNELS, FULL_KERNELS))


def window_flash_ms(o: dict) -> float | None:
    """Device time per step of the windowed layers' attention kernels."""
    return _kernels_ms(o, (WINDOW_KERNELS,))


def gqa_flash_roofline(o: dict) -> float | None:
    """Least time of one forward and one backward call a layer at the exact
    visible pairs, k and v read once at their own heads
    (``flops_gqa_moe_lm.gqa_flash_least_seconds``), over the time the kernels
    took."""
    took = gqa_flash_ms(o)
    if took is None:
        return None
    least = flops_gqa_moe_lm.gqa_flash_least_seconds(
        o["config"], o["global_batch"], o["gqa_moe_lm_shape"]["seq_len"],
        o["device_kind"])
    return 100.0 * least * 1e3 / took


def sparse_ffn_ms(o: dict) -> float | None:
    """Device time per step of the sparse layers' expert layer: routing,
    grouped products, shared expert."""
    return _scope_ms(o, "moe")


def sparse_experts_ms(o: dict) -> float | None:
    """The grouped products over the held experts."""
    return _scope_ms(o, "moe_experts")


def sparse_route_ms(o: dict) -> float | None:
    """Router matmul, sigmoid, top-k, sort, gather and weighted scatter."""
    return _scope_ms(o, "moe_route")


def sparse_shared_ms(o: dict) -> float | None:
    """The shared expert, which every token passes."""
    return _scope_ms(o, "moe_shared")


def sparse_lm_head_ms(o: dict) -> float | None:
    """Final norm, head and loss, forward and backward."""
    return _scope_ms(o, "lm_head")


def sparse_held_rows(o: dict) -> float | None:
    """Assignments to held experts a step, summed over the sparse layers:
    the window's mean of the step rows' ``moe_held_rows``."""
    if "gqa_moe_lm_shape" not in o:
        return None
    rows = [r["moe_held_rows"] for r in o.get("window_rows", ())
            if "moe_held_rows" in r]
    return statistics.fmean(rows) if rows else None


def sparse_experts_roofline(o: dict) -> float | None:
    """Least time of the three grouped products, forward and backward, at the
    step's mean held rows over ``sparse_experts_ms``."""
    took, rows = sparse_experts_ms(o), sparse_held_rows(o)
    if took is None or rows is None:
        return None
    least = flops_gqa_moe_lm.grouped_products_least_seconds(
        rows, o["config"], o["device_kind"])
    return 100.0 * least * 1e3 / took


READERS = {"gqa_attn_ms": gqa_attn_ms, "gqa_flash_ms": gqa_flash_ms,
           "window_flash_ms": window_flash_ms,
           "gqa_flash_roofline": gqa_flash_roofline,
           "sparse_ffn_ms": sparse_ffn_ms,
           "sparse_experts_ms": sparse_experts_ms,
           "sparse_experts_roofline": sparse_experts_roofline,
           "sparse_held_rows": sparse_held_rows,
           "sparse_route_ms": sparse_route_ms,
           "sparse_shared_ms": sparse_shared_ms,
           "sparse_lm_head_ms": sparse_lm_head_ms}
