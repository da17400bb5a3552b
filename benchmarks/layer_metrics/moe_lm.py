"""Per-layer metrics of a sparse language model's train cell (latent
attention, a mixture of experts), read from the profiler trace
(``benchmarks/trace/reduce.py``) and the step rows through what
``benchmarks/drivers/train_moe_lm.py`` observed. Off the TPU, and for a
program that has no such scope, kernel or counter, every reader returns
nothing.

The program's inner scopes (``mla``, ``moe``, ``moe_route``, ``moe_experts``)
are path components of an operation's ``op_name`` whichever way it is run
(forward, backward, a remat policy's second forward), so one name finds all
three; the outer ones sit inside the differentiated function and are named
``jvp(<scope>)`` and ``transpose(jvp(<scope>))``, summed here.
"""

from __future__ import annotations

import statistics

from benchmarks import flops_moe_lm

#: what ``reduce_profile(scopes=...)`` has to be told
INNER_SCOPES = ("mla", "moe", "moe_route", "moe_experts", "moe_shared")
OUTER_SCOPES = ("embed", "decoder_stack", "lm_head")


def scope_names(scope: str) -> tuple[str, ...]:
    if scope in INNER_SCOPES:
        return (scope,)
    return f"jvp({scope})", f"transpose(jvp({scope}))"


def _trace(o: dict) -> dict | None:
    if o.get("platform") != "tpu" or "moe_lm_shape" not in o:
        return None
    return o.get("trace")


def _scope_ms(o: dict, scope: str) -> float | None:
    t = _trace(o)
    if t is None or not t.get("scoped_ops"):
        return None
    found = sum(t["scope_ms"].get(name, 0.0) for name in scope_names(scope))
    return found or None


def mla_ms(o: dict) -> float | None:
    """Device time per step of latent attention in every layer: projections,
    latent norm, rotary, the attention kernels, output projection."""
    return _scope_ms(o, "mla")


def moe_ms(o: dict) -> float | None:
    """Device time per step of the sparse layers' expert layer: routing,
    grouped products, shared experts."""
    return _scope_ms(o, "moe")


def moe_route_ms(o: dict) -> float | None:
    """Router matmul, sigmoid, top-k, sort, gather and weighted scatter."""
    return _scope_ms(o, "moe_route")


def moe_experts_ms(o: dict) -> float | None:
    """The grouped products over the held experts."""
    return _scope_ms(o, "moe_experts")


def lm_head_ms(o: dict) -> float | None:
    """Final norm, head and loss, forward and backward."""
    return _scope_ms(o, "lm_head")


def mla_flash_ms(o: dict) -> float | None:
    """Device time per step of the causal flash-attention kernels."""
    t = _trace(o)
    if t is None or not o.get("flash_calls"):
        return None
    found = sum(t["kernel_ms"].get(k, 0.0) for k in o.get("flash_kernels", ()))
    return found or None


def mla_flash_roofline(o: dict) -> float | None:
    """Least time of one forward and one backward causal call a layer at the
    unpadded widths (``flops_moe_lm.mla_flash_least_seconds``) over the time
    the kernels took."""
    took = mla_flash_ms(o)
    if took is None:
        return None
    least = flops_moe_lm.mla_flash_least_seconds(
        o["config"], o["global_batch"], o["moe_lm_shape"]["seq_len"],
        o["device_kind"])
    return 100.0 * least * 1e3 / took


def moe_held_rows(o: dict) -> float | None:
    """Assignments to held experts a step, summed over the sparse layers:
    the window's mean of the step rows' ``moe_held_rows``."""
    rows = [r["moe_held_rows"] for r in o.get("window_rows", ())
            if "moe_held_rows" in r]
    return statistics.fmean(rows) if rows else None


def moe_experts_roofline(o: dict) -> float | None:
    """Least time of the three grouped products, forward and backward, at the
    step's mean ``moe_held_rows`` over ``moe_experts_ms``."""
    took, rows = moe_experts_ms(o), moe_held_rows(o)
    if took is None or rows is None:
        return None
    least = flops_moe_lm.grouped_products_least_seconds(
        rows, o["config"], o["device_kind"])
    return 100.0 * least * 1e3 / took


READERS = {"mla_ms": mla_ms, "moe_ms": moe_ms, "moe_route_ms": moe_route_ms,
           "moe_experts_ms": moe_experts_ms, "lm_head_ms": lm_head_ms,
           "mla_flash_ms": mla_flash_ms,
           "mla_flash_roofline": mla_flash_roofline,
           "moe_experts_roofline": moe_experts_roofline,
           "moe_held_rows": moe_held_rows}
