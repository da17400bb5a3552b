"""Per-layer metrics of a hybrid language model's train cell (linear-attention
layers with a chunked delta-rule scan beside latent-attention ones, a mixture
of experts behind both), read from the profiler trace
(``benchmarks/trace/reduce.py``), the step rows and the program's registry
through what ``benchmarks/drivers/train_hybrid_lm.py`` observed. Off the TPU,
and for a program that has no such scope, kernel or counter (the parent of the
PR that brought them), every reader returns nothing.

The scopes ``kda``, ``kda_proj``, ``kda_scan``, ``kda_out`` are plain path
components of an operation's ``op_name`` whichever way it is run (forward,
backward, a remat policy's second forward, a checkpointed slab's rebuild), so
one name finds them all. The mechanisms this model shares with the sparse
decoder (latent attention and its flash kernels, the expert layer, the head)
are read by ``moe_lm.py``'s functions, imported, under names of this cell: a
metric has one reader and the accepted entries list only the other cell
(PERF.md section 7 (i), (ii)).
"""

from __future__ import annotations

from benchmarks import flops_hybrid_lm
from benchmarks.layer_metrics import moe_lm

#: what ``reduce_profile(scopes=...)`` has to be told
INNER_SCOPES = ("kda", "kda_proj", "kda_scan", "kda_out", *moe_lm.INNER_SCOPES)
OUTER_SCOPES = moe_lm.OUTER_SCOPES


def scope_names(scope: str) -> tuple[str, ...]:
    if scope in INNER_SCOPES:
        return (scope,)
    return moe_lm.scope_names(scope)


def observe(run, result) -> dict:
    """What this module's readers need beside the driver's own observations:
    the key ``moe_lm.py``'s readers look for, and the registry's count of the
    scans built."""
    from jimm_tpu import obs
    snapshot = obs.snapshot()
    return {"moe_lm_shape": {"seq_len": run.cell["traffic_params"]["seq_len"]},
            "kda_counters": {k: snapshot[k] for k in (
                "jimm_kda_calls_total", "jimm_kda_chunks_total")
                if k in snapshot}}


def _kda_scope_ms(o: dict, scope: str) -> float | None:
    t = moe_lm._trace(o)
    if t is None or not t.get("scoped_ops"):
        return None
    return t["scope_ms"].get(scope) or None


def kda_ms(o: dict) -> float | None:
    """Device time per step of Kimi Delta Attention in every KDA layer."""
    return _kda_scope_ms(o, "kda")


def kda_proj_ms(o: dict) -> float | None:
    """Projections, convolutions, the norms on q and k, both gates."""
    return _kda_scope_ms(o, "kda_proj")


def kda_scan_ms(o: dict) -> float | None:
    """The chunked delta-rule scan alone: forward, backward, recompute."""
    return _kda_scope_ms(o, "kda_scan")


def kda_out_ms(o: dict) -> float | None:
    """The gated per-head norm and the output projection."""
    return _kda_scope_ms(o, "kda_out")


def kda_scan_roofline(o: dict) -> float | None:
    """Least time of one forward and one backward of the recurrence a KDA
    layer (``flops_hybrid_lm.kda_scan_least_seconds``: what any
    implementation must compute and move) over ``kda_scan_ms``."""
    took = kda_scan_ms(o)
    if took is None:
        return None
    least = flops_hybrid_lm.kda_scan_least_seconds(
        o["config"], o["global_batch"], o["moe_lm_shape"]["seq_len"],
        o["device_kind"])
    return 100.0 * least * 1e3 / took


def kda_scan_steps(o: dict) -> float | None:
    """Chunk steps in sequence that one forward pass of a training step
    walks: the chunks of one built scan (``jimm_kda_chunks_total`` over
    ``jimm_kda_calls_total``) times the KDA layers held. The backward and
    each recompute walk as many again."""
    counters = o.get("kda_counters") or {}
    calls = counters.get("jimm_kda_calls_total")
    if not calls or "moe_lm_shape" not in o:
        return None
    layers = flops_hybrid_lm.layer_mixers(o["config"]).count("kda")
    return counters["jimm_kda_chunks_total"] / calls * layers


def hybrid_mla_flash_roofline(o: dict) -> float | None:
    """Least time of one forward and one backward causal call a
    latent-attention layer over the time the kernels took."""
    took = moe_lm.mla_flash_ms(o)
    if took is None:
        return None
    least = flops_hybrid_lm.mla_flash_least_seconds(
        o["config"], o["global_batch"], o["moe_lm_shape"]["seq_len"],
        o["device_kind"])
    return 100.0 * least * 1e3 / took


def hybrid_shared_ms(o: dict) -> float | None:
    """The shared expert, which every token passes."""
    return moe_lm._scope_ms(o, "moe_shared")


def hybrid_experts_roofline(o: dict) -> float | None:
    """``moe_lm.moe_experts_roofline`` (least time of the three grouped
    products, forward and backward, at the step's mean held rows over the time
    they took) under this configuration's key for the experts held."""
    config = {**o.get("config", {})}
    config["n_routed_experts"] = config.get("num_experts")
    return moe_lm.moe_experts_roofline({**o, "config": config})


READERS = {"kda_ms": kda_ms, "kda_proj_ms": kda_proj_ms,
           "kda_scan_ms": kda_scan_ms, "kda_scan_roofline": kda_scan_roofline,
           "kda_scan_steps": kda_scan_steps,
           "hybrid_mla_ms": moe_lm.mla_ms,
           "hybrid_mla_flash_ms": moe_lm.mla_flash_ms,
           "hybrid_mla_flash_roofline": hybrid_mla_flash_roofline,
           "hybrid_moe_ms": moe_lm.moe_ms,
           "hybrid_experts_ms": moe_lm.moe_experts_ms,
           "hybrid_lm_head_ms": moe_lm.lm_head_ms,
           "hybrid_held_rows": moe_lm.moe_held_rows,
           "kda_out_ms": kda_out_ms,
           "hybrid_route_ms": moe_lm.moe_route_ms,
           "hybrid_shared_ms": hybrid_shared_ms,
           "hybrid_experts_roofline": hybrid_experts_roofline}
