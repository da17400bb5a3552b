"""Per-layer metrics of a dense hybrid language model's train cell (Mamba-2
state-space layers beside grouped-query attention ones, a SwiGLU in every
layer), read from the profiler trace (``benchmarks/trace/reduce.py``) and the
program's registry through what ``benchmarks/drivers/train_hybrid_lm.py``
observed. Off the TPU, and for a program that has no such scope or counter
(the parent of the PR that brought them), every reader returns nothing.

The scopes ``ssm``, ``ssm_proj``, ``ssm_scan``, ``ssm_out`` are plain path
components of an operation's ``op_name`` whichever way it is run (forward,
backward, a remat policy's second forward), so one name finds them all. The
attention layer (``attn``) and the head (``lm_head``) are given to the trace's
reduction, so their times are in the run's ``trace`` event, and are read in
``fwd_bwd_ms`` and the breakdown, under no name of their own here.
"""

from __future__ import annotations

from benchmarks import flops_granite

#: what ``reduce_profile(scopes=...)`` has to be told
INNER_SCOPES = ("ssm", "ssm_proj", "ssm_scan", "ssm_out", "attn")
OUTER_SCOPES = ("embed", "decoder_stack", "lm_head")


def scope_names(scope: str) -> tuple[str, ...]:
    if scope in INNER_SCOPES:
        return (scope,)
    return f"jvp({scope})", f"transpose(jvp({scope}))"


def observe(run, result) -> dict:
    """What this module's readers need beside the driver's own observations:
    the cell's length and the registry's count of the scans built."""
    from jimm_tpu import obs
    snapshot = obs.snapshot()
    return {"granite_shape": {"seq_len": run.cell["traffic_params"]["seq_len"]},
            "ssm_counters": {k: snapshot[k] for k in (
                "jimm_ssm_calls_total", "jimm_ssm_chunks_total")
                if k in snapshot}}


def _ssm_scope_ms(o: dict, scope: str) -> float | None:
    if o.get("platform") != "tpu" or "granite_shape" not in o:
        return None
    t = o.get("trace")
    if t is None or not t.get("scoped_ops"):
        return None
    return t["scope_ms"].get(scope) or None


def ssm_ms(o: dict) -> float | None:
    """Device time per step of the Mamba-2 mixer in every Mamba-2 layer."""
    return _ssm_scope_ms(o, "ssm")


def ssm_proj_ms(o: dict) -> float | None:
    """``W_in``, the convolution, its SiLU, the softplus on ``dt``."""
    return _ssm_scope_ms(o, "ssm_proj")


def ssm_scan_ms(o: dict) -> float | None:
    """The chunked selective scan alone: forward, backward, recompute."""
    return _ssm_scope_ms(o, "ssm_scan")


def ssm_out_ms(o: dict) -> float | None:
    """The ``D`` term, the gated norm and ``W_out``."""
    return _ssm_scope_ms(o, "ssm_out")


def ssm_scan_roofline(o: dict) -> float | None:
    """Least time of one forward and one backward of the recurrence a
    Mamba-2 layer (``flops_granite.ssm_scan_least_seconds``: what any
    implementation must compute and move) over ``ssm_scan_ms``."""
    took = ssm_scan_ms(o)
    if took is None:
        return None
    least = flops_granite.ssm_scan_least_seconds(
        o["config"], o["global_batch"], o["granite_shape"]["seq_len"],
        o["device_kind"])
    return 100.0 * least * 1e3 / took


def ssm_scan_steps(o: dict) -> float | None:
    """Chunk steps in sequence that one forward pass of a training step
    walks: the chunks of one built scan (``jimm_ssm_chunks_total`` over
    ``jimm_ssm_calls_total``) times the Mamba-2 layers held. The backward
    walks as many again."""
    counters = o.get("ssm_counters") or {}
    calls = counters.get("jimm_ssm_calls_total")
    if not calls or "granite_shape" not in o:
        return None
    layers = flops_granite.layer_mixers(o["config"]).count("mamba")
    return counters["jimm_ssm_chunks_total"] / calls * layers


READERS = {"ssm_ms": ssm_ms, "ssm_proj_ms": ssm_proj_ms,
           "ssm_scan_ms": ssm_scan_ms, "ssm_out_ms": ssm_out_ms,
           "ssm_scan_roofline": ssm_scan_roofline,
           "ssm_scan_steps": ssm_scan_steps}
