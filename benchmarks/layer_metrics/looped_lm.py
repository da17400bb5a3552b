"""Per-layer metrics of a looped language model's train cell, read from the
profiler trace (``benchmarks/trace/reduce.py``) through what
``benchmarks/drivers/train_lm.py`` observed. Off the TPU, and for a program
that has no such scope or kernel, every reader returns nothing.

The program's scopes sit inside the differentiated function, so the compiled
step names their operations ``jvp(<scope>)`` (forward) and
``transpose(jvp(<scope>))`` (backward): a scope's time is the two summed.
"""

from __future__ import annotations

from benchmarks import flops_lm

SCOPES = ("embed", "loop_stack", "exit_head")


def scope_names(scope: str) -> tuple[str, str]:
    """What ``reduce_profile(scopes=...)`` has to be told for ``scope``."""
    return f"jvp({scope})", f"transpose(jvp({scope}))"


def _trace(o: dict) -> dict | None:
    if o.get("platform") != "tpu":
        return None
    return o.get("trace")


def _scope_ms(o: dict, scope: str) -> float | None:
    t = _trace(o)
    if t is None or not t.get("scoped_ops"):
        return None
    found = sum(t["scope_ms"].get(name, 0.0) for name in scope_names(scope))
    return found or None


def loop_stack_ms(o: dict) -> float | None:
    """Device time per step of the R x n block applications, forward and
    backward (and the norm that closes each pass)."""
    return _scope_ms(o, "loop_stack")


def exit_head_ms(o: dict) -> float | None:
    """Device time per step of the gate, the head and the loss of the R
    passes, forward and backward."""
    return _scope_ms(o, "exit_head")


def causal_flash_ms(o: dict) -> float | None:
    """Device time per step of the causal flash-attention kernels."""
    t = _trace(o)
    if t is None or not o.get("flash_calls") or "lm_shape" not in o:
        return None
    found = sum(t["kernel_ms"].get(k, 0.0) for k in o.get("flash_kernels", ()))
    return found or None


def causal_flash_roofline(o: dict) -> float | None:
    """Least time the chip could take for the step's causal attention (one
    forward and one backward call per block application, FLOPs at half of
    S^2; ``flops_lm.causal_flash_least_seconds``) over the time the kernels
    took. A remat policy that runs the forward kernel again lowers it."""
    took = causal_flash_ms(o)
    if took is None:
        return None
    least = flops_lm.causal_flash_least_seconds(
        o["config"], o["global_batch"], o["lm_shape"]["seq_len"],
        o["device_kind"])
    return 100.0 * least * 1e3 / took


READERS = {"loop_stack_ms": loop_stack_ms, "exit_head_ms": exit_head_ms,
           "causal_flash_ms": causal_flash_ms,
           "causal_flash_roofline": causal_flash_roofline}
