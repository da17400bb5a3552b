"""Per-layer metrics read from the profiler trace (benchmarks/trace/reduce.py).

A device time exists only where a TPU ran: on any other platform these
readers return nothing, so a rehearsal never prints a CPU number under a
device metric's name.
"""

from __future__ import annotations

from benchmarks import flops


def _trace(o: dict) -> dict | None:
    if o.get("platform") != "tpu":
        return None
    return o.get("trace")


def device_idle_pct(o: dict) -> float | None:
    """100 x (1 - union of device-op intervals / window)."""
    t = _trace(o)
    return None if t is None else t["idle_pct"]


def _scope(o: dict, scope: str) -> float | None:
    t = _trace(o)
    if t is None or not t.get("scoped_ops"):
        return None
    return t["scope_ms"].get(scope)


def fwd_bwd_ms(o: dict) -> float | None:
    return _scope(o, "fwd_bwd")


def optimizer_ms(o: dict) -> float | None:
    return _scope(o, "optimizer_update")


def collective_ms(o: dict) -> float | None:
    t = _trace(o)
    if t is None or not t["collective_ops"]:
        return None
    return t["collective_ms"]


def collective_exposed_ms(o: dict) -> float | None:
    t = _trace(o)
    if t is None or not t["collective_ops"]:
        return None
    return t["collective_exposed_ms"]


def flash_ms(o: dict) -> float | None:
    """Device time per step of the flash-attention kernels."""
    t = _trace(o)
    if t is None or not o.get("flash_calls"):
        return None
    found = sum(t["kernel_ms"].get(k, 0.0) for k in o.get("flash_kernels", ()))
    return found or None


def flash_roofline(o: dict) -> float | None:
    """Least time the chip could take for the step's attention calls (the
    larger of FLOPs / peak FLOP/s and bytes / peak bytes/s, from shapes) over
    the time the kernels took."""
    took = flash_ms(o)
    if took is None:
        return None
    v = flops.vision_tower(o["config"])
    heads = v["num_attention_heads"]
    shape = dict(batch=o["global_batch"], seq=v["seq_len"], heads=heads,
                 head_dim=v["hidden_size"] // heads)
    least = 0.0
    for backward in (False, True):
        cost = flops.flash_attention_cost(**shape, backward=backward)
        seconds, _ = flops.roofline_least_seconds(
            cost["flops"], cost["bytes"], o["device_kind"])
        least += seconds * v["num_hidden_layers"]
    return 100.0 * least * 1e3 / took


READERS = {"device_idle_pct": device_idle_pct, "fwd_bwd_ms": fwd_bwd_ms,
           "optimizer_ms": optimizer_ms, "collective_ms": collective_ms,
           "collective_exposed_ms": collective_exposed_ms,
           "flash_ms": flash_ms, "flash_roofline": flash_roofline}
