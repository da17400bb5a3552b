"""Train cells, host clock: the program's own rows and goodput buckets.

A reader returns nothing where the driver observed none of what it reads
(another driver's cell), and the harness leaves the metric out.
"""

from __future__ import annotations

import statistics

from benchmarks import flops


def step_ms(o: dict) -> float | None:
    """Median ``step_time_s`` of the window's rows (StepTimer: host clock
    around the jitted step, ended by block_until_ready)."""
    if "window_rows" not in o:
        return None
    return 1e3 * statistics.median(r["step_time_s"] for r in o["window_rows"])


def _bucket_ms(o: dict, bucket: str) -> float | None:
    """A goodput bucket per step. The program keeps one total per bucket for
    the whole loop, so this is the mean over all of the loop's steps, the
    warm-up ones and the traced tail included, not the window's alone."""
    if f"{bucket}_s" not in o.get("goodput", {}):
        return None
    return 1e3 * o["goodput"][f"{bucket}_s"] / o["steps_total"]


def data_wait_ms(o: dict) -> float | None:
    """``next(data)`` in the CLI loop. The cells are fed from a pool made in
    set-up (``benchmarks/traffic.py``), so this holds the CLI's placement of
    a ready batch (dispatching the host-to-device copy) and no generation."""
    return _bucket_ms(o, "data_wait")


def host_sync_ms(o: dict) -> float | None:
    return _bucket_ms(o, "host_sync")


def mfu_pct(o: dict) -> float | None:
    """Model FLOPs (no recompute) x steps / window / (chips x published
    peak). Exists only against a device that peaks.json knows."""
    if "flops_per_step" not in o or o["platform"] != "tpu":
        return None
    peak = flops.peaks(o["device_kind"])["bf16_tflops"] * 1e12
    done = o["flops_per_step"] * len(o["window_rows"])
    return 100.0 * done / o["window_s"] / (o["chips"] * peak)


READERS = {"step_ms": step_ms, "data_wait_ms": data_wait_ms,
           "host_sync_ms": host_sync_ms, "mfu_pct": mfu_pct}
