"""Train cells: the loop's own phases, step by step.

``jimm_tpu.cli train`` times each region of its loop under a phase name
(``jimm_tpu/obs/goodput.py``: ``next_batch``, ``place``, ``dispatch``,
``device_wait``, ``host_sync``) and writes them into the step's row of
``--metrics-file`` as ``phases``: ``[name, start_unix_ns, dur_ns]`` of
everything measured since the row before. So a row holds its own step's
first four phases and the ``host_sync`` of the step before it.

- The ``*_ms`` readers of a phase give the median over the window's rows
  (the driver's ``window_rows``: no warm-up step, no traced tail) of the time
  a step spent in that phase. ``loop_other_ms`` is what is left of each
  step's period (its row's ``time`` less the row's before) after its five
  phases, then the median: the loop's unnamed glue.
- The ``idle_*_ms`` readers give the device's idle time per step while the
  host was in that phase, from the traced tail: the device trace joined with
  the rows' ``phases`` (``benchmarks/trace/host_join.py``). The six add up to
  ``device_idle_pct`` x the traced period. Off the TPU they give nothing,
  like every reader of a device time.

A reader returns None where the rows carry no ``phases`` (an earlier
program), and the harness leaves the metric out.
"""

from __future__ import annotations

import functools
import statistics

from benchmarks.trace import host_join

PHASES = ("next_batch", "place", "dispatch", "device_wait", "host_sync")


def _in_phase_ms(row: dict, *phases: str) -> float:
    return sum(dur for name, _, dur in row["phases"] if name in phases) / 1e6


def _window(o: dict) -> list[dict] | None:
    rows = o.get("window_rows")
    if not rows or not all(r.get("phases") for r in rows):
        return None
    return rows


def _phase_ms(o: dict, phase: str) -> float | None:
    rows = _window(o)
    if rows is None or not any(name == phase for r in rows
                               for name, _, _ in r["phases"]):
        return None  # e.g. no "place" under a mesh: the prefetch thread places
    return statistics.median(_in_phase_ms(r, phase) for r in rows)


def loop_other_ms(o: dict) -> float | None:
    rows = _window(o)
    if rows is None:
        return None
    stamp = {r["step"]: r["time"] for r in o["rows"]}
    return statistics.median(
        1e3 * (r["time"] - stamp[r["step"] - 1]) - _in_phase_ms(r, *PHASES)
        for r in rows)


def _idle_ms(o: dict, part: str) -> float | None:
    if o.get("platform") != "tpu":
        return None
    joined = host_join.join_observed(o)
    if joined is None or not joined["clock_check"]["ordered"]:
        return None
    return joined["idle_ms"][part]


READERS = {
    "next_batch_ms": functools.partial(_phase_ms, phase="next_batch"),
    "place_ms": functools.partial(_phase_ms, phase="place"),
    "dispatch_ms": functools.partial(_phase_ms, phase="dispatch"),
    "device_wait_ms": functools.partial(_phase_ms, phase="device_wait"),
    "log_ms": functools.partial(_phase_ms, phase="host_sync"),
    "loop_other_ms": loop_other_ms,
    **{f"idle_{part}_ms": functools.partial(_idle_ms, part=part)
       for part in host_join.PARTS},
}
