"""Which host phase the chip was waiting under: the device trace joined with
the train loop's own per-step phases.

The benchmark traces with the profiler's host tracer off (it slowed a batch's
placement from 35 ms to half a second; ``harness.start_device_trace``), so
the trace holds no host span. The program keeps its own: every row of
``--metrics-file`` carries ``phases``, a list of ``[name, start_unix_ns,
dur_ns]`` stamped with ``time.time_ns()`` (``jimm_tpu/obs/goodput.py``). The
link between the two clocks is one number: the ``.xplane.pb`` holds a plane
``Task Environment`` whose stat ``profile_start_time`` is the Unix time, in
nanoseconds, that every event's ``start_ns`` counts from.

The join takes the device's idle intervals exactly as ``reduce.reduce_events``
does (device 0, operations on the "XLA Ops" line, from the first program start
to the last) and gives each stretch of them to the host phase that was open
at that time:

- ``input``:    ``next_batch`` or ``place``
- ``dispatch``: ``dispatch``
- ``launch``:   ``device_wait``, before that step's program started
- ``wakeup``:   ``device_wait``, after that step's program ended
- ``log``:      ``host_sync``
- ``unnamed``:  under no phase (the loop's glue), under a phase that has no
  part here (``checkpoint``), or inside a running program (the holes between
  its operations, which no host phase explains; logged apart as
  ``in_program_ms``)

The six add up to the idle time of the window. A step's program is found by
order: the last program in the trace is the last row's step, and so on
backwards. Whether that, and the shared zero, hold is checked on every join
(``clock_check``): each step's program must start after the step's
``dispatch`` began and end before its ``device_wait`` ended. Where it does
not, the split means nothing and none is given.
"""

from __future__ import annotations

import functools
import json
from pathlib import Path
from typing import Sequence

from benchmarks import harness
from benchmarks.trace import reduce
from benchmarks.trace.reduce import Event

PARTS = ("input", "dispatch", "launch", "wakeup", "log", "unnamed")
_PART_OF_PHASE = {"next_batch": "input", "place": "input",
                  "dispatch": "dispatch", "host_sync": "log"}
ENVIRONMENT_PLANE = "Task Environment"


def find_run_dir(first_row: dict) -> Path | None:
    """The run directory under ``harness.RUNS_DIR`` whose ``metrics.jsonl``
    begins with this row (its ``time`` and ``phases`` stamps name one run)."""
    for path in sorted(harness.RUNS_DIR.glob("*/metrics.jsonl")):
        with open(path) as f:
            line = f.readline()
        try:
            if line.strip() and json.loads(line) == first_row:
                return path.parent
        except json.JSONDecodeError:
            continue
    return None


def profile_start_unix_ns(xplane: Path) -> int | None:
    """``profile_start_time`` of the capture: the zero of its events."""
    import warnings

    import jax
    data = jax.profiler.ProfileData.from_file(str(xplane))
    plane = data.find_plane_with_name(ENVIRONMENT_PLANE)
    if plane is None:
        return None
    with warnings.catch_warnings():
        # iterating stats warns about a builtin type's __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        start = dict(plane.stats).get("profile_start_time")
    return None if start is None else int(start)


def host_spans(rows: Sequence[dict], start_unix_ns: int) -> list[dict]:
    """Every phase of every row on the trace's clock (microseconds), with
    the step it belongs to: a row holds its own step's phases after the
    previous step's ``host_sync`` and ``checkpoint``."""
    out = []
    for row in rows:
        own = False
        for name, start, dur in row.get("phases", ()):
            own = own or name in ("next_batch", "place", "dispatch")
            lo = (start - start_unix_ns) / 1e3
            out.append({"phase": name, "lo": lo, "hi": lo + dur / 1e3,
                        "step": row["step"] if own else row["step"] - 1})
    return out


def join(events: Sequence[Event], start_unix_ns: int,
         rows: Sequence[dict]) -> dict:
    """Idle time of device 0 per host phase, and the clock check."""
    planes = reduce.device_planes(events)
    if not planes:
        raise ValueError("the trace holds no device plane")
    ops = reduce.op_events(events, planes[0])
    if not ops:
        raise ValueError(f"no operation ran on {planes[0]} in the trace")
    mods = reduce.module_events(events, planes[0])
    if len(mods) >= 2:
        lo, hi, periods = mods[0].start_us, mods[-1].start_us, len(mods) - 1
    else:  # no module line (a CPU capture): as reduce_events
        lo = min(e.start_us for e in ops)
        hi = max(e.end_us for e in ops)
        periods = 1
    busy = reduce.clip(reduce.union((e.start_us, e.end_us) for e in ops),
                       lo, hi)
    idle = reduce.gaps(busy, lo, hi)

    spans = host_spans(rows, start_unix_ns)
    # a step's program, by order from the end
    steps = [r["step"] for r in rows if "phases" in r]
    program = dict(zip(reversed(steps), reversed(mods)))

    # the holes between a running program's operations are no host phase's
    running = reduce.union((m.start_us, m.end_us) for m in mods)
    between = reduce.subtract(idle, running)

    named_us = dict.fromkeys(PARTS[:-1], 0.0)

    def name(part: str, a: float, b: float) -> None:
        named_us[part] += reduce.total(reduce.clip(between, a, b))

    by_step: dict[int, dict] = {}
    for s in spans:
        by_step.setdefault(s["step"], {})[s["phase"]] = s
        if s["phase"] in _PART_OF_PHASE:
            name(_PART_OF_PHASE[s["phase"]], s["lo"], s["hi"])
        elif s["phase"] == "device_wait" and s["step"] in program:
            mod = program[s["step"]]
            name("launch", s["lo"], min(mod.start_us, s["hi"]))
            name("wakeup", max(mod.end_us, s["lo"]), s["hi"])
    total_us = reduce.total(idle)
    idle_ms = {part: us / 1e3 / periods for part, us in named_us.items()}
    idle_ms["unnamed"] = total_us / 1e3 / periods - sum(idle_ms.values())

    # the clock check: one line per step whose program the trace holds
    checked = []
    for step, mod in sorted(program.items()):
        mine = by_step.get(step, {})
        if "dispatch" not in mine or "device_wait" not in mine:
            continue
        checked.append({
            "step": step, "program_ms": mod.dur_us / 1e3,
            "program_start_after_dispatch_began_ms":
                (mod.start_us - mine["dispatch"]["lo"]) / 1e3,
            "program_start_after_dispatch_ended_ms":
                (mod.start_us - mine["dispatch"]["hi"]) / 1e3,
            "wakeup_ms": (mine["device_wait"]["hi"] - mod.end_us) / 1e3})
    wakeups = [c["wakeup_ms"] for c in checked]
    ordered = bool(checked) and all(
        c["program_start_after_dispatch_began_ms"] >= 0
        and c["wakeup_ms"] >= 0 for c in checked)
    return {
        "device_plane": planes[0], "periods": periods,
        "window_ms": (hi - lo) / 1e3, "idle_total_ms": total_us / 1e3,
        "profile_start_unix_ns": start_unix_ns,
        "idle_ms": idle_ms,
        "in_program_ms": (total_us - reduce.total(between)) / 1e3 / periods,
        "clock_check": {
            "ordered": ordered,
            "wakeup_spread_ms": (max(wakeups) - min(wakeups)
                                 if wakeups else None),
            "steps": checked},
    }


@functools.lru_cache(maxsize=4)
def join_run(run_dir: Path) -> dict | None:
    """The join of one run's capture (``<run_dir>/profile``) with its rows
    (``<run_dir>/metrics.jsonl``); None where either is missing, the capture
    names no ``profile_start_time`` or the rows carry no ``phases``."""
    xplane = reduce.find_profile_file(run_dir / "profile", ".xplane.pb")
    metrics = run_dir / "metrics.jsonl"
    if xplane is None or not metrics.is_file():
        return None
    rows = [json.loads(line) for line in metrics.read_text().splitlines()]
    if not any("phases" in r for r in rows):
        return None
    start = profile_start_unix_ns(xplane)
    if start is None:
        return None
    joined = join(reduce.load_events(xplane), start, rows)
    harness.log(event="host_join", run_dir=str(run_dir), **joined)
    return joined


def join_observed(o: dict) -> dict | None:
    """The join for what a train driver observed, found through its rows."""
    rows = o.get("rows")
    if not rows or "phases" not in rows[-1]:
        return None
    run_dir = find_run_dir(rows[0])
    return None if run_dir is None else join_run(run_dir)
