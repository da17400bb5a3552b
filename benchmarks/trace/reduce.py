"""From a profiler trace to numbers: the reduction every PR shares.

Input is what ``jax.profiler`` leaves under ``<dir>/plugins/profile/<time>/``:
the ``.xplane.pb`` (read with ``jax.profiler.ProfileData``, nothing but JAX)
or, for a recorded fixture, the Chrome ``.trace.json.gz``. Both become one
flat list of :class:`Event` on one clock (microseconds), and everything below
works on that list, so it can be checked on a hand-made trace.

What it computes, on ONE device (the first; under SPMD all run the same
program) unless said otherwise:

- the steady window: from the start of the first program execution ("XLA
  Modules" line) to the start of the last one, so it holds whole periods of
  (program + gap to the next) and no profiler start-up or tail;
- ``busy_s``: the union of the intervals in which an operation ran ("XLA
  Ops" line), clipped to the window, averaged over the device planes;
  ``idle_pct = 100 * (1 - busy / window)``;
- the longest idle gaps, under the one label the caller gives: the trace is
  taken with the host tracer off (``harness.start_device_trace``), so it
  holds no host span to name a gap by;
- device time per named scope (``fwd_bwd``, ``optimizer_update``): ops whose
  scope path holds that name. A TPU trace names an operation by its HLO
  instruction and carries no scope, so the caller passes ``hlo``, a map from
  instruction name to its ``op_name`` path read off the compiled program's
  text (``benchmarks/drivers/train_cli.py::hlo_index``);
- device time per kernel: Pallas kernels (``tpu_custom_call`` in ``hlo``)
  whose path holds the kernel's name;
- collectives: total duration of all-gather / all-reduce / reduce-scatter /
  collective-permute / all-to-all (an async pair counts from the start op to
  the end of its done op), and the part of it during which no other
  operation ran on that device ("exposed");
- the ten operations that took most time (copy of
  ``jimm_tpu/obs/prof/opstats.py::aggregate_ops``, by name).
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import re
import sys
from pathlib import Path
from typing import Iterable, Sequence

Interval = tuple[float, float]

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
#: container events that would double-count their children (opstats._NON_OP)
_NON_OP = re.compile(
    r"^(while(\.|$)|conditional(\.|$)|jit_|\d+$|SyncOnDone|.*Module)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_us: float
    dur_us: float
    args: dict

    @property
    def end_us(self) -> float:
        return self.start_us + self.dur_us


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------

def find_profile_file(source: str | Path, suffix: str) -> Path | None:
    source = Path(source)
    if source.is_file():
        return source if source.name.endswith(suffix) else None
    paths = sorted(glob.glob(str(source / "**" / f"*{suffix}"),
                             recursive=True))
    return Path(paths[-1]) if paths else None


def events_from_xplane(path: Path) -> list[Event]:
    import warnings

    import jax
    data = jax.profiler.ProfileData.from_file(str(path))
    out = []
    with warnings.catch_warnings():
        # iterating an event's stats warns about a builtin type's __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            for line in plane.lines:
                for e in line.events:
                    out.append(Event(plane.name, line.name, e.name,
                                     e.start_ns / 1e3, e.duration_ns / 1e3,
                                     dict(e.stats)))
    return out


def events_from_chrome(path: Path) -> list[Event]:
    opener = gzip.open if path.name.endswith(".gz") else open
    with opener(path, "rt") as f:
        raw = json.load(f)["traceEvents"]
    planes = {e["pid"]: e["args"].get("name", "") for e in raw
              if e.get("ph") == "M" and e.get("name") == "process_name"}
    lines = {(e["pid"], e["tid"]): e["args"].get("name", "") for e in raw
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    return [Event(planes.get(e["pid"], str(e["pid"])),
                  lines.get((e["pid"], e.get("tid")), ""), e["name"],
                  float(e["ts"]), float(e.get("dur", 0)), e.get("args", {}))
            for e in raw if e.get("ph") == "X"]


def load_events(source: str | Path) -> list[Event]:
    xplane = find_profile_file(source, ".xplane.pb")
    if xplane is not None:
        return events_from_xplane(xplane)
    chrome = find_profile_file(source, ".trace.json.gz")
    if chrome is None:
        raise FileNotFoundError(f"no .xplane.pb or .trace.json.gz under "
                                f"{source}")
    return events_from_chrome(chrome)


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> list[Interval]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> list[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals: Sequence[Interval], holes: Sequence[Interval]
             ) -> list[Interval]:
    """The parts of ``intervals`` (a union) that no hole (a union) covers."""
    out = []
    holes = list(holes)
    for a, b in intervals:
        cur = a
        for ha, hb in holes:
            if hb <= cur or ha >= b:
                continue
            if ha > cur:
                out.append((cur, ha))
            cur = max(cur, hb)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> list[Interval]:
    return subtract([(lo, hi)], busy)


# ---------------------------------------------------------------------------
# Picking events
# ---------------------------------------------------------------------------

def device_planes(events: Sequence[Event]) -> list[str]:
    """Accelerator planes; for a CPU capture (fixtures, rehearsals) the host
    plane that runs the ops."""
    names = sorted({e.plane for e in events})
    accel = [n for n in names if n.startswith("/device:")
             and "CUSTOM" not in n.upper()]
    tpu = [n for n in accel if re.match(r"^/device:(TPU|GPU):\d+", n)]
    return tpu or accel or [n for n in names if n.startswith("/host:CPU")]


def is_op(e: Event, have_op_lines: bool) -> bool:
    if have_op_lines:
        if e.line != OPS_LINE:  # not "Async XLA Ops": those overlap compute
            return False
    elif not e.line.startswith("tf_XLA"):
        return False  # CPU capture: ops run on the executor threads
    if e.dur_us <= 0 or _NON_OP.match(instruction(e.name)):
        return False
    return not e.name.startswith("ThreadpoolListener")


def op_events(events: Sequence[Event], plane: str) -> list[Event]:
    on_plane = [e for e in events if e.plane == plane]
    have = any(e.line == OPS_LINE for e in on_plane)
    return [e for e in on_plane if is_op(e, have)]


def module_events(events: Sequence[Event], plane: str) -> list[Event]:
    """Whole-program executions, in time order."""
    mods = [e for e in events if e.plane == plane and e.line == MODULES_LINE]
    return sorted(mods, key=lambda e: e.start_us)


def instruction(name: str) -> str:
    """``%fusion.105 = (f32[...]) fusion(...)`` -> ``fusion.105``: a TPU
    trace names an operation by its whole HLO instruction."""
    return name.lstrip("%").split(" ", 1)[0]


def scope_path(e: Event, hlo: dict | None = None) -> str:
    """The op's source path with its named scopes: from ``hlo`` by
    instruction name, else wherever the trace itself keeps it."""
    if hlo:
        found = hlo.get(instruction(e.name))
        if found is not None:
            return found["op_name"]
    for key in ("tf_op", "long_name"):
        v = e.args.get(key)
        if isinstance(v, str) and "/" in v:
            return v
    return ""


def collective_kind(name: str) -> str | None:
    base = instruction(name)
    for kind in COLLECTIVES:
        if base.startswith(kind):
            return kind
    return None


def collective_intervals(ops: Sequence[Event]) -> list[Interval]:
    """One interval per collective: a synchronous op's own span; for an async
    pair, from the start op's beginning to the end of its done op."""
    out: list[Interval] = []
    open_starts: dict[str, list[Event]] = {}
    for e in sorted(ops, key=lambda e: e.start_us):
        kind = collective_kind(e.name)
        if kind is None:
            continue
        m = re.match(r"^([a-z\-]+?)-(start|done)(.*)$", instruction(e.name))
        if m and m.group(2) == "start":
            open_starts.setdefault(m.group(1), []).append(e)
        elif m and m.group(2) == "done":
            pending = open_starts.get(m.group(1))
            if pending:
                out.append((pending.pop(0).start_us, e.end_us))
            else:
                out.append((e.start_us, e.end_us))
        else:
            out.append((e.start_us, e.end_us))
    for pending in open_starts.values():
        out.extend((e.start_us, e.end_us) for e in pending)
    return out


def aggregate_ops(ops: Sequence[Event]) -> list[dict]:
    """Total device time and count per op name, most expensive first."""
    agg: dict[str, list] = {}
    for e in ops:
        row = agg.setdefault(instruction(e.name),
                             [0.0, 0, e.args.get("hlo_category", "?")])
        row[0] += e.dur_us
        row[1] += 1
    rows = [{"name": k, "total_us": v[0], "count": v[1], "category": v[2]}
            for k, v in agg.items()]
    rows.sort(key=lambda r: -r["total_us"])
    return rows


# ---------------------------------------------------------------------------
# The reduction
# ---------------------------------------------------------------------------

def reduce_events(events: Sequence[Event], *, hlo: dict | None = None,
                  scopes: Sequence[str] = ("fwd_bwd", "optimizer_update"),
                  kernels: Sequence[str] = (),
                  gap_label: str = "unattributed",
                  top: int = 10, top_gaps: int = 5) -> dict:
    planes = device_planes(events)
    if not planes:
        raise ValueError("the trace holds no device plane")
    first = planes[0]
    mods = module_events(events, first)
    ops = op_events(events, first)
    if not ops:
        raise ValueError(f"no operation ran on {first} in the trace")
    if len(mods) >= 2:
        lo, hi, periods = mods[0].start_us, mods[-1].start_us, len(mods) - 1
    else:  # no module line (CPU capture): first op to last op, one period
        lo = min(e.start_us for e in ops)
        hi = max(e.end_us for e in ops)
        periods = 1
    window_us = hi - lo

    busy_by_plane = []
    for plane in planes:
        plane_ops = ops if plane == first else op_events(events, plane)
        busy_by_plane.append(total(clip(union(
            (e.start_us, e.end_us) for e in plane_ops), lo, hi)))
    busy_us = sum(busy_by_plane) / len(busy_by_plane)

    in_window = [e for e in ops if lo <= e.start_us < hi]
    busy = clip(union((e.start_us, e.end_us) for e in ops), lo, hi)

    out: dict = {
        "device_plane": first, "device_planes": len(planes),
        "periods": periods, "window_s": window_us / 1e6,
        "busy_s": busy_us / 1e6,
        "idle_pct": 100.0 * (1.0 - busy_us / window_us) if window_us else None,
        "ops_in_window": len(in_window),
    }

    # per named scope, per period
    paths = [scope_path(e, hlo) for e in in_window]
    out["scope_ms"] = {}
    for scope in scopes:
        pattern = re.compile(rf"(^|/){re.escape(scope)}(/|$)")
        dur = sum(e.dur_us for e, path in zip(in_window, paths)
                  if pattern.search(path))
        out["scope_ms"][scope] = dur / 1e3 / periods
    out["scoped_ops"] = sum(1 for path in paths if path)
    out["unscoped_ms"] = sum(e.dur_us for e, path in zip(in_window, paths)
                             if not path) / 1e3 / periods

    # per kernel, per period: Pallas custom calls whose path (or, in a trace
    # that names its kernels, whose name) holds the kernel's name
    out["kernel_ms"] = {}
    out["kernel_calls"] = {}
    for kernel in kernels:
        hits = []
        for e, path in zip(in_window, paths):
            entry = (hlo or {}).get(instruction(e.name))
            pallas = entry["pallas"] if entry else True
            if pallas and (kernel in path or kernel in instruction(e.name)):
                hits.append(e)
        out["kernel_ms"][kernel] = sum(e.dur_us for e in hits) / 1e3 / periods
        out["kernel_calls"][kernel] = len(hits) / periods

    # collectives
    coll = clip(union(collective_intervals(in_window)), lo, hi)
    compute = union((e.start_us, e.end_us) for e in in_window
                    if collective_kind(e.name) is None)
    out["collective_ms"] = total(coll) / 1e3 / periods
    out["collective_exposed_ms"] = total(subtract(coll, compute)) / 1e3 / periods
    out["collective_ops"] = sum(1 for e in in_window
                                if collective_kind(e.name) is not None) / periods

    # breakdown
    idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:top_gaps]
    out["longest_gaps_s"] = [(b - a) / 1e6 for a, b in idle]
    out["breakdown"] = {
        "device_ops": [[r["name"], r["total_us"] / 1e6]
                       for r in aggregate_ops(in_window)[:top]],
        "idle_gaps": [[gap_label, sum(out["longest_gaps_s"])]] if idle else [],
    }
    return out


def reduce_profile(source: str | Path, **kw) -> dict:
    return reduce_events(load_events(source), **kw)


def describe(source: str | Path, limit: int = 4) -> None:
    """Print what a trace holds: planes, lines, counts, a few events with
    their stats. For looking at a real trace by hand before trusting the
    reduction above."""
    events = load_events(source)
    seen: dict[tuple[str, str], list[Event]] = {}
    for e in events:
        seen.setdefault((e.plane, e.line), []).append(e)
    for (plane, line), evs in sorted(seen.items()):
        print(f"PLANE {plane!r} LINE {line!r}: {len(evs)} events, "
              f"{sum(e.dur_us for e in evs) / 1e3:.3f} ms")
        for e in sorted(evs, key=lambda e: -e.dur_us)[:limit]:
            print(f"    {e.name[:80]!r} start={e.start_us:.1f}us "
                  f"dur={e.dur_us:.1f}us args={ {k: str(v)[:120] for k, v in e.args.items()} }")


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[2] == "--describe":
        describe(sys.argv[1])
    else:
        print(json.dumps(reduce_profile(sys.argv[1]), indent=1))
