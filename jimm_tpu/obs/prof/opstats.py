"""Offline analysis of committed profiler captures — **no jax import**.

A committed capture (see :mod:`jimm_tpu.obs.prof.capture`) contains the
``*.trace.json.gz`` Chrome-trace file the jax profiler writes. This module
turns those into:

- a top-k per-op table (``op_table`` / ``top_ops``): self-time, occurrence
  count, bytes accessed, achieved HBM bandwidth — FlashAttention's
  IO-accounting argument turned into a runtime artifact;
- a **direction-aware diff** between two captures (``diff_ops``): op time
  is lower-better, so a positive delta is a regression and a negative one
  an improvement.

Everything here is stdlib-only so ``jimm-tpu obs prof ls/show/diff`` stays
usable on a machine (or in a CI lane) with no accelerator stack installed.
The parsing core is shared with :func:`jimm_tpu.train.profile.op_stats`,
which wraps these rows in its ``OpStat`` dataclass.
"""

from __future__ import annotations

import glob
import gzip
import json
import re
from pathlib import Path

__all__ = [
    "aggregate_ops", "diff_ops", "find_trace_file", "load_trace_events",
    "op_table", "render_diff", "render_table", "top_ops",
]

#: container/framework events that would double-count their children
_NON_OP = re.compile(r"^(while\.|jit_|\d+$|SyncOnDone|.*Module)")


def find_trace_file(source: str | Path) -> Path:
    """Newest ``*.trace.json.gz`` under ``source`` (a capture dir, a raw
    ``--profile-dir``, or the file itself)."""
    source = Path(source)
    if source.is_file():
        return source
    paths = sorted(glob.glob(str(source / "**" / "*.trace.json.gz"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.trace.json.gz under {source}")
    return Path(paths[-1])


def load_trace_events(source: str | Path) -> list[dict]:
    """The ``traceEvents`` list from the newest trace file under
    ``source`` (gzip or plain JSON)."""
    path = find_trace_file(source)
    opener = gzip.open if path.name.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)["traceEvents"]


def aggregate_ops(events: list[dict], *,
                  device: int | None = 0) -> list[dict]:
    """Aggregate device-op self times from raw trace events into rows
    ``{name, category, total_us, count, bytes_accessed, long_name}``,
    sorted by descending total time.

    ``device`` picks ONE device pid (default: the first) — under SPMD every
    core runs the same program, and summing across cores would report
    n_devices times the per-step time. ``None`` aggregates all devices."""
    pids = {e["pid"]: e["args"].get("name", "")
            for e in events if e.get("ph") == "M"
            and e.get("name") == "process_name"}
    tnames = {(e["pid"], e["tid"]): e["args"].get("name", "")
              for e in events if e.get("ph") == "M"
              and e.get("name") == "thread_name"}
    device_pids = {p for p, n in pids.items() if n.startswith("/device:")}
    if device_pids and device is not None:
        device_pids = {sorted(device_pids)[device]}
    if not device_pids:  # CPU-only capture: ops run inside the host process
        device_pids = set(pids)

    def is_op_lane(lane: str) -> bool:
        # TPU: per-core "XLA Ops" lanes; CPU: tf_XLAEigen/... executor
        # threads. Everything else (python host frames, "Steps", module
        # lanes) would double-count or pollute the aggregation.
        return "XLA Ops" in lane or lane.startswith("tf_XLA")

    have_op_lanes = any(is_op_lane(n) for n in tnames.values())

    agg: dict[str, list] = {}
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        lane = tnames.get((e["pid"], e["tid"]), "")
        if have_op_lanes:
            if not is_op_lane(lane):
                continue
        elif lane == "python":
            continue
        if _NON_OP.match(e["name"]):
            continue
        a = e.get("args", {})
        r = agg.setdefault(e["name"], [0.0, 0, 0, "",
                                       a.get("hlo_category", "?")])
        r[0] += e.get("dur", 0)
        r[1] += 1
        r[2] += int(a.get("bytes_accessed", 0) or 0)
        r[3] = r[3] or a.get("long_name", "")

    rows = [{"name": k, "category": v[4], "total_us": v[0], "count": v[1],
             "bytes_accessed": v[2], "long_name": v[3]}
            for k, v in agg.items()]
    rows.sort(key=lambda r: -r["total_us"])
    return rows


def op_table(source: str | Path, *, device: int | None = 0) -> list[dict]:
    """``aggregate_ops`` over the newest trace file under ``source``."""
    return aggregate_ops(load_trace_events(source), device=device)


def top_ops(rows: list[dict], k: int = 20,
            by: str = "total_us") -> list[dict]:
    return sorted(rows, key=lambda r: -r.get(by, 0))[:k]


def _gbps(row: dict) -> float:
    if not row["total_us"]:
        return 0.0
    return row["bytes_accessed"] / (row["total_us"] * 1e-6) / 1e9


def render_table(rows: list[dict], *, top: int = 20) -> str:
    """Human-readable top-k table (us, n, MB total, GB/s)."""
    total = sum(r["total_us"] for r in rows)
    lines = [f"device op time: {total / 1e3:.2f} ms over {len(rows)} ops",
             f"{'us':>10} {'n':>5} {'MB':>9} {'GB/s':>7}  name"]
    for r in rows[:top]:
        lines.append(f"{r['total_us']:10.1f} {r['count']:5d} "
                     f"{r['bytes_accessed'] / 1e6:9.2f} {_gbps(r):7.1f}  "
                     f"{r['name'][:60]}")
    return "\n".join(lines)


def diff_ops(before: list[dict], after: list[dict], *,
             threshold: float = 0.10, top: int = 20,
             min_us: float = 1.0) -> dict:
    """Direction-aware per-op diff between two op tables.

    Op time is lower-better: an op whose ``total_us`` grew by more than
    ``threshold`` (fractionally) is a *regression*, one that shrank is an
    *improvement*. Ops below ``min_us`` in both tables are noise and
    skipped. The overall ``verdict`` is ``"regression"`` when total
    device-op time grew past the threshold, else ``"ok"``."""
    b = {r["name"]: r for r in before}
    a = {r["name"]: r for r in after}
    regressions, improvements, added, removed = [], [], [], []
    for name in sorted(set(b) | set(a)):
        bu = b.get(name, {}).get("total_us", 0.0)
        au = a.get(name, {}).get("total_us", 0.0)
        if bu < min_us and au < min_us:
            continue
        if name not in b:
            added.append({"name": name, "after_us": au})
            continue
        if name not in a:
            removed.append({"name": name, "before_us": bu})
            continue
        delta = au - bu
        frac = delta / bu if bu else 0.0
        entry = {"name": name, "before_us": round(bu, 1),
                 "after_us": round(au, 1), "delta_us": round(delta, 1),
                 "delta_frac": round(frac, 4)}
        if frac > threshold:
            regressions.append(entry)
        elif frac < -threshold:
            improvements.append(entry)
    regressions.sort(key=lambda e: -e["delta_us"])
    improvements.sort(key=lambda e: e["delta_us"])
    total_b = sum(r["total_us"] for r in before)
    total_a = sum(r["total_us"] for r in after)
    total_frac = (total_a - total_b) / total_b if total_b else 0.0
    return {
        "total_before_us": round(total_b, 1),
        "total_after_us": round(total_a, 1),
        "total_delta_frac": round(total_frac, 4),
        "threshold": threshold,
        "regressions": regressions[:top],
        "improvements": improvements[:top],
        "added": added[:top],
        "removed": removed[:top],
        "verdict": "regression" if total_frac > threshold else "ok",
    }


def render_diff(d: dict) -> str:
    lines = [f"total device-op time: {d['total_before_us'] / 1e3:.2f} ms -> "
             f"{d['total_after_us'] / 1e3:.2f} ms "
             f"({d['total_delta_frac']:+.1%}) [{d['verdict']}]"]
    for label, mark in (("regressions", "REGRESSION"),
                        ("improvements", "+"),):
        for e in d[label]:
            lines.append(f"{mark} {e['name'][:56]}: {e['before_us']}us -> "
                         f"{e['after_us']}us ({e['delta_frac']:+.1%})")
    for e in d["added"]:
        lines.append(f"? new op {e['name'][:56]} ({e['after_us']}us)")
    for e in d["removed"]:
        lines.append(f"? gone op {e['name'][:56]} ({e['before_us']}us)")
    return "\n".join(lines)
