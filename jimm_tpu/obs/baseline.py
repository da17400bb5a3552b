"""Adopted performance baselines and the regression gate behind
``jimm-tpu obs regress``.

MEASUREMENTS.jsonl is an append-only trajectory: every bench/smoke run adds
rows, including **fallback** rows that a smoke recorded on the CPU. The
baseline store makes the trajectory gate-able:

- :func:`is_fallback` is the single source of truth for "this row is not a
  real measurement" (the ``fallback: true`` stamp, plus the legacy
  ``"(cpu smoke)"`` metric-name convention).
- :class:`BaselineStore` holds one adopted reference value per
  ``(workload, backend, preset, metric)`` key in a small JSON file
  (``BASELINES.json``), written only by an explicit ``adopt``.
- :func:`check_rows` compares fresh rows against the store with
  direction-aware thresholds (throughput-like metrics must not drop,
  latency-like metrics must not rise) and **excludes fallback rows from
  comparison** while still reporting them — so a CPU fallback can fail CI
  by policy (``--fail-on-fallback``) instead of polluting the baselines.
"""

from __future__ import annotations

import json
from pathlib import Path

__all__ = ["BaselineStore", "check_rows", "comparable_metrics",
           "is_fallback", "row_key", "summarize", "DEFAULT_THRESHOLD"]

DEFAULT_THRESHOLD = 0.20

# metric -> +1 (higher is better) / -1 (lower is better)
METRIC_DIRECTIONS = {
    "images_per_sec": +1,
    "img_per_sec": +1,
    "qps": +1,
    "mfu": +1,
    "goodput": +1,
    "recall": +1,
    # ann frontier: measured recall vs the exact oracle — a ≥20% recall
    # drop gates exactly like a ≥20% throughput drop
    "recall_at_10": +1,
    "value": +1,
    # tiered retrieval: device-resident footprint of the serving index —
    # a growth past the budget (arena leak, plan regression) gates like
    # a latency regression
    "resident_bytes": -1,
    "step_time_ms": -1,
    "latency_ms": -1,
    "latency_p50_ms": -1,
    "latency_p99_ms": -1,
}


def is_fallback(rec: dict) -> bool:
    """True when the row is a stand-in measurement, not the real backend:
    the explicit ``fallback`` stamp, or the legacy ``"(cpu smoke)"``
    metric-name convention from early bench rounds."""
    if rec.get("fallback"):
        return True
    metric = rec.get("metric")
    return isinstance(metric, str) and "(cpu smoke)" in metric


def _preset_of(rec: dict) -> str:
    for key in ("preset", "model", "case", "variant"):
        v = rec.get(key)
        if isinstance(v, dict):
            v = ",".join(f"{k}={val}" for k, val in sorted(v.items()))
        if v:
            return str(v)
    return "-"


def row_key(rec: dict) -> str | None:
    """Stable ``workload/backend/preset[/precision][/attn_impl][/seq...]``
    identity for one row, or None for rows that carry no workload identity
    at all.

    Precision/attn-impl segments append only when the row stamps them
    (bench/train rows since the low-precision fast path landed), so legacy
    rows keep their adopted keys — and a bf16 baseline can never be
    compared against an fp8 or int8-attention run of the same preset.
    ``seq_len``/``seq_parallel`` segment the same way (rows since the
    sequence-parallel mesh axis landed): an 8-chip ring run of a preset
    never gates against its single-chip baseline, and a longer-sequence
    NaFlex/temporal row never gates against the short one. ``seq_parallel``
    only appends when > 1, so a stamped-but-degenerate run keeps the
    single-chip key."""
    workload = rec.get("phase") or rec.get("metric")
    if not workload:
        return None
    backend = rec.get("backend") or rec.get("device") or "unknown"
    key = f"{workload}/{backend}/{_preset_of(rec)}"
    precision = rec.get("precision")
    if precision:
        key += f"/{precision}"
    attn_impl = rec.get("attn_impl")
    if attn_impl:
        key += f"/{attn_impl}"
    seq_len = rec.get("seq_len")
    if seq_len:
        key += f"/seq{int(seq_len)}"
    seq_parallel = rec.get("seq_parallel")
    if seq_parallel and int(seq_parallel) > 1:
        key += f"/sp{int(seq_parallel)}"
    return key


def comparable_metrics(rec: dict) -> dict[str, float]:
    """The gate-able numeric metrics present on a row."""
    out = {}
    for name in METRIC_DIRECTIONS:
        v = rec.get(name)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[name] = float(v)
    return out


class BaselineStore:
    """Per-(workload,backend,preset,metric) adopted reference values.

    File shape::

        {"baselines": {"<key>": {"<metric>": {"value": 505.0,
                                              "ts": "...", "note": "..."}}}}
    """

    def __init__(self, path: str | Path):
        self.path = Path(path)
        self.baselines: dict[str, dict[str, dict]] = {}
        if self.path.exists():
            try:
                data = json.loads(self.path.read_text(encoding="utf-8"))
            except (OSError, json.JSONDecodeError):
                data = {}
            if isinstance(data, dict):
                bl = data.get("baselines", {})
                if isinstance(bl, dict):
                    self.baselines = bl

    def save(self) -> None:
        self.path.parent.mkdir(parents=True, exist_ok=True)
        tmp = self.path.with_suffix(self.path.suffix + ".tmp")
        tmp.write_text(json.dumps({"baselines": self.baselines}, indent=2,
                                  sort_keys=True) + "\n", encoding="utf-8")
        tmp.replace(self.path)

    def get(self, key: str, metric: str) -> float | None:
        entry = self.baselines.get(key, {}).get(metric)
        return None if entry is None else float(entry["value"])

    def adopt_rows(self, rows: list[dict], *, note: str | None = None,
                   include_fallback: bool = False) -> list[str]:
        """Adopt the (non-fallback) rows' metrics as new baselines; the
        last row per key wins. Returns the adopted ``key:metric`` names."""
        adopted = []
        for rec in rows:
            if is_fallback(rec) and not include_fallback:
                continue
            key = row_key(rec)
            if key is None:
                continue
            for metric, value in comparable_metrics(rec).items():
                entry = {"value": value, "ts": rec.get("ts")}
                if note:
                    entry["note"] = note
                self.baselines.setdefault(key, {})[metric] = entry
                adopted.append(f"{key}:{metric}")
        return adopted


def check_rows(store: BaselineStore, rows: list[dict], *,
               threshold: float = DEFAULT_THRESHOLD) -> list[dict]:
    """Compare fresh rows against adopted baselines.

    Returns one verdict dict per (row, metric):
    ``{"key", "metric", "fresh", "baseline", "delta_frac", "status"}`` with
    status in ``regression`` (worse than baseline beyond the threshold,
    direction-aware), ``improved`` (better beyond the threshold — adoption
    candidate), ``ok``, ``fallback_excluded`` (never compared), or
    ``no_baseline``.
    """
    verdicts = []
    for rec in rows:
        key = row_key(rec)
        if key is None:
            continue
        if is_fallback(rec):
            verdicts.append({"key": key, "metric": rec.get("metric"),
                             "fresh": None, "baseline": None,
                             "delta_frac": None,
                             "status": "fallback_excluded"})
            continue
        for metric, fresh in comparable_metrics(rec).items():
            base = store.get(key, metric)
            if base is None:
                verdicts.append({"key": key, "metric": metric,
                                 "fresh": fresh, "baseline": None,
                                 "delta_frac": None,
                                 "status": "no_baseline"})
                continue
            delta = (fresh - base) / base if base else 0.0
            direction = METRIC_DIRECTIONS[metric]
            # inclusive: a drop of exactly the threshold fails the gate
            worse = -delta * direction
            if worse >= threshold - 1e-9:
                status = "regression"
            elif -worse >= threshold - 1e-9:
                status = "improved"
            else:
                status = "ok"
            verdicts.append({"key": key, "metric": metric, "fresh": fresh,
                             "baseline": base,
                             "delta_frac": round(delta, 4),
                             "status": status})
    return verdicts


def summarize(verdicts: list[dict]) -> dict[str, int]:
    out = {"ok": 0, "regression": 0, "improved": 0, "no_baseline": 0,
           "fallback_excluded": 0}
    for v in verdicts:
        out[v["status"]] = out.get(v["status"], 0) + 1
    return out
