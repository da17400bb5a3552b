"""Shared tolerant reader for MEASUREMENTS.jsonl.

One place owns the parse rules (line must be a JSON object; anything else —
partial writes from a killed attempt, log noise — is skipped) so the
consumers (adopt_sweep ranking, bench_sweep skip-resume) cannot drift.
"""

from __future__ import annotations

import json
import pathlib

MEASUREMENTS = pathlib.Path(__file__).resolve().parent.parent \
    / "MEASUREMENTS.jsonl"


def read_records(path: pathlib.Path | None = None) -> list[dict]:
    recs: list[dict] = []
    try:
        lines = (path or MEASUREMENTS).read_text(errors="replace") \
            .splitlines()
    except OSError:
        return recs
    for line in lines:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict):
            recs.append(rec)
    return recs
