"""Profiling hooks (SURVEY §5 tracing row): `jax.profiler` trace capture
around training steps, viewable in TensorBoard / Perfetto — plus an
offline per-op analyzer so a capture can be read without TensorBoard
(`python -m jimm_tpu profile-analyze`).

Since the continuous profiler landed, :func:`trace` delegates to
:func:`jimm_tpu.obs.prof.capture.profiler_session` — the process-wide
sanctioned ``start_trace``/``stop_trace`` home (lint JL022) — so a
one-shot ``--profile-dir`` capture and the ``--prof-ring`` continuous ring
can never double-start the profiler. The parsing core lives jax-free in
:mod:`jimm_tpu.obs.prof.opstats`; this module keeps the :class:`OpStat`
shape the CLI and tests consume."""

from __future__ import annotations

import collections
from contextlib import contextmanager
from dataclasses import dataclass
from pathlib import Path

from jimm_tpu.obs.prof.opstats import op_table


@contextmanager
def trace(log_dir: str | Path):
    """Capture a trace of the enclosed steps (on a TPU the device planes
    alone: see ``capture._JaxProfiler.start``)::

        with trace("/tmp/profile"):
            for _ in range(5):
                train_step(...)
    """
    from jimm_tpu.obs.prof.capture import profiler_session
    with profiler_session(log_dir):
        yield


# ---------------------------------------------------------------------------
# Offline trace analysis
# ---------------------------------------------------------------------------

@dataclass
class OpStat:
    """One XLA op aggregated across its occurrences in a trace.
    ``bytes_accessed`` is the TOTAL over all occurrences."""

    name: str
    category: str
    total_us: float
    count: int
    bytes_accessed: int
    long_name: str

    @property
    def gbps(self) -> float:
        """Achieved HBM bandwidth (GB/s) — the number that shows whether a
        fusion is bandwidth-bound or stalling."""
        if not self.total_us:
            return 0.0
        return self.bytes_accessed / (self.total_us * 1e-6) / 1e9


def op_stats(log_dir: str | Path, *, device: int | None = 0) -> list[OpStat]:
    """Aggregate device-op self times from the newest ``*.trace.json.gz``
    under ``log_dir`` (written by :func:`trace`). Pure stdlib — no
    TensorBoard required.

    ``device`` picks ONE device pid (default: the first) — under SPMD every
    core runs the same program, and summing across cores would report
    n_devices times the per-step time. ``None`` aggregates all devices."""
    return [OpStat(**row) for row in op_table(log_dir, device=device)]


def summarize(stats: list[OpStat], top: int = 25, steps: int = 1) -> str:
    """Human-readable per-op and per-category summary. ``steps`` divides the
    totals so numbers read as per-training-step."""
    total = sum(s.total_us for s in stats)
    by_cat = collections.Counter()
    for s in stats:
        by_cat[s.category] += s.total_us
    lines = [f"device op time: {total / steps / 1e3:.2f} ms/step",
             "by category (ms/step):"]
    for cat, us in by_cat.most_common():
        lines.append(f"  {us / steps / 1e3:9.2f}  {cat}")
    lines.append(f"top {top} ops (ms/step, n/step, MB/occurrence, GB/s):")
    for s in stats[:top]:
        per_occ = s.bytes_accessed / max(s.count, 1)
        lines.append(
            f"  {s.total_us / steps / 1e3:8.2f} n={s.count // steps:4d} "
            f"{per_occ / 1e6:8.1f}MB {s.gbps:6.0f}GB/s  "
            f"{s.name[:44]:44s} {s.long_name[:60]}")
    return "\n".join(lines)
