"""Goodput accounting: classify training wall time into named buckets.

"Goodput" here is the fraction of wall-clock time the accelerator spends on
actual training steps, as opposed to compiling, waiting for data, writing
checkpoints, or syncing scalars back to the host. The accounter is a small
stopwatch ledger: wrap each region of the training loop in
``acct.measure("bucket")`` and ask for a :meth:`report` at the end — the
residual (python glue between the measured regions) is attributed to
``other`` so the buckets always sum to exactly the wall time. The wall time
runs from the accounter's birth: ``cli.train`` creates it in its first
statement, so a run's start-up is inside the wall time, under ``setup``.

Buckets (the fixed vocabulary the docs and CI smoke assert on):

- ``setup``      — what ``train()`` does before its loop: imports, the
                   backend's start, building model, optimizer and data
- ``compile``    — first-step tracing/compilation (and explicit AOT compiles)
- ``data_wait``  — blocked on the input pipeline (``next(iterator)``)
- ``step``       — dispatched training step incl. the device sync that
                   realizes the loss on host
- ``checkpoint`` — orbax save/restore
- ``host_sync``  — metric logging, console/JSONL writes
- ``preemption_save`` — SIGTERM grace-window save (initiate + final flush)
- ``lost_work``  — wall time a preemption/restart discarded (grace-window
                   steps whose results are thrown away, work since the
                   last committed checkpoint on a crash)
- ``replan``     — live topology replans: mesh re-planning between
                   supervised attempts, serve-engine replica swaps
- ``heal``       — self-heal wall time: probe + rebuild around a fenced
                   replica (the replan it triggers books separately)
- ``other``      — residual wall time not covered by a measure() region

MFU-adjusted goodput = goodput × MFU: the fraction of *peak hardware* FLOPs
the whole loop achieves, not just the step function — the number that tells
you whether to optimize the kernel or the pipeline around it.

Phases (the train loop's finer names under the buckets; ``measure()`` takes
either, and a bucket's name is a phase of itself):

- ``next_batch``  → ``data_wait``: ``next()`` on the input iterator alone
- ``place``       → ``data_wait``: dispatch of the host-to-device copy
- ``dispatch``    → ``step``: the call of the jitted step until it returns
- ``device_wait`` → ``step``: ``block_until_ready`` on the loss

- ``imports``         → ``setup``: ``train()``'s own imports (jax, flax,
  ``jimm_tpu.data`` / ``.parallel`` / ``.train``: optax, grain, orbax)
- ``backend_init``    → ``setup``: platform and cluster configuration, the
  compile cache, the mesh, the first ``jax.default_backend()`` (the TPU
  runtime's start)
- ``model_build``     → ``setup``: the constructor or ``from_pretrained``,
  the head's fit and the precision policy
- ``optimizer_build`` → ``setup``: ``make_optimizer``
- ``data_build``      → ``setup``: the step function's choice and the input
  iterator with its wrappers

(``dispatch`` and ``device_wait`` land in ``compile`` on the first step:
``bucket=``; building the ``CheckpointManager`` and a resume's ``restore``
are a ``checkpoint``). Every
measured region is also kept as ``[phase, start_unix_ns, dur_ns]`` until
:meth:`GoodputAccounter.drain` hands it out, once: the loop writes them into
its step's ``--metrics-file`` row, and because ``start`` is ``time.time_ns()``
they lie on a profiler capture's clock (its ``profile_start_time``) without
the profiler's host tracer having recorded anything.
"""

from __future__ import annotations

import collections
import threading
import time
from contextlib import contextmanager

from jimm_tpu.obs.registry import MetricRegistry, enabled, get_registry

__all__ = ["BUCKETS", "PHASES", "SETUP_PHASES", "GoodputAccounter"]

BUCKETS = ("setup", "compile", "data_wait", "step", "checkpoint",
           "host_sync", "preemption_save", "lost_work", "replan", "heal")
#: the phases of ``setup``, in the order ``cli.train`` runs through them
SETUP_PHASES = ("imports", "backend_init", "model_build", "optimizer_build",
                "data_build")
#: phase -> the bucket it adds to, unless ``measure(..., bucket=)`` says so
PHASES = {"next_batch": "data_wait", "place": "data_wait",
          "dispatch": "step", "device_wait": "step",
          **{name: "setup" for name in SETUP_PHASES},
          **{name: name for name in BUCKETS}}
#: spans kept until the next drain(); bounds an accounter nobody drains
MAX_UNDRAINED_SPANS = 4096


class GoodputAccounter:
    """Wall-time ledger over the fixed bucket vocabulary.

    Also mirrors per-bucket cumulative seconds into the ``jimm_train``
    registry as ``goodput_{bucket}_seconds_total`` counters plus a
    ``goodput_ratio`` gauge, so the unified snapshot carries the breakdown
    without a separate report call.
    """

    def __init__(self, registry: MetricRegistry | None = None):
        self._lock = threading.Lock()
        self._seconds = {name: 0.0 for name in BUCKETS}
        self._spans: collections.deque[list] = collections.deque(
            maxlen=MAX_UNDRAINED_SPANS)
        self._t_start = time.monotonic()
        #: the run's :class:`~jimm_tpu.obs.compiles.CompileWatch`, where its
        #: owner opened one: ``cli.train`` hangs it here and drains both into
        #: the same rows (one more local in ``train`` would cost set-up time)
        self.compiles = None
        self.registry = registry if registry is not None \
            else get_registry("jimm_train")
        self._counters = {
            name: self.registry.counter(f"goodput_{name}_seconds_total")
            for name in BUCKETS}
        self.registry.gauge("goodput_ratio", self.goodput)
        self.registry.gauge("goodput_wall_s", self.wall_s)

    @contextmanager
    def measure(self, phase: str, bucket: str | None = None):
        """Attribute the wrapped region's wall time to ``phase``'s bucket
        (or to ``bucket``), and keep it as a span for :meth:`drain`."""
        if phase not in PHASES:
            raise KeyError(f"unknown goodput phase {phase!r}; "
                           f"expected one of {tuple(PHASES)}")
        bucket = PHASES[phase] if bucket is None else bucket
        if bucket not in self._seconds:
            raise KeyError(f"unknown goodput bucket {bucket!r}; "
                           f"expected one of {BUCKETS}")
        if not enabled():
            yield
            return
        # wall clock for where the span lies, monotonic for how long it is
        start_unix_ns = time.time_ns()
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            dur_ns = time.perf_counter_ns() - t0
            dt = dur_ns / 1e9
            with self._lock:
                self._seconds[bucket] += dt
                self._spans.append([phase, start_unix_ns, dur_ns])
            self._counters[bucket].inc(dt)

    def drain(self) -> list[list]:
        """Every ``[phase, start_unix_ns, dur_ns]`` measured since the last
        call, in the order the regions ended; each is handed out once."""
        with self._lock:
            spans = list(self._spans)
            self._spans.clear()
        return spans

    def add(self, bucket: str, seconds: float) -> None:
        """Attribute already-measured time (e.g. a StepTimer reading)."""
        if bucket not in self._seconds:
            raise KeyError(f"unknown goodput bucket {bucket!r}")
        with self._lock:
            self._seconds[bucket] += seconds
        self._counters[bucket].inc(seconds)

    # -- read -------------------------------------------------------------

    def wall_s(self) -> float:
        return time.monotonic() - self._t_start

    def seconds(self, wall: float | None = None) -> dict[str, float]:
        with self._lock:
            out = dict(self._seconds)
        # Residual: wall time no measure() region claimed. Clamped at 0 so
        # overlapping regions (a bug, but survivable) can't go negative.
        if wall is None:
            wall = self.wall_s()
        out["other"] = max(0.0, wall - sum(out.values()))
        return out

    def goodput(self) -> float:
        """step-time / wall-time, in [0, 1]."""
        wall = self.wall_s()
        if wall <= 0:
            return 0.0
        with self._lock:
            step = self._seconds["step"]
        return min(1.0, step / wall)

    def report(self, mfu: float | None = None) -> dict[str, float]:
        """Flat report: per-bucket seconds + fractions (summing to 1.0 by
        construction), goodput, and MFU-adjusted goodput when an MFU is
        supplied."""
        wall = self.wall_s()
        secs = self.seconds(wall)
        out: dict[str, float] = {"wall_s": round(wall, 4)}
        for name, s in secs.items():
            out[f"{name}_s"] = round(s, 4)
            out[f"{name}_frac"] = round(s / wall, 4) if wall > 0 else 0.0
        # derive goodput from the same wall sample as the fracs instead of
        # calling goodput() (which resamples the clock): every field in one
        # report must describe the same instant, or goodput and
        # mfu_adjusted_goodput drift apart whenever the scheduler preempts
        # between reads
        g = min(1.0, secs["step"] / wall) if wall > 0 else 0.0
        out["goodput"] = round(g, 4)
        if mfu is not None:
            out["mfu"] = round(mfu, 4)
            out["mfu_adjusted_goodput"] = round(g * mfu, 4)
        return out
