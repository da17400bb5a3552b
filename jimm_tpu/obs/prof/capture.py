"""Windowed ``jax.profiler`` capture manager: continuous ring + deep capture.

Two capture kinds, one on-disk ring:

- **window** — the always-on continuous profiler. ``on_step(step)`` (train)
  opens a short capture every ``every_steps`` steps and commits it after
  ``window_steps``; between captures the hook is one integer compare, which
  is how the ring holds its ≤1% step-time overhead budget.
- **deep** — anomaly-triggered. ``trigger(cid=...)`` opens a longer capture
  tagged with the incident's flight-recorder correlation id and commits it
  on a timer (serve incidents have no step boundary), emitting
  ``prof_capture_started`` / ``prof_capture_committed`` journal events on
  that cid so the capture joins the incident chain.

Ring discipline (journal-style rotation, AOT-store atomicity):

- a capture records into ``cap-NNNNNN-<kind>.tmp/``; commit writes
  ``meta.json`` (tmp file + ``os.replace``) then renames the whole dir to
  ``cap-NNNNNN-<kind>/`` — readers only ever see complete captures;
- committed captures are evicted oldest-first once the ring exceeds its
  hard byte budget;
- a capture that fails to stop, or a leftover ``.tmp`` dir from a crash,
  is moved under ``quarantine/`` with a reason file — **never deleted** —
  so evidence of a broken profiler run survives for a human.

Only this module (and the :func:`profiler_session` primitive below) may
call ``jax.profiler.start_trace``/``stop_trace`` — lint rule JL022 fences
every other call site, because a bypass would race the process-wide
profiler session and escape the byte budget. jax is imported lazily so the
``obs prof ls/show/diff`` CLI stays jax-free.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from contextlib import contextmanager
from pathlib import Path

from jimm_tpu.obs.journal import get_journal, new_correlation_id
from jimm_tpu.obs.registry import get_registry

__all__ = [
    "CaptureManager", "configure_capture", "get_capture_manager",
    "list_captures", "maybe_trigger", "profiler_session", "reset_capture",
]

META_NAME = "meta.json"
_PREFIX = "cap-"
_TMP_SUFFIX = ".tmp"

#: process-wide profiler session lock: jax allows exactly one active trace,
#: so every sanctioned entry point serializes on this.
_SESSION_LOCK = threading.Lock()


class _JaxProfiler:
    """Default backend: the real ``jax.profiler`` (imported lazily so the
    module itself stays importable without jax)."""

    def start(self, log_dir: str) -> None:
        """Device planes only on a TPU: JAX's defaults (Python tracer on,
        host tracer 2) put traced steps 2.1 s apart instead of 0.27 s, and
        the host tracer alone slowed a batch's placement from 35 ms to 0.5 s
        (PERF.md, PR 22, finding 2), so a capture measured its own profiler.
        The loop's host side is in the same run's ``--metrics-file`` rows
        (``phases``, on this capture's clock). A CPU runs its operations on
        host threads, so there the host tracer stays."""
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0 if jax.default_backend() == "tpu" else 2
        jax.profiler.start_trace(log_dir, profiler_options=options)  # jaxlint: disable=JL022 — the sanctioned home: CaptureManager/profiler_session route every capture here

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()  # jaxlint: disable=JL022 — sanctioned home (see start)


@contextmanager
def profiler_session(log_dir: str | Path):
    """The ONE raw trace primitive outside :class:`CaptureManager`: capture
    the enclosed region into ``log_dir``, holding the process-wide session
    lock so a one-shot ``--profile-dir`` trace and the continuous ring can
    never double-start the profiler. Library code goes through this (or a
    manager) — never ``jax.profiler.start_trace`` directly (JL022)."""
    Path(log_dir).mkdir(parents=True, exist_ok=True)
    prof = _JaxProfiler()
    with _SESSION_LOCK:
        prof.start(str(log_dir))
        try:
            yield
        finally:
            prof.stop()


def _dir_bytes(root: Path) -> int:
    total = 0
    for base, _dirs, files in os.walk(root):
        for name in files:
            try:
                total += os.path.getsize(os.path.join(base, name))
            except OSError:
                pass
    return total


def _read_meta(cap_dir: Path) -> dict | None:
    try:
        with open(cap_dir / META_NAME) as f:
            meta = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    return meta if isinstance(meta, dict) else None


def list_captures(root: str | Path) -> list[dict]:
    """Committed capture metas under ``root``, oldest first. jax-free —
    this is what ``obs prof ls`` and the timeline exporter read."""
    root = Path(root)
    out = []
    if not root.is_dir():
        return out
    for entry in sorted(root.iterdir()):
        if not entry.name.startswith(_PREFIX) \
                or entry.name.endswith(_TMP_SUFFIX) or not entry.is_dir():
            continue
        meta = _read_meta(entry)
        if meta is not None:
            meta = dict(meta, path=str(entry))
            out.append(meta)
    out.sort(key=lambda m: m.get("seq", 0))
    return out


class CaptureManager:
    """Owns one capture ring rooted at ``root``.

    Args:
        root: ring directory (created; ``quarantine/`` lives under it).
        max_ring_bytes: hard byte budget for committed captures — commit
            evicts oldest-first past this.
        every_steps: continuous mode — open a window capture every N steps
            (0 disables the ring; ``trigger`` still works).
        window_steps: steps per window capture.
        deep_window_s: wall-clock length of a triggered deep capture
            (committed by a timer thread — serve incidents have no steps).
        min_trigger_interval_s: deep-capture rate limit; triggers inside
            the interval are counted as suppressed, not captured.
        journal: explicit :class:`EventJournal` (default: process global).
        profiler: injectable start/stop backend (tests); default jax.
    """

    def __init__(self, root: str | Path, *, max_ring_bytes: int = 64 << 20,
                 every_steps: int = 200, window_steps: int = 2,
                 deep_window_s: float = 1.5,
                 min_trigger_interval_s: float = 10.0,
                 journal=None, profiler=None):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.quarantine_dir = self.root / "quarantine"
        self.max_ring_bytes = int(max_ring_bytes)
        self.every_steps = int(every_steps)
        self.window_steps = max(1, int(window_steps))
        self.deep_window_s = float(deep_window_s)
        self.min_trigger_interval_s = float(min_trigger_interval_s)
        self._journal = journal
        self._profiler = profiler or _JaxProfiler()
        self._lock = threading.RLock()
        self._active: dict | None = None
        self._timer: threading.Timer | None = None
        self._last_trigger_mono: float | None = None
        self._triggered_cids: set[str] = set()
        reg = get_registry("jimm_prof")
        self._captures_total = reg.counter("captures_total")
        self._deep_total = reg.counter("deep_captures_total")
        self._evicted_total = reg.counter("evicted_total")
        self._quarantined_total = reg.counter("quarantined_total")
        self._suppressed_total = reg.counter("trigger_suppressed_total")
        self._failed_total = reg.counter("capture_failures_total")
        self._overhead = reg.counter("overhead_seconds_total")
        reg.gauge("ring_bytes", self.ring_bytes)
        reg.gauge("capture_active",
                  lambda: 1.0 if self._active is not None else 0.0)
        # crash recovery: count what already committed, quarantine
        # leftover .tmp dirs (a crash mid-capture), never delete them
        self._entries: list[dict] = [
            {"seq": m.get("seq", 0), "path": Path(m["path"]),
             "bytes": int(m.get("bytes", 0))}
            for m in list_captures(self.root)]
        self._seq = max([e["seq"] for e in self._entries], default=0)
        for entry in sorted(self.root.iterdir()):
            if entry.name.startswith(_PREFIX) \
                    and entry.name.endswith(_TMP_SUFFIX):
                self._quarantine(entry, "incomplete capture (crash?)")

    # -- journal/metrics helpers ------------------------------------------

    def _emit(self, event: str, *, cid: str | None = None, **fields):
        journal = self._journal if self._journal is not None \
            else get_journal()
        return journal.emit(event, cid=cid, **fields)

    def ring_bytes(self) -> float:
        """Committed bytes currently in the ring (quarantine excluded)."""
        with self._lock:
            return float(sum(e["bytes"] for e in self._entries))

    # -- capture lifecycle ------------------------------------------------

    def start(self, kind: str, *, cid: str | None = None,
              reason: str | None = None, step: int | None = None,
              window_s: float | None = None) -> dict | None:
        """Open a capture. Returns its (in-progress) meta, or None when a
        capture is already active or the profiler session is held
        elsewhere (a one-shot ``profiler_session`` in flight)."""
        t0 = time.perf_counter()
        with self._lock:
            if self._active is not None:
                return None
            if not _SESSION_LOCK.acquire(blocking=False):
                return None
            self._seq += 1
            name = f"{_PREFIX}{self._seq:06d}-{kind}"
            tmp = self.root / (name + _TMP_SUFFIX)
            try:
                tmp.mkdir(parents=True, exist_ok=True)
                self._profiler.start(str(tmp))
            except Exception as e:  # noqa: BLE001 — a broken profiler must never take down the serving/training process; the failure is counted, journaled, and quarantined
                _SESSION_LOCK.release()
                self._failed_total.inc()
                self._emit("prof_capture_failed", cid=cid, kind=kind,
                           error=f"{type(e).__name__}: {e}")
                if tmp.exists():
                    self._quarantine(tmp, f"start failed: {e}")
                return None
            meta = {"seq": self._seq, "name": name, "kind": kind,
                    "cid": cid, "reason": reason, "step": step,
                    "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
                    "start_mono": round(time.monotonic(), 6)}
            if window_s is not None:
                meta["window_s"] = window_s
            self._active = dict(meta, _dir=tmp)
            self._emit("prof_capture_started", cid=cid, kind=kind,
                       capture=name, reason=reason, step=step)
            if kind == "deep":
                self._deep_total.inc()
                self._timer = threading.Timer(
                    window_s if window_s is not None else self.deep_window_s,
                    self.commit)
                self._timer.daemon = True
                self._timer.start()
        self._overhead.inc(time.perf_counter() - t0)
        return meta

    def commit(self) -> dict | None:
        """Stop the active capture, finalize it atomically into the ring,
        journal ``prof_capture_committed`` (with ``dur_s`` so the timeline
        renders the window), and enforce the byte budget."""
        t0 = time.perf_counter()
        with self._lock:
            act = self._active
            if act is None:
                return None
            self._active = None
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
            tmp = act.pop("_dir")
            try:
                self._profiler.stop()
            except Exception as e:  # noqa: BLE001 — see start(): a failed stop quarantines the evidence instead of crashing the host process
                _SESSION_LOCK.release()
                self._failed_total.inc()
                self._emit("prof_capture_failed", cid=act.get("cid"),
                           kind=act["kind"], capture=act["name"],
                           error=f"{type(e).__name__}: {e}")
                self._quarantine(tmp, f"stop failed: {e}")
                return None
            _SESSION_LOCK.release()
            end = time.monotonic()
            meta = {k: v for k, v in act.items()}
            meta["end_mono"] = round(end, 6)
            meta["dur_s"] = round(end - meta["start_mono"], 6)
            meta["bytes"] = _dir_bytes(tmp)
            final = self.root / meta["name"]
            try:
                tmp_meta = tmp / (META_NAME + _TMP_SUFFIX)
                with open(tmp_meta, "w") as f:
                    json.dump(meta, f, indent=2, sort_keys=True)
                    f.write("\n")
                os.replace(tmp_meta, tmp / META_NAME)
                os.replace(tmp, final)
            except OSError as e:
                self._failed_total.inc()
                self._quarantine(tmp, f"commit failed: {e}")
                return None
            self._entries.append({"seq": meta["seq"], "path": final,
                                  "bytes": meta["bytes"]})
            self._captures_total.inc()
            self._emit("prof_capture_committed", cid=meta.get("cid"),
                       kind=meta["kind"], capture=meta["name"],
                       bytes=meta["bytes"], dur_s=meta["dur_s"],
                       step=meta.get("step"))
            self._enforce_budget()
        self._overhead.inc(time.perf_counter() - t0)
        return meta

    def _enforce_budget(self) -> None:
        # oldest-first eviction, always keeping the newest capture even
        # when it alone exceeds the budget (a ring that can hold nothing
        # is useless; the budget bounds accumulation, not one artifact)
        total = sum(e["bytes"] for e in self._entries)
        while total > self.max_ring_bytes and len(self._entries) > 1:
            old = self._entries.pop(0)
            shutil.rmtree(old["path"], ignore_errors=True)
            total -= old["bytes"]
            self._evicted_total.inc()

    def _quarantine(self, path: Path, reason: str) -> None:
        """Corrupt/incomplete capture: move aside, never delete."""
        self.quarantine_dir.mkdir(parents=True, exist_ok=True)
        dest = self.quarantine_dir / path.name
        i = 0
        while dest.exists():
            i += 1
            dest = self.quarantine_dir / f"{path.name}.{i}"
        try:
            os.replace(path, dest)
            with open(dest / "QUARANTINE_REASON.txt", "w") as f:
                f.write(reason + "\n")
        except OSError:
            return
        self._quarantined_total.inc()

    # -- continuous mode (train step hook) --------------------------------

    def on_step(self, step: int) -> None:
        """Per-step hook for the continuous ring. Fast path (no capture
        active, not a capture step) is one modulo + compares."""
        act = self._active
        if act is not None:
            if act["kind"] == "window" \
                    and step - (act.get("step") or 0) >= self.window_steps:
                self.commit()
            return
        if self.every_steps <= 0:
            return
        # offset 2 into each period: past the compile step and the first
        # post-restore step, matching the --profile-dir window choice
        if step % self.every_steps == 2 % self.every_steps and step > 0:
            self.start("window", step=step)

    def flush(self) -> dict | None:
        """Commit whatever is active (end-of-run / engine shutdown)."""
        return self.commit()

    # -- anomaly trigger --------------------------------------------------

    def trigger(self, cid: str | None = None, reason: str | None = None,
                *, window_s: float | None = None) -> dict | None:
        """Deep capture on an incident. Rate-limited (one per
        ``min_trigger_interval_s``) and deduped per cid — heal, replan, and
        SLO burn often fire on the same incident within milliseconds, and
        one deep capture per incident is the useful artifact. An active
        *window* capture is committed first; an active *deep* capture
        suppresses the trigger."""
        with self._lock:
            now = time.monotonic()
            if cid is not None and cid in self._triggered_cids:
                self._suppressed_total.inc()
                return None
            if self._last_trigger_mono is not None and \
                    now - self._last_trigger_mono \
                    < self.min_trigger_interval_s:
                self._suppressed_total.inc()
                return None
            if self._active is not None:
                if self._active["kind"] == "deep":
                    self._suppressed_total.inc()
                    return None
                self.commit()
            cid = cid or new_correlation_id()
            meta = self.start("deep", cid=cid, reason=reason,
                              window_s=window_s)
            if meta is not None:
                self._last_trigger_mono = now
                self._triggered_cids.add(cid)
                if len(self._triggered_cids) > 1024:
                    # cid dedup is per recent incident, not forever
                    self._triggered_cids = set(list(
                        self._triggered_cids)[-256:])
            return meta

    def ls(self) -> list[dict]:
        return list_captures(self.root)

    def close(self) -> None:
        self.flush()


# ---------------------------------------------------------------------------
# process-global manager (env: JIMM_PROF_DIR) — the wiring surface the
# serve engine / SLO listener / goodput advisor hang their triggers on
# ---------------------------------------------------------------------------

_global_manager: CaptureManager | None = None
_env_checked = False


def configure_capture(root: str | Path, **kwargs) -> CaptureManager:
    """Install the process-global capture manager (``--prof-dir`` flags and
    smokes call this; ``JIMM_PROF_DIR`` configures it implicitly)."""
    global _global_manager, _env_checked
    _global_manager = CaptureManager(root, **kwargs)
    _env_checked = True
    return _global_manager


def get_capture_manager() -> CaptureManager | None:
    """The global manager, auto-configured from ``JIMM_PROF_DIR`` on first
    call; None when profiling is not enabled (the common case — every
    trigger site must tolerate it)."""
    global _env_checked, _global_manager
    if _global_manager is None and not _env_checked:
        _env_checked = True
        root = os.environ.get("JIMM_PROF_DIR")
        if root:
            _global_manager = CaptureManager(root)
    return _global_manager


def maybe_trigger(cid: str | None = None, reason: str | None = None,
                  *, window_s: float | None = None) -> dict | None:
    """Trigger a deep capture iff a global manager is configured — the
    no-op-by-default hook incident paths call unconditionally."""
    mgr = get_capture_manager()
    if mgr is None:
        return None
    try:
        return mgr.trigger(cid, reason, window_s=window_s)
    except Exception:  # noqa: BLE001 — profiling is observability: it must never convert an incident into a crash
        return None


def reset_capture() -> None:
    """Drop the global manager (tests)."""
    global _global_manager, _env_checked
    if _global_manager is not None:
        try:
            _global_manager.flush()
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass
    _global_manager = None
    _env_checked = False
