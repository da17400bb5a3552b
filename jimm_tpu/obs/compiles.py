"""Every compile request of a run, named, on the goodput phases' clock.

JAX reports each stage of making a program through ``jax.monitoring`` with
the function's name: tracing it to a jaxpr, lowering the jaxpr to an MLIR
module, and the backend's compile, which on a persistent-cache hit is the
retrieval of the executable. :class:`CompileWatch` is the program's one
listener to them. It keeps each as ``[kind, fun_name, start_unix_ns,
dur_ns]`` with ``kind`` in ``trace``, ``lower``, ``compile``, and counts the
persistent cache's hits and misses. ``start`` is ``time.time_ns()`` at the
event less its duration (JAX reports a stage when it ends), so an event lies
on the clock of :mod:`jimm_tpu.obs.goodput`'s phases and its parent is the
phase whose interval holds its start. A function jitted inside another is
traced inside the outer's trace, and a lowering rule may trace a helper (a
model's step holds hundreds of jitted ``jax.numpy`` functions): a ``trace``
that began inside another stage is part of that stage and is not kept, so
the kept events of one thread never overlap.

A watch listens between :meth:`CompileWatch.listen` and
:meth:`CompileWatch.close`, or inside a ``with`` on it, as often as it is
asked to. ``cli.train`` builds one when its imports are done, has it listen
through the stretches of set-up that build programs (a ``with`` beside the
phase's ``measure()``: gone on every way out) and through the loop (closed in
its ``finally``), drains it wherever it drains its accounter, so that an event
lands in the row of the phase that holds it, and writes what :func:`row_keys`
makes of a step's events into the ``--metrics-file`` row (``compiles``,
``cache_hits``, ``cache_misses``: file-only, and only where there is
something to write). The totals are mirrored into the ``jimm_train`` registry as
``compile_requests_total``, ``compile_seconds_total`` (both of the
``compile`` kind: what the backend was asked for, and how long it took to
compile or load it), ``compile_cache_hits_total`` and
``compile_cache_misses_total``: a second watch alive beside the run's (a
script that counts over several runs, as ``chip_smoke.py``) takes a registry
of its own, or both count there. Under ``JIMM_OBS=0`` no listener is ever
registered and nothing is recorded.
"""

from __future__ import annotations

import collections
import threading
import time

from jimm_tpu.obs.registry import MetricRegistry, enabled, get_registry

__all__ = ["CompileWatch", "row_keys"]

#: jax.monitoring duration event -> the kind it is kept under
KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
#: events kept until the next drain(); bounds a watch nobody drains
MAX_UNDRAINED_EVENTS = 4096


def row_keys(events: list[list]) -> dict:
    """What a ``--metrics-file`` row gets of the events drained for it:
    ``compiles`` (the stages, in the order they ended) and ``cache_hits`` /
    ``cache_misses`` (counts), each only where there is something. A steady
    step's row gets ``{}``."""
    row: dict = {}
    for event in events:
        if event[0] in KINDS.values():
            row.setdefault("compiles", []).append(event)
        else:
            key = f"cache_{event[0]}"
            row[key] = row.get(key, 0) + 1
    return row


class CompileWatch:
    """``requests`` and ``cache`` count over all the watch has listened to;
    :meth:`drain` hands each event out once."""

    def __init__(self, registry: MetricRegistry | None = None) -> None:
        self._lock = threading.Lock()
        self._events: collections.deque[list] = collections.deque(
            maxlen=MAX_UNDRAINED_EVENTS)
        #: backend compile requests (compiles and cache loads alike)
        self.requests = 0
        self.cache = {"hits": 0, "misses": 0}
        self.listening = False
        registry = registry if registry is not None \
            else get_registry("jimm_train")
        self._requests_total = registry.counter("compile_requests_total")
        self._seconds_total = registry.counter("compile_seconds_total")
        self._cache_totals = {
            which: registry.counter(f"compile_cache_{which}_total")
            for which in self.cache}

    def listen(self) -> "CompileWatch":
        if enabled() and not self.listening:
            self.listening = True
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                self._duration)
            jax.monitoring.register_event_listener(self._event)
        return self

    def close(self) -> None:
        if self.listening:
            self.listening = False
            import jax
            jax.monitoring.unregister_event_duration_listener(self._duration)
            jax.monitoring.unregister_event_listener(self._event)

    __enter__ = listen

    def __exit__(self, *exc) -> None:
        self.close()

    def _duration(self, event: str, duration: float, **kw) -> None:
        kind = KINDS.get(event)
        if kind is None:
            return
        dur_ns = int(duration * 1e9)
        start_ns = time.time_ns() - dur_ns
        request = kind == "compile"
        with self._lock:
            self._drop_traces_since(start_ns)
            self._events.append([kind, str(kw.get("fun_name")), start_ns,
                                 dur_ns])
            self.requests += request
        if request:
            self._requests_total.inc()
            self._seconds_total.inc(duration)

    def _drop_traces_since(self, start_ns: int) -> None:
        """Take out the ``trace`` events that began inside the stage that
        has just ended: the functions it inlined."""
        events, kept = self._events, []
        while events and events[-1][2] >= start_ns:
            event = events.pop()
            if event[0] != "trace":
                kept.append(event)
        events.extend(reversed(kept))

    def _event(self, event: str, **kw) -> None:
        which = CACHE_EVENTS.get(event)
        if which is None:
            return
        with self._lock:
            self.cache[which] += 1
            self._events.append([which, "", time.time_ns(), 0])
        self._cache_totals[which].inc()

    def drain(self) -> list[list]:
        """Every event since the last call, in the order it was reported: a
        stage as ``[kind, fun_name, start_unix_ns, dur_ns]``, a look-up of
        the persistent cache as ``["hits" | "misses", "", unix_ns, 0]``. A
        steady step drains ``[]``, without the lock."""
        if not self._events:
            return []
        with self._lock:
            events = list(self._events)
            self._events.clear()
        return events
