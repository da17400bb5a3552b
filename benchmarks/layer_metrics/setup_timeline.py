"""Every cell: where ``setup_s`` goes.

Since PR 36 ``jimm_tpu.cli train`` is timed from its first statement: the
first ``--metrics-file`` row's ``phases`` begins with the set-up spans
(``jimm_tpu/obs/goodput.py::SETUP_PHASES``: ``imports``, ``backend_init``,
``model_build``, ``optimizer_build``, ``data_build``; a resume's
``checkpoint``), and each row carries, where there is any, ``compiles``
(``[kind, fun_name, start_unix_ns, dur_ns]`` of every trace, lowering and
backend compile or cache load since the row before:
``jimm_tpu/obs/compiles.py``) and ``cache_hits`` / ``cache_misses``.

The readers split the cell's ``setup_s`` (process start to the first measured
step: ``end_to_end/host_clock.py``) on that one clock:

    setup_before_train_s                    the benchmark's own start
    + setup_imports_s + setup_backend_init_s + setup_model_build_s
    + setup_optimizer_build_s + setup_data_build_s       the program's phases
    + setup_first_step_s                    step 0's dispatch + device_wait
    + setup_warmup_s                        row 0's stamp to the window
    + setup_unnamed_s                       what no span covers
    = setup_s

``setup_build_programs_s`` lies inside the two build phases, and
``setup_step_trace_lower_s`` / ``setup_step_compile_s`` inside step 0's
``dispatch``: they are not summed again. (The program keeps no stage that
began inside another, so a stretch is the sum of its events.)

They read the host's clock and the program's rows, never the device, so a
rehearsal reports them too. A reader returns None where row 0 carries no
set-up phase (an earlier program), and the harness leaves the metric out.
"""

from __future__ import annotations

import functools

#: ``jimm_tpu/obs/goodput.py::SETUP_PHASES``, spelled out: these readers also
#: run on a program that has no such name (the parent commit's)
SETUP_PHASES = ("imports", "backend_init", "model_build", "optimizer_build",
                "data_build")
BUILD_PHASES = ("model_build", "optimizer_build")


def _row0(o: dict) -> dict | None:
    """The first row, if it is a program's that times its set-up."""
    rows = o.get("rows")
    if not rows or not any(name in SETUP_PHASES
                           for name, _, _ in rows[0].get("phases", ())):
        return None
    return rows[0]


def _spans(row: dict, *names: str) -> list[tuple[int, int]]:
    """``(start_unix_ns, dur_ns)`` of the row's phases of these names."""
    return [(start, dur) for name, start, dur in row["phases"]
            if name in names]


def _seconds(spans) -> float:
    return sum(dur for _, dur in spans) / 1e9


def _setup_rows(o: dict) -> list[dict]:
    """The rows written up to the first measured step."""
    return [r for r in o["rows"] if r["time"] <= o["t_first_measured"]]


def _events_in(o: dict, spans, kinds) -> list[tuple[int, int]]:
    """``(start_unix_ns, dur_ns)`` of the set-up rows' compile events of
    these kinds whose start lies inside one of ``spans``."""
    return [(start, dur)
            for r in _setup_rows(o)
            for kind, _, start, dur in r.get("compiles", ())
            if kind in kinds
            and any(lo <= start < lo + length for lo, length in spans)]


def before_train_s(o: dict) -> float | None:
    row = _row0(o)
    if row is None:
        return None
    first = min(start for start, _ in _spans(row, *SETUP_PHASES))
    return first / 1e9 - o["t_process_start"]


def phase_s(o: dict, phase: str) -> float | None:
    row = _row0(o)
    return None if row is None else _seconds(_spans(row, phase))


def build_programs_s(o: dict) -> float | None:
    row = _row0(o)
    if row is None:
        return None
    return _seconds(_events_in(o, _spans(row, *BUILD_PHASES), ("compile",)))


def first_step_s(o: dict) -> float | None:
    row = _row0(o)
    if row is None:
        return None
    return _seconds(_spans(row, "dispatch", "device_wait"))


def step_stage_s(o: dict, kinds: tuple[str, ...]) -> float | None:
    row = _row0(o)
    if row is None:
        return None
    return _seconds(_events_in(o, _spans(row, "dispatch"), kinds))


def warmup_s(o: dict) -> float | None:
    row = _row0(o)
    return None if row is None else o["t_first_measured"] - row["time"]


NAMED = {
    "setup_before_train_s": before_train_s,
    **{f"setup_{phase}_s": functools.partial(phase_s, phase=phase)
       for phase in SETUP_PHASES},
    "setup_first_step_s": first_step_s,
    "setup_warmup_s": warmup_s,
}


def unnamed_s(o: dict) -> float | None:
    if _row0(o) is None:
        return None
    setup_s = o["t_first_measured"] - o["t_process_start"]
    return setup_s - sum(reader(o) for reader in NAMED.values())


def programs(o: dict) -> float | None:
    if _row0(o) is None:
        return None
    return sum(kind == "compile" for r in _setup_rows(o)
               for kind, _, _, _ in r.get("compiles", ()))


def cache_misses(o: dict) -> float | None:
    if _row0(o) is None:
        return None
    return sum(r.get("cache_misses", 0) for r in _setup_rows(o))


READERS = {
    **NAMED,
    "setup_build_programs_s": build_programs_s,
    "setup_step_trace_lower_s": functools.partial(
        step_stage_s, kinds=("trace", "lower")),
    "setup_step_compile_s": functools.partial(step_stage_s,
                                              kinds=("compile",)),
    "setup_unnamed_s": unnamed_s,
    "setup_programs": programs,
    "setup_cache_misses": cache_misses,
}
