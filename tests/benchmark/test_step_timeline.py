"""The loop's per-step phases as the benchmark reads them: the program-span
readers on hand-made rows, the join with the device trace on a hand-made
capture whose numbers are known, and both through a rehearsed run."""

import json

import jax
import pytest

from benchmarks import harness, run as bench_run
from benchmarks.trace import host_join, reduce
from benchmarks.trace.reduce import Event

#: Unix nanoseconds of the hand-made capture's zero (its profile_start_time)
T0 = 1_790_621_200_310_885_257
PROGRAM_SPAN = ("next_batch_ms", "place_ms", "dispatch_ms", "device_wait_ms",
                "log_ms", "loop_other_ms")
IDLE = tuple(f"idle_{part}_ms" for part in host_join.PARTS)


@pytest.fixture
def readers():
    return harness.load_readers("layer_metrics")


@pytest.fixture(autouse=True)
def _runs_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "RUNS_DIR", tmp_path / "runs")
    monkeypatch.setattr(harness, "enable_caches", lambda: "off (test)")
    host_join.join_run.cache_clear()


# ---------------------------------------------------------------------------
# program spans: hand-made rows
# ---------------------------------------------------------------------------

def _row(step, t_ms, *, log, nxt, place, dispatch, wait, with_phases=True):
    """A row stamped ``t_ms`` whose phases lie back to back before it."""
    row = {"step": step, "time": t_ms / 1e3,
           "step_time_s": (dispatch + wait) / 1e3, "loss": 1.0}
    if with_phases:
        at = int(t_ms * 1e6) - int((log + nxt + place + dispatch + wait) * 1e6)
        row["phases"] = []
        for name, ms in (("host_sync", log), ("next_batch", nxt),
                         ("place", place), ("dispatch", dispatch),
                         ("device_wait", wait)):
            row["phases"].append([name, at, int(ms * 1e6)])
            at += int(ms * 1e6)
    return row


def _observed(**kw):
    """Seven steps 100 ms apart: two warm-up (slow), three in the window,
    two in the traced tail (slow in another way)."""
    base = dict(log=1.0, nxt=0.5, place=4.0, dispatch=10.0, wait=80.0)
    rows = [
        _row(0, 100, **{**base, "dispatch": 90.0, "wait": 1.0}, **kw),
        _row(1, 200, **{**base, "place": 50.0}, **kw),
        _row(2, 300, **base, **kw),
        _row(3, 400, **{**base, "nxt": 0.7, "wait": 82.0}, **kw),
        _row(4, 500, **{**base, "nxt": 0.6, "wait": 81.0}, **kw),
        _row(5, 600, **{**base, "log": 9.0}, **kw),
        _row(6, 700, **{**base, "log": 9.0}, **kw),
    ]
    return {"rows": rows, "window_rows": rows[2:5], "platform": "cpu"}


def test_program_span_readers_take_the_median_over_the_window_only(readers):
    o = _observed()
    got = {name: readers[name](o) for name in PROGRAM_SPAN}
    assert got["next_batch_ms"] == pytest.approx(0.6)
    assert got["place_ms"] == pytest.approx(4.0), "not the warm-up's 50"
    assert got["dispatch_ms"] == pytest.approx(10.0), "not the first step's 90"
    assert got["device_wait_ms"] == pytest.approx(81.0)
    assert got["log_ms"] == pytest.approx(1.0), "not the traced tail's 9"
    # per step: 100 less (1 + .5 + 4 + 10 + 80) = 4.5, then 2.3 and 3.4
    assert got["loop_other_ms"] == pytest.approx(3.4)
    # the six are the window's period (medians of parts)
    assert sum(got.values()) == pytest.approx(100.0)
    assert got["dispatch_ms"] + got["device_wait_ms"] == pytest.approx(
        1e3 * sorted(r["step_time_s"] for r in o["window_rows"])[1])


def test_without_phases_the_program_span_readers_give_nothing(readers):
    o = _observed(with_phases=False)  # what the parent program writes
    assert [readers[name](o) for name in PROGRAM_SPAN] == [None] * 6
    assert [readers[name]({"platform": "tpu"}) for name in PROGRAM_SPAN] == [
        None] * 6, "another driver's cell"


def test_a_phase_the_loop_never_entered_is_left_out(readers):
    o = _observed()
    for r in o["rows"]:  # under a mesh the prefetch thread places
        r["phases"] = [p for p in r["phases"] if p[0] != "place"]
    assert readers["place_ms"](o) is None
    assert readers["next_batch_ms"](o) == pytest.approx(0.6)
    assert readers["loop_other_ms"](o) == pytest.approx(3.4 + 4.0)


# ---------------------------------------------------------------------------
# the join: a hand-made capture
# ---------------------------------------------------------------------------

def hand_made_events() -> list[Event]:
    """Three executions of one program on device 0, 1000 us apart, each 650
    us long with a 50 us hole between its two operations: the device idles
    400 us a period, 350 of them between programs."""
    ev = []
    for k in range(3):
        t = 1000.0 * (k + 1)
        ev += [Event("/device:TPU:0", "XLA Modules", "jit_train_step(1)",
                     t, 650.0, {}),
               Event("/device:TPU:0", "XLA Ops", "%fusion.1 = x", t, 400.0, {}),
               Event("/device:TPU:0", "XLA Ops", "%fusion.2 = x", t + 450,
                     200.0, {})]
    return ev


def hand_made_rows(shift_us: float = 0.0) -> list[dict]:
    """Steps 10, 11, 12 around those programs (microseconds on the trace's
    clock): the program starts 20 us into device_wait and the host wakes 50
    us after it ended; then 60 of logging, 10 of glue under no phase, 30 of
    next_batch, 100 of place, 80 of dispatch."""
    def span(name, lo, hi):
        return [name, T0 + int((lo + shift_us) * 1e3), int((hi - lo) * 1e3)]

    rows = []
    for k, step in enumerate((10, 11, 12)):
        t = 1000.0 * (k + 1)  # this step's program starts here
        phases = []
        if k:
            phases.append(span("host_sync", t - 300, t - 240))
        phases += [span("next_batch", t - 230, t - 200),
                   span("place", t - 200, t - 100),
                   span("dispatch", t - 100, t - 20),
                   span("device_wait", t - 20, t + 700)]
        rows.append({"step": step, "time": (T0 / 1e3 + t + 705) / 1e6,
                     "step_time_s": 800e-6, "loss": 1.0, "phases": phases})
    return rows


def test_each_gap_is_split_at_the_phase_and_program_boundaries():
    j = host_join.join(hand_made_events(), T0, hand_made_rows())
    assert j["periods"] == 2 and j["window_ms"] == pytest.approx(2.0)
    assert j["idle_total_ms"] == pytest.approx(0.8)
    # per step: the gap between programs is 350 us = 50 wake-up + 60 log +
    # 10 under no phase + 130 input + 80 dispatch + 20 launch; the 50 us
    # hole inside the running program is no phase's either
    assert j["idle_ms"] == pytest.approx(
        {"input": 0.130, "dispatch": 0.080, "launch": 0.020, "wakeup": 0.050,
         "log": 0.060, "unnamed": 0.010 + 0.050})
    assert j["in_program_ms"] == pytest.approx(0.050)
    assert sum(j["idle_ms"].values()) == pytest.approx(
        j["idle_total_ms"] / j["periods"])
    # what reduce_events calls idle, to the microsecond
    r = reduce.reduce_events(hand_made_events())
    assert sum(j["idle_ms"].values()) == pytest.approx(
        r["idle_pct"] / 100 * r["window_s"] * 1e3 / r["periods"])
    check = j["clock_check"]
    assert check["ordered"] is True
    assert [c["step"] for c in check["steps"]] == [10, 11, 12]
    assert all(c["wakeup_ms"] == pytest.approx(0.050)
               and c["program_start_after_dispatch_began_ms"]
               == pytest.approx(0.100)
               and c["program_start_after_dispatch_ended_ms"]
               == pytest.approx(0.020) for c in check["steps"])
    assert check["wakeup_spread_ms"] == pytest.approx(0.0, abs=1e-9)


def test_a_gap_under_no_phase_is_unnamed():
    rows = hand_made_rows()
    for r in rows:  # the loop logged nothing and placed nothing
        r["phases"] = [p for p in r["phases"]
                       if p[0] not in ("host_sync", "place")]
    j = host_join.join(hand_made_events(), T0, rows)
    assert j["idle_ms"] == pytest.approx(
        {"input": 0.030, "dispatch": 0.080, "launch": 0.020, "wakeup": 0.050,
         "log": 0.0, "unnamed": 0.010 + 0.050 + 0.060 + 0.100})


@pytest.mark.parametrize("shift_us, why", [
    (200.0, "the program would start before its dispatch began"),
    (-800.0, "the program would end after its device_wait ended")])
def test_clocks_that_do_not_line_up_fail_the_check(shift_us, why):
    j = host_join.join(hand_made_events(), T0, hand_made_rows(shift_us))
    assert j["clock_check"]["ordered"] is False, why
    # the parts still add up; the readers are the ones that refuse
    assert sum(j["idle_ms"].values()) == pytest.approx(0.4)


# ---------------------------------------------------------------------------
# the join: from the files of a run
# ---------------------------------------------------------------------------

XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0 %(modules)s }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0 %(ops)s }
  event_metadata { key: 1 value { id: 1 name: "jit_train_step(1)" } }
  event_metadata { key: 2 value { id: 2 name: "%%fusion.1 = x" } }
}
planes {
  id: 2 name: "Task Environment"
  stat_metadata { key: 1 value { id: 1 name: "profile_start_time" } }
  stats { metadata_id: 1 uint64_value: %(start)d }
}
"""


def write_hand_made_run(name: str, rows, *, start=T0) -> None:
    """A run directory as the train driver leaves it: ``metrics.jsonl`` and
    a real ``.xplane.pb`` (from a text proto) holding hand_made_events() and
    a ``Task Environment`` plane with ``profile_start_time``."""
    def events(line, metadata_id):
        return " ".join(
            f"events {{ metadata_id: {metadata_id} "
            f"offset_ps: {int(e.start_us * 1e6)} "
            f"duration_ps: {int(e.dur_us * 1e6)} }}"
            for e in hand_made_events() if e.line == line)

    text = XSPACE % {"modules": events("XLA Modules", 1),
                     "ops": events("XLA Ops", 2), "start": start}
    run_dir = harness.RUNS_DIR / name
    capture = run_dir / "profile" / "plugins" / "profile" / "2026_01_01"
    capture.mkdir(parents=True)
    (capture / "host.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    (run_dir / "metrics.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in rows))


def test_idle_readers_find_the_runs_capture_through_its_first_row(readers,
                                                                  capsys):
    rows = hand_made_rows()
    other = hand_made_rows()
    other[0]["time"] += 1.0
    write_hand_made_run("another-run", other, start=T0 + 5_000)
    write_hand_made_run("this-run", rows)
    assert host_join.find_run_dir(rows[0]) == harness.RUNS_DIR / "this-run"
    assert host_join.profile_start_unix_ns(reduce.find_profile_file(
        harness.RUNS_DIR / "this-run" / "profile", ".xplane.pb")) == T0

    o = {"rows": rows, "window_rows": rows[:1], "platform": "tpu"}
    got = {name: readers[name](o) for name in IDLE}
    assert got == pytest.approx(
        {"idle_input_ms": 0.130, "idle_dispatch_ms": 0.080,
         "idle_launch_ms": 0.020, "idle_wakeup_ms": 0.050,
         "idle_log_ms": 0.060, "idle_unnamed_ms": 0.060})
    logged = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [e["event"] for e in logged] == ["host_join"], "joined once for six"
    assert logged[0]["clock_check"]["ordered"] is True
    assert logged[0]["profile_start_unix_ns"] == T0

    # off the TPU no device_trace reader gives a number
    assert [readers[name]({**o, "platform": "cpu"}) for name in IDLE] == [
        None] * 6
    manifest = harness.load_manifest()
    assert {m["name"] for m in manifest["per_layer"]
            if m["source"] == "device_trace"} >= set(IDLE)


@pytest.mark.parametrize("case", ["no_phases", "no_run_dir", "no_capture",
                                  "no_profile_start_time", "clock_check"])
def test_idle_readers_give_nothing_where_the_join_cannot_be_made(readers,
                                                                 case):
    rows = hand_made_rows(200.0 if case == "clock_check" else 0.0)
    if case == "no_phases":  # the parent program's rows
        for r in rows:
            del r["phases"]
    if case != "no_run_dir":
        write_hand_made_run("this-run", rows)
    run_dir = harness.RUNS_DIR / "this-run"
    if case == "no_capture":
        reduce.find_profile_file(run_dir / "profile", ".xplane.pb").unlink()
    if case == "no_profile_start_time":
        xplane = reduce.find_profile_file(run_dir / "profile", ".xplane.pb")
        xplane.write_bytes(
            jax.profiler.ProfileData.text_proto_to_serialized_xspace(
                'planes { id: 1 name: "/device:TPU:0" }'))
    o = {"rows": rows, "window_rows": rows[:1], "platform": "tpu"}
    assert [readers[name](o) for name in IDLE] == [None] * 6


# ---------------------------------------------------------------------------
# end to end: a rehearsed run
# ---------------------------------------------------------------------------

def test_a_rehearsed_run_carries_phases_and_the_join_reads_its_capture(capsys):
    cell = "siglip_b16_256.train"
    rc = bench_run.main(["--workload", cell, "--seed", "2147483659",
                         "--seconds", "2", "--trace", "1", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0, out[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert set(PROGRAM_SPAN) <= set(line["metrics"])
    assert not set(IDLE) & set(line["metrics"]), (
        "a CPU run never prints under a device metric's name")

    run_dir = harness.RUNS_DIR / f"{cell}-s2147483659-t1"
    rows = [json.loads(r)
            for r in (run_dir / "metrics.jsonl").read_text().splitlines()]
    assert all("phases" in r for r in rows)
    for r in rows:  # on a loaded CPU medians of parts say little: row by row
        assert sum(dur for name, _, dur in r["phases"]
                   if name in ("dispatch", "device_wait")) / 1e9 == (
            pytest.approx(r["step_time_s"], abs=5e-3))
    assert host_join.find_run_dir(rows[0]) == run_dir
    j = host_join.join_run(run_dir)
    # the capture began while the loop ran: its zero lies inside the rows
    assert rows[0]["time"] * 1e9 < j["profile_start_unix_ns"] < (
        rows[-1]["time"] * 1e9)
    assert j["idle_total_ms"] > 0
    assert sum(j["idle_ms"].values()) == pytest.approx(
        j["idle_total_ms"] / j["periods"])
    # a CPU capture has no program line, so no step's program to check by
    assert j["clock_check"]["steps"] == []
    # yet the clocks do line up: some of the idle time lies under a phase
    # (a zero that is off leaves every gap under none)
    assert j["idle_ms"]["unnamed"] < sum(j["idle_ms"].values())
