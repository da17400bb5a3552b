"""Tests for jimm_tpu.obs.prof — the continuous-profiling capture ring,
the HBM watchdog, the jax-free op-stats diff — plus the satellite pieces
that ride on them: the byte-bounded serve trace ring, rotation-surviving
``obs tail --follow``, and the prof lane on the incident timeline.

Every test injects a fake profiler/sampler, so nothing here starts a real
``jax.profiler`` session or needs a device.
"""

import io
import json
import os
import threading
import time

import pytest

from jimm_tpu.obs.journal import EventJournal
from jimm_tpu.obs.prof.capture import (CaptureManager, configure_capture,
                                       list_captures, maybe_trigger,
                                       reset_capture)
from jimm_tpu.obs.prof.memory import MemoryMonitor
from jimm_tpu.obs.prof.opstats import diff_ops, top_ops


class FakeProfiler:
    """Writes a deterministic payload instead of a real xplane capture."""

    def __init__(self, payload_bytes: int = 512):
        self.payload_bytes = payload_bytes
        self.active_dir = None
        self.sessions = 0

    def start(self, log_dir: str) -> None:
        assert self.active_dir is None, "double start"
        self.active_dir = log_dir
        self.sessions += 1

    def stop(self) -> None:
        assert self.active_dir is not None, "stop without start"
        os.makedirs(self.active_dir, exist_ok=True)
        with open(os.path.join(self.active_dir, "fake.xplane.pb"),
                  "wb") as f:
            f.write(b"x" * self.payload_bytes)
        self.active_dir = None


def make_manager(tmp_path, **kw):
    journal = EventJournal()  # memory-only ring
    kw.setdefault("profiler", FakeProfiler())
    kw.setdefault("min_trigger_interval_s", 0.0)
    mgr = CaptureManager(tmp_path / "ring", journal=journal, **kw)
    return mgr, journal


def journal_events(journal, name=None):
    recs = list(journal._ring)
    return [r for r in recs if name is None or r["event"] == name]


class TestCaptureManager:
    def test_ring_windows_commit_on_schedule(self, tmp_path):
        mgr, journal = make_manager(tmp_path, every_steps=10, window_steps=2)
        for step in range(35):
            mgr.on_step(step)
        metas = mgr.ls()
        # windows open at steps 2/12/22/32 (offset 2: past compile) and
        # commit two steps later
        assert [m["kind"] for m in metas] == ["window"] * 4
        assert all(m["name"].startswith("cap-") for m in metas)
        # every committed capture journaled a started/committed pair on
        # one cid, with a dur_s the timeline can render as a span
        started = journal_events(journal, "prof_capture_started")
        committed = journal_events(journal, "prof_capture_committed")
        assert len(started) == len(committed) == 4
        for s, c in zip(started, committed):
            assert s["cid"] == c["cid"]
            assert c["dur_s"] >= 0
            assert c["bytes"] > 0

    def test_trigger_deep_capture_tags_cid_and_dedupes(self, tmp_path):
        mgr, journal = make_manager(tmp_path, every_steps=0,
                                    deep_window_s=0.02)
        meta = mgr.trigger("c-incident", "heal")
        assert meta is not None and meta["kind"] == "deep"
        assert meta["cid"] == "c-incident"
        # second trigger on the same incident is suppressed: one deep
        # capture per incident is the useful artifact
        assert mgr.trigger("c-incident", "replan") is None
        deadline = time.monotonic() + 2.0
        while not mgr.ls() and time.monotonic() < deadline:
            time.sleep(0.005)
        metas = mgr.ls()
        assert len(metas) == 1 and metas[0]["cid"] == "c-incident"
        committed = journal_events(journal, "prof_capture_committed")
        assert len(committed) == 1 and committed[0]["cid"] == "c-incident"

    def test_byte_budget_evicts_oldest(self, tmp_path):
        mgr, _ = make_manager(tmp_path, every_steps=0,
                              profiler=FakeProfiler(payload_bytes=1000),
                              max_ring_bytes=2500)
        for i in range(4):
            assert mgr.start("window", step=i) is not None
            mgr.commit()
        metas = mgr.ls()
        # 4 x ~1000B captures under a 2500B budget: oldest evicted first,
        # the newest always survives
        assert 1 <= len(metas) < 4
        seqs = [m["seq"] for m in metas]
        assert seqs == sorted(seqs) and seqs[-1] == 4
        assert 1 not in seqs
        assert mgr.ring_bytes() <= 2500

    def test_leftover_tmp_quarantined_not_deleted(self, tmp_path):
        root = tmp_path / "ring"
        stale = root / "cap-000007-window.tmp"
        stale.mkdir(parents=True)
        (stale / "partial.pb").write_bytes(b"wreck")
        mgr, _ = make_manager(tmp_path)
        assert mgr.ls() == []
        qdir = root / "quarantine"
        moved = list(qdir.glob("*/partial.pb"))
        assert len(moved) == 1 and moved[0].read_bytes() == b"wreck"

    def test_global_maybe_trigger_is_noop_unconfigured(self, tmp_path):
        reset_capture()
        try:
            os.environ.pop("JIMM_PROF_DIR", None)
            assert maybe_trigger("c-x", "heal") is None
            configure_capture(tmp_path / "g", profiler=FakeProfiler(),
                              min_trigger_interval_s=0.0, deep_window_s=0.01)
            meta = maybe_trigger("c-x", "heal")
            assert meta is not None and meta["cid"] == "c-x"
        finally:
            reset_capture()


class TestMemoryMonitor:
    def test_leak_watchdog_one_record_per_episode(self, tmp_path):
        journal = EventJournal()
        rows = {"bytes": 0.0}

        def sampler():
            return [{"device": 0, "source": "fake",
                     "bytes_in_use": rows["bytes"],
                     "peak_bytes_in_use": rows["bytes"],
                     "bytes_limit": 1 << 30, "fragmentation": 0.0}]

        mon = MemoryMonitor(leak_window=3, leak_min_growth_frac=0.01,
                            leak_min_growth_bytes=1000, journal=journal,
                            sampler=sampler)
        mon.register_subsystem("serve_buffers", lambda: 42.0)
        # monotonic growth across the window -> exactly one record,
        # a dip closes the episode, renewed growth opens a second
        for b in (1000, 2000, 3000, 4000, 5000, 1000, 2000, 3000, 4000,
                  5000):
            rows["bytes"] = float(b)
            mon.sample()
        leaks = journal_events(journal, "hbm_leak_suspected")
        assert len(leaks) == 2
        assert all(r["cid"] for r in leaks)
        assert leaks[0]["cid"] != leaks[1]["cid"]
        assert leaks[0]["growth_bytes"] > 0
        from jimm_tpu.obs import get_registry
        snap = get_registry("jimm_hbm").snapshot()
        assert snap["device0_bytes_in_use"] == 5000.0
        assert snap["subsystem_serve_buffers_bytes"] == 42.0

    def test_raising_subsystem_reports_zero(self):
        mon = MemoryMonitor(sampler=lambda: [], journal=EventJournal())

        def boom():
            raise RuntimeError("index offline")

        mon.register_subsystem("retrieval_index", boom)
        report = mon.sample()
        assert report["subsystems"]["retrieval_index"] == 0.0


class TestOpStatsDiff:
    ROWS_BEFORE = [
        {"name": "fusion.1", "category": "fusion", "total_us": 100.0,
         "count": 10, "bytes_accessed": 1000, "long_name": "f1"},
        {"name": "copy.2", "category": "copy", "total_us": 50.0,
         "count": 5, "bytes_accessed": 500, "long_name": "c2"},
        {"name": "gone.3", "category": "fusion", "total_us": 20.0,
         "count": 2, "bytes_accessed": 0, "long_name": "g3"},
    ]

    def test_direction_aware_verdict(self):
        after = [
            dict(self.ROWS_BEFORE[0], total_us=300.0),   # 3x slower
            dict(self.ROWS_BEFORE[1], total_us=30.0),    # 40% faster
            {"name": "new.4", "category": "fusion", "total_us": 5.0,
             "count": 1, "bytes_accessed": 0, "long_name": "n4"},
        ]
        d = diff_ops(self.ROWS_BEFORE, after, threshold=0.10)
        # verdict keys on TOTAL device-op time (the step-time proxy)
        assert d["verdict"] == "regression"
        assert d["total_delta_frac"] > 0.10
        assert [r["name"] for r in d["regressions"]] == ["fusion.1"]
        assert [r["name"] for r in d["improvements"]] == ["copy.2"]
        assert [r["name"] for r in d["added"]] == ["new.4"]
        assert [r["name"] for r in d["removed"]] == ["gone.3"]
        # time is lower-better: total going DOWN must not be a regression
        d2 = diff_ops(self.ROWS_BEFORE, self.ROWS_BEFORE, threshold=0.10)
        assert d2["verdict"] == "ok" and not d2["regressions"]

    def test_top_ops_by_bytes(self):
        rows = top_ops(self.ROWS_BEFORE, k=2, by="bytes_accessed")
        assert [r["name"] for r in rows] == ["fusion.1", "copy.2"]


class TestServeTraceRingBudget:
    """Satellite: recent_traces is byte-bounded, not just entry-bounded."""

    def _engine(self, **kw):
        from jimm_tpu.serve import BucketTable, InferenceEngine
        return InferenceEngine(lambda b: b, item_shape=(3,),
                               buckets=BucketTable((1, 2)), **kw)

    def test_byte_budget_drops_oldest_and_counts(self):
        engine = self._engine(recent_traces_entries=1000,
                              recent_traces_max_bytes=2048)
        row = {"trace_id": "t", "replica": 0, "bucket": 1,
               "queue_s": 0.001, "pad_s": 0.0, "device_s": 0.002,
               "readback_s": 0.0, "total_s": 0.003, "done_mono": 1.0,
               "note": "x" * 100}
        for i in range(100):
            engine._record_trace(dict(row, trace_id=f"t{i:03d}"))
        assert engine._traces_bytes <= 2048
        assert len(engine.recent_traces) < 100
        # newest survive, oldest dropped, and the drop is observable
        assert engine.recent_traces[-1]["trace_id"] == "t099"
        snap = engine.metrics.snapshot()
        dropped = snap["traces_dropped_total"]
        assert dropped == 100 - len(engine.recent_traces)
        assert snap["recent_traces_bytes"] == float(engine._traces_bytes)

    def test_single_oversized_row_is_kept(self):
        # the ring never evicts down to empty: the newest row always
        # survives even when it alone exceeds the budget
        engine = self._engine(recent_traces_max_bytes=64)
        engine._record_trace({"trace_id": "big", "note": "x" * 500})
        assert len(engine.recent_traces) == 1


class TestTailRotation:
    """Satellite: ``obs tail --follow`` survives journal rotation."""

    def test_follow_survives_rotation(self, tmp_path):
        from jimm_tpu.obs.cli import _tail_jsonl
        path = tmp_path / "journal.jsonl"
        journal = EventJournal(path, max_bytes=300, max_segments=3)
        journal.emit("before_rotation", phase="a")
        out = io.StringIO()
        state = {"polls": 0}

        def fake_sleep(_):
            state["polls"] += 1
            if state["polls"] == 1:
                # force rotation: pad past max_bytes so the live file is
                # renamed aside and recreated under the follower
                for i in range(8):
                    journal.emit("filler", i=i, pad="x" * 64)
                journal.emit("after_rotation", phase="b")

        rc = _tail_jsonl(str(path), follow=True, sleep=fake_sleep,
                         should_stop=lambda: state["polls"] >= 5, out=out)
        assert rc == 0
        text = out.getvalue()
        assert "before_rotation" in text
        # the follower reopened the recreated file and saw post-rotation
        # records — the old behavior read EOF on the renamed segment
        # forever
        assert "after_rotation" in text
        assert (tmp_path / "journal.1.jsonl").exists()

    def test_no_follow_reads_once_and_exits(self, tmp_path):
        from jimm_tpu.obs.cli import _tail_jsonl
        path = tmp_path / "m.jsonl"
        path.write_text(json.dumps({"ts": "t", "phase": "p", "v": 1}) + "\n")
        out = io.StringIO()
        assert _tail_jsonl(str(path), follow=False, out=out) == 0
        assert "[p] v=1" in out.getvalue()


class TestTimelineProfLane:
    """Satellite: a deep capture overlapping a replan renders on a shared
    clock — prof, serve, and goodput lanes in one trace, the capture span
    carrying the incident cid."""

    def test_deep_capture_overlaps_replan_on_shared_clock(self, tmp_path):
        from jimm_tpu.obs.timeline import (export_timeline,
                                           validate_chrome_trace)
        cid = "c-incident-7"
        # replan spans mono 10.0..10.4 (journal); the deep capture the
        # replan triggered spans 10.1..10.35 (capture meta); both stamped
        # from the same time.monotonic() clock
        events = [
            {"seq": 0, "ts": "t", "mono": 10.0, "event": "replan_started",
             "cid": cid},
            {"seq": 1, "ts": "t", "mono": 10.1, "event":
             "prof_capture_started", "cid": cid, "kind": "deep"},
            {"seq": 2, "ts": "t", "mono": 10.35, "event":
             "prof_capture_committed", "cid": cid, "kind": "deep",
             "dur_s": 0.25, "bytes": 4096},
            {"seq": 3, "ts": "t", "mono": 10.4, "event": "replan_done",
             "cid": cid, "dur_s": 0.4},
        ]
        captures = [{"seq": 1, "name": "cap-000001-deep", "kind": "deep",
                     "cid": cid, "reason": "replan", "step": None,
                     "ts": "t", "start_mono": 10.1, "end_mono": 10.35,
                     "dur_s": 0.25, "bytes": 4096}]
        goodput = {"step": 0.3, "replan": 0.1}
        trace = export_timeline(events, captures=captures, goodput=goodput)
        assert validate_chrome_trace(trace) == []
        by_lane = {}
        for ev in trace["traceEvents"]:
            if ev.get("ph") != "M":
                by_lane.setdefault(ev["tid"], []).append(ev)
        assert {"serve", "prof", "goodput"} <= set(by_lane)
        # the capture meta's span on the prof lane carries the incident
        # cid and sits inside the replan window on the shared clock
        cap = [e for e in by_lane["prof"] if e["ph"] == "X"
               and e["name"] == "capture:deep"]
        assert len(cap) == 1
        assert cap[0]["args"]["cid"] == cid
        replan = [e for e in by_lane["serve"]
                  if e["name"] == "replan_done"][0]
        assert replan["ts"] <= cap[0]["ts"]
        assert cap[0]["ts"] + cap[0]["dur"] \
            <= replan["ts"] + replan["dur"] + 1e-6
        # journal prof_* events land on the prof lane too
        assert any(e["name"] == "prof_capture_committed"
                   for e in by_lane["prof"])


class TestEngineTriggerWiring:
    """Incident paths call maybe_trigger with their cid (no-op here until a
    manager is configured; then a deep capture appears on that cid)."""

    def test_heal_and_replan_reasons_reach_manager(self, tmp_path):
        from jimm_tpu.serve.engine import _prof_trigger
        reset_capture()
        try:
            mgr = configure_capture(tmp_path / "ring",
                                    profiler=FakeProfiler(),
                                    min_trigger_interval_s=0.0,
                                    deep_window_s=0.01)
            _prof_trigger("c-heal-1", "heal")
            deadline = time.monotonic() + 2.0
            while not mgr.ls() and time.monotonic() < deadline:
                time.sleep(0.005)
            metas = mgr.ls()
            assert [m["cid"] for m in metas] == ["c-heal-1"]
            assert metas[0]["reason"] == "heal"
        finally:
            reset_capture()

    def test_trigger_never_raises_without_manager(self):
        from jimm_tpu.serve.engine import _prof_trigger
        reset_capture()
        try:
            os.environ.pop("JIMM_PROF_DIR", None)
            _prof_trigger("c-x", "slo_fast_burn")  # must be a silent no-op
        finally:
            reset_capture()


class TestJaxProfilerOptions:
    """The one sanctioned ``start_trace``: what ``--profile-dir`` and
    ``--prof-ring`` captures are taken with."""

    @pytest.mark.parametrize("backend, host_tracer_level",
                             [("tpu", 0), ("cpu", 2)])
    def test_python_tracer_off_and_on_a_tpu_the_host_tracer_too(
            self, monkeypatch, tmp_path, backend, host_tracer_level):
        import jax

        from jimm_tpu.obs.prof.capture import _JaxProfiler
        started = []
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda log_dir, **kw: started.append((log_dir, kw)))
        _JaxProfiler().start(str(tmp_path))
        (log_dir, kw), = started
        assert log_dir == str(tmp_path) and set(kw) == {"profiler_options"}
        options = kw["profiler_options"]
        assert isinstance(options, jax.profiler.ProfileOptions)
        assert options.python_tracer_level == 0
        assert options.host_tracer_level == host_tracer_level
