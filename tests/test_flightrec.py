"""Flight-recorder tests: journal, timeline export and SLO burn rates,
including the crash-shaped edge cases (rotation mid-write, truncated tails,
empty/partial timelines, zero-traffic burn windows, single-sample
percentiles)."""

import json

import pytest

from jimm_tpu.obs.journal import (EventJournal, chain, configure_journal,
                                  correlate, current_cid, get_journal,
                                  new_correlation_id, read_events,
                                  reset_journal)
from jimm_tpu.obs.registry import Histogram, MetricRegistry, percentile
from jimm_tpu.obs.slo import SloEngine, SloObjective
from jimm_tpu.obs.timeline import (export_timeline, journal_to_trace_events,
                                   traces_to_trace_events,
                                   validate_chrome_trace, write_timeline)


@pytest.fixture
def fresh_global_journal():
    """Give the test an isolated memory-only global journal."""
    j = configure_journal(None)
    yield j
    reset_journal()


# ---------------------------------------------------------------------------
# journal
# ---------------------------------------------------------------------------

class TestJournal:
    def test_emit_record_shape_and_seq(self):
        j = EventJournal()
        a = j.emit("preempt_detected", cid="c1", step=7)
        b = j.emit("grace_save_committed", cid="c1", dur_s=0.5)
        assert a["seq"] == 0 and b["seq"] == 1
        assert a["event"] == "preempt_detected" and a["step"] == 7
        assert a["cid"] == "c1" and "ts" in a and "mono" in a
        assert b["mono"] >= a["mono"]
        assert [r["event"] for r in j.tail(10)] == [
            "preempt_detected", "grace_save_committed"]

    def test_concurrent_emit_seq_matches_ring_order(self):
        # regression (JL017): seq was minted outside the journal lock, so
        # two threads could append to the ring in the opposite order of
        # their seq values; readers treat seq as the total order
        import threading

        n_threads, per_thread = 8, 200
        j = EventJournal(ring=n_threads * per_thread)
        start = threading.Barrier(n_threads)

        def hammer(tid):
            start.wait()
            for i in range(per_thread):
                j.emit("hammer", tid=tid, i=i)

        threads = [threading.Thread(target=hammer, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        tail = j.tail(n_threads * per_thread)
        seqs = [r["seq"] for r in tail]
        assert seqs == sorted(seqs), "ring order must equal seq order"
        assert len(set(seqs)) == len(seqs) == n_threads * per_thread

    def test_correlation_ids_unique_and_ambient(self):
        assert new_correlation_id() != new_correlation_id()
        j = EventJournal()
        assert current_cid() is None
        with correlate("inc-1"):
            assert current_cid() == "inc-1"
            inherited = j.emit("checkpoint_restored", step=3)
            explicit = j.emit("other", cid="inc-2")
        outside = j.emit("standalone")
        assert inherited["cid"] == "inc-1"
        assert explicit["cid"] == "inc-2"
        assert outside["cid"] is None
        # correlate(None) is a no-op block, not a crash
        with correlate(None):
            assert current_cid() is None

    def test_chain_filters_one_incident_in_order(self):
        j = EventJournal()
        j.emit("replica_fault", cid="i1", replica=0)
        j.emit("unrelated")
        j.emit("replica_fenced", cid="i1")
        j.emit("replica_fault", cid="i2", replica=1)
        j.emit("heal_rebuilt", cid="i1", dur_s=0.1)
        got = [e["event"] for e in j.chain("i1")]
        assert got == ["replica_fault", "replica_fenced", "heal_rebuilt"]
        assert chain(j.events(), "i2")[0]["replica"] == 1

    def test_persistence_and_tolerant_read(self, tmp_path):
        path = tmp_path / "journal.jsonl"
        j = EventJournal(path)
        j.emit("a", x=1)
        j.emit("b", x=2)
        j.close()
        # crash mid-write: a truncated final line plus log noise
        with open(path, "a", encoding="utf-8") as f:
            f.write('{"seq": 2, "event": "tru')
        events = read_events(path)
        assert [e["event"] for e in events] == ["a", "b"]
        # and a journal reopened on the same path appends, not truncates
        j2 = EventJournal(path)
        j2.emit("c")
        j2.close()
        assert [e["event"] for e in read_events(path)] == ["a", "b", "c"]

    def test_rotation_mid_write_preserves_every_record(self, tmp_path):
        path = tmp_path / "journal.jsonl"
        j = EventJournal(path, max_bytes=512, max_segments=3)
        n = 40
        for i in range(n):
            j.emit("tick", i=i, pad="x" * 64)
        j.close()
        segments = sorted(p.name for p in tmp_path.iterdir())
        assert "journal.jsonl" in segments and "journal.1.jsonl" in segments
        assert len(segments) <= 4  # live + max_segments rotated
        events = read_events(path)
        # rotation drops only whole oldest segments, never mid-record
        assert all(e["event"] == "tick" for e in events)
        got = [e["i"] for e in events]
        assert got == sorted(got)
        assert got[-1] == n - 1
        for line in path.read_text().splitlines():
            assert json.loads(line)  # every surviving line parses whole

    def test_ring_survives_without_path_and_bounds_memory(self):
        j = EventJournal(ring=8)
        for i in range(20):
            j.emit("e", i=i)
        assert [r["i"] for r in j.events()] == list(range(12, 20))

    def test_global_journal_env_config(self, tmp_path, monkeypatch):
        reset_journal()
        target = tmp_path / "j.jsonl"
        monkeypatch.setenv("JIMM_JOURNAL", str(target))
        try:
            get_journal().emit("from_env")
            assert [e["event"] for e in read_events(target)] == ["from_env"]
        finally:
            reset_journal()

    def test_configure_journal_replaces_global(self, fresh_global_journal):
        assert get_journal() is fresh_global_journal
        fresh_global_journal.emit("one")
        assert get_journal().tail(5)[0]["event"] == "one"


# ---------------------------------------------------------------------------
# timeline
# ---------------------------------------------------------------------------

class TestTimeline:
    def test_empty_journal_exports_valid_trace(self, tmp_path):
        trace = export_timeline([])
        assert validate_chrome_trace(trace) == []
        assert all(e["ph"] == "M" for e in trace["traceEvents"])
        out = write_timeline(tmp_path / "t.json", trace)
        assert json.loads(out.read_text())["displayTimeUnit"] == "ms"

    def test_partial_records_without_mono_are_skipped(self):
        events = [{"event": "ok", "mono": 10.0, "seq": 0},
                  {"event": "truncated", "seq": 1},          # no mono
                  {"event": "corrupt", "mono": "nan?"}]      # bad mono
        tev = journal_to_trace_events(events)
        assert [e["name"] for e in tev] == ["ok"]
        assert validate_chrome_trace(export_timeline(events)) == []

    def test_instant_vs_span_and_lanes(self):
        events = [
            {"event": "preempt_detected", "mono": 100.0, "cid": "c1"},
            {"event": "grace_save_committed", "mono": 101.0, "cid": "c1",
             "dur_s": 0.5},
            {"event": "replica_fenced", "mono": 100.2, "cid": "c2"},
            {"event": "advisor_decision", "mono": 100.3},
            {"event": "custom_thing", "mono": 100.4},
        ]
        tev = {e["name"]: e for e in journal_to_trace_events(events)}
        assert tev["preempt_detected"]["ph"] == "i"
        assert tev["preempt_detected"]["ts"] == 0.0
        assert tev["preempt_detected"]["tid"] == "train"
        span = tev["grace_save_committed"]
        assert span["ph"] == "X" and span["dur"] == pytest.approx(5e5)
        # the span is placed backwards from its end stamp
        assert span["ts"] == pytest.approx((101.0 - 0.5 - 100.0) * 1e6)
        assert tev["replica_fenced"]["tid"] == "serve"
        assert tev["advisor_decision"]["tid"] == "advisor"
        assert tev["custom_thing"]["tid"] == "events"
        assert tev["grace_save_committed"]["args"]["cid"] == "c1"

    def test_serve_traces_on_replica_lanes(self):
        rows = [{"trace_id": 7, "replica": 1, "bucket": 4,
                 "queue_s": 0.01, "pad_s": 0.002, "device_s": 0.05,
                 "readback_s": 0.003, "total_s": 0.07, "done_mono": 50.0},
                {"trace_id": 8}]  # legacy row, no done_mono: skipped
        tev = traces_to_trace_events(rows)
        assert {e["tid"] for e in tev} == {"replica1"}
        assert [e["name"] for e in tev] == ["queue", "pad", "device",
                                           "readback"]
        # phases lie end to end and finish at done_mono
        end = tev[-1]["ts"] + tev[-1]["dur"]
        start = tev[0]["ts"]
        assert end - start == pytest.approx(
            (0.01 + 0.002 + 0.05 + 0.003) * 1e6)
        assert validate_chrome_trace(export_timeline([], traces=rows)) == []

    def test_merged_export_shares_one_clock(self):
        events = [{"event": "replica_fault", "mono": 99.0, "cid": "x"}]
        rows = [{"trace_id": 1, "replica": 0, "device_s": 0.1,
                 "total_s": 0.1, "done_mono": 100.0}]
        trace = export_timeline(events, traces=rows,
                                goodput={"step": 2.0, "heal": 0.5,
                                         "empty": 0.0})
        assert validate_chrome_trace(trace) == []
        by_name = {e["name"]: e for e in trace["traceEvents"]}
        assert by_name["replica_fault"]["ts"] == 0.0  # earliest event is t0
        assert by_name["step"]["tid"] == "goodput"
        assert "empty" not in by_name  # zero buckets draw nothing
        lanes = {e["args"]["name"] for e in trace["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert {"serve", "replica0", "goodput"} <= lanes

    def test_validator_rejects_malformed_events(self):
        bad = {"traceEvents": [
            {"ph": "X", "pid": 1, "tid": "t", "ts": 0.0, "dur": 1.0},
            {"name": "n", "ph": "Z", "pid": 1, "tid": "t", "ts": 0.0},
            {"name": "n", "ph": "i", "pid": 1, "tid": "t", "ts": -5.0},
            {"name": "n", "ph": "X", "pid": 1, "tid": "t", "ts": 0.0},
            {"name": "n", "ph": "i", "ts": 0.0},
            "not an event",
        ]}
        problems = validate_chrome_trace(bad)
        assert len(problems) == 6
        assert validate_chrome_trace("nope") == ["trace must be a JSON "
                                                 "object"]
        assert validate_chrome_trace({}) == ["traceEvents must be a list"]


# ---------------------------------------------------------------------------
# SLO burn rates
# ---------------------------------------------------------------------------

def make_engine(objectives=None, **kw):
    """An engine on a fake clock and a private registry (no hub publish)."""
    clock = {"t": 1000.0}
    kw.setdefault("registry", MetricRegistry("slo_test"))
    eng = SloEngine(objectives, clock=lambda: clock["t"], **kw)
    return eng, clock


class TestSlo:
    def test_objective_validation(self):
        assert SloObjective(0.999).error_budget == pytest.approx(0.001)
        with pytest.raises(ValueError):
            SloObjective(availability=1.0)
        with pytest.raises(ValueError):
            SloObjective(availability=0.9, latency_ms=0)
        with pytest.raises(ValueError):
            SloObjective.from_dict({"availability": 0.9, "bogus": 1})
        assert SloObjective.from_dict(
            {"availability": 0.99, "latency_ms": 250}).latency_ms == 250.0

    def test_zero_traffic_windows_burn_nothing(self):
        eng, clock = make_engine({"t": SloObjective(0.9)})
        assert eng.burn_rate("t", 60.0) == 0.0
        assert eng.fast_burning() == []
        # traffic, then a long quiet stretch: the window empties again
        eng.observe("t", False)
        assert eng.burn_rate("t", 60.0) > 0.0
        clock["t"] += 10_000.0
        assert eng.burn_rate("t", 60.0) == 0.0

    def test_burn_rate_math(self):
        # availability 0.9 -> budget 0.1; 1 bad in 10 -> bad_frac 0.1 ->
        # burn exactly 1.0 (spending the budget exactly as provisioned)
        eng, clock = make_engine({"t": SloObjective(0.9)})
        for _ in range(9):
            eng.observe("t", True)
        eng.observe("t", False)
        assert eng.burn_rate("t", 60.0) == pytest.approx(1.0)
        # all-bad traffic burns at 1/budget
        eng2, _ = make_engine({"t": SloObjective(0.9)})
        eng2.observe("t", False)
        assert eng2.burn_rate("t", 60.0) == pytest.approx(10.0)

    def test_multi_window_guard(self):
        # a fresh burst of errors after a long clean stretch: the fast
        # window pages only once the slow window is burning too
        eng, clock = make_engine({"t": SloObjective(0.5)},
                                 fast_window_s=60, slow_window_s=600,
                                 fast_burn_threshold=1.5)
        for _ in range(400):
            eng.observe("t", True)
        clock["t"] += 300.0
        eng.observe("t", False)
        # fast window: 1 bad / 1 total -> burn 2.0 >= 1.5; slow window is
        # diluted by the 400 good -> not burning -> guard holds
        assert eng.burn_rate("t", 60.0) == pytest.approx(2.0)
        assert eng.burn_rate("t", 600.0) < 1.0
        assert eng.fast_burning() == []
        for _ in range(500):
            eng.observe("t", False)
        assert "t" in eng.fast_burning()

    def test_latency_target_counts_slow_success_as_bad(self):
        eng, _ = make_engine({"t": SloObjective(0.9, latency_ms=100.0)})
        assert eng.observe("t", True, latency_s=0.05) is True
        assert eng.observe("t", True, latency_s=0.5) is False
        assert eng.observe("t", False, latency_s=0.01) is False
        snap = eng.snapshot()["tenants"]["t"]
        assert snap["good_total"] == 1 and snap["bad_total"] == 2

    def test_unknown_tenant_folds_to_default(self):
        eng, _ = make_engine({"vip": SloObjective(0.99)})
        eng.observe("attacker-invented-name", False)
        eng.observe(None, True)
        snap = eng.snapshot()["tenants"]
        assert set(snap) == {"vip", "default"}  # bounded cardinality
        assert snap["default"]["bad_total"] == 1
        assert snap["default"]["good_total"] == 1

    def test_publishes_jimm_slo_series(self):
        from jimm_tpu import obs
        eng = SloEngine({"alice": SloObjective(0.99)})
        try:
            eng.observe("alice", True)
            snap = obs.snapshot()
            assert snap["jimm_slo_alice_good_total"] == 1
            assert "jimm_slo_alice_fast_burn_rate" in snap
        finally:
            from jimm_tpu.obs.registry import unpublish
            unpublish("jimm_slo")

    def test_snapshot_shape(self):
        eng, _ = make_engine({"t": SloObjective(0.999)})
        snap = eng.snapshot()
        assert snap["fast_window_s"] == 60.0
        assert snap["fast_burn_threshold"] == 14.4
        assert snap["fast_burning"] == []
        assert snap["tenants"]["t"]["objective"] == {"availability": 0.999}


class TestSloTransitions:
    """Fast-burn *transition* events (add_listener) under bursty traffic.

    The listener contract is edge-triggered: one call on entering fast
    burn, one on exiting, nothing while the state holds — this is what
    the cascade autoscaler hangs capacity decisions on.
    """

    @staticmethod
    def make_listening_engine(**kw):
        kw.setdefault("fast_window_s", 60)
        kw.setdefault("slow_window_s", 600)
        kw.setdefault("fast_burn_threshold", 1.5)
        eng, clock = make_engine({"t": SloObjective(0.5)}, **kw)
        events = []
        eng.add_listener(
            lambda tenant, entered, fast, slow:
            events.append((tenant, entered, fast, slow)))
        return eng, clock, events

    def test_enter_fires_once_not_per_observation(self):
        eng, clock, events = self.make_listening_engine()
        # all-bad traffic: budget 0.5 -> burn 2.0 in both windows, over
        # the 1.5 fast threshold and the 1.0 slow guard immediately
        eng.observe("t", False)
        assert events == [("t", True, pytest.approx(2.0),
                           pytest.approx(2.0))]
        # staying in fast burn is not a transition
        for _ in range(5):
            eng.observe("t", False)
        assert len(events) == 1

    def test_exit_fires_when_windows_drain(self):
        eng, clock, events = self.make_listening_engine()
        eng.observe("t", False)
        assert [e[1] for e in events] == [True]
        # idle past both windows: the exit is reported with the next
        # request (transitions are evaluated on observations)
        clock["t"] += 700.0
        assert len(events) == 1
        eng.observe("t", True)
        assert [e[1] for e in events] == [True, False]
        tenant, entered, fast, slow = events[-1]
        assert fast < 1.5 and slow < 1.0

    def test_burst_diluted_by_slow_window_never_fires(self):
        # a fresh error burst after a long clean stretch: fast window
        # burns but the 600s window is diluted -> multi-window guard
        # holds and no transition is emitted
        eng, clock, events = self.make_listening_engine()
        for _ in range(400):
            eng.observe("t", True)
        clock["t"] += 300.0
        eng.observe("t", False)
        assert eng.burn_rate("t", 60.0) >= 1.5
        assert eng.burn_rate("t", 600.0) < 1.0
        assert events == []
        # sustained errors eventually tip the slow window too -> enter
        for _ in range(500):
            eng.observe("t", False)
        assert [e[1] for e in events] == [True]
        assert events[0][3] >= 1.0

    def test_flap_across_windows_yields_paired_transitions(self):
        # bursty traffic that alternates bad bursts and quiet recovery:
        # each burn episode yields exactly one enter/exit pair
        eng, clock, events = self.make_listening_engine()
        for _ in range(3):
            eng.observe("t", False)          # enter
            clock["t"] += 700.0              # drain 60s and 600s windows
            eng.observe("t", True)           # exit reported here
            clock["t"] += 700.0              # drain the recovery probe too
        assert [e[1] for e in events] == [True, False] * 3

    def test_listener_errors_counted_not_raised(self):
        eng, clock, events = self.make_listening_engine()

        def broken(tenant, entered, fast, slow):
            raise RuntimeError("consumer bug")

        eng._listeners.insert(0, broken)
        # the broken consumer neither fails accounting nor starves the
        # healthy one
        assert eng.observe("t", False) is False
        assert [e[1] for e in events] == [True]
        assert eng.registry.counter("listener_errors_total").value == 1


# ---------------------------------------------------------------------------
# timeline / tail CLI verbs
# ---------------------------------------------------------------------------

class TestObsCli:
    def run_obs(self, *argv):
        from jimm_tpu.obs.cli import main
        return main(["obs", *argv])

    def test_regress_is_refused_by_the_parser(self, capsys):
        """The baseline gate went with the harness it read (PR 29): the
        benchmark's ledger is the one record of speed."""
        with pytest.raises(SystemExit) as e:
            self.run_obs("regress")
        assert e.value.code == 2
        assert "invalid choice: 'regress'" in capsys.readouterr().err

    def test_timeline_verb_round_trip(self, tmp_path, capsys):
        jpath = tmp_path / "journal.jsonl"
        j = EventJournal(jpath)
        cid = new_correlation_id()
        j.emit("replica_fault", cid=cid, replica=0)
        j.emit("heal_rebuilt", cid=cid, dur_s=0.2)
        j.close()
        traces = tmp_path / "traces.json"
        traces.write_text(json.dumps({"traces": [
            {"trace_id": 1, "replica": 0, "device_s": 0.01,
             "total_s": 0.01, "done_mono": 123.0}]}))
        out = tmp_path / "timeline.json"
        assert self.run_obs("timeline", str(jpath), "-o", str(out),
                            "--traces", str(traces)) == 0
        trace = json.loads(out.read_text())
        assert validate_chrome_trace(trace) == []
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"replica_fault", "heal_rebuilt", "device"} <= names

    def test_tail_traces_from_file(self, tmp_path, capsys):
        dump = tmp_path / "traces.json"
        dump.write_text(json.dumps({"traces": [
            {"trace_id": 42, "replica": 1, "bucket": 8, "queue_s": 0.001,
             "device_s": 0.02, "total_s": 0.021}]}))
        assert self.run_obs("tail", "--traces", str(dump)) == 0
        out = capsys.readouterr().out
        assert "42" in out and "replica=1" in out and "device=20.00ms" in out


# ---------------------------------------------------------------------------
# single-sample percentiles (the timeline/SLO tooling leans on these)
# ---------------------------------------------------------------------------

class TestPercentileEdges:
    def test_single_sample_histogram(self):
        h = Histogram("lat")
        h.observe(42.0)
        assert h.percentile(50) == 42.0
        assert h.percentile(99) == 42.0
        snap = h.snapshot()
        assert snap["lat_p50"] == snap["lat_p99"] == 42.0
        assert snap["lat_count"] == 1

    def test_empty_and_two_sample(self):
        assert percentile([], 99) == 0.0
        assert percentile([1.0], 0) == 1.0
        assert percentile([1.0, 9.0], 50) == 1.0  # nearest rank (banker's)
        assert percentile([1.0, 9.0], 99) == 9.0
        assert percentile([1.0, 9.0], 0) == 1.0


# ---------------------------------------------------------------------------
# policy slo section -> engine
# ---------------------------------------------------------------------------

class TestPolicySlo:
    def test_policy_slo_parses_and_feeds_engine(self):
        from jimm_tpu.serve.qos.policy import TenantRegistry
        reg = TenantRegistry.from_dict({
            "tenants": {"alice": {"class": "interactive"}},
            "slo": {"alice": {"availability": 0.999, "latency_ms": 250},
                    "default": {"availability": 0.99}},
        })
        assert reg.slo["alice"] == {"availability": 0.999,
                                    "latency_ms": 250.0}
        assert reg.describe()["slo"]["default"] == {"availability": 0.99}
        eng = SloEngine.from_objective_dicts(
            reg.slo, registry=MetricRegistry("slo_test2"))
        assert eng.objectives["alice"].latency_ms == 250.0

    def test_policy_slo_validation(self):
        from jimm_tpu.serve.qos.policy import (QosPolicyError,
                                               TenantRegistry)
        base = {"tenants": {"alice": {"class": "interactive"}}}
        with pytest.raises(QosPolicyError, match="not a declared tenant"):
            TenantRegistry.from_dict(
                dict(base, slo={"ghost": {"availability": 0.9}}))
        with pytest.raises(QosPolicyError, match="availability"):
            TenantRegistry.from_dict(
                dict(base, slo={"alice": {"availability": 2}}))
        with pytest.raises(QosPolicyError, match="unknown keys"):
            TenantRegistry.from_dict(
                dict(base, slo={"alice": {"burn": 1}}))
        assert TenantRegistry.from_dict(base).slo == {}


# ---------------------------------------------------------------------------
# the correlated incident chain through the supervisor
# ---------------------------------------------------------------------------

class TestIncidentChain:
    def test_supervisor_threads_one_cid_through_recovery(
            self, fresh_global_journal):
        from jimm_tpu.resilience import Supervisor

        calls = []

        def attempt(i, resume):
            # whatever the restarted attempt emits joins the incident
            calls.append(current_cid())
            if i == 0:
                raise RuntimeError("worker died")
            get_journal().emit("checkpoint_restored", step=3)
            return 0

        sup = Supervisor(max_restarts=2, sleep=lambda s: None)
        assert sup.run(attempt) == 0
        events = fresh_global_journal.events()
        failed = [e for e in events if e["event"] == "attempt_failed"]
        assert len(failed) == 1
        cid = failed[0]["cid"]
        assert cid
        got = [e["event"] for e in chain(events, cid)]
        assert got == ["attempt_failed", "restart", "checkpoint_restored",
                       "supervise_recovered"]
        # first attempt ran uncorrelated, the restart inherited the cid
        assert calls == [None, cid]

    def test_preemption_cid_carries_across_the_error(
            self, fresh_global_journal):
        from jimm_tpu.resilience import Supervisor
        from jimm_tpu.resilience.preemption import PreemptedError

        def attempt(i, resume):
            if i == 0:
                raise PreemptedError(5, cid="preempt-cid")
            return 0

        sup = Supervisor(max_restarts=1, sleep=lambda s: None)
        assert sup.run(attempt) == 0
        events = fresh_global_journal.events()
        got = {e["event"] for e in chain(events, "preempt-cid")}
        assert {"attempt_failed", "restart", "supervise_recovered"} <= got

    def test_give_up_emits_terminal_event(self, fresh_global_journal):
        from jimm_tpu.resilience import GiveUpError, Supervisor

        def attempt(i, resume):
            raise RuntimeError("boom")

        sup = Supervisor(max_restarts=1, sleep=lambda s: None)
        with pytest.raises(GiveUpError):
            sup.run(attempt)
        events = fresh_global_journal.events()
        gave_up = [e for e in events if e["event"] == "supervise_gave_up"]
        assert len(gave_up) == 1 and gave_up[0]["attempts"] == 2
        # both failures chained onto the one incident the first crash minted
        assert len(chain(events, gave_up[0]["cid"])) == 4
