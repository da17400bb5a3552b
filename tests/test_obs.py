"""jimm_tpu.obs: registry, spans, goodput, exporters, and the train+serve
unified-dump integration the CI smoke step re-asserts end to end."""

import json
import math
import time

import numpy as np
import pytest

from jimm_tpu import obs
from jimm_tpu.obs.compiles import row_keys
from jimm_tpu.obs.registry import _hub


@pytest.fixture(autouse=True)
def _obs_enabled():
    """Every test runs with obs on (the env default), restored afterwards."""
    prev = obs.enabled()
    obs.set_enabled(True)
    yield
    obs.set_enabled(prev)


class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = obs.MetricRegistry("t_basic")
        c = reg.counter("requests_total")
        c.inc()
        c.inc(4)
        assert c.value == 5
        g = reg.gauge("depth")
        g.set(3.5)
        assert g.read() == 3.5
        h = reg.histogram("lat_seconds")
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        assert h.count == 4
        assert h.sum == pytest.approx(10.0)
        snap = reg.snapshot()
        assert snap["requests_total"] == 5
        assert snap["depth"] == 3.5
        assert snap["lat_seconds_count"] == 4
        assert snap["lat_seconds_p99"] == 4.0

    def test_get_or_create_returns_same_instrument(self):
        reg = obs.MetricRegistry("t_same")
        assert reg.counter("a_total") is reg.counter("a_total")
        assert reg.histogram("h") is reg.histogram("h")

    def test_kind_conflict_raises(self):
        reg = obs.MetricRegistry("t_conflict")
        reg.counter("x_total")
        with pytest.raises(obs.DuplicateMetricError):
            reg.gauge("x_total")
        with pytest.raises(obs.DuplicateMetricError):
            reg.histogram("x_total")

    def test_gauge_rebind_latest_wins(self):
        reg = obs.MetricRegistry("t_rebind")
        reg.gauge("v", lambda: 1.0)
        reg.gauge("v", lambda: 2.0)
        assert reg.snapshot()["v"] == 2.0

    def test_raising_gauge_skipped(self):
        reg = obs.MetricRegistry("t_raise")
        reg.gauge("broken", lambda: 1 / 0)
        reg.counter("fine_total").inc()
        snap = reg.snapshot()
        assert "broken" not in snap and snap["fine_total"] == 1

    def test_percentile_matches_serve_metrics_index_math(self):
        # the shared helper must agree with ServeMetrics' historical
        # nearest-rank formula on the exact reservoir it used
        data = [float(i) for i in range(1, 101)]
        idx50 = min(len(data) - 1, int(round(50 / 100.0 * (len(data) - 1))))
        idx99 = min(len(data) - 1, int(round(99 / 100.0 * (len(data) - 1))))
        assert obs.percentile(data, 50) == sorted(data)[idx50]
        assert obs.percentile(data, 99) == sorted(data)[idx99]
        assert obs.percentile([], 50) == 0.0

    def test_hub_publish_latest_wins_and_unified_prefixing(self):
        a = obs.MetricRegistry("t_hub")
        a.counter("n_total").inc()
        obs.publish(a)
        b = obs.MetricRegistry("t_hub")
        b.counter("n_total").inc(7)
        obs.publish(b)
        try:
            snap = obs.snapshot()
            assert snap["t_hub_n_total"] == 7  # latest registry owns prefix
        finally:
            obs.unpublish("t_hub")

    def test_unified_snapshot_has_no_duplicate_series(self):
        # dict construction cannot hold dupes; assert the render agrees
        text = obs.render_prometheus()
        names = [line.split(" ")[0] for line in text.splitlines()
                 if line and not line.startswith("#")]
        assert len(names) == len(set(names))


class TestSpans:
    def test_span_records_into_spans_registry(self):
        with obs.span("unit_test_region"):
            time.sleep(0.002)
        reg = obs.get_registry("jimm_spans")
        snap = reg.snapshot()
        assert snap["unit_test_region_seconds_count"] >= 1
        assert snap["unit_test_region_seconds_p50"] >= 0.002

    def test_disabled_span_is_noop_singleton(self):
        obs.set_enabled(False)
        s1, s2 = obs.span("a"), obs.span("b")
        assert s1 is s2  # shared no-op object: no allocation when off

    def test_trace_ids_unique(self):
        ids = {obs.new_trace_id() for _ in range(100)}
        assert len(ids) == 100

    def test_disabled_overhead_under_one_percent_of_a_1ms_step(self):
        # acceptance: with obs disabled, instrumentation costs < 1% of a
        # step. Budget against a (pessimistically fast) 1 ms step: the
        # disabled span must cost < 10 us per call; measure the mean over
        # enough calls to drown out timer noise.
        obs.set_enabled(False)
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("hot_loop"):
                pass
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 10e-6, f"disabled span costs {per_call * 1e6:.2f}us"


class TestGoodput:
    def test_buckets_sum_to_wall_within_2_percent(self):
        acct = obs.GoodputAccounter(obs.MetricRegistry("t_goodput"))
        with acct.measure("compile"):
            time.sleep(0.03)
        for _ in range(3):
            with acct.measure("data_wait"):
                time.sleep(0.005)
            with acct.measure("step"):
                time.sleep(0.02)
            with acct.measure("host_sync"):
                time.sleep(0.002)
        with acct.measure("checkpoint"):
            time.sleep(0.01)
        report = acct.report()
        fracs = [report[f"{b}_frac"] for b in
                 ("compile", "data_wait", "step", "checkpoint",
                  "host_sync", "other")]
        assert sum(fracs) == pytest.approx(1.0, abs=0.02)
        assert report["goodput"] == pytest.approx(
            report["step_s"] / report["wall_s"], abs=0.01)

    def test_unknown_bucket_rejected(self):
        acct = obs.GoodputAccounter(obs.MetricRegistry("t_goodput2"))
        with pytest.raises(KeyError):
            with acct.measure("coffee"):
                pass

    def test_mfu_adjusted_goodput(self):
        acct = obs.GoodputAccounter(obs.MetricRegistry("t_goodput3"))
        with acct.measure("step"):
            time.sleep(0.01)
        report = acct.report(mfu=0.5)
        assert report["mfu"] == 0.5
        assert report["mfu_adjusted_goodput"] == pytest.approx(
            report["goodput"] * 0.5, abs=1e-3)  # report() rounds its fields

    def test_registry_mirroring(self):
        reg = obs.MetricRegistry("t_goodput4")
        acct = obs.GoodputAccounter(reg)
        with acct.measure("step"):
            time.sleep(0.005)
        snap = reg.snapshot()
        assert snap["goodput_step_seconds_total"] >= 0.005
        assert 0.0 <= snap["goodput_ratio"] <= 1.0


class TestGoodputPhases:
    """The train loop's phases under the buckets, and the per-step spans."""

    @pytest.mark.parametrize("phase, bucket", [
        ("next_batch", "data_wait"), ("place", "data_wait"),
        ("dispatch", "step"), ("device_wait", "step"),
        ("host_sync", "host_sync"), ("checkpoint", "checkpoint"),
        ("imports", "setup"), ("backend_init", "setup"),
        ("model_build", "setup"), ("optimizer_build", "setup"),
        ("data_build", "setup")])
    def test_a_phase_adds_to_its_bucket_and_to_nothing_else(self, phase,
                                                            bucket):
        from jimm_tpu.obs.goodput import PHASES
        assert PHASES[phase] == bucket
        reg = obs.MetricRegistry(f"t_phase_{phase}")
        acct = obs.GoodputAccounter(reg)
        with acct.measure(phase):
            time.sleep(0.003)
        secs = acct.seconds(wall=1.0)
        assert secs.pop(bucket) >= 0.003
        assert secs.pop("other") > 0
        assert set(secs.values()) == {0.0}
        snap = reg.snapshot()
        assert snap[f"goodput_{bucket}_seconds_total"] >= 0.003
        assert not any(phase in k for k in snap if phase != bucket), (
            "a phase is not mirrored to the registry under its own name")

    def test_setup_is_a_bucket_and_its_phases_are_the_ones_train_runs(self):
        from jimm_tpu.obs.goodput import BUCKETS, PHASES, SETUP_PHASES
        assert "setup" in BUCKETS and "setup" in obs.BUCKETS
        assert SETUP_PHASES == ("imports", "backend_init", "model_build",
                                "optimizer_build", "data_build")
        assert {p for p, b in PHASES.items() if b == "setup"} == {
            "setup", *SETUP_PHASES}
        acct = obs.GoodputAccounter(obs.MetricRegistry("t_setup_report"))
        with acct.measure("model_build"):
            time.sleep(0.02)
        report = acct.report()  # rounds its fields to 0.1 ms
        assert report["setup_s"] >= 0.02
        assert report["setup_frac"] == pytest.approx(
            report["setup_s"] / report["wall_s"], abs=0.01)

    def test_the_drain_at_step_0_hands_out_the_setup_spans_first(self):
        """``cli.train``'s loop drains after step 0's dispatch: everything
        measured since the accounter's birth, set-up first."""
        from jimm_tpu.obs.goodput import SETUP_PHASES
        acct = obs.GoodputAccounter(obs.MetricRegistry("t_setup_drain"))
        for phase in (*SETUP_PHASES, "next_batch", "place"):
            with acct.measure(phase):
                pass
        with acct.measure("dispatch", "compile"):
            time.sleep(0.002)
        names = [s[0] for s in acct.drain()]
        assert names == [*SETUP_PHASES, "next_batch", "place", "dispatch"]
        secs = acct.seconds()
        assert secs["setup"] > 0 and secs["compile"] >= 0.002
        # the wall runs from the accounter's birth: set-up is inside it
        assert acct.wall_s() >= secs["setup"] + secs["compile"]

    def test_the_first_steps_phases_can_land_in_compile(self):
        acct = obs.GoodputAccounter(obs.MetricRegistry("t_phase_compile"))
        with acct.measure("dispatch", "compile"):
            time.sleep(0.002)
        with acct.measure("device_wait", "compile"):
            time.sleep(0.002)
        secs = acct.seconds()
        assert secs["compile"] >= 0.004 and secs["step"] == 0.0
        assert [s[0] for s in acct.drain()] == ["dispatch", "device_wait"]

    @pytest.mark.parametrize("phase, bucket", [("coffee", None),
                                               ("dispatch", "coffee")])
    def test_unknown_phase_or_bucket_raises(self, phase, bucket):
        acct = obs.GoodputAccounter(obs.MetricRegistry(
            f"t_phase_unknown_{phase}"))
        with pytest.raises(KeyError):
            with acct.measure(phase, bucket):
                pass
        assert acct.drain() == []

    def test_draining_returns_each_span_once(self):
        acct = obs.GoodputAccounter(obs.MetricRegistry("t_phase_drain"))
        before = time.time_ns()
        for phase in ("next_batch", "place", "dispatch"):
            with acct.measure(phase):
                time.sleep(0.001)
        after = time.time_ns()
        spans = acct.drain()
        assert [s[0] for s in spans] == ["next_batch", "place", "dispatch"]
        for name, start, dur in spans:
            assert isinstance(start, int) and isinstance(dur, int)
            assert before <= start and start + dur <= after
            assert dur >= 1_000_000
        assert json.loads(json.dumps(spans)) == spans  # a row's field as is
        assert acct.drain() == []
        with acct.measure("host_sync"):
            pass
        assert [s[0] for s in acct.drain()] == ["host_sync"]
        # the spans are the buckets' time, span for span
        assert acct.seconds()["data_wait"] == pytest.approx(
            (spans[0][2] + spans[1][2]) / 1e9)

    def test_spans_of_one_thread_never_overlap(self):
        acct = obs.GoodputAccounter(obs.MetricRegistry("t_phase_overlap"))
        for _ in range(200):
            for phase in ("next_batch", "place", "dispatch", "device_wait",
                          "host_sync"):
                with acct.measure(phase):
                    pass
        spans = acct.drain()
        assert len(spans) == 1000
        for (_, a0, adur), (_, b0, _) in zip(spans, spans[1:]):
            assert a0 + adur <= b0

    def test_an_accounter_nobody_drains_stays_bounded(self):
        from jimm_tpu.obs.goodput import MAX_UNDRAINED_SPANS
        acct = obs.GoodputAccounter(obs.MetricRegistry("t_phase_bounded"))
        for _ in range(MAX_UNDRAINED_SPANS + 10):
            with acct.measure("step"):
                pass
        assert len(acct.drain()) == MAX_UNDRAINED_SPANS

    def test_disabled_records_nothing_and_reads_no_clock(self, monkeypatch):
        from jimm_tpu.obs import goodput
        acct = obs.GoodputAccounter(obs.MetricRegistry("t_phase_off"))
        obs.set_enabled(False)

        def no_clock(*a, **kw):
            raise AssertionError("JIMM_OBS=0 read a clock")

        for clock in ("time_ns", "perf_counter_ns", "perf_counter", "time"):
            monkeypatch.setattr(goodput.time, clock, no_clock)
        with acct.measure("dispatch"):
            pass
        monkeypatch.undo()
        assert acct.drain() == []
        assert acct.seconds(wall=1.0)["step"] == 0.0


def _monitoring_listeners():
    from jax._src import monitoring
    return (list(monitoring.get_event_duration_listeners())
            + list(monitoring.get_event_listeners()))


class TestCompileWatch:
    """``obs/compiles.py``: the program's one listener to jax.monitoring."""

    def test_every_request_is_named_and_lies_on_the_wall_clock(self):
        import jax
        import jax.numpy as jnp
        reg = obs.MetricRegistry("t_compiles")
        before = len(_monitoring_listeners())
        watch = obs.CompileWatch(reg)
        assert len(_monitoring_listeners()) == before, "not until asked to"
        watch.listen()
        try:
            assert len(_monitoring_listeners()) == before + 2
            t0 = time.time_ns()

            @jax.jit
            def a_function_of_this_test(x):
                return jnp.tanh(x) * 2 + jnp.clip(x, 0, 1)

            a_function_of_this_test(jnp.ones((3, 5))).block_until_ready()
            t1 = time.time_ns()
            row = row_keys(watch.drain())
        finally:
            watch.close()
        assert len(_monitoring_listeners()) == before
        mine = [e for e in row["compiles"]
                if "a_function_of_this_test" in e[1]]
        assert [e[0] for e in mine] == ["trace", "lower", "compile"]
        for kind, fun, start, dur in row["compiles"]:
            assert isinstance(start, int) and isinstance(dur, int)
            assert t0 - 1_000_000 <= start and start + dur <= t1
        # clip and tanh are jitted functions of their own: traced inside the
        # outer trace, and not kept
        by_clock = sorted(row["compiles"], key=lambda e: e[2])
        for (_, _, a0, adur), (_, _, b0, _) in zip(by_clock, by_clock[1:]):
            assert a0 + adur <= b0 + 1_000, "no event inside another"
        assert json.loads(json.dumps(row)) == row
        n = sum(e[0] == "compile" for e in row["compiles"])
        assert watch.requests == n >= 1
        snap = reg.snapshot()
        assert snap["compile_requests_total"] == n
        assert snap["compile_seconds_total"] == pytest.approx(
            sum(e[3] for e in row["compiles"] if e[0] == "compile") / 1e9,
            abs=1e-6)
        # each event is handed out once; a closed watch hears nothing
        assert row_keys(watch.drain()) == {}
        jax.jit(lambda x: x - 3)(jnp.ones(7)).block_until_ready()
        assert row_keys(watch.drain()) == {} and watch.requests == n

    def test_a_trace_inside_another_stage_is_dropped_with_hand_made_events(
            self, monkeypatch):
        from jimm_tpu.obs import compiles
        watch = obs.CompileWatch(obs.MetricRegistry("t_compiles_nested"))
        watch.close()  # fed by hand below
        now = [1_000_000_000_000]
        monkeypatch.setattr(compiles.time, "time_ns", lambda: now[0])
        trace, lower, backend = compiles.KINDS  # in the order of a request

        def ends(event, at_s, dur_s, fun):
            now[0] = int(1e12 + at_s * 1e9)
            watch._duration(event, dur_s, fun_name=fun)

        ends(trace, 0.5, 0.5, "earlier")           # [0.0, 0.5]
        ends(trace, 1.2, 0.1, "inner_a")           # [1.1, 1.2]
        ends(backend, 1.5, 0.2, "jit(constant)")   # [1.3, 1.5] inside outer
        ends(trace, 1.7, 0.1, "inner_b")           # [1.6, 1.7]
        ends(trace, 2.0, 1.0, "outer")             # [1.0, 2.0]
        ends(trace, 2.3, 0.1, "helper")            # [2.2, 2.3] inside lower
        ends(lower, 2.5, 0.5, "jit(outer)")        # [2.0, 2.5]
        ends(backend, 3.5, 1.0, "jit(outer)")      # [2.5, 3.5]
        ends("/jax/core/compile/something_else", 3.6, 0.1, "x")
        for _ in range(3):
            watch._event("/jax/compilation_cache/cache_hits")
        watch._event("/jax/compilation_cache/cache_misses")
        watch._event("/jax/compilation_cache/something_else")
        row = row_keys(watch.drain())
        assert [(e[0], e[1]) for e in row["compiles"]] == [
            ("trace", "earlier"), ("compile", "jit(constant)"),
            ("trace", "outer"), ("lower", "jit(outer)"),
            ("compile", "jit(outer)")]
        assert row["compiles"][2][2:] == [int(1e12 + 1e9), int(1e9)]
        assert (row["cache_hits"], row["cache_misses"]) == (3, 1)
        assert watch.requests == 2 and watch.cache == {"hits": 3,
                                                       "misses": 1}
        # counts alone make a row too; an empty one has no key at all
        watch._event("/jax/compilation_cache/cache_hits")
        assert row_keys(watch.drain()) == {"cache_hits": 1}
        assert row_keys(watch.drain()) == {}

    def test_a_watch_nobody_drains_stays_bounded(self):
        from jimm_tpu.obs import compiles
        watch = obs.CompileWatch(obs.MetricRegistry("t_compiles_bounded"))
        watch.close()
        for i in range(compiles.MAX_UNDRAINED_EVENTS + 10):
            watch._duration("/jax/core/compile/backend_compile_duration",
                            0.0, fun_name=f"jit(f{i})")
        assert len(row_keys(watch.drain())["compiles"]) == (
            compiles.MAX_UNDRAINED_EVENTS)
        assert watch.requests == compiles.MAX_UNDRAINED_EVENTS + 10

    def test_two_watches_alive_at_once_count_into_their_own_registries(self):
        """``chip_smoke.py`` counts over several ``cli.train`` calls, each of
        which opens the run's own: on one registry both would count."""
        import jax
        import jax.numpy as jnp
        outer_reg = obs.MetricRegistry("t_compiles_outer")
        run_reg = obs.MetricRegistry("t_compiles_run")
        outer = obs.CompileWatch(outer_reg).listen()
        run = obs.CompileWatch(run_reg).listen()
        try:
            jax.jit(lambda x: x * 7 - 2)(jnp.ones(13)).block_until_ready()
        finally:
            run.close()
            outer.close()
        n = run.requests
        assert n >= 1 and outer.requests == n
        assert run_reg.snapshot()["compile_requests_total"] == n
        assert outer_reg.snapshot()["compile_requests_total"] == n

    def test_a_watch_listens_inside_each_with_and_is_gone_on_every_way_out(
            self):
        """``cli.train`` enters its watch beside each phase of set-up that
        builds programs, and once more for the loop."""
        import jax
        import jax.numpy as jnp
        before = _monitoring_listeners()
        watch = obs.CompileWatch(obs.MetricRegistry("t_compiles_with"))
        with watch as entered:
            assert entered is watch and watch.listening
            assert watch.listen() is watch, "asking twice registers once"
            assert len(_monitoring_listeners()) == len(before) + 2
            jax.jit(lambda x: x / 3 + 8)(jnp.ones(17)).block_until_ready()
        assert _monitoring_listeners() == before and not watch.listening
        n = watch.requests
        assert n >= 1
        jax.jit(lambda x: x / 5 + 9)(jnp.ones(19)).block_until_ready()
        assert watch.requests == n, "between two stretches it hears nothing"
        with pytest.raises(ZeroDivisionError):
            with watch:
                jax.jit(lambda x: x / 7)(jnp.ones(23)).block_until_ready()
                1 / 0
        assert _monitoring_listeners() == before
        assert watch.requests > n, "and counts on where it left off"
        watch.close()  # closing a closed watch is nothing

    def test_a_row_is_made_of_the_events_drained_for_it(self):
        """``cli.train`` drains where it drains its accounter and adds the
        lists up, like a row's phases."""
        stage = ["compile", "jit(f)", 5, 7]
        assert row_keys([]) == {}
        assert row_keys([["hits", "", 1, 0]]) == {"cache_hits": 1}
        assert row_keys([["misses", "", 1, 0], stage] + [stage]) == {
            "cache_misses": 1, "compiles": [stage, stage]}

    def test_disabled_registers_no_listener_and_makes_no_row_key(self):
        import jax
        import jax.numpy as jnp
        obs.set_enabled(False)
        reg = obs.MetricRegistry("t_compiles_off")
        before = _monitoring_listeners()
        with obs.CompileWatch(reg) as watch:
            assert _monitoring_listeners() == before
            jax.jit(lambda x: x * 5 + 1)(jnp.ones(11)).block_until_ready()
        assert row_keys(watch.drain()) == {} and watch.requests == 0
        assert not any(reg.snapshot().values())
        assert _monitoring_listeners() == before


def test_the_star_import_finds_every_name_it_lists():
    """``__all__`` listed four names of a module deleted in PR 29."""
    namespace = {}
    exec("from jimm_tpu.obs import *", namespace)
    assert set(obs.__all__) <= set(namespace)
    assert {"GoodputAccounter", "CompileWatch", "span"} <= set(namespace)


class TestExporters:
    def test_prometheus_roundtrip(self):
        series = {"x_total": 3, "y": 1.5, "h_count": 7}
        text = obs.render_prometheus_text(series)
        assert "# TYPE x_total counter" in text
        assert "# TYPE y gauge" in text
        assert "# TYPE h_count counter" in text
        assert obs.parse_prometheus_text(text) == {
            "x_total": 3.0, "y": 1.5, "h_count": 7.0}

    def test_jsonl_exporter_measurements_format(self, tmp_path):
        path = tmp_path / "m.jsonl"
        rec = obs.JsonlExporter(str(path), phase="unit").export({"a": 1})
        line = json.loads(path.read_text().strip())
        assert line == rec
        assert line["phase"] == "unit" and "ts" in line and line["a"] == 1

    def test_console_table_and_diff(self):
        table = obs.console_table({"loss": 0.5, "steps_total": 10})
        assert "loss" in table and "steps_total" in table
        d = obs.diff_snapshots({"a": 1, "b": 2, "gone": 0},
                               {"a": 1, "b": 5, "new": 9})
        assert d["added"] == {"new": 9}
        assert d["removed"] == {"gone": 0}
        assert d["changed"]["b"]["delta"] == 3


class TestMfuDegenerate:
    def test_degenerate_inputs_return_zero_and_count(self):
        from jimm_tpu.train.metrics import mfu
        counter = obs.get_registry("jimm_train").counter(
            "mfu_degenerate_total")
        before = counter.value
        assert mfu(None, 1.0, n_devices=1) == 0.0          # cost analysis
        assert mfu(1e12, 0.0, n_devices=1) == 0.0          # zero step time
        assert mfu(1e12, -1.0, n_devices=1) == 0.0         # negative
        assert mfu(1e12, float("nan"), n_devices=1) == 0.0  # NaN time
        assert mfu(float("nan"), 1.0, n_devices=1) == 0.0  # NaN flops
        assert counter.value == before + 5

    def test_healthy_path_unchanged(self):
        import types

        from jimm_tpu.train.metrics import device_peak_tflops, mfu
        v5e = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
        peak = device_peak_tflops(v5e) * 1e12
        assert peak == 197e12
        got = mfu(peak * 0.4, 1.0, n_devices=1, device=v5e)
        assert got == pytest.approx(0.4)
        assert math.isfinite(got)

    def test_unknown_device_kind_has_no_peak(self):
        """No default peak and no CPU peak: an MFU for a device that is not
        in the table would be a made-up number."""
        import jax

        from jimm_tpu.train.metrics import device_peak_tflops, mfu
        cpu = jax.devices()[0]
        with pytest.raises(ValueError, match="no peak FLOP/s recorded"):
            device_peak_tflops(cpu)
        with pytest.raises(ValueError, match=repr(cpu.device_kind)):
            mfu(1e12, 1.0, n_devices=1)

    def test_compiled_flops_does_not_swallow_errors(self):
        from jimm_tpu.train.metrics import compiled_flops

        class Broken:
            def cost_analysis(self):
                raise RuntimeError("cost analysis unavailable")

        with pytest.raises(RuntimeError, match="unavailable"):
            compiled_flops(Broken())


class TestMetricsLoggerRegistry:
    def test_scalars_mirrored(self, tmp_path):
        from jimm_tpu.train.metrics import MetricsLogger
        reg = obs.MetricRegistry("t_logger")
        logger = MetricsLogger(print_every=0, registry=reg)
        logger.log(0, step_time_s=0.5, loss=2.0, note="non-numeric")
        logger.log(1, step_time_s=0.3, loss=1.0)
        logger.close()
        snap = reg.snapshot()
        assert snap["steps_logged_total"] == 2
        assert snap["step_time_seconds_count"] == 2
        assert snap["loss"] == 1.0  # last-value gauge
        assert "note" not in snap

    def test_file_only_fields_reach_the_row_and_nothing_else(self, tmp_path,
                                                             capsys):
        from jimm_tpu.train.metrics import MetricsLogger
        reg = obs.MetricRegistry("t_logger_file_only")
        logger = MetricsLogger(path=tmp_path / "m.jsonl", print_every=1,
                               registry=reg)
        phases = [["dispatch", 1790621375151315027, 4111337562]]
        logger.log(0, loss=2.0, file_only={"phases": phases})
        logger.close()
        row = json.loads((tmp_path / "m.jsonl").read_text())
        assert row["phases"] == phases and row["loss"] == 2.0
        assert "phases" not in capsys.readouterr().out
        assert not any("phases" in k for k in reg.snapshot())

    def test_no_registry_no_mirroring(self):
        from jimm_tpu.train.metrics import MetricsLogger
        logger = MetricsLogger(print_every=0)
        # sentinel name: other tests legitimately mirror common fields
        # (loss etc.) into the global jimm_train registry
        logger.log(0, zz_sentinel_unmirrored=1.0)
        logger.close()
        assert ("zz_sentinel_unmirrored"
                not in obs.get_registry("jimm_train").snapshot())


class TestServeIntegration:
    def _engine(self, **kw):
        from jimm_tpu.serve import BucketTable, InferenceEngine

        def forward(batch):
            return batch.reshape(batch.shape[0], -1)[:, :4]

        return InferenceEngine(forward, item_shape=(4, 4, 3),
                               buckets=BucketTable((1, 2, 4)),
                               max_delay_ms=2.0, **kw)

    def test_serve_metrics_publish_and_phase_decomposition(self):
        import asyncio

        engine = self._engine()
        item = np.zeros((4, 4, 3), np.float32)

        async def go():
            await engine.start()
            try:
                await asyncio.gather(*[engine.submit(item)
                                       for _ in range(8)])
            finally:
                await engine.stop()

        asyncio.run(go())
        m = engine.metrics
        snap = m.snapshot()
        # back-compat names intact
        assert snap["responses_total"] == 8
        # per-request decomposition: every phase observed per batch
        for phase in ("queue", "pad", "device", "readback"):
            assert snap[f"span_{phase}_p50_ms"] >= 0.0
            assert m.phase_percentile(phase, 50) >= 0.0
        # trace records decompose each request
        assert engine.recent_traces
        tr = engine.recent_traces[-1]
        assert set(tr) >= {"trace_id", "queue_s", "pad_s", "device_s",
                           "readback_s", "total_s"}
        assert tr["total_s"] >= tr["device_s"]
        # the unified dump carries the serve series under its prefix
        uni = obs.snapshot()
        assert uni["jimm_serve_responses_total"] == 8
        assert "jimm_serve_span_device_seconds_p50" in uni

    def test_trace_id_propagates_to_dispatch(self):
        import asyncio

        engine = self._engine()
        item = np.zeros((4, 4, 3), np.float32)

        async def go():
            await engine.start()
            try:
                await engine.submit(item, trace_id="t-fixed-id")
            finally:
                await engine.stop()

        asyncio.run(go())
        assert any(t["trace_id"] == "t-fixed-id"
                   for t in engine.recent_traces)

    def test_combined_train_and_serve_unified_dump(self):
        """The acceptance smoke in miniature: train-side goodput + serve
        engine in one process -> one snapshot with both namespaces, buckets
        summing to 100% +- 2%."""
        import asyncio

        acct = obs.GoodputAccounter()  # jimm_train registry
        with acct.measure("compile"):
            time.sleep(0.01)
        with acct.measure("step"):
            time.sleep(0.01)

        engine = self._engine()
        item = np.zeros((4, 4, 3), np.float32)

        async def go():
            await engine.start()
            try:
                await engine.submit(item)
            finally:
                await engine.stop()

        asyncio.run(go())

        uni = obs.snapshot()
        assert any(k.startswith("jimm_train_") for k in uni)
        assert any(k.startswith("jimm_serve_") for k in uni)
        report = acct.report()
        total = sum(report[f"{b}_frac"] for b in
                    ("compile", "data_wait", "step", "checkpoint",
                     "host_sync", "other"))
        assert total == pytest.approx(1.0, abs=0.02)


class TestObsCli:
    def test_snapshot_and_diff(self, tmp_path, capsys):
        from jimm_tpu.obs.cli import main
        before = tmp_path / "before.json"
        after_txt = tmp_path / "after.prom"
        before.write_text(json.dumps({"a_total": 1, "b": 2}))
        after_txt.write_text(obs.render_prometheus_text(
            {"a_total": 3, "c": 1}))

        assert main(["obs", "snapshot", str(before), "--json"]) == 0
        out = capsys.readouterr().out
        assert json.loads(out) == {"a_total": 1, "b": 2}

        # diff exits 1 when there are differences, prints the delta
        assert main(["obs", "diff", str(before), str(after_txt)]) == 1
        out = capsys.readouterr().out
        assert "a_total" in out and "+2" in out
        assert main(["obs", "diff", str(before), str(before)]) == 0

    def test_snapshot_save_for_later_diff(self, tmp_path, capsys):
        from jimm_tpu.obs.cli import main
        src = tmp_path / "metrics.prom"
        src.write_text(obs.render_prometheus_text({"x_total": 5}))
        out_json = tmp_path / "snap.json"
        assert main(["obs", "snapshot", str(src),
                     "-o", str(out_json)]) == 0
        capsys.readouterr()
        assert json.loads(out_json.read_text()) == {"x_total": 5.0}

    def test_wired_into_main_cli(self):
        from jimm_tpu.cli import build_parser
        args = build_parser().parse_args(["obs", "snapshot", "x.json"])
        assert args.obs_cmd == "snapshot"
        assert callable(args.fn)
