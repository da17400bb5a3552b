"""Where ``setup_s`` goes (``benchmarks/layer_metrics/setup_timeline.py``):
each reader on hand-made rows whose numbers are known, nothing on an earlier
program's rows, the identity that ties the fourteen to ``setup_s``, and all
of it through a rehearsed run of an image cell and of a language-model cell,
where the program's count of compile requests is the harness's."""

import json
import time

import pytest

from benchmarks import harness, run as bench_run
from benchmarks.layer_metrics import setup_timeline

#: Unix seconds of the hand-made process's start
T0 = 1_790_000_000
#: the parts that add up to ``setup_s``; the other five lie inside them
PARTS = ("setup_before_train_s", "setup_imports_s", "setup_backend_init_s",
         "setup_model_build_s", "setup_optimizer_build_s",
         "setup_data_build_s", "setup_first_step_s", "setup_warmup_s",
         "setup_unnamed_s")
INSIDE = ("setup_build_programs_s", "setup_step_trace_lower_s",
          "setup_step_compile_s", "setup_programs", "setup_cache_misses")


@pytest.fixture
def readers():
    return harness.load_readers("layer_metrics")


def ns(seconds: float) -> int:
    return T0 * 10**9 + int(round(seconds * 1e9))


def span(name, lo, hi):
    return [name, ns(lo), ns(hi) - ns(lo)]


def event(kind, fun, lo, hi):
    return [kind, fun, ns(lo), ns(hi) - ns(lo)]


def hand_made(setup=True, compiles=True) -> dict:
    """A process that starts at 0 s; ``train()`` begins at 4 s; the window
    begins with row 2's stamp at 40.5 s. Seconds after the start:

        imports 4-4.5 and 6-9, backend_init 4.5-6 and 9-9.25,
        model_build 10-20, optimizer_build 20-22, data_build 22.5-25 and
        25-25.5, next_batch 26-26.1, place 26.1-26.5, dispatch 26.5-36,
        (step 1's next_batch, place, dispatch 36-36.5), device_wait 36.5-38,
        row 0 written at 38.25, rows 1 and 2 at 39.5 and 40.5.
    """
    phases = [span("imports", 4, 4.5), span("backend_init", 4.5, 6),
              span("imports", 6, 9), span("backend_init", 9, 9.25),
              span("model_build", 10, 20), span("optimizer_build", 20, 22),
              span("data_build", 22.5, 25), span("data_build", 25, 25.5)]
    loop0 = [span("next_batch", 26, 26.1), span("place", 26.1, 26.5),
             span("dispatch", 26.5, 36), span("device_wait", 36.5, 38)]
    row0 = {"step": 0, "time": T0 + 38.25, "step_time_s": 11.0, "loss": 1.0,
            "phases": (phases if setup else []) + loop0}
    if compiles:
        row0["compiles"] = [
            event("trace", "_uniform", 10.5, 11),
            event("lower", "jit(_uniform)", 11, 11.25),
            event("compile", "jit(_uniform)", 11.25, 13.25),   # 2 s, model
            event("compile", "jit(_normal)", 14, 15.5),        # 1.5 s, model
            event("compile", "jit(zeros)", 20.5, 21),          # 0.5 s, opt.
            event("compile", "jit(convert)", 26.2, 26.3),      # in place
            event("trace", "train_step", 27, 30),              # 3 s
            event("lower", "jit(train_step)", 30, 31.5),       # 1.5 s
            event("compile", "jit(train_step)", 31.5, 35.5),   # 4 s
        ]
        row0["cache_hits"], row0["cache_misses"] = 3, 2
    rows = [row0]
    for step, at in ((1, 39.5), (2, 40.5), (3, 41.5), (4, 42.5)):
        rows.append({"step": step, "time": T0 + at, "step_time_s": 0.9,
                     "loss": 1.0,
                     "phases": [span("host_sync", at - 1.2, at - 1.1),
                                span("next_batch", at - 1.1, at - 1.0),
                                span("dispatch", at - 1.0, at - 0.9),
                                span("device_wait", at - 0.8, at - 0.1)]})
    if compiles:  # a late request: after the window opened, not set-up's
        rows[3]["compiles"] = [event("compile", "jit(late)", 41, 41.2)]
        rows[3]["cache_misses"] = 1
        rows[1]["compiles"] = [event("compile", "jit(again)", 38.5, 38.75)]
        rows[1]["cache_misses"] = 1
    return {"rows": rows, "window_rows": rows[3:], "platform": "cpu",
            "t_process_start": float(T0), "t_first_measured": T0 + 40.5}


EXPECTED = {
    "setup_before_train_s": 4.0,
    "setup_imports_s": 3.5,
    "setup_backend_init_s": 1.75,
    "setup_model_build_s": 10.0,
    "setup_optimizer_build_s": 2.0,
    "setup_data_build_s": 3.0,
    "setup_build_programs_s": 4.0,       # 2 + 1.5 + 0.5: not place's 0.1
    "setup_first_step_s": 11.0,          # dispatch 9.5 + device_wait 1.5
    "setup_step_trace_lower_s": 4.5,
    "setup_step_compile_s": 4.0,
    "setup_warmup_s": 2.25,              # 38.25 -> 40.5
    # 40.5 less 4 + 3.5 + 1.75 + 10 + 2 + 3 + 11 + 2.25: the gaps between
    # the spans, step 0's next_batch and place, step 1's call, the log
    "setup_unnamed_s": 3.0,
    "setup_programs": 6.0,               # five in row 0, one in row 1
    "setup_cache_misses": 3.0,           # not the window's
}


def test_the_module_has_one_reader_per_manifest_entry():
    manifest = harness.load_manifest()
    mine = [m for m in manifest["per_layer"] if m["moves"] == "setup_s"]
    assert [m["name"] for m in mine] == list(EXPECTED)
    assert set(setup_timeline.READERS) == set(EXPECTED) == {*PARTS, *INSIDE}
    assert manifest["per_layer"][-len(mine):] == mine, "appended at the end"
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["moves"] != "setup_s"}
    for m in mine:
        assert "workloads" not in m, "every cell reports setup_s"
        assert m["better"] == "lower" and m["layer"] in layers
        assert m["unit"] == ("s" if m["name"].endswith("_s") else "count")


@pytest.mark.parametrize("name", list(EXPECTED))
def test_a_reader_on_hand_made_rows(readers, name):
    assert readers[name](hand_made()) == pytest.approx(EXPECTED[name],
                                                       abs=1e-6)


@pytest.mark.parametrize("name", list(EXPECTED))
def test_a_reader_gives_nothing_without_a_setup_phase(readers, name):
    """The parent's rows have phases, and no set-up phase among them."""
    assert readers[name](hand_made(setup=False, compiles=False)) is None
    assert readers[name]({"platform": "tpu"}) is None, "another driver's"
    assert readers[name]({"rows": [], "t_process_start": 0.0,
                          "t_first_measured": 1.0}) is None


def test_the_parts_add_up_to_setup_s(readers):
    o = hand_made()
    setup_s = harness.load_readers("end_to_end")["setup_s"](o)
    assert setup_s == pytest.approx(40.5)
    assert sum(readers[name](o) for name in PARTS) == pytest.approx(
        setup_s, abs=1e-6)
    assert sum(EXPECTED[name] for name in PARTS) == pytest.approx(40.5)
    # the residual takes up a span that moved: the identity is by construction
    o["rows"][0]["phases"][4][2] -= 10**9  # model_build a second shorter
    assert readers["setup_model_build_s"](o) == pytest.approx(9.0)
    assert readers["setup_unnamed_s"](o) == pytest.approx(4.0)
    assert sum(readers[name](o) for name in PARTS) == pytest.approx(
        setup_s, abs=1e-6)


def test_with_spans_and_no_compile_events_the_counters_read_zero(readers):
    """A run that compiled nothing new (or the watch heard nothing)."""
    o = hand_made(compiles=False)
    for name in INSIDE:
        assert readers[name](o) == 0.0
    assert readers["setup_model_build_s"](o) == pytest.approx(10.0)


def test_a_span_counted_twice_shows_as_a_residual_below_zero(readers):
    """The identity holds by construction (``setup_unnamed_s`` is what is
    left), so it cannot show a stretch counted twice: the residual's sign
    does, and the rehearsed runs below hold it at or above zero."""
    o = hand_made()
    assert readers["setup_unnamed_s"](o) == pytest.approx(3.0)
    o["rows"][0]["phases"].insert(5, span("model_build", 12, 18))  # nested
    assert readers["setup_model_build_s"](o) == pytest.approx(16.0)
    assert readers["setup_unnamed_s"](o) == pytest.approx(-3.0)
    # a stage is the sum of its events: the program keeps none that began
    # inside another (jimm_tpu/obs/compiles.py)
    o = hand_made()
    o["rows"][0]["compiles"].append(
        event("compile", "jit(another)", 16, 17.5))
    assert readers["setup_build_programs_s"](o) == pytest.approx(5.5)
    assert readers["setup_programs"](o) == 7.0


# ---------------------------------------------------------------------------
# end to end: rehearsed runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", ["vit_l16_384.train", "ouro_2_6b.train"])
def test_a_rehearsed_run_prints_the_fourteen_and_they_add_up(
        cell, tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(harness, "RUNS_DIR", tmp_path / "runs")
    monkeypatch.setattr(harness, "enable_caches", lambda: "off (test)")
    watches = []

    class Kept(harness.CompileWatch):
        def __init__(self):
            super().__init__()
            watches.append(self)

    monkeypatch.setattr(harness, "CompileWatch", Kept)
    t_start = time.time()
    rc = bench_run.main(["--workload", cell, "--seed", "2147483693",
                         "--seconds", "2", "--trace", "1", "--rehearse"],
                        t_process_start=t_start)
    out = capsys.readouterr().out
    assert rc == 0, out[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    got = {name: m["value"] for name, m in line["metrics"].items()
           if name in EXPECTED}
    assert set(got) == set(EXPECTED)
    assert all(m["unit"] in ("s", "count") for name, m
               in line["metrics"].items() if name in EXPECTED)

    run_dir = harness.RUNS_DIR / f"{cell}-s2147483693-t1"
    rows = [json.loads(r)
            for r in (run_dir / "metrics.jsonl").read_text().splitlines()]
    # three warm-up steps: the window opens with the third row's stamp
    setup_s = rows[2]["time"] - t_start
    assert sum(got[name] for name in PARTS) == pytest.approx(setup_s,
                                                             abs=1e-3)
    assert all(got[name] >= 0 for name in EXPECTED if name != (
        "setup_unnamed_s"))
    assert -1e-3 <= got["setup_unnamed_s"] < 0.5 * setup_s
    assert got["setup_build_programs_s"] <= (
        got["setup_model_build_s"] + got["setup_optimizer_build_s"])
    assert 0 < got["setup_step_trace_lower_s"] + got[
        "setup_step_compile_s"] <= got["setup_first_step_s"]

    # the program's listener and the harness's heard the same requests
    # while cli.train ran (the driver compiles the step once more after it)
    (watch,) = watches
    lo = min(p[1] for p in rows[0]["phases"]) / 1e9
    hi = rows[-1]["time"]
    mine = [e for r in rows for e in r.get("compiles", ())
            if e[0] == "compile"]
    assert len(mine) == len(watch.between(lo, hi)) > 0
    assert sorted(e[1] for e in mine) == sorted(watch.between(lo, hi))
    assert got["setup_programs"] == len(mine), "none after the warm-up"
    assert not any("compiles" in r for r in rows[3:])
