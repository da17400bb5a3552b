"""The train loop keeps one step in flight (``jimm_tpu/cli.py::train``): step
i+1 is dispatched before step i's loss is read. That changes when a program
is launched and when a row is written, and nothing else: the same compiled
step runs on the same batches in the same order, checkpoints and drills see
the state they saw, and the benchmark's readers take the rows as they are."""

import dataclasses
import hashlib
import json
import pathlib

import numpy as np
import pytest

from jimm_tpu import cli, obs
from jimm_tpu.cli import main

REPO = pathlib.Path(__file__).resolve().parents[1]
TINY_VIT = ["train", "--preset", "vit-base-patch16-224", "--tiny",
            "--batch-size", "8", "--steps", "6", "--seed", "11",
            "--lr", "1e-3", "--warmup-steps", "2", "--weight-decay", "0.01"]


def read_rows(path):
    return [json.loads(line) for line in open(path)]


# ---------------------------------------------------------------------------
# the work is the plain loop's
# ---------------------------------------------------------------------------

def test_losses_are_those_of_a_plain_synchronous_loop(tmp_path):
    """Six steps through the CLI against a loop written here that reads each
    loss before it launches the next step: bit for bit the same numbers."""
    import jax
    import jax.numpy as jnp
    from flax import nnx

    from jimm_tpu import preset
    from jimm_tpu.data import blob_classification
    from jimm_tpu.train import (OptimizerConfig, make_classifier_train_step,
                                make_optimizer)

    metrics = tmp_path / "m.jsonl"
    assert main(TINY_VIT + ["--log-every", "0",
                            "--metrics-file", str(metrics)]) == 0
    rows = read_rows(metrics)
    assert [r["step"] for r in rows] == list(range(6))

    cfg = dataclasses.replace(
        cli._tiny_override(preset("vit-base-patch16-224")), num_classes=4)
    model = cli._model_cls("vit")(cfg, rngs=nnx.Rngs(11), mesh=None,
                                  rules=None, dtype=jnp.float32,
                                  param_dtype=jnp.float32)
    optimizer = make_optimizer(model, OptimizerConfig(
        learning_rate=1e-3, weight_decay=0.01, warmup_steps=2,
        total_steps=6, moment_dtype=None))
    step_fn = make_classifier_train_step(donate=True)
    data = blob_classification(8, image_size=cfg.vision.image_size,
                               num_classes=4, seed=11,
                               num_frames=cfg.vision.num_frames)
    plain = []
    for _ in range(6):
        batch = jax.tree.map(jnp.asarray, next(data))
        out = step_fn(model, optimizer, *batch)
        plain.append({k: float(v) for k, v in jax.device_get(out).items()})
    assert [r["loss"] for r in rows] == [p["loss"] for p in plain]
    assert [r["accuracy"] for r in rows] == [p["accuracy"] for p in plain]


CELLS = sorted(p.stem for p in (REPO / "benchmarks/workloads").glob("*.json"))
#: sha256 of the CPU lowering of each cell's step at the rehearsal's size, as
#: the commit before the loop kept a step in flight lowers it (b3dadd4): the
#: loop decides when a program is launched, never which. A change to the
#: model, the step or the optimizer moves these on purpose.
LOWERED_STEP = {
    "ouro_2_6b.train":
        "6b16d46782902c9713915a21cfba4880d330049d7b2e00552c4f8b0f76c2b5e8",
    "siglip_b16_256.train":
        "c9b165063b385bd35f2328f33713f9fc7747c10ead8bd6e680e5154b2fd2d3bf",
    "vit_l16_384.train":
        "2cade23434015a53dc351cba448c8791c66560469040d0ef6a12e6358f699b6b",
    # the sparse decoder's. Moved by PR 34, on purpose and at this size
    # only: a chunk of the grouped products has a floor of rows, their row
    # tile follows the groups and groups of 128 rows take tiles of their own
    # (`nn/moe.py`), which the 64 tokens of a rehearsal see; at the cell's
    # size all three give what they gave, and the lowered step is the
    # parent's (`scripts/lowered_step_hash.py`; it read 1071f273... as PR 32
    # lowered it)
    "kanana_2_30b_a3b.train":
        "a96bad3a8bbe3229591c0257836522c9d0e3d0a16592bd063a3563f42aac95fc",
    # grouped-query attention with windowed layers beside full ones (PR 34)
    "trinity_large.train":
        "ca16e4b89235d31826e60fad26c34aeab43579c4c7656171764d520e6bb3bc75",
    # Kimi Delta Attention beside position-free latent attention, four runs
    # of like layers (PR 38)
    "kimi_linear_48b_a3b.train":
        "4f8ee20c1fcbaaa809f391d70ddb4ef69f49ba80b504b48d12159cac48abad2b",
    # Mamba-2 beside position-free grouped-query attention, a dense stack of
    # three runs
    "granite_4_0_h_micro.train":
        "617b0e482c94f74ab33cf6477442d43bf800ff45bbb2bf14d2b6ce09e47d6b84",
}


@pytest.mark.parametrize("cell", CELLS)
def test_a_cells_tiny_step_lowers_to_the_same_text(cell, capsys):
    from jimm_tpu.parallel import use_sharding
    workload = json.loads(
        (REPO / "benchmarks/workloads" / f"{cell}.json").read_text())
    config = json.loads((REPO / "benchmarks/configs"
                         / f"{workload['config']}.json").read_text())
    traffic = workload["traffic_params"]
    result = cli.train(cli.build_parser().parse_args(
        ["train", "--preset", config["preset"], "--tiny", "--seed", "1",
         "--batch-size", str(traffic["rehearse_batch_size"]),
         "--steps", "2", "--log-every", "0", *traffic["cli_args"]]))
    with use_sharding(result.mesh, result.rules):
        text = result.step_fn.lower(result.model, result.optimizer,
                                    *result.batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED_STEP[cell]


# ---------------------------------------------------------------------------
# checkpoints, drills and resume see the state they saw
# ---------------------------------------------------------------------------

def committed_steps(ckpt_dir) -> list[int]:
    """Steps whose completion marker the checkpoint manager has written."""
    from jimm_tpu.train import CheckpointManager
    mgr = CheckpointManager(ckpt_dir)
    try:
        return sorted(mgr._marked_steps() or ())
    finally:
        mgr.close()


@pytest.fixture(scope="module")
def control(tmp_path_factory):
    """The uninterrupted run's rows."""
    path = tmp_path_factory.mktemp("control") / "m.jsonl"
    assert main(TINY_VIT + ["--log-every", "0", "--batch-fingerprint",
                            "--metrics-file", str(path)]) == 0
    return read_rows(path)


@pytest.mark.parametrize("drill, rows_left, committed, rc", [
    # the crash fires after step 2's save committed: its row is written on
    # the way out, and nothing later was dispatched
    ("crash@2", [0, 1, 2], [0, 1, 2], None),
    # SIGTERM after step 2: the grace save is step 2's, one grace step (3)
    # runs while it is written and its row is kept, its result thrown away
    ("preempt@2", [0, 1, 2, 3], [0, 1, 2], 75),
])
def test_a_drill_leaves_the_rows_and_the_checkpoint_it_left(
        tmp_path, control, drill, rows_left, committed, rc):
    ckpt = tmp_path / "ckpt"
    drilled = tmp_path / "drilled.jsonl"
    argv = TINY_VIT + ["--log-every", "0", "--batch-fingerprint",
                       "--ckpt-dir", str(ckpt), "--save-every", "1",
                       "--metrics-file", str(drilled),
                       "--inject-faults", drill]
    if rc is None:
        with pytest.raises(RuntimeError, match="injected failure at step 2"):
            main(argv)
    else:
        assert main(argv + ["--preemption-save"]) == rc
    rows = read_rows(drilled)
    assert [r["step"] for r in rows] == rows_left
    # the same work up to there, on the same batches
    for r, c in zip(rows, control):
        assert (r["loss"], r["batch_fingerprint"]) == (
            c["loss"], c["batch_fingerprint"])
    assert committed_steps(ckpt) == committed

    # --resume from step 2's state repeats the uninterrupted run's rows
    resumed = tmp_path / "resumed.jsonl"
    assert main(TINY_VIT + ["--log-every", "0", "--batch-fingerprint",
                            "--ckpt-dir", str(ckpt), "--save-every", "100",
                            "--resume", "--metrics-file", str(resumed)]) == 0
    rows = read_rows(resumed)
    assert [r["step"] for r in rows] == [3, 4, 5]
    for r, c in zip(rows, control[3:]):
        assert r["batch_fingerprint"] == c["batch_fingerprint"]
        np.testing.assert_allclose(r["loss"], c["loss"], rtol=2e-4)


def test_a_save_at_step_k_holds_the_state_after_step_k(tmp_path, control):
    """One save in the middle of an otherwise uninterrupted run, taken while
    the loop is a step ahead of its rows: resuming from it repeats the
    run."""
    ckpt = tmp_path / "ckpt"
    first = tmp_path / "first.jsonl"
    assert main(TINY_VIT + ["--log-every", "0", "--ckpt-dir", str(ckpt),
                            "--save-every", "3",
                            "--metrics-file", str(first)]) == 0
    assert [r["loss"] for r in read_rows(first)] == [
        c["loss"] for c in control]
    assert committed_steps(ckpt) == [0, 3]
    resumed = tmp_path / "resumed.jsonl"
    assert main(TINY_VIT + ["--log-every", "0", "--ckpt-dir", str(ckpt),
                            "--save-every", "100", "--resume",
                            "--metrics-file", str(resumed)]) == 0
    rows = read_rows(resumed)
    assert [r["step"] for r in rows] == [4, 5]
    for r, c in zip(rows, control[4:]):
        np.testing.assert_allclose(r["loss"], c["loss"], rtol=2e-4)


def test_a_failing_step_still_leaves_the_rows_before_it(tmp_path,
                                                        monkeypatch):
    """An exception out of the loop (here: the input runs dry after three
    batches) waits for the step in flight and writes its row."""
    from jimm_tpu import data as data_lib
    original = data_lib.blob_classification

    def three_batches(*a, **kw):
        it = original(*a, **kw)
        for _ in range(3):
            yield next(it)
        raise OSError("the input ran dry")

    monkeypatch.setattr(data_lib, "blob_classification", three_batches)
    metrics = tmp_path / "m.jsonl"
    with pytest.raises(OSError, match="ran dry"):
        main(TINY_VIT + ["--log-every", "0", "--metrics-file", str(metrics)])
    assert [r["step"] for r in read_rows(metrics)] == [0, 1, 2]


# ---------------------------------------------------------------------------
# set-up: the first row begins with it, and the listener leaves with the call
# ---------------------------------------------------------------------------

def _monitoring_listeners():
    from jax._src import monitoring
    return (list(monitoring.get_event_duration_listeners())
            + list(monitoring.get_event_listeners()))


def test_row_0_begins_with_the_setup_spans_and_names_every_compile(tmp_path):
    import time
    metrics = tmp_path / "m.jsonl"
    before = _monitoring_listeners()
    t0 = time.time_ns()
    assert main(TINY_VIT + ["--log-every", "0",
                            "--metrics-file", str(metrics)]) == 0
    t1 = time.time_ns()
    assert _monitoring_listeners() == before, "the listener left with train()"
    rows = read_rows(metrics)
    names = [p[0] for p in rows[0]["phases"]]
    assert names[:2] == ["backend_init", "imports"], (
        "from train()'s first statement, in the order they ran")
    builds = [n for n in names
              if n in ("model_build", "optimizer_build", "data_build")]
    assert builds == ["model_build", "optimizer_build", "data_build",
                      "data_build"], "in order; the wrappers' span is last"
    assert names.index("data_build") < names.index("next_batch")
    spans = sorted((p for r in rows for p in r["phases"]),
                   key=lambda p: p[1])
    assert t0 <= spans[0][1] and spans[0][0] == "backend_init"
    for (_, a0, adur), (_, b0, _) in zip(spans, spans[1:]):
        assert a0 + adur <= b0, "never overlapping"
    assert not any(p[0] in obs.goodput.SETUP_PHASES
                   for r in rows[1:] for p in r["phases"])

    # every compile request of the run is in row 0, inside the process's
    # lifetime and inside one phase: the small programs in the two builds,
    # the step's own three stages in step 0's dispatch
    events = rows[0]["compiles"]
    assert not any("compiles" in r or "cache_misses" in r for r in rows[1:])

    def parent(event):
        (found,) = [name for name, start, dur in spans
                    if start <= event[2] < start + dur]
        return found

    for kind, fun, start, dur in events:
        assert kind in ("trace", "lower", "compile")
        assert t0 <= start and start + dur <= t1
    parents = {parent(e) for e in events}
    # (a process that has built this model before holds the builds' small
    # programs already: only the step's own stages are sure to be there)
    assert "dispatch" in parents
    assert parents <= {"imports", "backend_init", "model_build",
                       "optimizer_build", "data_build", "next_batch", "place",
                       "dispatch"}
    (dispatch0,) = [p for p in rows[0]["phases"] if p[0] == "dispatch"]
    step = [(kind, fun) for kind, fun, start, _ in events
            if dispatch0[1] <= start < dispatch0[1] + dispatch0[2]]
    assert step == [("trace", "train_step"), ("lower", "jit(train_step)"),
                    ("compile", "jit(train_step)")]
    snap = obs.get_registry("jimm_train").snapshot()
    assert snap["compile_requests_total"] >= sum(
        e[0] == "compile" for e in events)


def test_a_late_compile_is_in_the_row_of_the_step_that_asked_for_it(
        tmp_path, monkeypatch):
    """Rows are grouped by step and so are their compile events: with one
    step in flight, row k is written after step k+1's dispatch, and what that
    dispatch compiled waits for row k+1. Here step 2's batch is half the
    size, and the step compiles a second time."""
    from jimm_tpu import data as data_lib
    original = data_lib.blob_classification

    def a_smaller_third_batch(*a, **kw):
        for i, batch in enumerate(original(*a, **kw)):
            yield tuple(x[:4] for x in batch) if i == 2 else batch

    monkeypatch.setattr(data_lib, "blob_classification",
                        a_smaller_third_batch)
    metrics = tmp_path / "m.jsonl"
    assert main(TINY_VIT + ["--log-every", "0",
                            "--metrics-file", str(metrics)]) == 0
    rows = read_rows(metrics)
    assert [i for i, r in enumerate(rows) if "compiles" in r] == [0, 2]
    (dispatch2,) = [p for p in rows[2]["phases"] if p[0] == "dispatch"]
    late = [(kind, fun) for kind, fun, start, _ in rows[2]["compiles"]
            if dispatch2[1] <= start < dispatch2[1] + dispatch2[2]]
    assert late == [("trace", "train_step"), ("lower", "jit(train_step)"),
                    ("compile", "jit(train_step)")]


def test_the_listener_is_gone_after_train_raises(tmp_path):
    """A ``crash@1`` drill out of the loop, and a refusal before the model
    is built: neither leaves a listener behind for the next call."""
    before = _monitoring_listeners()
    metrics = tmp_path / "m.jsonl"
    with pytest.raises(RuntimeError, match="injected failure at step 1"):
        main(TINY_VIT + ["--log-every", "0", "--metrics-file", str(metrics),
                         "--inject-faults", "crash@1"])
    assert _monitoring_listeners() == before
    rows = read_rows(metrics)
    assert [r["step"] for r in rows] == [0, 1]
    assert rows[0]["phases"][1][0] == "imports" and "compiles" in rows[0]
    with pytest.raises(SystemExit, match="--tiny conflicts"):
        main(TINY_VIT + ["--from-pretrained", str(tmp_path)])
    assert _monitoring_listeners() == before


def test_with_obs_off_no_span_no_listener_no_row_key(tmp_path, monkeypatch):
    import jax
    heard = []
    for register in ("register_event_duration_secs_listener",
                     "register_event_listener"):
        monkeypatch.setattr(jax.monitoring, register, heard.append)
    obs.CompileWatch(obs.MetricRegistry("t_loop_watch")).listen()
    assert len(heard) == 2, "what a watch registers with obs on"
    del heard[:]
    metrics = tmp_path / "m.jsonl"
    prev = obs.enabled()
    obs.set_enabled(False)
    try:
        assert main(TINY_VIT + ["--log-every", "0",
                                "--metrics-file", str(metrics)]) == 0
    finally:
        obs.set_enabled(prev)
    assert heard == [], "JIMM_OBS=0 registers no listener"
    for r in read_rows(metrics):
        assert r["phases"] == []
        assert not {"compiles", "cache_hits", "cache_misses"} & set(r)


# ---------------------------------------------------------------------------
# the counter that says the mechanism engaged
# ---------------------------------------------------------------------------

def test_dispatched_ahead_is_counted_and_on_the_goodput_line(capsys):
    counter = obs.get_registry("jimm_train").counter(
        "steps_dispatched_ahead_total")
    before = counter.value
    # a batch large enough that the CPU is still running a step when the
    # next call returns
    assert main(["train", "--preset", "vit-base-patch16-224", "--tiny",
                 "--batch-size", "64", "--steps", "6",
                 "--log-every", "0"]) == 0
    goodput = json.loads(next(
        line for line in capsys.readouterr().out.splitlines()
        if line.startswith("goodput: "))[9:])
    ahead = counter.value - before
    assert 0 <= ahead <= 5, "the first step has nothing to be ahead of"
    assert goodput["dispatched_ahead_frac"] == round(ahead / 6, 4)
    assert "jimm_train_steps_dispatched_ahead_total" in obs.snapshot()


# ---------------------------------------------------------------------------
# the benchmark's readers take the overlapped rows as they are
# ---------------------------------------------------------------------------

T0 = 1_790_621_200_310_885_257  # the capture's profile_start_time
PERIOD = 1000.0  # microseconds: each program starts where the last ended


def overlapped_events():
    """Four executions of one program on device 0, back to back: each 990 us
    of operations and a 10 us tail with none, which is all the device
    idles."""
    from benchmarks.trace.reduce import Event
    ev = []
    for k in range(4):
        t = PERIOD * (k + 1)
        ev += [Event("/device:TPU:0", "XLA Modules", "jit_train_step(1)",
                     t, PERIOD, {}),
               Event("/device:TPU:0", "XLA Ops", "%fusion.1 = x", t, 600.0,
                     {}),
               Event("/device:TPU:0", "XLA Ops", "%fusion.2 = x", t + 600,
                     390.0, {})]
    return ev


def overlapped_rows():
    """Steps 20..23 as the loop writes them with one step in flight (times
    in microseconds on the trace's clock, program i starting at t): the
    host wakes 40 us after program i-1 ended, logs for 60, draws and places
    the batch of step i+1 for 130 and dispatches it for 200, all inside
    program i, then waits for program i."""
    def span(name, lo, hi):
        return [name, T0 + int(lo * 1e3), int((hi - lo) * 1e3)]

    rows = []
    for k, step in enumerate((20, 21, 22, 23)):
        t = PERIOD * (k + 1)  # this step's program starts here
        # this step was dispatched inside the program before it
        phases = [span("host_sync", t - PERIOD + 40, t - PERIOD + 100),
                  span("next_batch", t - PERIOD + 100, t - PERIOD + 130),
                  span("place", t - PERIOD + 130, t - PERIOD + 230),
                  span("dispatch", t - PERIOD + 230, t - PERIOD + 430),
                  # after the next step's dispatch, to this program's end
                  span("device_wait", t + 430, t + PERIOD + 40)]
        rows.append({"step": step, "time": (T0 / 1e3 + t + PERIOD + 45) / 1e6,
                     "step_time_s": 810e-6, "loss": 1.0, "phases": phases})
    return rows


def test_the_benchmarks_readers_take_the_overlapped_rows():
    from benchmarks import harness
    from benchmarks.trace import host_join, reduce
    rows = overlapped_rows()
    j = host_join.join(overlapped_events(), T0, rows)
    assert j["periods"] == 3 and j["window_ms"] == pytest.approx(3.0)
    check = j["clock_check"]
    assert check["ordered"] is True
    assert [c["step"] for c in check["steps"]] == [20, 21, 22, 23]
    # program i starts 770 us after dispatch(i) began, because it waits for
    # program i-1, and the host wakes 40 us after it ended
    assert all(c["program_start_after_dispatch_began_ms"]
               == pytest.approx(0.770)
               and c["wakeup_ms"] == pytest.approx(0.040)
               for c in check["steps"])
    # the device idles 10 us a period, inside a running program: under no
    # host phase, and none of it before a launch
    assert j["idle_total_ms"] == pytest.approx(0.030)
    assert sum(j["idle_ms"].values()) == pytest.approx(
        j["idle_total_ms"] / j["periods"])
    assert j["idle_ms"] == pytest.approx(
        {"input": 0.0, "dispatch": 0.0, "launch": 0.0, "wakeup": 0.0,
         "log": 0.0, "unnamed": 0.010}, abs=1e-9)
    r = reduce.reduce_events(overlapped_events())
    assert sum(j["idle_ms"].values()) == pytest.approx(
        r["idle_pct"] / 100 * r["window_s"] * 1e3 / r["periods"])

    # the program-span readers: a row's phases are its own step's
    readers = harness.load_readers("layer_metrics")
    o = {"rows": rows, "window_rows": rows[1:3], "platform": "cpu"}
    assert {name: readers[name](o) for name in (
        "next_batch_ms", "place_ms", "dispatch_ms", "device_wait_ms",
        "log_ms")} == pytest.approx(
        {"next_batch_ms": 0.030, "place_ms": 0.100, "dispatch_ms": 0.200,
         "device_wait_ms": 0.610, "log_ms": 0.060})
    # a period less the five phases: nothing is left over or counted twice
    assert readers["loop_other_ms"](o) == pytest.approx(0.0, abs=1e-3)


def test_a_gap_between_two_queued_programs_is_named_by_the_phase_open_then():
    """Where the device does wait between two programs, the wait is still
    put down to the phase the host was in: here 30 us under the wait for
    the program before (its wake-up part)."""
    from benchmarks.trace import host_join
    events = overlapped_events()
    for e in events:  # every program but the first starts 30 us late
        if e.start_us >= 2 * PERIOD:
            e.start_us += 30.0
            if not e.name.startswith("%fusion.1"):
                e.dur_us -= 30.0  # and ends where it did
    j = host_join.join(events, T0, overlapped_rows())
    assert j["clock_check"]["ordered"] is True
    assert j["idle_ms"]["launch"] == 0.0
    assert j["idle_ms"]["wakeup"] == pytest.approx(0.030)
    assert j["idle_ms"]["unnamed"] == pytest.approx(0.010)
    assert sum(j["idle_ms"].values()) == pytest.approx(
        j["idle_total_ms"] / j["periods"])
