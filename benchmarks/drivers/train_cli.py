"""Train cells: one call of ``jimm_tpu.cli train`` per run.

The measured window is the CLI's own loop (data wait, placement, step, sync
and logging all inside), not a copy of it. So the number of steps is fixed
before the call: three warm-up steps plus as many as fill ``--seconds`` at
the cell's ``est_step_ms``, plus four that a traced run traces. It is a
function of ``--seconds`` and the cell's file alone, never of an earlier
run: ``cli train`` bakes ``--steps`` into the compiled step (the cosine
schedule's horizon), so another count is another program and a cold compile.
The window runs from the ``time`` stamp of the last warm-up row to that of
the last row before the traced tail of ``--metrics-file``.

The cell's file gives the CLI arguments of its traffic (loss, mesh, rules and
so on) as data; the loop is fed from the benchmark's seeded pool
(``benchmarks/traffic.py``); this driver adds the preset, the seed, the batch size, the step count and the
metrics file. With ``--trace 1`` a watcher thread traces the last four steps
with the host and Python tracers off (``harness.start_device_trace``). (The
program's own ``--profile-dir`` turns both on, which slowed the host eightfold
in the steps it captured; PERF.md, Findings, PR 22.)
"""

from __future__ import annotations

import contextlib
import io
import json
import math
import statistics
import sys
import threading
import time

from benchmarks import flops, harness, traffic as traffic_lib

#: compile, first execution, and one more: outside the window
WARMUP_STEPS = 3
#: the tail every run executes and only a traced run traces
TRACED_STEPS = 4
MIN_WINDOW_STEPS = 5


class _Tee(io.TextIOBase):
    """Passes the CLI's output through and keeps it for the goodput line."""

    def __init__(self, stream) -> None:
        self.stream = stream
        self.lines: list[str] = []
        self._partial = ""

    def write(self, text: str) -> int:
        self.stream.write(text)
        self._partial += text
        *whole, self._partial = self._partial.split("\n")
        self.lines.extend(whole)
        return len(text)

    def flush(self) -> None:
        self.stream.flush()


def planned_steps(run: harness.Run) -> int:
    """Steps to ask the CLI for: a function of ``--seconds`` and the cell's
    file, so that every run of the cell compiles the same program."""
    traffic = run.cell["traffic_params"]
    if run.rehearse:
        return WARMUP_STEPS + MIN_WINDOW_STEPS + TRACED_STEPS
    in_window = max(MIN_WINDOW_STEPS,
                    math.ceil(run.seconds * 1e3 / traffic["est_step_ms"]))
    return WARMUP_STEPS + in_window + TRACED_STEPS


def _rows(metrics_file) -> int:
    try:
        with open(metrics_file, "rb") as f:
            return sum(1 for _ in f)
    except FileNotFoundError:
        return 0


def trace_tail(metrics_file, after_rows: int, trace_dir, finished) -> None:
    """Watcher thread: start the profiler once ``after_rows`` steps are
    logged, stop it when the CLI has returned."""
    import jax
    while _rows(metrics_file) < after_rows and not finished.is_set():
        time.sleep(0.002)
    harness.start_device_trace(trace_dir)
    finished.wait()
    jax.profiler.stop_trace()


def global_batch_size(run: harness.Run) -> int:
    traffic = run.cell["traffic_params"]
    return (traffic["rehearse_batch_size"] if run.rehearse
            else traffic["batch_size"])


def cli_argv(run: harness.Run, steps: int, metrics_file) -> list[str]:
    traffic = run.cell["traffic_params"]
    argv = ["train", "--preset", run.config["preset"],
            "--seed", str(run.seed),
            "--batch-size", str(global_batch_size(run)),
            "--steps", str(steps), "--log-every", "1",
            "--metrics-file", str(metrics_file), *traffic["cli_args"]]
    if run.rehearse:
        argv.append("--tiny")
    return argv


def hlo_index(compiled_text: str) -> dict[str, dict]:
    """Instruction name -> its source path with the named scopes (the
    ``op_name`` metadata) and whether it is a Pallas kernel. The device trace
    names operations by instruction; the scopes are only here."""
    import re
    pattern = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
    index = {}
    for line in compiled_text.splitlines():
        m = pattern.match(line)
        if not m:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        index[m.group(1)] = {"op_name": op.group(1) if op else "",
                             "pallas": "tpu_custom_call" in line}
    return index


def resolved_runtime(result, compiled_text: str) -> dict:
    """What the code chose by itself for this cell."""
    v = result.model.config.vision
    flash_calls = compiled_text.count("tpu_custom_call")
    return {"attn_impl": v.attn_impl,
            "attention_path": "flash (Pallas)" if flash_calls else "XLA",
            "flash_calls": flash_calls, "scan_unroll": v.scan_unroll,
            "remat": v.remat, "remat_policy": v.remat_policy,
            "ln_impl": v.ln_impl, "precision": v.precision,
            "mesh": (dict(result.mesh.shape) if result.mesh is not None
                     else None)}


def run(run: harness.Run, devices) -> dict:
    import numpy as np

    from jimm_tpu import cli
    from jimm_tpu.parallel import use_sharding

    traffic = run.cell["traffic_params"]
    steps = planned_steps(run)
    last_window_step = steps - TRACED_STEPS  # exclusive
    metrics_file = run.run_dir / "metrics.jsonl"
    metrics_file.unlink(missing_ok=True)
    profile_dir = run.run_dir / "profile" if run.trace else None
    argv = cli_argv(run, steps, metrics_file)
    harness.log(event="train_cli", argv=argv, pool=traffic_lib.POOL,
                window_steps=last_window_step - WARMUP_STEPS)

    finished = threading.Event()
    watcher = None
    if run.trace:
        watcher = threading.Thread(
            target=trace_tail, name="bench-profiler",
            args=(metrics_file, last_window_step, profile_dir, finished))
        watcher.start()
    tee = _Tee(sys.stdout)
    try:
        with contextlib.redirect_stdout(tee), \
                traffic_lib.feed_cli(run.seed) as drawn:
            result = cli.train(cli.build_parser().parse_args(argv))
    finally:
        finished.set()
        if watcher is not None:
            watcher.join()
    if drawn["batches"] < steps:
        raise RuntimeError(f"the CLI drew {drawn['batches']} batches from the "
                           f"benchmark's pool in {steps} steps: it was fed by "
                           f"something else")
    goodput = {}
    for line in tee.lines:
        if line.startswith("goodput: "):
            goodput = json.loads(line[len("goodput: "):])

    rows = [json.loads(line) for line in metrics_file.read_text().splitlines()]
    if len(rows) != steps:
        raise RuntimeError(f"{len(rows)} steps logged, {steps} asked for")
    t_window_start = rows[WARMUP_STEPS - 1]["time"]
    t_window_end = rows[last_window_step - 1]["time"]
    window = rows[WARMUP_STEPS:last_window_step]
    window_s = t_window_end - t_window_start
    global_batch = global_batch_size(run)
    step_s = statistics.median(r["step_time_s"] for r in window)

    # -- the compiled step: what it asks of a device, and which path it took
    with use_sharding(result.mesh, result.rules):
        compiled = result.step_fn.lower(result.model, result.optimizer,
                                        *result.batch).compile()
    program = harness.program_bytes(compiled)
    compiled_text = compiled.as_text()
    runtime = resolved_runtime(result, compiled_text)
    stats_peak = harness.memory_stats_peak(devices)
    harness.log(event="resolved_runtime", **runtime)
    harness.log(event="window", steps=len(window), seconds=window_s,
                first_step_s=rows[0]["step_time_s"],
                warmup_step_s=[r["step_time_s"]
                               for r in rows[1:WARMUP_STEPS]],
                period_s=window_s / len(window),
                step_s_median=step_s, global_batch=global_batch,
                program_bytes=program, memory_stats_peak=stats_peak,
                goodput=goodput)

    # -- correctness
    losses = [r["loss"] for r in rows]
    finite = bool(np.all(np.isfinite(losses)))
    late = run.watch.between(t_window_start, t_window_end)
    from benchmarks.reference import parity
    agree = parity.check_train(run, result)
    harness.log(event="correct", losses_finite=finite,
                compile_requests_in_window=late, parity=agree)
    correct = finite and not late and agree["ok"]

    observed = {
        "rows": rows, "window_rows": window,
        "window_s": window_s, "steps_total": steps,
        "global_batch": global_batch, "goodput": goodput,
        "t_process_start": run.t_process_start,
        "t_first_measured": t_window_start,
        "flops_per_step": flops.train_step_flops(run.config, global_batch),
        "chips": run.chips, "device_kind": run.device["kind"],
        "platform": run.device["platform"],
        "program": program, "flash_calls": runtime["flash_calls"],
        "config": run.config,
        "flash_kernels": tuple(traffic.get("flash_kernels", ())),
    }
    outcome = {"correct": correct, "attempted": len(window),
               "failed": sum(not math.isfinite(r["loss"]) for r in window),
               "memory_peak_bytes": max(stats_peak, program["resident"]),
               "observed": observed}
    if run.trace:
        from benchmarks.trace import reduce
        outcome["trace"] = reduce.reduce_profile(
            profile_dir, hlo=hlo_index(compiled_text),
            kernels=tuple(traffic.get("flash_kernels", ())),
            # the CLI loop has no spans of its own (the next tracing issue's)
            gap_label="between steps (next batch, placement, sync, log)")
        observed["trace"] = outcome["trace"]
        harness.log(event="trace", **{k: v for k, v in outcome["trace"].items()
                                      if k != "breakdown"})
    return outcome
