"""Train cells of a language model whose modules the CELL'S FILE names: one
call of ``jimm_tpu.cli train`` per run, fed by the program's own token
generator.

The LAST copy of ``train_cli.run``'s frame. ``train_lm.py``,
``train_moe_lm.py`` and ``train_gqa_moe_lm.py`` hard-wire their family's FLOP
count, comparison and scopes and are not this PR's to edit; this one takes
them from ``traffic_params``:

- ``flops_module``: ``benchmarks/<name>.py`` with ``train_step_flops(config,
  batch, seq_len)``;
- ``parity_module``: ``benchmarks/reference/<name>.py`` with
  ``check_train(run, result)``;
- ``reader_module``: ``benchmarks/layer_metrics/<name>.py`` with
  ``INNER_SCOPES``, ``OUTER_SCOPES``, ``scope_names(scope)`` and
  ``observe(run, result)`` (what its readers need beside the observations
  every train cell leaves);
- ``flash_kernels``: the ``op_name`` paths of the Pallas calls to time.

So the ``benchmark`` PR that PERF.md section 7 (ii) asks for has a target: an
older LM cell moves here by naming its three modules in its file (and giving
its reader module an ``observe``). The same shape as the older drivers: the
measured window is the CLI's own loop, ``--steps`` is a function of
``--seconds`` and the cell's file alone, the last four steps are the traced
tail, the depth comes from the configuration's file (``num_layers``) and the
sequence length from the cell's (``seq_len``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import math
import statistics
import sys
import threading

from benchmarks import harness
from benchmarks.drivers import train_cli
from benchmarks.drivers.train_cli import (TRACED_STEPS, WARMUP_STEPS, _Tee,
                                          global_batch_size, hlo_index,
                                          planned_steps, trace_tail)


def cli_argv(run: harness.Run, steps: int, metrics_file) -> list[str]:
    """``train_cli``'s arguments, and at the timed size the depth and the
    length from the files. A rehearsal runs in float32 (``--bf16`` left out):
    at 64 wide and 32 tokens a bfloat16 model's distance from the reference
    says nothing (a stack of normalised linear-attention layers amplifies
    rounding noise there), while a float32 one is held to the reference's
    digits, which is what a rehearsal can check."""
    argv = train_cli.cli_argv(run, steps, metrics_file)
    if run.rehearse:
        return [a for a in argv if a != "--bf16"]
    return argv + ["--num-layers", str(run.config["num_layers"]),
                   "--seq-len", str(run.cell["traffic_params"]["seq_len"])]


def resolved_runtime(result, compiled_text: str) -> dict:
    """What the code chose by itself for this cell."""
    from jimm_tpu import obs
    d = result.model.config.decoder
    snapshot = obs.snapshot()
    flash_calls = compiled_text.count("tpu_custom_call")

    def kind(block) -> dict:
        mixer = next((name for name in ("kda", "mla", "gqa")
                      if getattr(block, name, None) is not None), "attention")
        return {"mixer": mixer, "sparse": block.moe is not None,
                "layers": block.depth}

    runs = ({name: kind(block) for name, block in d.runs()}
            if hasattr(d, "runs") else None)
    return {"attn_impl": d.attn_impl,
            "attention_path": "flash (Pallas)" if flash_calls else "XLA",
            "flash_calls": flash_calls,
            "flash_regimes": {k[len("jimm_flash_"):-len("_total")]: v
                              for k, v in snapshot.items()
                              if k.startswith("jimm_flash_")},
            "program_counters": {k: v for k, v in snapshot.items()
                                 if k.startswith(("jimm_kda_", "jimm_lm_",
                                                  "jimm_moe_"))},
            "scan_unroll": d.scan_unroll, "remat": d.remat,
            "remat_policy": d.remat_policy, "precision": d.precision,
            "layers": d.depth, "seq_len": d.seq_len, "runs": runs,
            "decoder": {f.name: getattr(d, f.name)
                        for f in dataclasses.fields(d)
                        if isinstance(getattr(d, f.name),
                                      (int, float, str, bool, type(None)))}}


def run(run: harness.Run, devices) -> dict:
    import numpy as np

    from jimm_tpu import cli
    from jimm_tpu.parallel import use_sharding

    traffic = run.cell["traffic_params"]
    flops_module = importlib.import_module(
        f"benchmarks.{traffic['flops_module']}")
    parity_module = importlib.import_module(
        f"benchmarks.reference.{traffic['parity_module']}")
    readers = importlib.import_module(
        f"benchmarks.layer_metrics.{traffic['reader_module']}")
    steps = planned_steps(run)
    last_window_step = steps - TRACED_STEPS  # exclusive
    metrics_file = run.run_dir / "metrics.jsonl"
    metrics_file.unlink(missing_ok=True)
    profile_dir = run.run_dir / "profile" if run.trace else None
    argv = cli_argv(run, steps, metrics_file)
    harness.log(event="train_cli", argv=argv,
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
        with contextlib.redirect_stdout(tee):
            result = cli.train(cli.build_parser().parse_args(argv))
    finally:
        finished.set()
        if watcher is not None:
            watcher.join()
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
    seq_len = result.model.config.decoder.seq_len

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
                step_s_median=statistics.median(r["step_time_s"]
                                                for r in window),
                global_batch=global_batch, tokens_per_step=global_batch * seq_len,
                program_bytes=program, memory_stats_peak=stats_peak,
                goodput=goodput,
                last_row={k: v for k, v in rows[-1].items() if k != "phases"})

    # -- correctness
    losses = [r["loss"] for r in rows]
    finite = bool(np.all(np.isfinite(losses)))
    late = run.watch.between(t_window_start, t_window_end)
    agree = parity_module.check_train(run, result)
    harness.log(event="correct", losses_finite=finite,
                compile_requests_in_window=late, parity=agree)
    correct = finite and not late and agree["ok"]

    flash_kernels = tuple(traffic.get("flash_kernels", ()))
    observed = {
        "rows": rows, "window_rows": window,
        "window_s": window_s, "steps_total": steps,
        "global_batch": global_batch, "goodput": goodput,
        "t_process_start": run.t_process_start,
        "t_first_measured": t_window_start,
        "flops_per_step": flops_module.train_step_flops(
            run.config, global_batch, traffic["seq_len"]),
        "chips": run.chips, "device_kind": run.device["kind"],
        "platform": run.device["platform"],
        "program": program, "flash_calls": runtime["flash_calls"],
        "config": run.config, "flash_kernels": flash_kernels,
        **readers.observe(run, result),
    }
    outcome = {"correct": correct, "attempted": len(window),
               "failed": sum(not math.isfinite(r["loss"]) for r in window),
               "memory_peak_bytes": max(stats_peak, program["resident"]),
               "observed": observed}
    if run.trace:
        from benchmarks.trace import reduce
        outcome["trace"] = reduce.reduce_profile(
            profile_dir, hlo=hlo_index(compiled_text), kernels=flash_kernels,
            scopes=("fwd_bwd", "optimizer_update",
                    *(name for scope in (*readers.OUTER_SCOPES,
                                         *readers.INNER_SCOPES)
                      for name in readers.scope_names(scope))),
            gap_label="between steps (next batch, placement, sync, log)")
        observed["trace"] = outcome["trace"]
        harness.log(event="trace", **{k: v for k, v in outcome["trace"].items()
                                      if k != "breakdown"})
    return outcome
