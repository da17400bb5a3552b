"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no network, nothing written outside the checkout. The cell is
``benchmarks/workloads/<name>.json``; its driver builds the system under test
from ``--seed``, warms up every shape, measures for ``--seconds`` and checks
the outputs against the plain reference. Without ``--trace`` the last line
of standard output holds the cell's end-to-end metrics; with ``--trace 1``
its per-layer metrics, read from the program's spans and counters and from
a profiler trace of a few steps.

Without a TPU (or with fewer chips than the cell asks for) it exits 2 and
prints no result. ``--rehearse`` runs the cell at a tiny size on whatever
backend is there and reports under that backend's name; it is for the tests
and never yields a device number.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks import harness  # noqa: E402


def main(argv: list[str] | None = None, *,
         t_process_start: float | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None,
                   help="length of the measured window (default: the "
                        "manifest's run_seconds)")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny size on any backend; reports that backend")
    args = p.parse_args(argv)

    manifest = harness.load_manifest()
    run = harness.load_run(
        harness.REPO, args.workload, seed=args.seed,
        seconds=(args.seconds if args.seconds is not None
                 else manifest["run_seconds"]),
        trace=bool(args.trace), rehearse=args.rehearse,
        t_process_start=t_process_start or time.time())
    try:
        devices = harness.claim_devices(run)
    except harness.NoAccelerator as e:
        print(e, file=sys.stderr)
        return 2

    import jax
    cache_dir = harness.enable_caches()
    harness.log(event="start", workload=run.name, seed=run.seed,
                seconds=run.seconds, trace=run.trace,
                size="rehearsal (tiny)" if run.rehearse else "published widths",
                device=run.device, devices_visible=len(jax.devices()),
                jax=jax.__version__, compile_cache_dir=cache_dir)
    run.watch = harness.CompileWatch()
    try:
        driver = harness.import_driver(run.cell["driver"])
        outcome = driver.run(run, devices)
    except Exception:  # noqa: BLE001 — no result line; the exit code says so
        traceback.print_exc()
        return 1
    finally:
        run.watch.close()
    metrics = harness.collect_metrics(manifest, run, outcome["observed"])
    harness.log(event="end", compile_cache=run.watch.cache,
                compile_requests=len(run.watch.requests),
                slow_compiles=[[c["fun"], round(c["seconds"], 1)]
                               for c in run.watch.requests
                               if c["seconds"] > 5],
                seconds_total=time.time() - run.t_process_start)
    print(harness.result_line(run, outcome, metrics), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(t_process_start=T_PROCESS_START))
