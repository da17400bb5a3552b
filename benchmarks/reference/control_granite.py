"""The controls of the ``granite_4_0_h_micro`` train cell: the cell once, then
its comparison twice more, each with the reference changed in one way that the
limits in ``benchmarks/reference/granite.py::TOLERANCE`` have to refuse
(PERF.md gives every reading beside each limit):

- ``float8``: every matmul operand of the reference rounded to float8 (e4m3),
  the nearest precision below the bfloat16 the configuration states
  (``control_lm.py``'s rounding), the recurrence's read of its state included;
- ``state_bf16``: the reference's recurrence rounds its carried state to
  bfloat16 after every token (what a scan that keeps its state in bfloat16
  computes).

    python3 benchmarks/reference/control_granite.py --workload <cell> --seed <n> --seconds <s>

Same arguments and same result line as ``benchmarks/run.py``; each control's
reading is a ``control_reading`` event before it.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

from benchmarks import harness  # noqa: E402
from benchmarks.reference import parity_granite  # noqa: E402
from benchmarks.reference.control_hybrid_lm import CONTROLS  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    import importlib

    from benchmarks import run as bench_run

    check_train = parity_granite.check_train

    def all_readings(run, result):
        first = check_train(run, result)
        ref = importlib.import_module(
            f"benchmarks.reference.{run.config['family']}")
        for control, (attribute, change) in CONTROLS.items():
            plain = getattr(ref, attribute)
            setattr(ref, attribute, change(plain))
            try:
                reading = check_train(run, result)
            finally:
                setattr(ref, attribute, plain)
            harness.log(
                event="control_reading", control=control,
                refused=not reading["ok"], errors=reading["errors"],
                tolerance=reading["tolerance"],
                loss_reference=reading["loss_reference"])
        return first

    parity_granite.check_train = all_readings
    try:
        return bench_run.main(argv, t_process_start=T_PROCESS_START)
    finally:
        parity_granite.check_train = check_train


if __name__ == "__main__":
    sys.exit(main())
