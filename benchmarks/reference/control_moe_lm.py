"""The low-precision control of a sparse language model's train cell: the
cell once, then its comparison again with every matmul operand of the
reference rounded to float8 (e4m3), the nearest precision below the bfloat16
the configuration states (``control_lm.py``'s rounding). The limits in
``benchmarks/reference/<family>.py::TOLERANCE`` have to refuse that second
reading (PERF.md gives both readings beside each limit).

    python3 benchmarks/reference/control_moe_lm.py --workload <cell> --seed <n> --seconds <s>

Same arguments and same result line as ``benchmarks/run.py``; the control's
reading is the ``lowp_reading`` event before it.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

from benchmarks import harness  # noqa: E402
from benchmarks.reference import parity_moe_lm  # noqa: E402
from benchmarks.reference.control_lm import float8_matmul  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    import importlib

    from benchmarks import run as bench_run

    check_train = parity_moe_lm.check_train

    def both(run, result):
        first = check_train(run, result)
        ref = importlib.import_module(
            f"benchmarks.reference.{run.config['family']}")
        plain = ref.matmul
        ref.matmul = float8_matmul(plain)
        try:
            low = check_train(run, result)
        finally:
            ref.matmul = plain
        harness.log(event="lowp_reading", refused=not low["ok"],
                    precision="float8_e4m3fn operands in every matmul of "
                              "the reference",
                    errors=low["errors"], tolerance=low["tolerance"],
                    routing_differs_per_layer=low["routing_differs_per_layer"],
                    loss_reference=low["loss_reference"])
        return first

    parity_moe_lm.check_train = both
    try:
        return bench_run.main(argv, t_process_start=T_PROCESS_START)
    finally:
        parity_moe_lm.check_train = check_train


if __name__ == "__main__":
    sys.exit(main())
