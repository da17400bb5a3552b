"""The low-precision control of a language-model train cell: the cell once,
then its comparison again with every matmul operand of the reference rounded
to float8 (e4m3), the nearest precision below the bfloat16 the configuration
states. The limits in ``benchmarks/reference/<family>.py::TOLERANCE`` have to
refuse that second reading (PERF.md gives both readings beside each limit).

    python3 benchmarks/reference/control_lm.py --workload <cell> --seed <n> --seconds <s>

Same arguments and same result line as ``benchmarks/run.py``; the control's
reading is the ``lowp_reading`` event before it. It runs after the measured
window, so the cell's end-to-end metrics are what ``run.py`` reads.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

from benchmarks import harness  # noqa: E402
from benchmarks.reference import parity_lm  # noqa: E402


def float8_matmul(plain):
    """``plain`` with both operands rounded to e4m3 (straight-through
    gradient, so the backward's matmuls take rounded operands too)."""
    import jax
    import jax.numpy as jnp

    def q8(a):
        return a + jax.lax.stop_gradient(
            a.astype(jnp.float8_e4m3fn).astype(jnp.float32) - a)

    return lambda a, b: plain(q8(a), q8(b))


def main(argv: list[str] | None = None) -> int:
    import importlib

    from benchmarks import run as bench_run

    check_train = parity_lm.check_train

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
                    loss_reference=low["loss_reference"])
        return first

    parity_lm.check_train = both
    try:
        return bench_run.main(argv, t_process_start=T_PROCESS_START)
    finally:
        parity_lm.check_train = check_train


if __name__ == "__main__":
    sys.exit(main())
