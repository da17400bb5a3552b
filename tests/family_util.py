"""What every decoder family's run through `jimm-tpu train` holds, checked
once here; each family's test adds its own checks on the rows."""

import json

import numpy as np

from jimm_tpu.cli import main

#: the host phases the loop writes into every row
PHASES = {"next_batch", "place", "dispatch", "device_wait"}


def train_rows(tmp_path, capsys, argv: list[str]) -> list[dict]:
    """Run ``jimm-tpu train`` with ``argv`` and a metrics file: one row a
    step (``--steps`` of them), each with a finite loss and the loop's
    host phases, and the goodput line on stdout. Returns the rows."""
    metrics = tmp_path / "metrics.jsonl"
    assert main(["train", *argv, "--metrics-file", str(metrics)]) == 0
    rows = [json.loads(line) for line in metrics.read_text().splitlines()]
    assert len(rows) == int(argv[argv.index("--steps") + 1])
    for row in rows:
        assert np.isfinite(row["loss"])
        assert {name for name, _, _ in row["phases"]} >= PHASES
    assert "goodput: " in capsys.readouterr().out
    return rows
