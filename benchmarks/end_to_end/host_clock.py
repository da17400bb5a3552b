"""End-to-end metrics, taken by the benchmark on the host's clock."""

from __future__ import annotations


def train_img_per_s(o: dict) -> float | None:
    """Global batch x steps in the window / wall time of the window, through
    the CLI's own loop (stamps of the ``--metrics-file`` rows)."""
    if "window_rows" not in o:
        return None
    return o["global_batch"] * len(o["window_rows"]) / o["window_s"]


def setup_s(o: dict) -> float | None:
    """Process start to the first measured step or request."""
    return o["t_first_measured"] - o["t_process_start"]


READERS = {"train_img_per_s": train_img_per_s, "setup_s": setup_s}
