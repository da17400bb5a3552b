"""What the compiled program itself says (counters, not times)."""

from __future__ import annotations


def flash_calls(o: dict) -> float | None:
    """``tpu_custom_call`` count in the compiled step's text: how many
    Pallas kernels the attention dispatch put on the path."""
    return o.get("flash_calls")


def hbm_program_gb(o: dict) -> float | None:
    """``compiled.memory_analysis()`` of the cell's program (the train
    step): arguments + temporaries + outputs - aliases, per device."""
    program = o.get("program")
    return None if program is None else program["resident"] / 1e9


READERS = {"flash_calls": flash_calls, "hbm_program_gb": hbm_program_gb}
