"""`chip_smoke.py` off the chip: it refuses to run without a TPU, and a
phase that raises fails the script. The rehearsals themselves are
in `test_zz_chip_rehearsal.py`."""

import importlib.util
import json
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _load(name):
    spec = importlib.util.spec_from_file_location(f"{name}_under_test",
                                                  REPO / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("script, argv", [("chip_smoke", []),
                                          ("chip_smoke", ["--multichip"])])
def test_without_a_tpu_nothing_runs_and_nothing_is_printed(script, argv,
                                                           capsys):
    """No TPU and no explicit rehearsal: non-zero exit, no result line, no
    device metric, no MFU."""
    assert _load(script).main(argv) != 0
    assert capsys.readouterr().out == ""


def test_a_phase_that_raises_fails_the_script(monkeypatch, capsys):
    smoke = _load("chip_smoke")

    def broken(args, watch):
        raise RuntimeError("kernel refused by the compiler")

    monkeypatch.setattr(smoke, "enable_cache", lambda: "unused")
    monkeypatch.setattr(smoke, "train_phase", lambda args, watch: None)
    monkeypatch.setattr(smoke, "kernel_phase", broken)
    assert smoke.main(["--rehearse"]) == 1
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last["ok"] is False and last["failed"] == ["kernels"]
    assert last["device"]["platform"] == "cpu"
