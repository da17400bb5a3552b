"""Test harness: force an 8-device virtual CPU platform so sharding,
FSDP/TP, ring-loss, and distributed tests run without a TPU pod
(SURVEY §4 "Implication for the build").

Must run before jax initializes a backend — pytest imports conftest first.
"""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

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
