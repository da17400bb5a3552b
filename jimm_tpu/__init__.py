"""jimm_tpu — a TPU-native image-model framework (ViT / CLIP / SigLIP).

TPU-first rebuild of the capabilities of `pythoncrazy/jimm`: flax-NNX models
with scanned layer stacks, logical-axis sharding policies over `jax.sharding`
meshes, pure-safetensors HuggingFace checkpoint loading (zero torch), Pallas
flash attention, and distributed contrastive training with a ring sigmoid
loss.

The package namespace is lazy (PEP 562): importing ``jimm_tpu`` (or a pure
host subpackage like ``jimm_tpu.aot``/``jimm_tpu.tune``/``jimm_tpu.obs``)
does NOT import jax. The model/config names below resolve on first access,
which is when the version floor is checked — so ``jimm-tpu tune ls``/
``aot ls``/``obs`` stay usable on a box with no accelerator stack.
"""

from __future__ import annotations

import importlib
from typing import Any

__version__ = "0.1.0"

#: lazily resolved public names -> defining module
_LAZY = {
    "CLIP": "jimm_tpu.models",
    "Ouro": "jimm_tpu.models",
    "Kanana": "jimm_tpu.models.kanana",
    "Trinity": "jimm_tpu.models.trinity",
    "KimiLinear": "jimm_tpu.models.kimi_linear",
    "Granite": "jimm_tpu.models.granite",
    "SigLIP": "jimm_tpu.models",
    "VisionTransformer": "jimm_tpu.models",
    "CLIPConfig": "jimm_tpu.configs",
    "SigLIPConfig": "jimm_tpu.configs",
    "ViTConfig": "jimm_tpu.configs",
    "OuroConfig": "jimm_tpu.configs",
    "KananaConfig": "jimm_tpu.configs",
    "TrinityConfig": "jimm_tpu.configs",
    "KimiLinearConfig": "jimm_tpu.configs",
    "GraniteConfig": "jimm_tpu.configs",
    "MoEDecoderConfig": "jimm_tpu.configs",
    "DecoderConfig": "jimm_tpu.configs",
    "VisionConfig": "jimm_tpu.configs",
    "TextConfig": "jimm_tpu.configs",
    "TransformerConfig": "jimm_tpu.configs",
    "PRESETS": "jimm_tpu.configs",
    "preset": "jimm_tpu.configs",
    "RUNTIME_FIELDS": "jimm_tpu.configs",
    "with_runtime": "jimm_tpu.configs",
}

__all__ = [
    "CLIP", "SigLIP", "VisionTransformer", "Ouro", "Kanana", "Trinity",
    "KimiLinear", "Granite", "OuroConfig", "DecoderConfig", "KananaConfig",
    "TrinityConfig", "KimiLinearConfig", "GraniteConfig",
    "MoEDecoderConfig",
    "CLIPConfig", "SigLIPConfig", "ViTConfig", "VisionConfig", "TextConfig",
    "TransformerConfig", "PRESETS", "preset",
    "RUNTIME_FIELDS", "with_runtime",
]


def _check_versions() -> None:
    """Fail fast with a clear message on JAX/flax older than the tested
    floor (pyproject.toml mirrors these; pip cannot enforce them for
    source checkouts or pre-installed environments)."""
    import jax
    from flax import __version__ as flax_version

    def parse(v: str) -> tuple[int, ...]:
        parts = []
        for p in v.split(".")[:3]:
            digits = "".join(ch for ch in p if ch.isdigit())
            if not digits:
                break
            parts.append(int(digits))
        return tuple(parts)

    floors = (("jax", jax.__version__, (0, 9)),
              ("flax", flax_version, (0, 12)))
    for name, have, floor in floors:
        if parse(have) and parse(have) < floor:
            raise ImportError(
                f"jimm_tpu requires {name} >= {'.'.join(map(str, floor))}, "
                f"found {have}. Upgrade with `pip install -U {name}` "
                f"(TPU: `pip install -U 'jax[tpu]'`).")


_ready = False


def _prepare() -> None:
    """Version floor, once, before any model/config attribute resolves."""
    global _ready
    if not _ready:
        _check_versions()
        _ready = True


def __getattr__(name: str) -> Any:
    module = _LAZY.get(name)
    if module is None:
        raise AttributeError(f"module 'jimm_tpu' has no attribute {name!r}")
    _prepare()
    value = getattr(importlib.import_module(module), name)
    globals()[name] = value
    return value


def __dir__() -> list[str]:
    return sorted(set(globals()) | set(_LAZY))
