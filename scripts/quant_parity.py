"""Quantized-vs-f32 parity + throughput harness (docs/quantization.md).

Builds the same model twice from one seed, int8-quantizes one copy via
``jimm_tpu.quant.quantize_model``, and measures what the low-precision
serving fast path actually costs in accuracy:

- **cosine**: per-image cosine similarity between the quantized and f32
  image embeddings (min and mean over the batch),
- **top1_agreement**: fraction of images whose argmax over a synthetic
  normalized class matrix is unchanged (the zero-shot proxy the serving
  path cares about),
- **imgs_per_sec**: steady-state throughput of the jitted f32 and int8
  forwards over the same batch (off the TPU the fields are
  ``cpu_imgs_per_sec_*``: host numbers that say nothing about the chip).

Prints one JSON line with ``"phase": "quant_parity"``, the ``backend`` it
ran on and a ``dtype`` field per variant.

Usage:
    JAX_PLATFORMS=cpu python -m scripts.quant_parity --preset tiny
    python -m scripts.quant_parity --preset clip-vit-base-patch16
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def build_models(preset_name: str, seed: int):
    from flax import nnx

    from jimm_tpu import CLIP, preset
    from jimm_tpu.cli import _tiny_override
    from jimm_tpu.quant import quantize_model

    if preset_name == "tiny":
        cfg = _tiny_override(preset("clip-vit-base-patch16"))
    else:
        cfg = preset(preset_name)
    model_f32 = CLIP(cfg, rngs=nnx.Rngs(seed))
    model_q = CLIP(cfg, rngs=nnx.Rngs(seed))
    n_quant = quantize_model(model_q)
    return cfg, model_f32, model_q, n_quant


def cosine_rows(a, b):
    import numpy as np
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    num = (a * b).sum(-1)
    den = np.linalg.norm(a, axis=-1) * np.linalg.norm(b, axis=-1)
    return num / np.maximum(den, 1e-12)


def top1_agreement(emb_a, emb_b, n_classes: int, seed: int) -> float:
    """Zero-shot proxy: random normalized class matrix, argmax agreement."""
    import numpy as np
    a = np.asarray(emb_a, dtype=np.float64)
    b = np.asarray(emb_b, dtype=np.float64)
    rng = np.random.default_rng(seed)
    classes = rng.normal(size=(n_classes, a.shape[-1]))
    classes /= np.linalg.norm(classes, axis=-1, keepdims=True)
    agree = (a @ classes.T).argmax(-1) == (b @ classes.T).argmax(-1)
    return float(agree.mean())


def throughput(fwd, x, iters: int) -> float:
    import jax
    y = fwd(x)
    jax.block_until_ready(y)  # warm compile outside the timed window
    t0 = time.perf_counter()
    for _ in range(iters):
        y = fwd(x)
    jax.block_until_ready(y)
    return x.shape[0] * iters / (time.perf_counter() - t0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--preset", default="tiny",
                    help="model preset name, or 'tiny' for the CPU-smoke "
                         "override of clip-vit-base-patch16")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--classes", type=int, default=1000,
                    help="synthetic zero-shot class count")
    ap.add_argument("--iters", type=int, default=3,
                    help="timed forward passes per variant")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from jimm_tpu.serve import counting_forward

    cfg, model_f32, model_q, n_quant = build_models(args.preset, args.seed)
    size = cfg.vision.image_size
    x = np.random.RandomState(args.seed).randn(
        args.batch, size, size, 3).astype(np.float32)

    fwd_f32, _ = counting_forward(model_f32, "encode_image")
    fwd_q, _ = counting_forward(model_q, "encode_image")
    emb_f32 = np.asarray(fwd_f32(x))
    emb_q = np.asarray(fwd_q(x))

    cos = cosine_rows(emb_q, emb_f32)
    rate = ("imgs_per_sec" if jax.default_backend() == "tpu"
            else "cpu_imgs_per_sec")
    rec = {
        "phase": "quant_parity",
        "preset": args.preset,
        "dtype": "int8",
        "baseline_dtype": "float32",
        "backend": jax.default_backend(),
        "batch": args.batch,
        "layers_quantized": n_quant,
        "cosine_min": round(float(cos.min()), 6),
        "cosine_mean": round(float(cos.mean()), 6),
        "top1_agreement": round(top1_agreement(
            emb_q, emb_f32, args.classes, args.seed), 4),
        f"{rate}_f32": round(throughput(fwd_f32, x, args.iters), 2),
        f"{rate}_int8": round(throughput(fwd_q, x, args.iters), 2),
    }
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
