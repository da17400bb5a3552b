"""Inference throughput for the BASELINE tracked inference configs.

`bench.py` (the metric of record) covers training; this measures the two
inference rows of `BASELINE.json`'s tracked configs on one chip:

  #1 ViT-B/16-224 classification  (ref `examples/vit_inference.py` flow)
  #2 CLIP-B/32 zero-shot image+text (ref `examples/clip_inference.py` flow)

Prints one JSON line per config: images/sec, ms/batch, and fwd MFU with
the FLOP count taken from XLA's own cost analysis of the compiled forward
(no analytic formula to drift). Random-init weights — throughput does not
depend on values. One process that measures on the TPU or exits non-zero;
``--tiny`` rehearses the path at toy shapes on any backend, under *_tiny_*
metric names with no per-chip unit and no MFU.
"""

from __future__ import annotations

import argparse
import json
import time


def bench_forward(label: str, forward, args, batch: int, steps: int,
                  warmup: int, *, tiny: bool) -> None:
    """Time the forward and print one record; at the real size it carries
    the fwd MFU from XLA's cost analysis of the compiled forward."""
    import jax

    out = forward(*args)  # compile
    jax.block_until_ready(out)
    for _ in range(max(warmup - 1, 0)):
        out = forward(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = forward(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / steps
    device = jax.devices()[0]
    rec = {
        "metric": label,
        "value": round(batch / dt, 2),
        "unit": "images/sec" if tiny else "images/sec/chip",
        "ms_per_batch": round(dt * 1e3, 3),
        "batch_size": batch,
        "backend": device.platform,
        "device": device.device_kind,
    }
    if not tiny:
        from jimm_tpu.train.metrics import compiled_flops, mfu

        # AOT re-compile round-trip (jit call cache does not share with it)
        flops = compiled_flops(
            forward.func.lower(*forward.args, *args).compile())
        rec["fwd_mfu"] = (round(mfu(flops, dt, n_devices=1), 4) if flops
                          else "unavailable")
    print(json.dumps(rec), flush=True)


def main() -> int:
    import jimm_tpu.utils.env
    jimm_tpu.utils.env.configure_platform()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    from jimm_tpu import CLIP, VisionTransformer, preset
    from jimm_tpu.utils import jit_forward

    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=0, help="0 = auto")
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--tiny", action="store_true",
                   help="rehearsal at toy shapes on any backend")
    args = p.parse_args()

    if jax.default_backend() != "tpu" and not args.tiny:
        raise SystemExit(f"inference_bench measures on a TPU; the backend "
                         f"here is {jax.default_backend()!r}. --tiny "
                         f"rehearses the path at toy shapes.")
    on_tpu = not args.tiny
    # auto batch comes from the serving bucket table (serve/buckets.py), so
    # the bench times the exact shapes `jimm-tpu serve` warm-compiles: the
    # largest bucket at the real size (256, BASELINE's inference batch), the
    # bucket holding 4 for --tiny
    from jimm_tpu.serve.buckets import default_buckets
    table = default_buckets()
    batch = args.batch or (table.max_size if on_tpu else table.select(4))
    if batch not in table.sizes:
        print(json.dumps({"note": f"batch {batch} is not a serving bucket "
                                  f"{list(table.sizes)}; the server would "
                                  f"pad it"}), flush=True)
    rng = np.random.RandomState(0)

    # BASELINE config #1: ViT-B/16-224 classification forward
    vit_preset = ("vit-base-patch16-224" if on_tpu else "vit-tiny-patch16-224")
    vcfg = preset(vit_preset, num_classes=1000)
    vit = VisionTransformer(vcfg, rngs=nnx.Rngs(0), dtype=jnp.bfloat16,
                            param_dtype=jnp.bfloat16)
    images = jnp.asarray(rng.randn(batch, vcfg.vision.image_size,
                                   vcfg.vision.image_size, 3), jnp.bfloat16)
    bench_forward(
        "vit_b16_224_infer_images_per_sec" if on_tpu
        else "vit_tiny_infer_images_per_sec",
        jit_forward(vit), (images,), batch, args.steps, args.warmup,
        tiny=args.tiny)

    # BASELINE config #2: CLIP-B/32 zero-shot (image + 8 prompts per batch)
    if on_tpu:
        ccfg = preset("clip-vit-base-patch32")
    else:  # tiny CLIP-shaped config: same flow, smoke-compile sized
        from jimm_tpu.configs import CLIPConfig, TextConfig, VisionConfig
        ccfg = CLIPConfig(
            vision=VisionConfig(image_size=32, patch_size=16, width=64,
                                depth=2, num_heads=2, mlp_dim=128,
                                act="quick_gelu", ln_eps=1e-5, pooling="cls",
                                pre_norm=True, patch_bias=False),
            text=TextConfig(vocab_size=64, context_length=8, width=64,
                            depth=2, num_heads=2, mlp_dim=128,
                            act="quick_gelu", ln_eps=1e-5, causal=True,
                            pooling="eot", proj_bias=False),
            projection_dim=64)
    clip = CLIP(ccfg, rngs=nnx.Rngs(0), dtype=jnp.bfloat16,
                param_dtype=jnp.bfloat16)
    cb = batch if on_tpu else 2
    cimg = jnp.asarray(rng.randn(cb, ccfg.vision.image_size,
                                 ccfg.vision.image_size, 3), jnp.bfloat16)
    # CLIP text pooling reads the EOT (max-id) token: put it once per row
    text = rng.randint(1, ccfg.text.vocab_size - 1,
                       size=(8, ccfg.text.context_length))
    text[:, -1] = ccfg.text.vocab_size - 1
    ctxt = jnp.asarray(text, jnp.int32)
    bench_forward(
        "clip_b32_zeroshot_images_per_sec" if on_tpu
        else "clip_tiny_zeroshot_images_per_sec",
        jit_forward(clip), (cimg, ctxt), cb, args.steps, args.warmup,
        tiny=args.tiny)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
