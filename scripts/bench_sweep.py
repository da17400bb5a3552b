"""Sweep train-step runtime variants in ONE process (single backend init,
shared compile cache) and print one JSON line per variant.

The benchmark of record stays `bench.py`; this is the tuning tool that finds
the flags `bench.py` should default to. Usage:

    python -m scripts.bench_sweep                       # the standard grid
    python -m scripts.bench_sweep --steps 30 \
        --variant remat=dots,ln=fused \
        --variant "remat=dots+ln,fused_qkv=1,unroll=6"
"""

from __future__ import annotations

import argparse
import json
import time

from scripts._measurements import MEASUREMENTS, read_records


def measured_variants(model: str) -> list[dict]:
    """Variant dicts that already have a real-TPU measurement."""
    return [rec["variant"] for rec in read_records(MEASUREMENTS)
            if rec.get("model") == model
            and isinstance(rec.get("variant"), dict)
            and isinstance(rec.get("mfu"), (int, float))
            and rec.get("mfu") > 0 and not rec.get("tiny")
            and "tpu" in str(rec.get("device", "")).lower()]


VARIANT_KEYS = frozenset(
    {"remat", "ln", "fused_qkv", "unroll", "moment", "donate", "attn",
     "batch"})


def parse_variant(s: str) -> dict:
    out = {}
    for kv in s.split(","):
        k, _, v = kv.partition("=")
        k = k.strip()
        if k not in VARIANT_KEYS:
            # a typo'd key silently running the baseline would produce a
            # misleading datapoint in the tool that picks bench defaults
            raise SystemExit(f"unknown variant key {k!r} in {s!r}; "
                             f"allowed: {sorted(VARIANT_KEYS)}")
        v = v.strip()
        if k in ("batch", "unroll"):
            try:
                ok = int(v) > 0
            except ValueError:
                ok = False
            if not ok:
                raise SystemExit(f"variant key {k!r} needs a positive "
                                 f"integer, got {v!r} in {s!r}")
        out[k] = v
    return out


#: ViT-L/16-384 grid (metric of record #2): smaller batch lever — the
#: 1.1 TFLOP/image model fits ~48/chip with aggressive remat, not 256
VIT_GRID = [
    "remat=dots",
    "remat=dots,ln=fused",
    "remat=dots,fused_qkv=1",
    "remat=dots+ln",
    "remat=dots+ln+act",
    "remat=dots,moment=bf16",
    "remat=dots+attn,attn=saveable",
    "remat=dots,batch=48",
    "remat=dots+ln+act,batch=48",
    "remat=dots+ln+act,ln=fused,batch=48",
]

STANDARD_GRID = [
    "remat=dots",
    "remat=dots,ln=fused",
    "remat=dots,fused_qkv=1",
    "remat=dots,ln=fused,fused_qkv=1",
    "remat=dots+ln",
    "remat=dots+ln+act",
    "remat=dots+ln+act,fused_qkv=1",
    "remat=dots,moment=bf16",
    "remat=dots+attn,attn=saveable",
    "remat=dots+ln+act+attn,attn=saveable",
    # batch scaling: larger per-chip batch amortizes fixed per-step cost
    # and can lift MFU directly if HBM allows (aggressive remat frees the
    # activation memory the bigger batch needs)
    "remat=dots,batch=192",
    "remat=dots,batch=256",
    "remat=dots+ln+act,batch=256",
    # composites: fused one-pass LN stacked on saved-LN/act remat (fused
    # bwd helps even when the fwd outputs are checkpointed), with and
    # without the batch lever
    "remat=dots,ln=fused,batch=256",
    "remat=dots+ln+act,ln=fused",
    "remat=dots+ln+act,ln=fused,batch=256",
]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="siglip_b16_256",
                   choices=["siglip_b16_256", "vit_l16_384"],
                   help="which bench config to sweep (matches bench.py "
                        "--model)")
    p.add_argument("--batch", type=int, default=0,
                   help="0 = auto (128 siglip / 32 vit-L)")
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--unroll", type=int, default=0,
                   help="default scan unroll for variants that don't set "
                        "it; 0 = full depth (12 siglip / 24 vit-L)")
    p.add_argument("--variant", action="append", default=None,
                   help="comma-separated k=v list; repeatable. Keys: remat, "
                        "attn, ln, fused_qkv, unroll, moment, donate, batch")
    p.add_argument("--tiny", action="store_true",
                   help="rehearse the whole grid on a tiny model (validates "
                        "the sweep itself on any backend; records carry no "
                        "MFU)")
    p.add_argument("--no-skip", action="store_true",
                   help="re-measure variants that already have a good TPU "
                        "record in MEASUREMENTS.jsonl (default: skip them, "
                        "so a second run resumes the grid instead of "
                        "restarting it)")
    args = p.parse_args()

    import jimm_tpu.utils.env
    jimm_tpu.utils.env.configure_platform()  # honors JIMM_PLATFORM=cpu

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    if jax.default_backend() != "tpu" and not args.tiny:
        raise SystemExit(f"bench_sweep measures on a TPU; the backend here "
                         f"is {jax.default_backend()!r}. --tiny rehearses "
                         f"the grid at a toy size.")
    from jimm_tpu.aot.export import enable_persistent_cache
    enable_persistent_cache()

    from jimm_tpu import SigLIP, VisionTransformer, preset
    from jimm_tpu.configs import parse_remat, with_runtime
    from jimm_tpu.train import (OptimizerConfig, make_classifier_train_step,
                                make_contrastive_train_step, make_optimizer,
                                mfu)
    from jimm_tpu.train.metrics import train_step_flops

    is_vit = args.model == "vit_l16_384"
    default_grid = VIT_GRID if is_vit else STANDARD_GRID
    variants = [parse_variant(v) for v in (args.variant or default_grid)]
    args.batch = args.batch or (32 if is_vit else 128)
    args.unroll = args.unroll or (24 if is_vit else 12)
    rng = np.random.RandomState(0)
    if args.tiny:
        from jimm_tpu.configs import (SigLIPConfig, TextConfig, ViTConfig,
                                      VisionConfig)
        tiny_vision = VisionConfig(image_size=32, patch_size=16, width=64,
                                   depth=2, num_heads=2, mlp_dim=128,
                                   act="gelu_tanh", pooling="map")
        if is_vit:
            base = ViTConfig(
                vision=VisionConfig(image_size=32, patch_size=16, width=64,
                                    depth=2, num_heads=2, mlp_dim=128,
                                    ln_eps=1e-12),
                num_classes=16)
        else:
            base = SigLIPConfig(
                vision=tiny_vision,
                text=TextConfig(vocab_size=64, context_length=8, width=64,
                                depth=2, num_heads=2, mlp_dim=128,
                                act="gelu_tanh", causal=False,
                                pooling="last", proj_bias=True),
                projection_dim=64)
        args.batch = min(args.batch, 8)
        args.unroll = min(args.unroll, 2)
    else:
        base = preset("vit-large-patch16-384" if is_vit
                      else "siglip-base-patch16-256")
    max_batch = max([args.batch] + [int(v["batch"]) for v in variants
                                    if "batch" in v])
    if args.tiny:
        max_batch = min(max_batch, 8)
    # Generator API: float32 straight off (randn would transiently allocate
    # a float64 copy — ~400 MB at the batch-256 grid entries)
    gen = np.random.default_rng(0)
    images_np = gen.standard_normal(
        (max_batch, base.vision.image_size, base.vision.image_size, 3),
        dtype=np.float32)
    if is_vit:
        labels_np = rng.randint(0, base.num_classes, size=(max_batch,))
    else:
        text_np = rng.randint(1, base.text.vocab_size,
                              size=(max_batch, base.text.context_length))

    already = [] if (args.no_skip or args.tiny) \
        else measured_variants(args.model)
    for v in variants:
        if v in already:
            print(json.dumps({"variant": v, "model": args.model,
                              "skipped": "already measured "
                                         "(MEASUREMENTS.jsonl)"}),
                  flush=True)
            continue
        vb = min(int(v.get("batch", args.batch)), max_batch)
        cfg = with_runtime(
            base,
            **parse_remat(v.get("remat", "dots")),
            attn_impl=v.get("attn", "auto"),
            scan_unroll=int(v.get("unroll", args.unroll)),
            ln_impl=v.get("ln", "xla"),
            fused_qkv=v.get("fused_qkv", "0") in ("1", "true"),
        )
        def sync(model, metrics):
            # through the last optimizer update, not the loss alone
            jax.block_until_ready((metrics, nnx.state(model, nnx.Param)))

        model = optimizer = step_fn = metrics = None
        try:
            donate = v.get("donate", "1") in ("1", "true")
            moment = {"bf16": "bfloat16"}.get(v.get("moment"))
            if is_vit:
                model = VisionTransformer(cfg, rngs=nnx.Rngs(0),
                                          dtype=jnp.bfloat16,
                                          param_dtype=jnp.bfloat16)
                step_fn = make_classifier_train_step(donate=donate)
                data = (jnp.asarray(images_np[:vb], jnp.bfloat16),
                        jnp.asarray(labels_np[:vb], jnp.int32))
            else:
                model = SigLIP(cfg, rngs=nnx.Rngs(0), dtype=jnp.bfloat16,
                               param_dtype=jnp.bfloat16)
                step_fn = make_contrastive_train_step("siglip", donate=donate)
                data = (jnp.asarray(images_np[:vb], jnp.bfloat16),
                        jnp.asarray(text_np[:vb], jnp.int32))
            optimizer = make_optimizer(model, OptimizerConfig(
                learning_rate=1e-3, moment_dtype=moment))

            t_c0 = time.perf_counter()
            for _ in range(args.warmup):
                metrics = step_fn(model, optimizer, *data)
            sync(model, metrics)
            compile_s = time.perf_counter() - t_c0
            t0 = time.perf_counter()
            for _ in range(args.steps):
                metrics = step_fn(model, optimizer, *data)
            sync(model, metrics)
            dt = (time.perf_counter() - t0) / args.steps
        except Exception as e:  # OOM on an aggressive save policy: keep going
            print(json.dumps({"variant": v, "error": repr(e)[:300]}),
                  flush=True)
            continue
        finally:
            # drop this variant's buffers even on failure, so an OOM'd
            # variant doesn't double-book HBM under the next one
            del model, optimizer, step_fn, metrics
        flops = train_step_flops(cfg, vb)
        print(json.dumps({
            "variant": v,
            "model": args.model,
            "batch": vb,
            "step_time_ms": round(dt * 1e3, 2),
            "images_per_sec": round(vb / dt, 1),
            "warmup_s": round(compile_s, 1),
            # fidelity markers: scripts/adopt_sweep.py must never rank a
            # tiny rehearsal record (which has no MFU) against a real TPU
            # measurement
            "device": jax.devices()[0].device_kind,
            **({"tiny": True} if args.tiny
               else {"mfu": round(mfu(flops, dt, n_devices=1), 4)}),
        }), flush=True)


if __name__ == "__main__":
    main()
