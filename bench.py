"""Benchmarks of record (BASELINE.md "Targets"): by default SigLIP-B/16-256
contrastive training throughput on one chip (images/sec/chip) + MFU; with
``--model vit_l16_384``, the second metric of record — ViT-L/16-384 ImageNet
classifier train MFU.

One process that measures on the TPU or fails: without a TPU backend it
exits non-zero and prints no result. Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

``vs_baseline`` is measured MFU / 0.50 — the north-star target from
`BASELINE.json` (the reference publishes no throughput numbers at all; 1.0
means the 50%-MFU bar is met on this chip count).

``--tiny`` is the rehearsal: a 2-layer, 64-wide model on whatever backend is
there, printed under its own metric name with no per-chip unit, no MFU and
no ``vs_baseline`` — it proves the measurement path, never a device number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="siglip_b16_256",
                   choices=["siglip_b16_256", "vit_l16_384"],
                   help="benchmark config: siglip_b16_256 (metric of record "
                        "#1, contrastive train images/sec/chip) or "
                        "vit_l16_384 (metric of record #2, ImageNet-shape "
                        "classifier train MFU)")
    p.add_argument("--batch-size", type=int, default=0,
                   help="0 = auto (TPU: 128 siglip / 32 vit-L, CPU: 8)")
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--remat", default=None,
                   help="activation rematerialization inside the layer scan: "
                        "none (remat off), full (remat, recompute all), or "
                        "dots with +ln/+act/+attn suffixes (save matmul "
                        "[+layernorm][+activation][+attention-prob] outputs), "
                        "e.g. dots+ln+act")
    p.add_argument("--attn", default=None,
                   choices=["auto", "xla", "flash", "flash_int8", "saveable"],
                   help="attention kernel (flash_int8 = int8-QK flash "
                        "fwd+bwd; saveable = einsum with checkpoint-named "
                        "probs, pair with --remat dots+attn)")
    p.add_argument("--precision", default=None,
                   choices=["bf16", "fp8_hybrid", "int8_qk"],
                   help="training precision policy applied to the bench "
                        "model (quant.policy.apply_precision_policy); "
                        "stamped on the JSON row so obs-regress baselines "
                        "never conflate bf16 and low-precision runs")
    p.add_argument("--unroll", type=int, default=0,
                   help="layer-scan unroll factor; 0 = auto: full unroll for "
                        "the model's depth (12 ViT-B towers / 24 ViT-L — XLA "
                        "fuses the stacked-grad updates, ~+5 MFU points, and "
                        "full unroll enables the analytic-vs-XLA MFU "
                        "crosscheck)")
    p.add_argument("--ln", choices=["xla", "fused"], default=None,
                   help="LayerNorm kernel (fused = one-pass Pallas)")
    p.add_argument("--fused-qkv", action="store_true",
                   help="q/k/v as one (H, 3H) matmul")
    p.add_argument("--no-donate", action="store_true",
                   help="disable model/optimizer buffer donation")
    p.add_argument("--moment-dtype", choices=["f32", "bf16"], default=None,
                   help="Adam first-moment dtype (bf16 halves that buffer's "
                        "HBM traffic)")
    p.add_argument("--tune-cache", default=None,
                   help="resolve Pallas kernel block sizes from this tuned-"
                        "config cache (populate with `jimm-tpu tune`); "
                        "lookup only — misses fall back to safe defaults")
    p.add_argument("--tiny", action="store_true",
                   help="rehearsal: a 2-layer 64-wide model on any backend, "
                        "printed under a *_tiny_* metric name with no MFU")
    args = p.parse_args(argv)
    if args.remat is not None:
        # fail malformed --remat at parse time, not minutes later in the
        # first jit trace
        from jimm_tpu.configs import parse_remat
        try:
            parse_remat(args.remat)
        except ValueError as e:
            p.error(str(e))
    return args


#: (TPU metric name, unit) per --model
METRICS = {
    "siglip_b16_256": ("siglip_b16_256_train_images_per_sec_per_chip",
                       "images/sec/chip"),
    "vit_l16_384": ("vit_l16_384_train_mfu", "mfu"),
}

#: --tiny metric names: a rehearsal can never impersonate the real metric
TINY_METRICS = {
    "siglip_b16_256": "siglip_tiny_train_images_per_sec",
    "vit_l16_384": "vit_tiny_train_images_per_sec",
}


#: bench --model -> preset key in jimm_tpu/adopted_runtime.json
BENCH_PRESET = {"siglip_b16_256": "siglip-base-patch16-256",
                "vit_l16_384": "vit-large-patch16-384"}


def resolve_adopted_defaults(args: argparse.Namespace, on_tpu: bool) -> bool:
    """Fill flags left at their parser defaults (None/0) from the adopted
    sweep winner (`scripts/adopt_sweep.py --apply`), then apply builtin
    fallbacks. Adopted values are used on TPU only — that is where they
    were measured. Returns True when any adopted value was used."""
    adopted: dict = {}
    if on_tpu:
        try:
            from jimm_tpu.configs import ADOPTED_RUNTIME_PATH
            entry = (json.loads(ADOPTED_RUNTIME_PATH.read_text())
                     ["presets"][BENCH_PRESET[args.model]])
            adopted = dict(entry.get("variant", {}))
        except (OSError, KeyError, ValueError, TypeError, AttributeError):
            # missing file OR valid-JSON-wrong-container corruption: builtins
            adopted = {}
    used = False

    def fill(name: str, key: str, cast=str) -> None:
        nonlocal used
        if getattr(args, name) in (None, 0) and key in adopted:
            setattr(args, name, cast(adopted[key]))
            used = True

    # validate adopted values HERE, at read time: a corrupted or hand-edited
    # adopted_runtime.json must degrade to builtin defaults with a warning,
    # not fail minutes later inside the first jit trace
    if adopted:
        try:
            from jimm_tpu.configs import parse_remat
            if "remat" in adopted:
                parse_remat(str(adopted["remat"]))
            ok = (str(adopted.get("attn", "auto"))
                  in ("auto", "xla", "flash", "flash_int8", "saveable")
                  and str(adopted.get("ln", "xla")) in ("xla", "fused")
                  and str(adopted.get("moment", "f32")) in ("f32", "bf16")
                  and str(adopted.get("precision", "bf16"))
                  in ("bf16", "fp8_hybrid", "int8_qk")
                  and int(adopted.get("unroll", 1)) >= 1
                  and int(adopted.get("batch", 1)) >= 1)
            if not ok:
                raise ValueError(f"invalid adopted variant {adopted}")
        except (ValueError, TypeError) as e:
            print(f"ignoring adopted defaults: {e}", file=sys.stderr)
            adopted = {}
    fill("remat", "remat")
    fill("attn", "attn")
    fill("ln", "ln")
    fill("moment_dtype", "moment")
    fill("precision", "precision")
    fill("unroll", "unroll", int)
    fill("batch_size", "batch", int)
    # store_true flags: an absent flag can adopt, a passed flag always wins
    if (not args.fused_qkv
            and str(adopted.get("fused_qkv", "")).lower() in ("1", "true")):
        args.fused_qkv, used = True, True
    if (not args.no_donate
            and str(adopted.get("donate", "")).lower() in ("0", "false")):
        args.no_donate, used = True, True
    args.remat = args.remat or "dots"
    args.attn = args.attn or "auto"
    args.ln = args.ln or "xla"
    args.moment_dtype = args.moment_dtype or "f32"
    args.precision = args.precision or "bf16"
    return used


def run(args: argparse.Namespace) -> int:
    import jimm_tpu.utils.env
    jimm_tpu.utils.env.configure_platform()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.tiny:
        print(f"bench.py measures on a TPU; the backend here is "
              f"{jax.default_backend()!r} "
              f"({jax.devices()[0].device_kind}). Nothing was measured. "
              f"--tiny rehearses the path at a toy size.", file=sys.stderr)
        return 1

    from jimm_tpu.aot.export import enable_persistent_cache
    enable_persistent_cache()

    from jimm_tpu import SigLIP, VisionTransformer, preset
    from jimm_tpu.configs import (SigLIPConfig, TextConfig, ViTConfig,
                                  VisionConfig, parse_remat, with_runtime)
    from jimm_tpu.train import OptimizerConfig, make_optimizer, mfu
    from jimm_tpu.train.metrics import compiled_flops, train_step_flops

    if args.tune_cache:
        # before any trace: fused ops resolve block sizes through
        # tune.best_config at trace time (lookup only, never a measurement)
        from jimm_tpu.tune import configure as tune_configure
        tune_configure(args.tune_cache)

    # adopted values were measured at the real widths on the TPU only
    adopted_defaults = resolve_adopted_defaults(args,
                                                on_tpu and not args.tiny)
    # auto-unroll = the model's full depth, so the MFU crosscheck (which
    # needs a fully-unrolled scan) guards every default run of either metric
    unroll = args.unroll or (24 if args.model == "vit_l16_384" else 12)
    if args.tiny:
        unroll = max(min(unroll, 2), 1)
    runtime = dict(**parse_remat(args.remat), attn_impl=args.attn,
                   ln_impl=args.ln, fused_qkv=args.fused_qkv,
                   scan_unroll=unroll)
    rng = np.random.RandomState(0)

    if args.model == "vit_l16_384":
        # Metric of record #2 (BASELINE.md): ViT-L/16-384 ImageNet-shape
        # classifier fine-tune step, bf16. Batch auto 32: ~1.1 TFLOP/image,
        # activations with remat fit one chip's 16G HBM comfortably.
        batch = args.batch_size or (8 if args.tiny else 32)
        if args.tiny:
            cfg = ViTConfig(
                vision=VisionConfig(image_size=32, patch_size=16, width=64,
                                    depth=2, num_heads=2, mlp_dim=128,
                                    ln_eps=1e-12),
                num_classes=16)
        else:
            cfg = preset("vit-large-patch16-384")
    else:
        batch = args.batch_size or (8 if args.tiny else 128)
        if args.tiny:
            cfg = SigLIPConfig(
                vision=VisionConfig(image_size=32, patch_size=16, width=64,
                                    depth=2, num_heads=2, mlp_dim=128,
                                    act="gelu_tanh", pooling="map"),
                text=TextConfig(vocab_size=64, context_length=8, width=64,
                                depth=2, num_heads=2, mlp_dim=128,
                                act="gelu_tanh", causal=False, pooling="last",
                                proj_bias=True),
                projection_dim=64)
        else:
            # remat: without it the scan saves every layer's activations and
            # a big-batch training step overflows one chip's 16G HBM. Policy
            # "dots" keeps matmul outputs and recomputes only elementwise
            # ops — far cheaper than full recompute.
            cfg = preset("siglip-base-patch16-256")
    cfg = with_runtime(cfg, **runtime)

    moment_dtype = "bfloat16" if args.moment_dtype == "bf16" else None
    opt_cfg = OptimizerConfig(learning_rate=1e-3, moment_dtype=moment_dtype)
    images = jnp.asarray(rng.randn(batch, cfg.vision.image_size,
                                   cfg.vision.image_size, 3), jnp.bfloat16)
    if args.model == "vit_l16_384":
        from jimm_tpu.train import make_classifier_train_step
        model = VisionTransformer(cfg, rngs=nnx.Rngs(0), dtype=jnp.bfloat16,
                                  param_dtype=jnp.bfloat16)
        step_fn = make_classifier_train_step(donate=not args.no_donate)
        data = (images,
                jnp.asarray(rng.randint(0, cfg.num_classes, size=(batch,)),
                            jnp.int32))
    else:
        from jimm_tpu.train import make_contrastive_train_step
        model = SigLIP(cfg, rngs=nnx.Rngs(0), dtype=jnp.bfloat16,
                       param_dtype=jnp.bfloat16)
        step_fn = make_contrastive_train_step("siglip",
                                              donate=not args.no_donate)
        data = (images,
                jnp.asarray(rng.randint(1, cfg.text.vocab_size,
                                        size=(batch, cfg.text.context_length)),
                            jnp.int32))
    if args.precision != "bf16":
        from jimm_tpu.quant.policy import apply_precision_policy
        apply_precision_policy(model, args.precision)
    optimizer = make_optimizer(model, opt_cfg)

    def sync_all() -> None:
        # the loss alone can be ready before the optimizer update is: wait
        # for the updated parameters too
        jax.block_until_ready((metrics, nnx.state(model, nnx.Param)))

    t0 = time.perf_counter()
    metrics = step_fn(model, optimizer, *data)
    sync_all()
    compile_s = time.perf_counter() - t0
    for _ in range(max(args.warmup - 1, 0)):
        metrics = step_fn(model, optimizer, *data)
    sync_all()

    # total time over a long chain of state-dependent steps, full param sync
    # at the end
    t0 = time.perf_counter()
    for _ in range(args.steps):
        metrics = step_fn(model, optimizer, *data)
    sync_all()
    dt = (time.perf_counter() - t0) / args.steps

    # per-step spread via the shared obs percentile helper: a short synced
    # probe (the chain-timed dt above stays the metric of record — per-step
    # sync adds overhead, but the p50/p99 spread it yields catches
    # stragglers a mean cannot)
    from jimm_tpu.obs import percentile as _pctl
    probe_times = []
    for _ in range(min(args.steps, 8)):
        tp = time.perf_counter()
        metrics = step_fn(model, optimizer, *data)
        sync_all()
        probe_times.append(time.perf_counter() - tp)

    images_per_sec = batch / dt
    device = jax.devices()[0]
    result = {
        # which device measured this row, as jax reports it
        "backend": device.platform,
        "device": device.device_kind,
        "images_per_sec": round(images_per_sec, 2),
        "first_step_s": round(compile_s, 2),
        "step_time_ms": round(dt * 1e3, 2),
        "step_time_p50_ms": round(_pctl(probe_times, 50) * 1e3, 2),
        "step_time_p99_ms": round(_pctl(probe_times, 99) * 1e3, 2),
        "batch_size": batch,
        "steps_timed": args.steps,
        "remat": args.remat,
        "attn": args.attn,
        # explicit row-identity fields for obs-regress baselines: a bf16
        # baseline must never gate (or be gated by) an fp8/int8 run
        "attn_impl": args.attn,
        "precision": args.precision,
        "unroll": unroll,
        "ln": args.ln,
        "fused_qkv": args.fused_qkv,
        "moment_dtype": args.moment_dtype,
        "donate": not args.no_donate,
        "adopted_defaults": adopted_defaults,
        # serving-ledger topology triple (docs/serving.md): the train bench
        # is single-device single-program, so the triple is fixed — recorded
        # anyway so every ledger row carries the same schema
        "n_devices": 1,
        "replicas": 1,
        "model_parallel": 1,
        # sequence identity: obs-regress keys segment on these, so a long-
        # sequence (temporal/NaFlex) or ring-sharded run never gates
        # against the short single-chip baseline
        "seq_len": int(cfg.vision.seq_len),
        "seq_parallel": 1,
    }
    if args.tiny:
        # a rehearsal: its own metric name, no per-chip unit, no MFU
        print(json.dumps({"metric": TINY_METRICS[args.model],
                          "value": round(images_per_sec, 2),
                          "unit": "images/sec", **result}), flush=True)
        return 0

    # analytic model FLOPs — XLA cost analysis counts scanned layers once
    flops = train_step_flops(cfg, batch)
    achieved_mfu = mfu(flops, dt, n_devices=1)
    metric, unit = METRICS[args.model]
    # for vit the metric of record IS the MFU (BASELINE.md "ViT-L/16
    # ImageNet train MFU"); throughput rides along as a field
    value = (round(achieved_mfu, 4) if args.model == "vit_l16_384"
             else round(images_per_sec, 2))
    result = {"metric": metric, "value": value, "unit": unit,
              "vs_baseline": round(achieved_mfu / 0.50, 4),
              "mfu": round(achieved_mfu, 4), **result}

    # Analytic-vs-XLA cross-check: when the layer scan is fully unrolled
    # (unroll >= depth, the default config) the one scan iteration's body
    # holds every layer, so XLA's cost analysis counts the whole model and
    # the two numbers must agree up to remat recompute (compiled >= analytic,
    # well under 2x for the shipped policies). A drifted train_step_flops
    # formula would silently inflate MFU; this refuses to report mfu at all
    # in that case.
    full_unroll = (cfg.vision.scan_unroll >= cfg.vision.depth
                   and (not hasattr(cfg, "text")
                        or cfg.text.scan_unroll >= cfg.text.depth))
    if not full_unroll:
        crosscheck = "skipped: scan not fully unrolled"
    else:
        cflops = compiled_flops(
            step_fn.lower(model, optimizer, *data).compile())
        crosscheck = (round(cflops / flops, 3) if cflops
                      else "unavailable: cost analysis reported no flops")
    result["mfu_crosscheck"] = crosscheck
    if isinstance(crosscheck, float) and not (0.5 <= crosscheck <= 2.0):
        # the analytic FLOP formula disagrees with XLA's count: the MFU
        # number cannot be trusted, so don't report one
        del result["mfu"]
        result["vs_baseline"] = 0.0
        if args.model == "vit_l16_384":
            result["value"] = 0.0  # for vit, value holds the mfu
        result["mfu_error"] = (
            f"analytic train_step_flops is {crosscheck}x XLA cost analysis "
            "(tolerance [0.5, 2.0]); mfu withheld")
    elif achieved_mfu > 0.95:
        result["warning"] = ("implied MFU exceeds physical plausibility — "
                             "timing artifact, rerun with more --steps")
    print(json.dumps(result), flush=True)
    return 0


def main(argv: list[str] | None = None) -> int:
    return run(parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
