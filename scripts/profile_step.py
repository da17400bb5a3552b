"""Capture a jax.profiler trace of the SigLIP train step on TPU, print the
top ops by self-time (via tensorboard_plugin_profile's xplane converter),
and emit a JSON summary line with the per-op attribution. One process; run
it on the chip through the chip tool (`chiprun -- python -m
scripts.profile_step`), the trace lands under ``chiprun_out/``.

Usage:
    python -m scripts.profile_step [--attn xla] [--remat dots+ln] [--top 25]
    python -m scripts.profile_step --adopted   # use the adopted sweep winner
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time


def apply_adopted(args: argparse.Namespace) -> bool:
    """Overwrite execution flags from the adopted sweep winner
    (jimm_tpu/adopted_runtime.json) so the profile describes the exact
    config the bench of record runs."""
    try:
        from jimm_tpu.configs import ADOPTED_RUNTIME_PATH
        v = (json.loads(ADOPTED_RUNTIME_PATH.read_text())
             ["presets"]["siglip-base-patch16-256"]["variant"])
    except (OSError, KeyError, ValueError):
        print("no adopted variant recorded; using flag defaults",
              file=sys.stderr)
        return False
    args.attn = str(v.get("attn", args.attn))
    args.remat = str(v.get("remat", args.remat))
    args.unroll = int(v.get("unroll", args.unroll))
    args.batch = int(v.get("batch", args.batch))
    return True


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--attn", default="auto")
    p.add_argument("--remat", default="dots",
                   help="remat spec: none, full, or dots[+ln][+act][+attn]")
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--unroll", type=int, default=12)
    p.add_argument("--top", type=int, default=25)
    p.add_argument("--dir", default="chiprun_out/profile_step")
    p.add_argument("--adopted", action="store_true",
                   help="take attn/remat/unroll/batch from the adopted "
                        "sweep winner (scripts/adopt_sweep.py --apply)")
    args = p.parse_args()
    adopted = apply_adopted(args) if args.adopted else False

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    from jimm_tpu.aot.export import enable_persistent_cache
    enable_persistent_cache()

    from jimm_tpu import SigLIP, preset
    from jimm_tpu.configs import parse_remat, with_runtime
    from jimm_tpu.train import (OptimizerConfig, make_contrastive_train_step,
                                make_optimizer, mfu)
    from jimm_tpu.train.metrics import train_step_flops

    cfg = preset("siglip-base-patch16-256")
    cfg = with_runtime(cfg, **parse_remat(args.remat), attn_impl=args.attn,
                       scan_unroll=args.unroll)
    model = SigLIP(cfg, rngs=nnx.Rngs(0), dtype=jnp.bfloat16,
                   param_dtype=jnp.bfloat16)
    optimizer = make_optimizer(model, OptimizerConfig(learning_rate=1e-3))
    step_fn = make_contrastive_train_step("siglip", donate=True)
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(args.batch, 256, 256, 3), jnp.bfloat16)
    text = jnp.asarray(rng.randint(1, cfg.text.vocab_size,
                                   size=(args.batch, 64)), jnp.int32)
    for _ in range(3):
        m = step_fn(model, optimizer, images, text)
    jax.block_until_ready((m, nnx.state(model, nnx.Param)))

    from jimm_tpu import obs

    jax.profiler.start_trace(args.dir)
    t0 = time.perf_counter()
    # obs.span bridges to jax.profiler.TraceAnnotation while a trace is
    # live, so each dispatch shows up as a named host lane in the capture
    for i in range(args.steps):
        with obs.span(f"profile_step_{i}"):
            m = step_fn(model, optimizer, images, text)
    jax.block_until_ready((m, nnx.state(model, nnx.Param)))
    dt = (time.perf_counter() - t0) / args.steps
    jax.profiler.stop_trace()
    print(f"step time {dt*1e3:.1f} ms ({args.batch/dt:.0f} img/s)")

    summary = {
        "metric": "profile_step",
        "value": round(args.batch / dt, 2),
        "unit": "images/sec/chip",
        "step_time_ms": round(dt * 1e3, 2),
        "mfu": round(mfu(train_step_flops(cfg, args.batch), dt,
                         n_devices=1), 4),
        "batch_size": args.batch,
        "remat": args.remat, "attn": args.attn, "unroll": args.unroll,
        "adopted": adopted,
        "device": jax.devices()[0].device_kind,
    }
    # the trace-analysis import below can be slow/fragile; the timing line
    # must survive regardless, and the enriched line supersedes it
    print(json.dumps(summary), flush=True)
    try:
        summary["top_ops"] = analyze(args.dir, args.top)
        print(json.dumps(summary), flush=True)
    except Exception as e:  # noqa: BLE001 — attribution is best-effort
        print(f"trace analysis failed: {e!r}", file=sys.stderr)


def analyze(log_dir: str, top: int) -> list[dict]:
    from tensorboard_plugin_profile.convert import raw_to_tool_data

    xplanes = sorted(glob.glob(
        f"{log_dir}/**/*.xplane.pb", recursive=True))
    xplane = xplanes[-1]
    data, _ = raw_to_tool_data.xspace_to_tool_data(
        [xplane], "framework_op_stats", params={})
    if isinstance(data, bytes):
        data = data.decode()
    stats = json.loads(data)
    # gviz table: first entry has cols/rows
    table = stats[0]
    cols = [c["label"] for c in table["cols"]]
    rows = [[c["v"] for c in r["c"]] for r in table["rows"]]
    i_name = cols.index("Operation")
    i_self = cols.index("Total self time (us)")
    i_occ = cols.index("#Occurrences")
    i_type = cols.index("Type")
    rows.sort(key=lambda r: -float(r[i_self]))
    total = sum(float(r[i_self]) for r in rows)
    print(f"\ntotal device self time: {total/1e3:.1f} ms; top {top} ops:")
    print(f"{'%':>6s} {'ms':>9s} {'n':>5s}  {'type':22s} name")
    out = []
    for r in rows[:top]:
        pct = 100 * float(r[i_self]) / total
        print(f"{pct:6.2f} {float(r[i_self])/1e3:9.2f} {int(r[i_occ]):5d}  "
              f"{str(r[i_type])[:22]:22s} {str(r[i_name])[:90]}")
        out.append({"pct": round(pct, 2),
                    "ms": round(float(r[i_self]) / 1e3, 2),
                    "n": int(r[i_occ]),
                    "type": str(r[i_type])[:40],
                    "name": str(r[i_name])[:90]})
    return out[:10]  # JSON line stays small; full table is printed above


if __name__ == "__main__":
    sys.exit(main())
