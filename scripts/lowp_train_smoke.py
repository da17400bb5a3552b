"""CI smoke for the low-precision training fast path (``--precision``).

Three gates, end to end through the real ``jimm-tpu train`` CLI on CPU
(interpret-mode Pallas fp8 kernels — the same wrapper/grid code TPU runs):

1. **Same data**: the ``fp8_hybrid`` run and its ``bf16`` control log
   per-step batch fingerprints (``--batch-fingerprint``); they must match
   step for step, so the loss comparison is apples to apples.
2. **Loss parity**: the fp8 run's final-step training loss must match the
   bf16 control within ``LOSS_RTOL`` — delayed scaling plus saturating
   quantization must not bend the tiny-run loss curve.
3. **Zero re-tunes on a warm cache**: the fp8 run executes twice against
   one ``JIMM_TUNE_CACHE`` with ``JIMM_TUNE=1``. Life 1 may measure (the
   cache is cold); life 2 must add ZERO new cache entries — tune keys
   (kernel version + shapes + dtypes) are stable, so a warm cache means
   lookup only, and a re-tune here would mean the fp8 kernels' keys churn
   per process.

Exits nonzero (with a JSON error line) on any violation.

Usage:
    JAX_PLATFORMS=cpu python -m scripts.lowp_train_smoke
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import tempfile

PRESET = "vit-tiny-patch16-224"
STEPS = 6
BATCH = 4
LOSS_RTOL = 2e-2


def fail(msg: str) -> int:
    print(json.dumps({"metric": "lowp_train_smoke", "value": 0.0,
                      "error": msg}), flush=True)
    return 1


def run_train(precision: str, metrics_file: pathlib.Path,
              tune_cache: pathlib.Path | None) -> dict:
    """One tiny CLI train run; returns its parsed goodput report."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if tune_cache is not None:
        env["JIMM_TUNE"] = "1"
        env["JIMM_TUNE_CACHE"] = str(tune_cache)
    cmd = [sys.executable, "-m", "jimm_tpu.cli", "train",
           "--preset", PRESET, "--tiny",
           "--steps", str(STEPS), "--batch-size", str(BATCH),
           "--precision", precision, "--moment-dtype", "bf16",
           "--batch-fingerprint", "--log-every", "1",
           "--metrics-file", str(metrics_file)]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"train --precision {precision} failed: "
                           f"{proc.stderr[-1500:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("goodput: "):
            return json.loads(line[len("goodput: "):])
    raise RuntimeError(f"train --precision {precision} printed no "
                       f"goodput line")


def read_metrics(metrics_file: pathlib.Path) -> list[dict]:
    rows = [json.loads(line) for line in
            metrics_file.read_text().splitlines() if line.strip()]
    return [r for r in rows if "loss" in r]


def cache_entries(root: pathlib.Path) -> set[str]:
    return {str(p.relative_to(root)) for p in root.rglob("*") if p.is_file()}


def main() -> int:
    argparse.ArgumentParser(description=__doc__.splitlines()[0]).parse_args()

    with tempfile.TemporaryDirectory(prefix="lowp_smoke_") as tmp:
        tmpdir = pathlib.Path(tmp)
        cache = tmpdir / "tune_cache"
        cache.mkdir()

        # --- bf16 control, then fp8 life 1 (cold cache, may tune) --------
        run_train("bf16", tmpdir / "bf16.jsonl", None)
        run_train("fp8_hybrid", tmpdir / "fp8_life1.jsonl", cache)
        warm = cache_entries(cache)

        # --- fp8 life 2: warm cache must stay byte-for-byte the same -----
        fp8_goodput = run_train("fp8_hybrid", tmpdir / "fp8.jsonl", cache)
        if cache_entries(cache) != warm:
            added = sorted(cache_entries(cache) - warm)
            return fail(f"warm tune cache grew on the second fp8 run "
                        f"(re-tuned): {added[:5]}")

        control = read_metrics(tmpdir / "bf16.jsonl")
        lowp = read_metrics(tmpdir / "fp8.jsonl")
        if len(control) != STEPS or len(lowp) != STEPS:
            return fail(f"expected {STEPS} logged steps, got "
                        f"{len(control)} (bf16) / {len(lowp)} (fp8)")

        # --- gate 1: identical data streams ------------------------------
        fp_c = [r.get("batch_fingerprint") for r in control]
        fp_l = [r.get("batch_fingerprint") for r in lowp]
        if None in fp_c or None in fp_l:
            return fail("batch fingerprints missing from metrics rows")
        if fp_c != fp_l:
            return fail(f"batch fingerprints diverge between variants "
                        f"(first mismatch at step "
                        f"{next(i for i, (a, b) in enumerate(zip(fp_c, fp_l)) if a != b)})")

        # --- gate 2: loss parity at the final step ------------------------
        loss_c, loss_l = control[-1]["loss"], lowp[-1]["loss"]
        rel = abs(loss_l - loss_c) / max(abs(loss_c), 1e-9)
        if rel > LOSS_RTOL:
            return fail(f"final loss diverged: bf16 {loss_c:.4f} vs "
                        f"fp8_hybrid {loss_l:.4f} (rel {rel:.3f} > "
                        f"{LOSS_RTOL})")

    result = {
        "metric": "lowp_train_smoke", "value": 1.0,
        "precision": "fp8_hybrid",
        "moment_dtype": fp8_goodput.get("moment_dtype"),
        "steps": STEPS, "batch_size": BATCH,
        "loss_bf16": loss_c, "loss_fp8": loss_l, "loss_rel_diff": rel,
        "tune_entries": len(warm),
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
