"""Pick the measured-best sweep variant and adopt it as the framework's
default execution config.

Reads sweep records from MEASUREMENTS.jsonl (phase "sweep") or from a
bench_sweep output file passed with --from. Only records with a real mfu field count; error records,
CPU runs, --tiny validation runs, and records with no device provenance
are ignored. Prints the winner, the full ranking, and the exact flag
spelling for bench.py / docs.

With ``--apply``, writes the winner into ``jimm_tpu/adopted_runtime.json``
(with full provenance: mfu, step time, device, source commit, timestamp).
That file is consumed by ``jimm_tpu.configs.adopted_runtime`` so
``jimm train --preset <name>`` and ``bench.py`` run the measured-best
execution config by default; explicit flags still win.

    python -m scripts.adopt_sweep              # rank only
    python -m scripts.adopt_sweep --apply      # rank + write adopted file
    python -m scripts.adopt_sweep --from chiprun_out/sweep.log
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:  # jimm_tpu.configs import, any invocation style
    sys.path.insert(0, str(REPO))


def load_records(path: pathlib.Path, phase_filter: bool,
                 phase: str = "sweep") -> list[dict]:
    from scripts._measurements import read_records
    recs = []
    for rec in read_records(path):
        if phase_filter and rec.get("phase") != phase:
            continue
        if "variant" not in rec or not isinstance(rec.get("mfu"), float):
            continue
        # fidelity: a --tiny validation or CPU run must never supersede a
        # real TPU measurement of the same variant in the ranking; a record
        # with NO device provenance is treated as low-fidelity too —
        # re-measure rather than trust it
        device = str(rec.get("device", "")).lower()
        if rec.get("tiny") or "cpu" in device or not device:
            continue
        recs.append(rec)
    return recs


def rank_records(recs: list[dict]) -> list[dict]:
    """Best-first ranking with last-record-per-variant-wins (later attempts
    supersede partial earlier ones)."""
    by_variant: dict[str, dict] = {}
    for rec in recs:
        by_variant[json.dumps(rec["variant"], sort_keys=True)] = rec
    return sorted(by_variant.values(), key=lambda r: -r["mfu"])


def flags_for(variant: dict) -> str:
    """bench.py flag spelling for a sweep variant dict."""
    parts = []
    if "remat" in variant:
        parts.append(f"--remat {variant['remat']}")
    if "attn" in variant:
        parts.append(f"--attn {variant['attn']}")
    if variant.get("ln") == "fused":
        parts.append("--ln fused")
    if variant.get("fused_qkv") in ("1", "true"):
        parts.append("--fused-qkv")
    if variant.get("moment") == "bf16":
        parts.append("--moment-dtype bf16")
    if "unroll" in variant:
        parts.append(f"--unroll {variant['unroll']}")
    if "batch" in variant:
        parts.append(f"--batch-size {variant['batch']}")
    if variant.get("donate") in ("0", "false"):
        parts.append("--no-donate")
    return " ".join(parts)


def runtime_for(variant: dict) -> dict:
    """Sweep variant -> `with_runtime` kwargs (execution-strategy fields
    only; batch/moment/donate are bench-level knobs, kept in bench_flags)."""
    from jimm_tpu.configs import parse_remat
    rt: dict = {}
    if "remat" in variant:
        rt.update(parse_remat(variant["remat"]))
    if "attn" in variant:
        rt["attn_impl"] = variant["attn"]
    if "ln" in variant:
        rt["ln_impl"] = variant["ln"]
    if "fused_qkv" in variant:
        rt["fused_qkv"] = str(variant["fused_qkv"]).lower() in ("1", "true")
    if "unroll" in variant:
        rt["scan_unroll"] = int(variant["unroll"])
    return rt


def apply_adoption(best: dict, preset_name: str) -> pathlib.Path:
    """Write the winner into jimm_tpu/adopted_runtime.json (merge-preserving
    other presets' entries), with full measurement provenance."""
    import subprocess
    import time
    from jimm_tpu.configs import ADOPTED_RUNTIME_PATH
    try:
        commit = subprocess.run(["git", "-C", str(REPO), "rev-parse",
                                 "--short", "HEAD"], capture_output=True,
                                text=True, timeout=10
                                ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 — provenance only, never fatal
        commit = "unknown"
    data: dict = {}
    if ADOPTED_RUNTIME_PATH.exists():
        try:
            data = json.loads(ADOPTED_RUNTIME_PATH.read_text())
        except json.JSONDecodeError:
            data = {}
    variant = best["variant"]
    data.setdefault("presets", {})[preset_name] = {
        "runtime": runtime_for(variant),
        "variant": variant,
        "bench_flags": flags_for(variant),
        "provenance": {
            "mfu": best.get("mfu"),
            "step_time_ms": best.get("step_time_ms"),
            "images_per_sec": best.get("images_per_sec"),
            "device": best.get("device"),
            "measured_at": best.get("ts"),
            "adopted_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "adopted_from_commit": commit,
            "source": "scripts/adopt_sweep.py --apply",
        },
    }
    ADOPTED_RUNTIME_PATH.write_text(json.dumps(data, indent=2,
                                               sort_keys=True) + "\n")
    return ADOPTED_RUNTIME_PATH


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--from", dest="src", default=None,
                   help="bench_sweep output file (default: repo "
                        "MEASUREMENTS.jsonl, sweep phase)")
    p.add_argument("--top", type=int, default=5)
    p.add_argument("--apply", action="store_true",
                   help="write the winner to jimm_tpu/adopted_runtime.json "
                        "so CLI presets and bench.py default to it")
    p.add_argument("--preset", default="siglip-base-patch16-256",
                   help="preset the sweep measured (adoption key)")
    p.add_argument("--phase", default="sweep",
                   help="MEASUREMENTS.jsonl phase tag to rank (the watcher "
                        "persists the ViT sweep as 'vit_sweep')")
    args = p.parse_args()

    path = pathlib.Path(args.src) if args.src else REPO / "MEASUREMENTS.jsonl"
    if not path.exists():
        print(f"no records: {path} does not exist", file=sys.stderr)
        return 1
    recs = load_records(path, phase_filter=args.src is None,
                        phase=args.phase)
    # records tag the bench model they measured; a ViT sweep log must never
    # adopt under the SigLIP preset key (or vice versa). Pre-r5 records
    # without the tag pass through.
    expected_model = {"siglip-base-patch16-256": "siglip_b16_256",
                      "vit-large-patch16-384": "vit_l16_384"}.get(args.preset)
    def _model_mismatch(r):
        return (expected_model and r.get("model")
                and r["model"] != expected_model)

    dropped = [r for r in recs if _model_mismatch(r)]
    if dropped:
        print(f"ignoring {len(dropped)} records measured on "
              f"{dropped[0]['model']!r} (adopting for {args.preset!r})",
              file=sys.stderr)
        recs = [r for r in recs if not _model_mismatch(r)]
    if not recs:
        print(f"no usable sweep records (variant + float mfu) in {path}",
              file=sys.stderr)
        return 1
    ranked = rank_records(recs)

    print(f"{len(ranked)} variants measured; top {args.top}:")
    for rec in ranked[:args.top]:
        print(f"  mfu={rec['mfu']:.4f}  "
              f"step={rec.get('step_time_ms', '?')}ms  "
              f"img/s={rec.get('images_per_sec', '?')}  "
              f"{json.dumps(rec['variant'])}")
    best = ranked[0]
    print("\nadopt as bench.py defaults / run as:")
    print(f"  python bench.py {flags_for(best['variant'])}")
    if args.apply:
        path = apply_adoption(best, args.preset)
        print(f"adopted -> {path} (preset {args.preset}, "
              f"mfu={best.get('mfu')})")
    if isinstance(best.get("mfu"), float) and best["mfu"] >= 0.50:
        print(f"\nNORTH STAR MET: mfu={best['mfu']:.4f} >= 0.50")
    return 0


if __name__ == "__main__":
    sys.exit(main())
