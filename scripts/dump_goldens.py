"""One-time, network-gated golden recorder for real-checkpoint parity.

Runs the HF torch oracles for the tracked checkpoints
(`tests/golden_util.GOLDEN_SPECS`: google/vit-base-patch16-224,
openai/clip-vit-base-patch32, google/siglip-base-patch16-256) on the
deterministic golden inputs and records logits + tower embeddings into
small checked-in ``tests/goldens/<name>.npz`` files. After one successful
run (with network + torch + transformers, e.g. on a dev workstation),
`tests/test_goldens.py` asserts bit-faithful loading of the *actual
published weights* offline — neither torch nor network at test time. The
build environment here has zero egress, so this script is expected to run
elsewhere; it is written defensively and prints exactly what it produced.

Every invocation appends a dated per-checkpoint outcome to
``tests/goldens/ATTEMPTS.log`` (committed), so a blocked-egress attempt
leaves auditable evidence distinguishable from "never tried".

Usage:
    python -m scripts.dump_goldens --all          [--out tests/goldens]
    python -m scripts.dump_goldens --only NAME
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tests"))
sys.path.insert(0, str(REPO))
from golden_util import GOLDEN_SPECS, golden_image, golden_text  # noqa: E402


def dump_one(name: str, spec: dict, out_dir: Path) -> None:
    import torch
    img = golden_image(spec["image_size"])
    pixel = torch.tensor(img).permute(0, 3, 1, 2)
    record: dict[str, np.ndarray] = {"image": img}

    if spec["family"] == "vit":
        from transformers import ViTForImageClassification
        model = ViTForImageClassification.from_pretrained(spec["repo"]).eval()
        with torch.no_grad():
            record["logits"] = model(pixel_values=pixel).logits.numpy()
    else:
        txt = golden_text(spec["family"], spec["ctx"])
        record["text"] = txt
        if spec["family"] == "clip":
            from transformers import CLIPModel
            model = CLIPModel.from_pretrained(spec["repo"]).eval()
        else:
            from transformers import SiglipModel
            model = SiglipModel.from_pretrained(spec["repo"]).eval()
        with torch.no_grad():
            out = model(input_ids=torch.tensor(txt), pixel_values=pixel)
            # forward() L2-normalizes its image_embeds/text_embeds outputs;
            # jimm's encode_image/encode_text are unnormalized, so record
            # the get_*_features projections (what tests/test_clip.py's
            # oracle uses too)
            record["image_embeds"] = model.get_image_features(
                pixel_values=pixel).numpy()
            record["text_embeds"] = model.get_text_features(
                input_ids=torch.tensor(txt)).numpy()
        record["logits"] = out.logits_per_image.numpy()

    out_path = out_dir / f"{name}.npz"
    np.savez_compressed(out_path, **record)
    sizes = {k: v.shape for k, v in record.items()}
    print(f"wrote {out_path} ({out_path.stat().st_size} bytes): {sizes}")


def _soft_alarm(seconds: int):
    """SIGALRM -> TimeoutError, self-contained (no jimm_tpu import — see the
    call site). Returns a disarm() that cancels and restores the handler."""
    import signal

    def on_alarm(signum, frame):
        raise TimeoutError(f"no progress after {seconds}s (hung download?)")

    prev = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(seconds)

    def disarm():
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)

    return disarm


def _log_attempt(out_dir: Path, name: str, outcome: str) -> None:
    ts = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    with open(out_dir / "ATTEMPTS.log", "a") as f:
        f.write(f"{ts} {name}: {outcome}\n")


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=str(REPO / "tests" / "goldens"))
    p.add_argument("--only", default=None,
                   help="dump a single spec by name")
    p.add_argument("--all", action="store_true",
                   help="dump every spec (the default; explicit for queue "
                        "scripts)")
    p.add_argument("--per-spec-timeout", type=int, default=240,
                   help="soft alarm per checkpoint: a hung download must "
                        "log a dated failure and move on, not stall the "
                        "whole attempt")
    args = p.parse_args(argv)
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    names = [args.only] if args.only else list(GOLDEN_SPECS)
    failed = []
    for name in names:
        try:
            # self-contained alarm: this script runs on external machines
            # with torch+transformers but no jax/flax, where importing
            # jimm_tpu would fail
            disarm = _soft_alarm(args.per_spec_timeout)
            try:
                dump_one(name, GOLDEN_SPECS[name], out_dir)
            finally:
                disarm()
            _log_attempt(out_dir, name, "OK — golden recorded")
        except Exception as e:  # noqa: BLE001 — log evidence, keep going
            reason = (f"FAILED {type(e).__name__}: "
                      f"{' '.join(str(e).split())[:200]}")
            _log_attempt(out_dir, name, reason)
            print(f"{name}: {reason}", file=sys.stderr)
            failed.append(name)
    if failed:
        print(f"{len(failed)}/{len(names)} failed (egress blocked?) — see "
              f"{out_dir / 'ATTEMPTS.log'}", file=sys.stderr)
        return 1
    print("done — check the .npz files in, then tests/test_goldens.py "
          "runs offline against locally cached checkpoints")
    return 0


if __name__ == "__main__":
    sys.exit(main())
