"""CI drill for continuous profiling + HBM observability (ISSUE 18).

Four legs, all through shipped code paths:

**Ring leg.** ``jimm-tpu train --prof-ring`` at an aggressive cadence
(``--prof-every 5``) so a short run commits several real window captures;
asserts the ring holds >= 2 committed captures, stays under its byte
budget, and that every capture journaled a ``prof_capture_started`` /
``prof_capture_committed`` pair.

**Diff leg.** ``jimm-tpu obs prof diff`` over the two newest ring
captures — run in a SUBPROCESS that asserts ``jax`` was never imported,
proving the analysis path works on a dev box against rsynced artifacts.

**Incident leg.** The elastic kill-drill (2-replica x 2-way engine, one
replica's forward replaced with a raiser) with a capture manager
configured: the heal path must auto-trigger a deep capture tagged with
the incident's correlation id, and the journal chain for that cid must
include ``prof_capture_committed``.

**Overhead leg.** Interleaved ring-on / ring-off tiny-train pairs; the
minimum over pairs of (median on-step time / median off-step time) must
be <= 1.01 — the <=1% overhead budget the ring ships under. The ratio is
of CPU step times and is printed in the summary's ``overhead`` field.

Exits nonzero with a JSON error line on any violation.

Usage:
    JAX_PLATFORMS=cpu python -m scripts.prof_smoke
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import tempfile
import time
from pathlib import Path

RING_STEPS = 14
RING_EVERY = 5
RING_BUDGET = 32 << 20
OVERHEAD_PAIRS = 3
OVERHEAD_STEPS = 24
OVERHEAD_GATE = 1.01


def fail(msg: str) -> int:
    print(json.dumps({"metric": "prof_smoke", "value": 0.0, "error": msg}),
          flush=True)
    return 1


def _train(tmp: Path, tag: str, steps: int, prof_ring: Path | None,
           every: int = 200) -> tuple[int, Path]:
    from jimm_tpu import cli
    metrics = tmp / f"metrics_{tag}.jsonl"
    argv = ["train", "--preset", "vit-tiny-patch16-224", "--tiny",
            "--batch-size", "4", "--steps", str(steps), "--seed", "7",
            "--log-every", "0", "--metrics-file", str(metrics)]
    if prof_ring is not None:
        argv += ["--prof-ring", str(prof_ring),
                 "--prof-every", str(every), "--prof-window", "1",
                 "--prof-ring-bytes", str(RING_BUDGET)]
    rc = cli.main(argv)
    return rc, metrics


def _step_times(metrics: Path, skip: int = 2) -> list[float]:
    """Per-step times from the metrics JSONL, skipping compile/warmup."""
    times = []
    for line in metrics.read_text().splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        t = rec.get("step_time_s")
        if isinstance(t, (int, float)) and rec.get("step", 0) >= skip:
            times.append(float(t))
    return times


def ring_leg(tmp: Path) -> tuple[str | None, dict, list[dict]]:
    from jimm_tpu.obs.journal import get_journal
    from jimm_tpu.obs.prof.capture import list_captures, reset_capture

    ring = tmp / "ring"
    rc, _ = _train(tmp, "ring", RING_STEPS, ring, every=RING_EVERY)
    reset_capture()
    if rc:
        return f"train --prof-ring exited {rc}", {}, []
    metas = list_captures(ring)
    if len(metas) < 2:
        return f"expected >= 2 ring captures, got {len(metas)}", {}, []
    total = sum(m["bytes"] for m in metas)
    if total > RING_BUDGET:
        return f"ring over budget: {total} > {RING_BUDGET}", {}, []
    events = [e["event"] for e in get_journal().tail(200)]
    started = events.count("prof_capture_started")
    committed = events.count("prof_capture_committed")
    if committed < len(metas) or started < committed:
        return (f"journal pairs off: {started} started, {committed} "
                f"committed, {len(metas)} on disk"), {}, []
    return None, {"captures": len(metas), "ring_bytes": total,
                  "kinds": [m["kind"] for m in metas]}, metas


def diff_leg(metas: list[dict]) -> tuple[str | None, dict]:
    newest = [str(m["path"]) for m in metas[-2:]]
    # jax-free proof: diff in a subprocess and assert jax never imported
    code = (
        "import sys\n"
        "from jimm_tpu.obs.cli import main\n"
        "rc = main(['obs', 'prof', 'diff', '--json', sys.argv[1], "
        "sys.argv[2]])\n"
        "assert 'jax' not in sys.modules, 'diff path imported jax'\n"
        "sys.exit(0 if rc in (0, 1) else 2)\n"
    )
    env = dict(os.environ)
    env.pop("JIMM_PROF_DIR", None)
    proc = subprocess.run([sys.executable, "-c", code, *newest],
                          capture_output=True, text=True, env=env,
                          timeout=120)
    if proc.returncode not in (0, 1):
        return (f"jax-free diff failed rc={proc.returncode}: "
                f"{proc.stderr[-400:]}"), {}
    d = json.loads(proc.stdout)
    if d.get("verdict") not in ("ok", "regression"):
        return f"diff produced no verdict: {d}", {}
    return None, {"verdict": d["verdict"],
                  "total_delta_frac": d["total_delta_frac"],
                  "jax_free": True}


def incident_leg(tmp: Path) -> tuple[str | None, dict]:
    import asyncio

    import numpy as np
    from flax import nnx

    from jimm_tpu import CLIP, preset
    from jimm_tpu.aot import ArtifactStore
    from jimm_tpu.cli import _tiny_override
    from jimm_tpu.obs.journal import chain, get_journal
    from jimm_tpu.obs.prof.capture import configure_capture, reset_capture
    from jimm_tpu.serve import (BucketTable, InferenceEngine,
                                build_replica_forwards, plan_topology)

    mgr = configure_capture(tmp / "incident_ring", deep_window_s=0.3,
                            min_trigger_interval_s=0.0)
    cfg = _tiny_override(preset("clip-vit-base-patch16"))
    model = CLIP(cfg, rngs=nnx.Rngs(0))
    size = cfg.vision.image_size
    plan = plan_topology(2, 2)
    try:
        with tempfile.TemporaryDirectory(prefix="prof-smoke-") as root:
            store = ArtifactStore(root)

            def build():
                return build_replica_forwards(
                    model, plan, method="encode_image",
                    item_shape=(size, size, 3), store=store,
                    label="prof_smoke")

            forwards, traces = build()
            engine = InferenceEngine(forwards, item_shape=(size, size, 3),
                                     buckets=BucketTable((1, 4)),
                                     max_delay_ms=2.0, trace_count=traces)
            engine.warmup_blocking()
            engine.set_heal(build)
            x = np.random.RandomState(0).rand(size, size, 3) \
                .astype(np.float32)

            class Raiser:
                def __call__(self, _):
                    raise RuntimeError("injected: replica device lost")

            async def drive():
                await engine.start()
                try:
                    for _ in range(4):
                        await engine.submit(x)
                    engine._replicas[1].forward = Raiser()
                    for _ in range(400):
                        try:
                            await engine.submit(x)
                        except RuntimeError:
                            pass
                        if engine.metrics.count("replans_total") >= 1:
                            return None
                        await asyncio.sleep(0.01)
                    return "no replan happened"
                finally:
                    await engine.stop()

            err = asyncio.run(drive())
            if err:
                return f"kill-drill: {err}", {}
            deadline = time.monotonic() + 10.0
            while not mgr.ls() and time.monotonic() < deadline:
                time.sleep(0.05)
            mgr.flush()
            captures = mgr.ls()
            events = list(get_journal().tail(400))
            faults = [e for e in events if e["event"] == "replica_fault"
                      and e.get("cid")]
            if not faults:
                return "no correlated replica_fault", {}
            cid = faults[-1]["cid"]
            tagged = [c for c in captures if c.get("cid") == cid]
            if not tagged:
                return (f"no deep capture on incident cid {cid}: "
                        f"{[c.get('cid') for c in captures]}"), {}
            incident = [e["event"] for e in chain(events, cid)]
            if "prof_capture_committed" not in incident:
                return (f"prof_capture_committed missing from chain: "
                        f"{incident}"), {}
            return None, {"cid": cid, "deep_capture": tagged[0]["name"],
                          "capture_bytes": tagged[0]["bytes"],
                          "reason": tagged[0]["reason"]}
    finally:
        reset_capture()


def overhead_leg(tmp: Path) -> tuple[str | None, dict]:
    from jimm_tpu.obs.prof.capture import reset_capture

    ratios = []
    for pair in range(OVERHEAD_PAIRS):
        # interleave on/off so in-process warmup and machine drift hit
        # both sides of every pair equally
        rc, m_on = _train(tmp, f"on{pair}", OVERHEAD_STEPS,
                          tmp / f"ovh_ring{pair}")
        reset_capture()
        if rc:
            return f"ring-on run {pair} exited {rc}", {}
        rc, m_off = _train(tmp, f"off{pair}", OVERHEAD_STEPS, None)
        if rc:
            return f"ring-off run {pair} exited {rc}", {}
        on = _step_times(m_on)
        off = _step_times(m_off)
        if len(on) < 8 or len(off) < 8:
            return f"too few step times (on={len(on)}, off={len(off)})", {}
        ratios.append(statistics.median(on) / statistics.median(off))
    best = min(ratios)
    if best > OVERHEAD_GATE:
        return (f"ring overhead over budget: min ratio {best:.4f} > "
                f"{OVERHEAD_GATE} (pairs: "
                f"{[round(r, 4) for r in ratios]})"), {}
    return None, {"min_ratio": round(best, 4),
                  "ratios": [round(r, 4) for r in ratios],
                  "gate": OVERHEAD_GATE, "steps": OVERHEAD_STEPS,
                  "prof_every_default": 200}


def hbm_leg() -> tuple[str | None, dict]:
    import jax.numpy as jnp

    from jimm_tpu.obs.prof.memory import MemoryMonitor

    # a pinned live array the sampler must see, whatever the earlier legs
    # left resident (CPU backends report via jax.live_arrays fallback)
    anchor = jnp.ones((256, 256), jnp.float32)
    anchor.block_until_ready()
    mon = MemoryMonitor()
    report = mon.sample()
    del anchor
    if not report["devices"]:
        return "device_memory_rows returned no devices", {}
    sources = {r["source"] for r in report["devices"]}
    if report["total_bytes_in_use"] < 256 * 256 * 4:
        return (f"live bytes not attributed: "
                f"{report['total_bytes_in_use']} (sources={sources})"), {}
    return None, {"devices": len(report["devices"]),
                  "sources": sorted(sources),
                  "total_bytes_in_use": report["total_bytes_in_use"]}


def main() -> int:
    # must land before jax initializes its backends (incident leg is 2x2)
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    import jax
    if jax.device_count() < 8:
        return fail(f"need 8 virtual devices, have {jax.device_count()}")

    tmp = Path(tempfile.mkdtemp(prefix="prof_smoke_"))
    err, ring_summary, metas = ring_leg(tmp)
    if err:
        return fail(f"ring leg: {err}")
    err, diff_summary = diff_leg(metas)
    if err:
        return fail(f"diff leg: {err}")
    err, incident_summary = incident_leg(tmp)
    if err:
        return fail(f"incident leg: {err}")
    err, overhead_summary = overhead_leg(tmp)
    if err:
        return fail(f"overhead leg: {err}")
    err, hbm_summary = hbm_leg()
    if err:
        return fail(f"hbm leg: {err}")

    print(json.dumps({"metric": "prof_smoke", "value": 1.0,
                      "ring": ring_summary, "diff": diff_summary,
                      "incident": incident_summary,
                      "overhead": overhead_summary,
                      "hbm": hbm_summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
