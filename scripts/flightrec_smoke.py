"""CI drill for flight-recorder observability (ISSUE 13).

One journal, three legs, all through shipped code paths:

**Train leg — correlated preemption chain.** ``supervise --elastic
--shrink-plan 8,4`` with ``preempt@2`` injected (the ISSUE-12 kill-drill).
The preemption guard mints a correlation id; the smoke asserts the journal
reconstructs the whole incident from that one cid: ``preempt_detected →
grace_save_committed → attempt_failed → restart → checkpoint_restored →
mesh_resharded → supervise_recovered``, in order.

**Serve leg — correlated fault→heal→replan chain.** A 2-replica x 2-way
engine over a warm AOT store gets one replica killed under traffic; the
watchdog mints the incident cid and the smoke asserts ``replica_fault →
replica_fenced → heal_probe → heal_rebuilt → replan_started →
replan_done`` all carry it, with ``dur_s`` on the heal/replan spans and
wall time booked into the ``goodput_heal`` / ``goodput_replan`` buckets.
With a capture ring configured, the heal path also auto-triggers a deep
profiler capture on the SAME incident cid — the smoke asserts
``prof_capture_started``/``prof_capture_committed`` join the chain and
the committed artifact's ``meta.json`` carries the cid.

**Timeline leg.** ``export_timeline`` over the full journal plus the
engine's ``recent_traces`` must validate with zero problems and cover both
incidents (both root cids appear in the trace's args).

Exits nonzero with a JSON error line on any violation.

Usage:
    JAX_PLATFORMS=cpu python -m scripts.flightrec_smoke
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
from pathlib import Path

STEPS = 6
REPLICAS = 2
MODEL_PARALLEL = 2

TRAIN_CHAIN = ["preempt_detected", "grace_save_committed", "attempt_failed",
               "restart", "checkpoint_restored", "mesh_resharded",
               "supervise_recovered"]
SERVE_CHAIN = ["replica_fault", "replica_fenced", "heal_probe",
               "heal_rebuilt", "replan_started", "replan_done"]


def fail(msg: str) -> int:
    print(json.dumps({"metric": "flightrec_smoke", "value": 0.0,
                      "error": msg}), flush=True)
    return 1


def assert_subsequence(names: list[str], want: list[str],
                       what: str) -> str | None:
    """``want`` must appear within ``names`` in order (gaps allowed)."""
    it = iter(names)
    for step in want:
        if not any(n == step for n in it):
            return (f"{what}: chain missing '{step}' (or out of order); "
                    f"chain events were {names}")
    return None


def train_leg(tmp: Path, journal: Path) -> tuple[str | None, dict]:
    from jimm_tpu import cli
    from jimm_tpu.obs.journal import chain, read_events

    rc = cli.main(["supervise", "--max-restarts", "2",
                   "--backoff-base-s", "0.01", "--seed", "0",
                   "--elastic", "--shrink-plan", "8,4",
                   "--journal", str(journal), "--",
                   "train", "--preset", "vit-tiny-patch16-224", "--tiny",
                   "--batch-size", "8", "--steps", str(STEPS),
                   "--save-every", "1", "--log-every", "0", "--seed", "7",
                   "--ckpt-dir", str(tmp / "ckpt"),
                   "--inject-faults", "preempt@2"])
    if rc:
        return f"supervised elastic drill exited {rc}", {}

    events = read_events(journal)
    preempts = [e for e in events if e["event"] == "preempt_detected"]
    if len(preempts) != 1:
        return f"expected exactly 1 preempt_detected, got {len(preempts)}", {}
    cid = preempts[0].get("cid")
    if not cid:
        return "preempt_detected carries no correlation id", {}
    incident = [e["event"] for e in chain(events, cid)]
    err = assert_subsequence(incident, TRAIN_CHAIN, "train incident")
    if err:
        return err, {}
    return None, {"cid": cid, "chain_len": len(incident)}


def serve_leg(journal: Path,
              prof_dir: Path) -> tuple[str | None, dict, list[dict]]:
    import asyncio
    import time

    import numpy as np
    from flax import nnx

    from jimm_tpu import CLIP, preset
    from jimm_tpu.aot import ArtifactStore
    from jimm_tpu.cli import _tiny_override
    from jimm_tpu.obs.journal import chain, read_events
    from jimm_tpu.obs.prof.capture import configure_capture, reset_capture
    from jimm_tpu.serve import (BucketTable, InferenceEngine,
                                build_replica_forwards, plan_topology)

    # deep captures on incidents: the heal path maybe_trigger()s into this
    # ring, tagging the capture with the incident cid
    prof_mgr = configure_capture(prof_dir, deep_window_s=0.3,
                                 min_trigger_interval_s=0.0)

    cfg = _tiny_override(preset("clip-vit-base-patch16"))
    model = CLIP(cfg, rngs=nnx.Rngs(0))
    size = cfg.vision.image_size
    plan = plan_topology(REPLICAS, MODEL_PARALLEL)

    with tempfile.TemporaryDirectory(prefix="jimm-flightrec-") as root:
        store = ArtifactStore(root)

        def build():
            return build_replica_forwards(
                model, plan, method="encode_image",
                item_shape=(size, size, 3), store=store,
                label="flightrec_smoke")

        forwards1, traces1 = build()
        warm1 = InferenceEngine(forwards1, item_shape=(size, size, 3),
                                buckets=BucketTable((1, 4)),
                                max_delay_ms=2.0, trace_count=traces1)
        warm1.warmup_blocking()

        forwards, traces = build()
        engine = InferenceEngine(forwards, item_shape=(size, size, 3),
                                 buckets=BucketTable((1, 4)),
                                 max_delay_ms=2.0, trace_count=traces)
        engine.warmup_blocking()
        engine.set_heal(build)

        x = np.random.RandomState(0).rand(size, size, 3).astype(np.float32)

        class Raiser:
            def __call__(self, _):
                raise RuntimeError("injected: replica device lost")

        async def drive():
            await engine.start()
            try:
                for _ in range(8):
                    await engine.submit(x)
                engine._replicas[1].forward = Raiser()
                for _ in range(400):
                    try:
                        await engine.submit(x)
                    except RuntimeError:
                        pass
                    if engine.metrics.count("replans_total") >= 1:
                        break
                    await asyncio.sleep(0.01)
                else:
                    return "no replan happened"
                for _ in range(8):
                    await engine.submit(x)
                return None
            finally:
                await engine.stop()

        err = asyncio.run(drive())
        rows = list(engine.recent_traces)
        if err:
            reset_capture()
            return f"serve leg: {err}", {}, rows

        # the deep capture commits on its window timer; wait it out, then
        # drop the global manager so later legs see a clean slate
        deadline = time.monotonic() + 10.0
        while not prof_mgr.ls() and time.monotonic() < deadline:
            time.sleep(0.05)
        prof_mgr.flush()
        captures = prof_mgr.ls()
        reset_capture()

        events = read_events(journal)
        faults = [e for e in events if e["event"] == "replica_fault"
                  and e.get("cid")]
        if not faults:
            return "no correlated replica_fault in the journal", {}, rows
        cid = faults[0]["cid"]
        incident = chain(events, cid)
        err = assert_subsequence([e["event"] for e in incident],
                                 SERVE_CHAIN, "serve incident")
        if err:
            return err, {}, rows
        spans = {e["event"]: e.get("dur_s") for e in incident
                 if "dur_s" in e}
        if not spans.get("heal_rebuilt") or not spans.get("replan_done"):
            return (f"heal/replan events carry no dur_s spans: "
                    f"{spans}"), {}, rows
        heal_s = engine.metrics.count("goodput_heal_seconds_total")
        replan_s = engine.metrics.count("goodput_replan_seconds_total")
        if heal_s <= 0 or replan_s <= 0:
            return (f"goodput heal/replan buckets not booked "
                    f"(heal={heal_s}, replan={replan_s})"), {}, rows
        if not rows or not any("done_mono" in r for r in rows):
            return "recent_traces rows carry no done_mono anchor", {}, rows
        # the incident's deep capture: journaled on the SAME root cid,
        # and the committed artifact's meta agrees
        chain_events = [e["event"] for e in incident]
        for ev in ("prof_capture_started", "prof_capture_committed"):
            if ev not in chain_events:
                return (f"{ev} missing from incident chain {cid}: "
                        f"{chain_events}"), {}, rows
        tagged = [c for c in captures if c.get("cid") == cid]
        if not tagged:
            return (f"no committed capture carries the incident cid {cid}: "
                    f"{[c.get('cid') for c in captures]}"), {}, rows
        return None, {"cid": cid, "chain_len": len(incident),
                      "goodput_heal_s": round(heal_s, 4),
                      "goodput_replan_s": round(replan_s, 4),
                      "deep_capture": tagged[0]["name"],
                      "capture_bytes": tagged[0]["bytes"]}, rows


def timeline_leg(tmp: Path, journal: Path, rows: list[dict],
                 cids: list[str]) -> tuple[str | None, dict]:
    from jimm_tpu.obs.journal import read_events
    from jimm_tpu.obs.timeline import (export_timeline,
                                       validate_chrome_trace,
                                       write_timeline)

    events = read_events(journal)
    trace = export_timeline(events, traces=rows)
    problems = validate_chrome_trace(trace)
    if problems:
        return f"timeline invalid: {problems[:5]}", {}
    seen = {e.get("args", {}).get("cid") for e in trace["traceEvents"]}
    for cid in cids:
        if cid not in seen:
            return f"timeline covers neither incident: {cid} missing", {}
    out = write_timeline(tmp / "timeline.json", trace)
    return None, {"trace_events": len(trace["traceEvents"]),
                  "path": str(out)}


def main() -> int:
    # must land before jax initializes its backends
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    import jax
    if jax.device_count() < 8:
        return fail(f"need 8 virtual devices, have {jax.device_count()} — "
                    f"was XLA_FLAGS set before another jax import?")

    from jimm_tpu.obs.journal import configure_journal

    tmp = Path(tempfile.mkdtemp(prefix="flightrec_smoke_"))
    journal = tmp / "journal.jsonl"
    # serve-side events go through the global journal; the train leg's
    # `supervise --journal` repoints the same process at the same file
    configure_journal(journal)

    err, train_summary = train_leg(tmp, journal)
    if err:
        return fail(f"train leg: {err}")
    err, serve_summary, rows = serve_leg(journal, tmp / "prof")
    if err:
        return fail(f"serve leg: {err}")
    err, timeline_summary = timeline_leg(
        tmp, journal, rows, [train_summary["cid"], serve_summary["cid"]])
    if err:
        return fail(f"timeline leg: {err}")
    print(json.dumps({"metric": "flightrec_smoke", "value": 1.0,
                      "train": train_summary, "serve": serve_summary,
                      "timeline": timeline_summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
