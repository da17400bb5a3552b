"""Orbax-based sharded checkpoint save/restore — the reference is load-only
(SURVEY §5): no save path, no optimizer state, no resume.

Saves the full training state (model params + optimizer state + step) with
async, sharded orbax writes; restores onto the *current* mesh sharding (so a
run can resume on a different topology). HF-interoperable safetensors export
lives in `jimm_tpu/weights/export.py`.
"""

from __future__ import annotations

import os
import warnings
from pathlib import Path
from typing import Any

import numpy as np
import orbax.checkpoint as ocp
from flax import nnx



def _split_state(obj) -> Any:
    return nnx.state(obj)


def _storage_layout(model: nnx.Module) -> dict[str, Any] | None:
    """Fingerprint of any baked pipeline placement (`nn/transformer.py`
    pp_stages): layer rows are stored in circular schedule order, so a
    restore into a DIFFERENT placement would permute layers silently —
    shapes all match. Recorded at save, validated at restore."""
    cfg = getattr(model, "config", None)
    if cfg is None:
        return None
    layout: dict[str, Any] = {}
    for tower in ("vision", "text"):
        t = getattr(cfg, tower, None)
        if (t is not None and getattr(t, "pipeline", False)
                and t.pp_virtual > 1 and t.pp_stages):
            layout[tower] = {"pp_stages": t.pp_stages,
                             "pp_virtual": t.pp_virtual, "depth": t.depth}
    return layout or None


def _mesh_layout(mesh) -> dict[str, Any] | None:
    """JSON-able fingerprint of the mesh a state was saved under (axis
    sizes + device count). Orbax's ``StandardRestore`` already reshards
    every array onto the *target* state's shardings, so a mesh change needs
    no data movement here — the layout is recorded so restore can tell an
    elastic topology change apart from a same-shape resume and count it."""
    if mesh is None:
        return None
    return {"axes": {str(k): int(v) for k, v in dict(mesh.shape).items()},
            "n_devices": int(mesh.devices.size)}


def _relayout(state, saved: dict | None, current: dict | None):
    """Re-permute stacked layer rows from a checkpoint's baked pipeline
    placement to the target model's (either may be canonical=None). Applies
    to every leaf under a tower's ``blocks`` whose leading dim is the layer
    count — model params and mirrored optimizer moments alike."""
    from jimm_tpu.parallel.pipeline import circular_layer_order

    perms: dict[str, np.ndarray] = {}
    for tower in ("vision", "text"):
        s = (saved or {}).get(tower)
        c = (current or {}).get(tower)
        if s == c:
            continue
        if s and c and s["depth"] != c["depth"]:
            raise ValueError(f"{tower} depth changed between checkpoint "
                             f"({s['depth']}) and model ({c['depth']})")
        depth = (s or c)["depth"]

        def order(layout):
            if not layout:
                return np.arange(depth)
            return circular_layer_order(depth, layout["pp_stages"],
                                        layout["pp_virtual"])

        o_saved, o_cur = order(s), order(c)
        inv_saved = np.empty(depth, np.int64)
        inv_saved[o_saved] = np.arange(depth)
        perm = inv_saved[o_cur]  # saved-storage -> canonical -> cur-storage
        if not np.array_equal(perm, np.arange(depth)):
            perms[tower] = perm
    if not perms:
        return state

    out = []
    for path, leaf in nnx.to_flat_state(state):
        keys = tuple(str(k) for k in path)
        tower = next((t for t in perms if t in keys), None)
        if tower is not None and "blocks" in keys:
            perm = perms[tower]
            # get_value(): flax 0.12 deprecates .value access on Variables
            val = (leaf.get_value() if hasattr(leaf, "get_value")
                   else leaf)
            if getattr(val, "ndim", 0) >= 1 and val.shape[0] == len(perm):
                new = val[perm]
                if getattr(val, "sharding", None) is not None:
                    # the gather's output sharding is XLA's choice; pin it
                    # back so restore keeps its onto-current-sharding
                    # contract (stage-sharded pipelined params especially)
                    import jax
                    new = jax.device_put(new, val.sharding)
                leaf = leaf.replace(new) if hasattr(leaf, "replace") else new
        out.append((path, leaf))
    return nnx.from_flat_state(out)


def _pin_unannotated(state, mesh):
    """Leaves the model never annotated (optimizer scalars like the Adam
    step count) restore *committed to a single device*: orbax reshards
    onto the target's sharding, and an unannotated target array means
    SingleDeviceSharding. A later jit mixing them with mesh-committed
    params then refuses placement outright. Re-pin such leaves replicated
    over the live mesh — exactly where jit would have put them before the
    restore committed them."""
    if mesh is None:
        return state
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    rep = NamedSharding(mesh, PartitionSpec())
    out = []
    for path, leaf in nnx.to_flat_state(state):
        val = leaf.get_value() if hasattr(leaf, "get_value") else leaf
        sh = getattr(val, "sharding", None)
        if sh is not None and not isinstance(sh, NamedSharding):
            new = jax.device_put(val, rep)
            leaf = leaf.replace(new) if hasattr(leaf, "replace") else new
        out.append((path, leaf))
    return nnx.from_flat_state(out)


class CheckpointManager:
    """Thin nnx-aware wrapper over ``orbax.checkpoint.CheckpointManager``."""

    def __init__(self, directory: str | Path, *, max_to_keep: int = 3,
                 save_interval_steps: int = 1, mesh=None):
        self._dir = Path(directory).absolute()
        #: mesh the live model is sharded over (None = unsharded). Saves
        #: record its layout; restore compares it against the checkpoint's
        #: and counts a topology change when they differ (elastic restarts
        #: that lost or gained devices land here).
        self.mesh = mesh
        #: ``{"saved": ..., "current": ...}`` of the last restore that
        #: crossed a mesh change, else None
        self.last_topology_change: dict[str, Any] | None = None
        self._mgr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                save_interval_steps=save_interval_steps,
                create=True))
        # orbax's step scan ignores hidden dirs, so the marker and
        # quarantine sidecars can live inside the checkpoint root
        self._markers = self._dir / ".jimm_markers"
        #: steps whose async save was initiated but not yet known committed
        self._pending: list[int] = []
        #: user-supplied ``extra`` metadata of the last restored step
        #: (e.g. the grain data-iterator state) — populated by `restore`
        self.last_restored_extra: dict[str, Any] = {}

    @property
    def directory(self) -> Path:
        return self._dir

    def save(self, step: int, model: nnx.Module,
             optimizer: nnx.Optimizer | None = None, *,
             extra: dict[str, Any] | None = None, force: bool = False) -> bool:
        """Async-save model (+ optimizer) state at ``step``."""
        from jimm_tpu.obs import get_registry, span
        with span("checkpoint_save"):
            items: dict[str, Any] = {
                "model": ocp.args.StandardSave(nnx.state(model, nnx.Param))}
            if optimizer is not None:
                items["opt"] = ocp.args.StandardSave(
                    nnx.state(optimizer, nnx.optimizer.OptState))
            meta = dict(extra or {})
            layout = _storage_layout(model)
            if layout is not None:
                meta["_storage_layout"] = layout
            mesh_layout = _mesh_layout(self.mesh)
            if mesh_layout is not None:
                meta["_mesh_layout"] = mesh_layout
            if meta:
                items["extra"] = ocp.args.JsonSave(meta)
            saved = self._mgr.save(step, args=ocp.args.Composite(**items),
                                   force=force)
        if saved:
            # entering an actual save waits out the previous async write
            # (orbax serializes them), so every earlier pending step is
            # committed by now — the new step stays pending until the next
            # save/wait/close proves its own write finished
            self._flush_markers()
            self._pending.append(step)
            get_registry("jimm_train").counter("checkpoint_saves_total").inc()
        return saved

    # -- completion markers -------------------------------------------------
    # orbax's latest_step()/all_steps() scan bare step directories, so a
    # partially-written dir left by a mid-save kill looks identical to a
    # committed checkpoint and silently wins the "latest" race. A marker
    # file is dropped (atomic tmp + rename) only once a step's async write
    # is known finished; restore trusts markers, not directory listings.

    def _write_marker(self, step: int) -> None:
        self._markers.mkdir(exist_ok=True)
        tmp = self._markers / f".{step}.tmp"
        tmp.write_text("complete\n")
        os.replace(tmp, self._markers / str(step))

    def _flush_markers(self) -> None:
        if not self._pending:
            return
        for step in self._pending:
            self._write_marker(step)
        self._pending.clear()
        from jimm_tpu.resilience.supervisor import note_checkpoint_completed
        note_checkpoint_completed()

    def _marked_steps(self) -> set[int] | None:
        """Steps with a completion marker, or None when this checkpoint
        tree predates markers entirely (then orbax's listing is all we
        have, the historical behavior)."""
        if not self._markers.is_dir():
            return None
        marked = {int(p.name) for p in self._markers.iterdir()
                  if p.name.isdigit()}
        return marked or None

    def _steps_on_disk(self) -> set[int]:
        # a direct listing, not self._mgr.all_steps(): orbax caches its
        # step scan at manager creation, which would miss dirs that appear
        # or vanish (quarantine) while this process runs
        if not self._dir.is_dir():
            return set()
        return {int(p.name) for p in self._dir.iterdir()
                if p.is_dir() and p.name.isdigit()}

    def completed_steps(self) -> list[int]:
        """Ascending steps that are both on disk and marked complete."""
        existing = self._steps_on_disk()
        marked = self._marked_steps()
        if marked is None:
            return sorted(existing)
        return sorted(existing & marked)

    def quarantine_step(self, step: int, reason: str) -> Path | None:
        """Move a bad step directory into ``.quarantine/`` — never delete,
        so the bytes stay available for a post-mortem. Returns the new
        location, or None when the move lost a race."""
        from jimm_tpu.obs import get_registry
        src = self._dir / str(step)
        qdir = self._dir / ".quarantine"
        try:
            qdir.mkdir(exist_ok=True)
            dest = qdir / str(step)
            n = 0
            while dest.exists():
                n += 1
                dest = qdir / f"{step}-{n}"
            os.replace(src, dest)
            (dest / ".jimm_quarantine_reason.txt").write_text(reason + "\n")
        except OSError:
            return None
        (self._markers / str(step)).unlink(missing_ok=True)
        get_registry("jimm_train").counter(
            "checkpoint_quarantined_total").inc()
        from jimm_tpu.obs.journal import get_journal
        get_journal().emit("checkpoint_quarantined", step=step,
                           reason=reason, dest=str(dest))
        self._mgr.reload()  # drop the manager's cached view of the tree
        return dest

    def _sweep_partial_dirs(self, *, newer_than: int) -> None:
        """Quarantine unmarked step dirs newer than the newest completed
        step — the torso a mid-save kill leaves behind — so orbax's own
        step scan can never resurrect them."""
        marked = self._marked_steps()
        if marked is None:
            return
        for step in self._steps_on_disk():
            if (step > newer_than and step not in marked
                    and step not in self._pending):
                self.quarantine_step(
                    step, "partial write (no completion marker)")

    def restore(self, model: nnx.Module,
                optimizer: nnx.Optimizer | None = None,
                *, step: int | None = None) -> int:
        """Restore in place (onto each param's current sharding); returns the
        restored step.

        With ``step=None`` the newest *completed* checkpoint is used —
        partial step dirs (no completion marker) are swept aside, and a
        step whose restore fails (corrupted bytes) is quarantined, never
        deleted, before falling back to the previous good step. An explicit
        ``step`` restores exactly that step and propagates its errors.

        Baked pipeline placement (`nn/transformer.py` pp_stages) stores
        layer rows in circular schedule order. When the checkpoint's layout
        differs from the model's, the stacked layer arrays are re-permuted
        through canonical order (saved-storage -> canonical -> current-
        storage), so a pipelined run can be evaluated or fine-tuned with any
        other placement — including none."""
        if step is not None:
            return self._restore_step(step, model, optimizer)
        candidates = self.completed_steps()
        if not candidates:
            raise FileNotFoundError("no checkpoint found")
        self._sweep_partial_dirs(newer_than=candidates[-1])
        for cand in reversed(candidates):
            try:
                return self._restore_step(cand, model, optimizer)
            except Exception as e:
                dest = self.quarantine_step(
                    cand, f"restore failed: {type(e).__name__}: {e}")
                warnings.warn(
                    f"checkpoint step {cand} failed to restore "
                    f"({type(e).__name__}: {e}); quarantined to {dest}, "
                    f"falling back to the previous good step",
                    RuntimeWarning, stacklevel=2)
        raise FileNotFoundError(
            f"no restorable checkpoint: all {len(candidates)} candidate "
            f"step(s) failed and were quarantined")

    def _restore_step(self, step: int, model: nnx.Module,
                      optimizer: nnx.Optimizer | None = None) -> int:
        from jimm_tpu.obs import get_registry, span
        from jimm_tpu.obs.journal import get_journal
        get_registry("jimm_train").counter("checkpoint_restores_total").inc()
        # inherits the ambient incident cid when the supervisor is
        # restarting around a failure — the restore joins that chain
        get_journal().emit("checkpoint_restored", step=step)
        with span("checkpoint_restore"):
            model_state = nnx.state(model, nnx.Param)
            items: dict[str, Any] = {
                "model": ocp.args.StandardRestore(model_state)}
            if optimizer is not None:
                items["opt"] = ocp.args.StandardRestore(
                    nnx.state(optimizer, nnx.optimizer.OptState))
            # probe for the optional extra/ item by its committed directory
            # (the manager uses default step naming) instead of
            # catch-and-retry: a corrupt/unreadable extra must FAIL the
            # restore, not silently skip the placement guard below, and a
            # genuine model-state error must not trigger a pointless second
            # multi-GB restore attempt
            has_extra = (self._mgr.directory / str(step) / "extra").exists()
            if has_extra:
                items["extra"] = ocp.args.JsonRestore()
            restored = self._mgr.restore(step,
                                         args=ocp.args.Composite(**items))
            saved_meta = (restored.get("extra") or {}) if has_extra else {}
            self.last_restored_extra = {
                k: v for k, v in saved_meta.items()
                if k not in ("_storage_layout", "_mesh_layout")}
            self._note_mesh_change(step, saved_meta.get("_mesh_layout"))
            saved = saved_meta.get("_storage_layout")
            current = _storage_layout(model)
            model_state = restored["model"]
            opt_state = restored.get("opt")
            if saved != current:
                model_state = _relayout(model_state, saved, current)
                if opt_state is not None:
                    # optimizer moments live under opt.model mirroring the
                    # param tree; same stacked rows, same re-permutation
                    opt_state = _relayout(opt_state, saved, current)
            model_state = _pin_unannotated(model_state, self.mesh)
            if opt_state is not None:
                opt_state = _pin_unannotated(opt_state, self.mesh)
            nnx.update(model, model_state)
            if optimizer is not None:
                nnx.update(optimizer, opt_state)
        return step

    def _note_mesh_change(self, step: int, saved: dict | None) -> None:
        """Detect restore-onto-a-different-mesh (elastic shrink/grow).

        The actual resharding is free: ``StandardRestore`` targets the live
        model's NamedShardings, so the arrays land distributed over
        whatever mesh the model was rebuilt on. What a topology change
        still needs is to be *visible* — the counter is what drills and
        dashboards assert on."""
        current = _mesh_layout(self.mesh)
        if saved is None or current is None or saved == current:
            return
        self.last_topology_change = {"step": step, "saved": saved,
                                     "current": current}
        from jimm_tpu.obs import get_registry
        from jimm_tpu.obs.journal import get_journal
        get_registry("jimm_train").counter(
            "checkpoint_topology_changes_total").inc()
        get_journal().emit("mesh_resharded", step=step, saved=saved,
                           current=current)
        print(  # jaxlint: disable=JL007 — one-shot operator narration of an elastic restore, mirrors the supervisor's restart lines
            f"[checkpoint] step {step} saved on mesh {saved['axes']} "
            f"({saved['n_devices']} devices), restored onto "
            f"{current['axes']} ({current['n_devices']} devices) — "
            f"resharded onto the current topology")

    def latest_step(self) -> int | None:
        """Newest *completed* step (marker-verified) — unlike raw orbax,
        a partially-written step directory can never be "latest"."""
        steps = self.completed_steps()
        return steps[-1] if steps else None

    def wait(self) -> None:
        self._mgr.wait_until_finished()
        self._flush_markers()

    def close(self) -> None:
        self._mgr.close()  # waits out in-flight async saves
        self._flush_markers()
