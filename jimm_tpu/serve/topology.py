"""Multi-chip serving topology: replica groups of (data=1, model=k[, seq=s])
submeshes.

One host holds N visible devices; the serving engine wants R independent
*replicas* (inter-request parallelism — each replica computes a whole
micro-batch) that are each k-way *model-parallel* (intra-request parallelism
— one forward's matmuls sharded Megatron-style over k chips). The planner
here partitions the device list into R contiguous groups of k and builds one
``Mesh`` with axes ``("data", "model")`` = ``(1, k)`` per group; the forwards
built from the plan carry ``NamedSharding`` annotations from
:mod:`jimm_tpu.parallel.sharding` on both parameters (``sharded_copy`` with
the ``tp`` rules) and batches (a single sharded ``device_put`` per
micro-batch — never per-leaf transfers).

The degenerate ``replicas=1, model_parallel=1`` plan is *trivial*: callers
must take today's single-device path (plain jitted forward, no mesh, no
device_put) so single-chip serving stays byte-identical. ``plan_topology``
rejects infeasible splits (``R * k > n_devices``) with an error that names
the fix.

Plans are **revisable at runtime**: :meth:`TopologyPlan.revise` derives a
new plan (grow, shrink, or re-partition around a lost group) and
``build_replica_forwards`` over it produces the forward list that
``InferenceEngine.replan`` swaps in live — queued requests ride through,
and a warm AOT store makes the rebuild trace-free. The boot-time plan is
just the first revision.

FastUSP (PAPERS.md) motivates exactly this two-level split — replication for
throughput, tensor parallelism for per-request latency on towers too big for
one chip.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import numpy as np

__all__ = ["ReplicaForward", "TopologyPlan", "build_replica_forwards",
           "plan_topology"]


@dataclasses.dataclass(frozen=True)
class TopologyPlan:
    """The outcome of partitioning ``n_devices`` into replica groups.

    ``device_groups`` holds the concrete device objects, one tuple of
    ``model_parallel`` devices per replica, in ``jax.devices()`` order
    (contiguous groups — on TPU, neighbouring devices share ICI links, so
    the model-axis collectives stay on-slice). Devices beyond
    ``replicas * model_parallel`` are left unused (reported, not silently
    dropped).
    """

    replicas: int
    model_parallel: int
    n_devices: int
    device_groups: tuple[tuple, ...]
    seq_parallel: int = 1

    @property
    def is_trivial(self) -> bool:
        """True for the 1x1x1 plan: callers must use the single-device serve
        path (no mesh, no sharded transfers) — byte-compatible with a serve
        stack that never imported this module."""
        return (self.replicas == 1 and self.model_parallel == 1
                and self.seq_parallel == 1)

    @property
    def devices_used(self) -> int:
        return self.replicas * self.model_parallel * self.seq_parallel

    def meshes(self) -> list:
        """One ``(data=1, model=k[, seq=s])`` mesh per replica group. The
        ``seq`` axis only exists when ``seq_parallel > 1`` so degenerate
        plans build exactly today's two-axis meshes (same shape_tuple, same
        AOT fingerprints)."""
        from jimm_tpu.parallel.mesh import make_mesh
        axes = {"data": 1, "model": self.model_parallel}
        if self.seq_parallel > 1:
            axes["seq"] = self.seq_parallel
        return [make_mesh(dict(axes), devices=list(group))
                for group in self.device_groups]

    def describe(self) -> dict:
        """Flat JSON-able summary for ready lines and healthz."""
        return {"n_devices": self.n_devices, "replicas": self.replicas,
                "model_parallel": self.model_parallel,
                "seq_parallel": self.seq_parallel,
                "devices_used": self.devices_used,
                "devices_unused": self.n_devices - self.devices_used}

    def revise(self, *, replicas: int | None = None,
               model_parallel: int | None = None,
               seq_parallel: int | None = None,
               devices: Sequence | None = None) -> "TopologyPlan":
        """Derive a runtime revision of this plan: same partitioning rules,
        new shape and/or device set. Unspecified dimensions keep their
        current values; ``devices=None`` re-plans over this plan's own
        device list (flattened groups plus any unused tail is NOT
        recoverable here — pass the surviving ``jax.devices()`` subset
        explicitly when healing around lost hardware). Feed the result to
        :func:`build_replica_forwards` and then
        ``InferenceEngine.replan`` to apply it live."""
        if devices is None:
            devices = [d for group in self.device_groups for d in group]
        return plan_topology(
            self.replicas if replicas is None else replicas,
            self.model_parallel if model_parallel is None else model_parallel,
            self.seq_parallel if seq_parallel is None else seq_parallel,
            devices=devices)


def _feasible_splits(n: int, limit: int = 16) -> str:
    """Every (data, model, seq) factorization of ``n`` — the menu an
    operator picks from when their requested split doesn't fit."""
    triples = [(r, m, (n // r) // m)
               for r in range(1, n + 1) if n % r == 0
               for m in range(1, n // r + 1) if (n // r) % m == 0]
    shown = ", ".join(f"data={r} model={m} seq={s}" for r, m, s in
                      triples[:limit])
    extra = len(triples) - limit
    return shown + (f", ... ({extra} more)" if extra > 0 else "")


def plan_topology(replicas: int | None = None,
                  model_parallel: int | None = None,
                  seq_parallel: int | None = None,
                  devices: Sequence | None = None) -> TopologyPlan:
    """Partition the visible devices into ``replicas`` groups of
    ``model_parallel * seq_parallel``.

    Defaults are conservative: ``replicas=1, model_parallel=1,
    seq_parallel=1`` (the trivial single-device plan) — scaling out is an
    explicit operator choice via ``--replicas``/``--model-parallel``/
    ``--seq-parallel``. Raises ``ValueError`` when the split does not fit
    the device count, naming both sides of the inequality AND enumerating
    every feasible (data, model, seq) factorization of the visible count,
    so the error is actionable from a launch log.
    """
    if devices is None:
        import jax
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    replicas = 1 if replicas is None else int(replicas)
    model_parallel = 1 if model_parallel is None else int(model_parallel)
    seq_parallel = 1 if seq_parallel is None else int(seq_parallel)
    if replicas < 1 or model_parallel < 1 or seq_parallel < 1:
        raise ValueError(
            f"replicas ({replicas}), model_parallel ({model_parallel}) and "
            f"seq_parallel ({seq_parallel}) must all be >= 1")
    need = replicas * model_parallel * seq_parallel
    if need > n:
        raise ValueError(
            f"topology needs replicas * model_parallel * seq_parallel = "
            f"{replicas} * {model_parallel} * {seq_parallel} = {need} "
            f"devices but only {n} are visible; feasible splits for {n} "
            f"device(s): {_feasible_splits(n)}. Lower "
            f"--replicas/--model-parallel/--seq-parallel or raise the "
            f"device count (e.g. XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} on CPU)")
    group_size = model_parallel * seq_parallel
    groups = tuple(tuple(devices[i * group_size:(i + 1) * group_size])
                   for i in range(replicas))
    return TopologyPlan(replicas=replicas, model_parallel=model_parallel,
                        seq_parallel=seq_parallel, n_devices=n,
                        device_groups=groups)


class ReplicaForward:
    """One replica's warm forward: a single sharded ``device_put`` of the
    padded batch onto the replica's mesh, then the replica-local compiled
    forward (plain counting jit or a store-backed
    :class:`~jimm_tpu.aot.warmup.AotForward`).

    The batch transfer is ONE ``jax.device_put`` of the whole padded array
    with a ``NamedSharding`` — the input lands committed to the replica's
    devices, so the compiled program never sees a host fallback transfer
    and never migrates buffers between replicas.

    With ``rules`` set (seq-parallel plans), every trace — warmup AND the
    serving call — runs under ``use_sharding(mesh, rules)`` so the
    attention dispatch sees the live ``seq`` axis and routes to the
    sequence-parallel schemes; ``rules=None`` plans trace exactly as
    before (byte-identical degenerate collapse).
    """

    def __init__(self, inner: Callable, mesh, batch_sharding, rules=None):
        self._inner = inner
        self.mesh = mesh
        self.batch_sharding = batch_sharding
        self._rules = rules

    def _ctx(self):
        import contextlib
        if self._rules is None:
            return contextlib.nullcontext()
        from jimm_tpu.parallel.sharding import use_sharding
        return use_sharding(self.mesh, self._rules)

    def prepare_bucket(self, bucket: int) -> str:
        """Delegate AOT warm-start to the wrapped forward (engine warmup
        calls this per bucket); plain jitted inners report "compile"."""
        prepare = getattr(self._inner, "prepare_bucket", None)
        if prepare is None:
            return "compile"
        with self._ctx():
            return prepare(bucket)

    @property
    def trace_count(self) -> Callable[[], int] | None:
        return getattr(self._inner, "trace_count", None)

    def __call__(self, padded):
        import jax
        with self._ctx():
            x = jax.device_put(np.asarray(padded), self.batch_sharding)
            return self._inner(x)


def build_replica_forwards(model, plan: TopologyPlan, *, method: str,
                           item_shape: tuple[int, ...],
                           in_dtype: Any = np.float32, store=None,
                           label: str = ""
                           ) -> tuple[list[ReplicaForward],
                                      Callable[[], int]]:
    """Materialize the plan: one sharded model copy + warm forward per
    replica group.

    Each replica gets an independent parameter copy placed on its submesh
    via :func:`~jimm_tpu.parallel.sharding.sharded_copy` with the ``tp``
    (Megatron tensor-parallel) rules — on a ``model=1`` submesh that
    degenerates to whole-params-on-one-chip, which is exactly replicated
    serving. With ``store`` set, every replica forward is an
    :class:`~jimm_tpu.aot.warmup.AotForward` keyed on the replica mesh (all
    replicas share one fingerprint — same shapes, same mesh shape — so one
    write-through warms every replica and the next restart).

    Returns ``(forwards, trace_count)`` where ``trace_count`` sums fresh
    traces across replicas: the number the engine exports as
    ``compile_count`` and the zero-recompiles-after-warmup checks read.
    """
    import dataclasses as _dc

    from jax.sharding import NamedSharding

    from jimm_tpu.parallel.sharding import TENSOR_PARALLEL, sharded_copy

    # seq-parallel plans compose TP params with seq-sharded activations;
    # degenerate plans keep the plain TP rules and trace with no ambient
    # context at all — byte-identical to the pre-seq serve stack.
    seq_rules = None
    if plan.seq_parallel > 1:
        seq_rules = _dc.replace(TENSOR_PARALLEL, seq="seq", pos="seq")
    param_rules = TENSOR_PARALLEL if seq_rules is None else seq_rules
    batch_spec = TENSOR_PARALLEL.spec(
        "batch", *([None] * len(tuple(item_shape))))
    forwards: list[ReplicaForward] = []
    counters: list[Callable[[], int]] = []
    for mesh in plan.meshes():
        replica_model = sharded_copy(model, mesh, param_rules)
        batch_sharding = NamedSharding(mesh, batch_spec)
        if store is not None:
            from jimm_tpu.aot.warmup import AotForward
            inner = AotForward(replica_model, method=method,
                               item_shape=item_shape, in_dtype=in_dtype,
                               store=store, label=label, mesh=mesh,
                               in_sharding=batch_sharding)
            counters.append(inner.trace_count)
        else:
            from jimm_tpu.serve.engine import counting_forward
            inner, traces = counting_forward(replica_model, method)
            counters.append(traces)
        forwards.append(ReplicaForward(inner, mesh, batch_sharding,
                                       rules=seq_rules))
    return forwards, lambda: sum(c() for c in counters)
