"""Device-mesh construction for single-host, multi-host slice, and
multi-slice (ICI x DCN hybrid) topologies.

The reference builds only a trivial single-host mesh
(`examples/vit_training.py:180-183`). TPU pods need: ICI-contiguous axes for
tensor/FSDP sharding inside a slice and a DCN axis for data parallelism
across slices. `jax.experimental.mesh_utils` computes ICI-friendly device
orders; we wrap it with a named-axis dict API.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Mapping

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh


#: Canonical physical mesh-axis names. Every ``Mesh`` built in this package
#: and every ``PartitionSpec`` in library/test code draws from this
#: vocabulary — ``jimm_tpu.lint`` rule JL004 flags any other axis string as a
#: probable typo (a misspelled axis silently shards nothing).
#: ``tests/test_lint.py`` asserts the linter's copy stays in sync.
MESH_AXES: tuple[str, ...] = ("data", "model", "replica", "seq", "stage")


def make_mesh(axes: Mapping[str, int] | None = None,
              devices: list | None = None) -> Mesh:
    """Build a mesh from ``{"axis": size}``; ``-1`` means "all remaining
    devices". Axis order follows dict order (outermost first) — put the
    slowest-varying (DCN/data) axis first, ICI-heavy (model) axes last, which
    keeps model-axis collectives on ICI neighbours.
    """
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if axes is None:
        axes = {"data": n}
    axes = OrderedDict(axes)
    sizes = list(axes.values())
    if sizes.count(-1) > 1:
        raise ValueError("at most one axis may be -1")
    known = int(np.prod([s for s in sizes if s != -1]))
    if -1 in sizes:
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[sizes.index(-1)] = n // known
    if int(np.prod(sizes)) != n:
        raise ValueError(f"mesh {dict(zip(axes, sizes))} != {n} devices")
    try:
        dev_array = mesh_utils.create_device_mesh(sizes, devices=devices)
    except (ValueError, AssertionError):  # non-TPU or odd topology
        dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, tuple(axes.keys()))


def make_hybrid_mesh(ici: Mapping[str, int], dcn: Mapping[str, int],
                     devices: list | None = None) -> Mesh:
    """Multi-slice mesh: ``dcn`` axes span slices (data-parallel over DCN),
    ``ici`` axes live inside a slice. E.g. v5e-64 = 4 slices of 16:
    ``make_hybrid_mesh(ici={"data": 4, "model": 4}, dcn={"replica": 4})``.

    ``devices`` defaults to ``jax.devices()``; they must carry a
    ``slice_index`` attribute (real multi-slice TPUs do; tests pass mocks).
    """
    ici = OrderedDict(ici)
    dcn = OrderedDict(dcn)
    # create_hybrid_device_mesh multiplies same-rank shapes elementwise, so
    # pad each side with 1s to keep dcn and ici axes distinct and named.
    mesh_shape = (1,) * len(dcn) + tuple(ici.values())
    dcn_shape = tuple(dcn.values()) + (1,) * len(ici)
    dev_array = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=mesh_shape, dcn_mesh_shape=dcn_shape,
        devices=devices if devices is not None else jax.devices())
    return Mesh(dev_array, tuple(dcn.keys()) + tuple(ici.keys()))


#: Named pod topologies for the BASELINE.json tracked configs: mesh recipe +
#: sharding-rules preset + the ring axis for the sigmoid loss. "hybrid"
#: entries build a DCN x ICI mesh (multi-slice); others a single-slice mesh.
TOPOLOGIES: dict[str, dict] = {
    # BASELINE config #3: ViT-L/16-384 fine-tune, FSDP over one v5e-16 slice
    "v5e-16-fsdp": {"axes": {"data": 16}, "rules": "fsdp",
                    "ring_axis": "data"},
    # BASELINE config #4: SigLIP-B/16-256 ring-loss training on one slice
    "v5e-16-dp": {"axes": {"data": 16}, "rules": "dp", "ring_axis": "data"},
    # BASELINE config #5: SigLIP2-L/16-512 pod-scale — 4 slices of 16 chips,
    # FSDP(data) x TP(model) inside each slice, pure DP across DCN
    "v5e-64-fsdp-tp": {"ici": {"data": 4, "model": 4},
                       "dcn": {"replica": 4}, "rules": "hybrid_fsdp_tp",
                       "ring_axis": ("replica", "data")},
}


def make_topology(name: str, devices: list | None = None):
    """Build ``(mesh, rules_name, ring_axis)`` for a named pod topology."""
    spec = TOPOLOGIES[name]
    if "ici" in spec:
        mesh = make_hybrid_mesh(spec["ici"], spec["dcn"], devices=devices)
    else:
        mesh = make_mesh(spec["axes"], devices=devices)
    return mesh, spec["rules"], spec["ring_axis"]


def initialize_distributed(coordinator_address: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None) -> None:
    """Multi-host bootstrap. On Cloud TPU the arguments are auto-detected from
    the metadata server; pass them explicitly elsewhere. Safe to call twice.

    Arguments left ``None`` fall back to the ``JIMM_COORDINATOR`` /
    ``JIMM_NUM_PROCESSES`` / ``JIMM_PROCESS_ID`` env vars that
    ``python -m jimm_tpu.launch`` exports into its children, so a launched
    worker bootstraps with a bare ``initialize_distributed()`` (platform
    overrides from ``JIMM_PLATFORM``/``JIMM_HOST_DEVICES`` are applied
    first — they must land before the backend initializes).

    Errors are surfaced, not swallowed: when the caller passed explicit
    coordinator arguments a failure means a real multi-host misconfiguration,
    and degrading to single-process would train silently wrong. Only the
    argument-free auto-detect path downgrades to a warning (it legitimately
    fails on non-pod environments).
    """
    import os

    from jimm_tpu.utils.env import configure_platform
    configure_platform()
    if coordinator_address is None:
        coordinator_address = os.environ.get("JIMM_COORDINATOR")
    if num_processes is None and os.environ.get("JIMM_NUM_PROCESSES"):
        num_processes = int(os.environ["JIMM_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JIMM_PROCESS_ID"):
        process_id = int(os.environ["JIMM_PROCESS_ID"])
    # NB: no jax.process_count() pre-check — that call would itself
    # initialize the XLA backend, after which jax.distributed.initialize
    # hard-errors ("must be called before any JAX calls"); is_initialized()
    # answers without touching the backend (found by
    # tests/test_distributed.py's real two-process cluster).
    if jax.distributed.is_initialized():
        return
    explicit = (coordinator_address is not None or num_processes is not None
                or process_id is not None)
    try:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
    except (RuntimeError, ValueError) as e:
        # jax phrases double-init as "should only be called once"
        msg = str(e).lower()
        if "already" in msg or "only be called once" in msg:
            return
        if explicit:
            raise
        import warnings
        warnings.warn(f"jax.distributed.initialize auto-detect failed "
                      f"({e}); continuing single-process", RuntimeWarning)


def resolve_mesh_axis(mesh, axis_name: str) -> dict:
    """Validate that ``axis_name`` exists on ``mesh`` (or, when ``mesh`` is
    None, on the ambient mesh installed by ``use_sharding``/``jax.set_mesh``)
    and return the mesh shape dict. Shared by the sequence-parallel
    attention schemes (`ring_attention`, `ulysses_attention`)."""
    if mesh is None:
        ambient = jax.sharding.get_abstract_mesh()
        if ambient.empty:
            raise ValueError("no mesh given and no ambient mesh installed "
                             "(use use_sharding(mesh, ...))")
        if axis_name not in ambient.shape:
            raise ValueError(f"ambient mesh {dict(ambient.shape)} has no "
                             f"{axis_name!r} axis")
        return dict(ambient.shape)
    if axis_name not in mesh.shape:
        raise ValueError(f"mesh {dict(mesh.shape)} has no {axis_name!r} axis")
    return dict(mesh.shape)
