"""Sharding by policy: logical axis names + one rules table.

The reference threads a ``sharded_init`` helper through 120+ constructor call
sites, hard-coding a physical ``("data"/"batch", "model")`` mesh into every
module (ref `src/jimm/common/utils.py:14-25` and e.g.
`common/transformer.py:64-99`). Here modules annotate parameters with
*logical* axis names only; a single :class:`ShardingRules` table maps logical
axes to physical mesh axes. Switching between single-device, DP, TP, FSDP, or
FSDP+TP is a rules swap — no model code changes.

Logical axis vocabulary
-----------------------
========== ======================================================
``layers``  stacked-transformer-layer axis (scan over layers)
``embed``   model hidden dimension
``heads``   attention projection output dim (num_heads * head_dim)
``mlp``     MLP intermediate dimension
``vocab``   token-embedding vocabulary dim
``proj``    contrastive projection output dim
``classes`` classifier output dim
``patch``   conv patch spatial/in-channel dims (never sharded)
``batch``   activation batch dim
``seq``     activation sequence dim (context parallelism)
``pos``     positional-embedding sequence dim
========== ======================================================
"""

from __future__ import annotations

import dataclasses
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Callable, Sequence

import jax
import numpy as np
from flax import nnx
from flax.core import spmd as _core_spmd
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Parameters are annotated with logical names; we never want flax to eagerly
# reshard at creation time (we control placement explicitly).
nnx.use_eager_sharding(False)

MeshAxis = str | tuple[str, ...] | None


@dataclass(frozen=True)
class ShardingRules:
    """Logical → physical mesh-axis mapping."""

    layers: MeshAxis = None
    embed: MeshAxis = None
    heads: MeshAxis = None
    mlp: MeshAxis = None
    vocab: MeshAxis = None
    proj: MeshAxis = None
    classes: MeshAxis = None
    patch: MeshAxis = None
    batch: MeshAxis = None
    seq: MeshAxis = None
    pos: MeshAxis = None
    #: the leading axis of a sparse layer's stacked expert weights
    #: (`nn/moe.py`): no preset maps it, the exchange across chips is not built
    expert: MeshAxis = None

    def to_flax_rules(self) -> tuple[tuple[str, MeshAxis], ...]:
        return tuple((f.name, getattr(self, f.name))
                     for f in dataclasses.fields(self))

    def spec(self, *names: str | None) -> P:
        """PartitionSpec for a tuple of logical axis names."""
        return P(*(getattr(self, n) if n is not None else None for n in names))


# ---------------------------------------------------------------------------
# Presets
# ---------------------------------------------------------------------------

REPLICATED = ShardingRules()

#: Pure data parallelism: only activations are sharded.
DATA_PARALLEL = ShardingRules(batch="data")

#: Megatron-style tensor parallelism over a "model" axis: qkv/fc1 column-
#: parallel (output dim sharded), out-proj/fc2 row-parallel (input dim
#: sharded); XLA inserts the reduce at row-parallel outputs.
TENSOR_PARALLEL = ShardingRules(
    heads="model", mlp="model", vocab="model", proj="model",
    classes="model", batch="data")

#: FSDP/ZeRO-3: every parameter sharded over the data axis along its embed
#: dim; XLA all-gathers params per layer on use and reduce-scatters grads.
#: (vocab must stay None here — ("vocab", "embed") params would otherwise
#: map two dims onto the same mesh axis.)
FSDP = ShardingRules(embed="data", batch="data", mlp=None, heads=None)

#: 2-D FSDP ("data") x TP ("model") — the single-slice training layout.
FSDP_TP = ShardingRules(
    embed="data", heads="model", mlp="model", vocab="model", proj="model",
    classes="model", batch="data")

#: Multi-slice pod layout (BASELINE config #5, v5e-64 = 4 slices x 16):
#: FSDP over the intra-slice "data" axis, TP over the intra-slice "model"
#: axis, pure data parallelism over the cross-slice DCN "replica" axis —
#: parameters replicate across slices so only gradient all-reduce rides DCN.
HYBRID_FSDP_TP = ShardingRules(
    embed="data", heads="model", mlp="model", vocab="model", proj="model",
    classes="model", batch=("replica", "data"))

#: Context/sequence parallelism for long sequences (ring attention):
#: activations sharded over the sequence axis.
SEQUENCE_PARALLEL = ShardingRules(batch="data", seq="seq", pos="seq")

#: Long-context training at scale: FSDP/ZeRO-3 parameters over "data"
#: composed with sequence-sharded activations over "seq" (ring or ulysses
#: attention across it). The memory-critical pair — params AND the long
#: sequence both sharded.
FSDP_SP = ShardingRules(embed="data", batch="data", seq="seq", pos="seq",
                        mlp=None, heads=None)

#: Pipeline parallelism: the stacked ``layers`` axis sharded over "stage";
#: forward runs the microbatched ppermute loop
#: (`jimm_tpu/parallel/pipeline.py`, enabled by ``pipeline=True`` in the
#: encoder config). Composes with data parallelism over "data".
PIPELINE = ShardingRules(layers="stage", batch="data")

PRESET_RULES: dict[str, ShardingRules] = {
    "replicated": REPLICATED,
    "dp": DATA_PARALLEL,
    "tp": TENSOR_PARALLEL,
    "fsdp": FSDP,
    "fsdp_tp": FSDP_TP,
    "hybrid_fsdp_tp": HYBRID_FSDP_TP,
    "sp": SEQUENCE_PARALLEL,
    "fsdp_sp": FSDP_SP,
    "pp": PIPELINE,
}


# ---------------------------------------------------------------------------
# Context: ambient mesh + rules
# ---------------------------------------------------------------------------

@contextmanager
def use_sharding(mesh: Mesh | None, rules: ShardingRules | str | None = None):
    """Install ``mesh`` + ``rules`` as ambient context.

    Inside this context model code may call :func:`logical_constraint` and
    parameter initializers annotated via :func:`logical` resolve to physical
    ``PartitionSpec`` s through the rules table.
    """
    if isinstance(rules, str):
        rules = PRESET_RULES[rules]
    old_rules = _core_spmd.get_logical_axis_rules()
    if rules is not None:
        _core_spmd.set_logical_axis_rules(rules.to_flax_rules())
    try:
        if mesh is not None:
            with jax.set_mesh(mesh):
                yield
        else:
            yield
    finally:
        _core_spmd.set_logical_axis_rules(old_rules)


def current_rules() -> ShardingRules | None:
    flat = _core_spmd.get_logical_axis_rules()
    if not flat:
        return None
    return ShardingRules(**dict(flat))


def manual_axis_names(mesh: Any) -> frozenset[str]:
    """Axes of ``mesh`` in Manual mode, i.e. already mapped by an enclosing
    ``shard_map``."""
    manual = jax.sharding.AxisType.Manual
    return frozenset(n for n, t in zip(mesh.axis_names, mesh.axis_types)
                     if t == manual)


def logical(init: Callable, *names: str | None) -> Callable:
    """Annotate an initializer with logical axis names (sharding metadata)."""
    return nnx.with_partitioning(init, tuple(names))


def logical_constraint(x: jax.Array, *names: str | None) -> jax.Array:
    """Constrain an activation to the ambient rules; no-op without context.

    Inside ``shard_map`` the manual axes are filtered OUT of the spec (those
    dims are already local), but constraints on any still-auto axes of a
    partially-manual mesh (``shard_map(..., axis_names=...)`` subsets) are
    preserved rather than dropped wholesale."""
    rules = current_rules()
    mesh = jax.sharding.get_abstract_mesh()
    if rules is None or mesh.empty or not mesh.shape_tuple:
        return x
    spec = rules.spec(*names)
    manual = manual_axis_names(mesh)
    if manual:
        def keep(axis):
            axes = axis if isinstance(axis, tuple) else (axis,)
            kept = tuple(a for a in axes if a is not None and a not in manual)
            if not kept:
                return None
            return kept if len(kept) > 1 else kept[0]

        spec = P(*(keep(a) for a in spec))
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Applying sharding to models/state
# ---------------------------------------------------------------------------

def prune_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop sharding on dims the mesh can't divide evenly (e.g. a 7-class
    classifier head over a 2-way model axis) — replicate those dims instead."""
    out = []
    for dim, axis in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axis is None:
            out.append(None)
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        ways = int(np.prod([mesh.shape[a] for a in axes]))
        out.append(axis if dim % ways == 0 else None)
    return P(*out)


def partition_specs(state: Any) -> Any:
    """PartitionSpec pytree for an nnx state, resolving logical names through
    the ambient rules (falls back to raw names if no rules installed)."""
    return nnx.get_partition_spec(state)


def shard_model(model: nnx.Module, mesh: Mesh,
                rules: ShardingRules | str = REPLICATED) -> nnx.Module:
    """Eagerly ``device_put`` every parameter of an existing model onto
    ``mesh`` per ``rules``. Used for the reference-style ``Model(...,
    mesh=mesh)`` constructor contract."""
    if isinstance(rules, str):
        rules = PRESET_RULES[rules]
    with use_sharding(mesh, rules):
        state = nnx.state(model)
        specs = nnx.get_partition_spec(state)

        def put(leaf, spec):
            val = leaf.get_value() if isinstance(leaf, nnx.Variable) else leaf
            s = spec.get_value() if isinstance(spec, nnx.Variable) else spec
            if not isinstance(s, P):
                s = P()
            s = prune_spec(s, np.shape(val), mesh)
            return jax.device_put(val, NamedSharding(mesh, s))

        new_state = jax.tree.map(put, state, specs,
                                 is_leaf=lambda x: isinstance(x, nnx.Variable))
        nnx.update(model, new_state)
    return model


def sharded_copy(model: nnx.Module, mesh: Mesh,
                 rules: ShardingRules | str = REPLICATED) -> nnx.Module:
    """A *new* model whose parameters are ``device_put`` onto ``mesh`` per
    ``rules``, leaving ``model`` untouched. This is the replica primitive of
    multi-chip serving (``serve/topology.py``): one host-resident model fans
    out into N independent copies, each pinned to its own submesh, so the
    replicas can compute concurrently without sharing buffers."""
    if isinstance(rules, str):
        rules = PRESET_RULES[rules]
    graphdef, state = nnx.split(model)
    with use_sharding(mesh, rules):
        specs = nnx.get_partition_spec(state)

        def put(leaf, spec):
            val = leaf.get_value() if isinstance(leaf, nnx.Variable) else leaf
            s = spec.get_value() if isinstance(spec, nnx.Variable) else spec
            if not isinstance(s, P):
                s = P()
            s = prune_spec(s, np.shape(val), mesh)
            return jax.device_put(val, NamedSharding(mesh, s))

        new_state = jax.tree.map(put, state, specs,
                                 is_leaf=lambda x: isinstance(x, nnx.Variable))
    return nnx.merge(graphdef, new_state)


def create_sharded(ctor: Callable[[], nnx.Module], mesh: Mesh,
                   rules: ShardingRules | str = REPLICATED) -> nnx.Module:
    """Initialize a model with parameters *born sharded* (init runs under jit
    with sharding constraints, so no single-device materialization)."""
    if isinstance(rules, str):
        rules = PRESET_RULES[rules]

    @nnx.jit
    def _create():
        model = ctor()
        state = nnx.state(model)
        specs = nnx.get_partition_spec(state)

        def constrain(leaf, spec):
            val = leaf.get_value() if isinstance(leaf, nnx.Variable) else leaf
            s = spec.get_value() if isinstance(spec, nnx.Variable) else spec
            if not isinstance(s, P):
                s = P()
            s = prune_spec(s, np.shape(val), mesh)
            return jax.lax.with_sharding_constraint(val, s)

        state = jax.tree.map(constrain, state, specs,
                             is_leaf=lambda x: isinstance(x, nnx.Variable))
        nnx.update(model, state)
        return model

    with use_sharding(mesh, rules):
        return _create()


def shard_batch(batch: Any, mesh: Mesh,
                rules: ShardingRules | str = DATA_PARALLEL,
                names: Sequence[str | None] | None = None) -> Any:
    """Place a host batch onto the mesh, sharding the leading (batch) dim."""
    if isinstance(rules, str):
        rules = PRESET_RULES[rules]

    def put(x):
        x = np.asarray(x)
        spec_names = names if names is not None else (
            ["batch"] + [None] * (x.ndim - 1))
        return jax.device_put(x, NamedSharding(mesh, rules.spec(*spec_names)))

    return jax.tree.map(put, batch)
