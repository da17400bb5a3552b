"""Serialize/deserialize compiled serve forwards via ``jax.export``, and
wire the JAX persistent compilation cache for train steps.

What an artifact holds: the StableHLO module ``jax.export`` produces for
``model.<method>`` traced at one (bucket, *item_shape) input — with the
model's *parameters as call arguments*, not baked-in constants. Loading an
artifact therefore skips the expensive half of cold start (Python trace +
jaxpr lowering of the whole model) and works for any checkpoint of the
same architecture; the live model supplies the parameter leaves at call
time. Exotic-dtype state leaves (PRNG keys — not serializable as call
arguments by the export flatbuffer schema) are closed over as trace-time
constants instead; they are bytes-tiny and inert in eval forwards.

The second lever is the XLA-level persistent compilation cache
(:func:`enable_persistent_cache`): with it, even the backend compile of a
deserialized module is a disk hit on restart. The two compose — artifact
store above (trace+lower), jax cache below (XLA optimize+codegen).
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Callable

__all__ = ["enable_persistent_cache", "load_serve_forward",
           "serialize_serve_forward"]


def _partition_state(model):
    """Split a live nnx model into (merge recipe, plain array leaves).

    Returns ``(rebuild, arg_leaves, arg_specs)`` where ``rebuild(leaves)``
    reconstitutes the module inside a trace, ``arg_leaves`` are the
    plain-dtype state arrays (exported as call arguments, in deterministic
    tree-flatten order), and extended-dtype leaves (PRNG keys) are captured
    by ``rebuild`` as constants.
    """
    import jax
    from flax import nnx

    graphdef, state = nnx.split(model)
    leaves, treedef = jax.tree.flatten(state)

    def _plain(leaf) -> bool:
        try:
            return not jax.dtypes.issubdtype(leaf.dtype, jax.dtypes.extended)
        except (TypeError, AttributeError):
            return True

    arg_idx = [i for i, leaf in enumerate(leaves) if _plain(leaf)]
    consts = {i: leaf for i, leaf in enumerate(leaves) if not _plain(leaf)}
    arg_leaves = [leaves[i] for i in arg_idx]
    arg_specs = [jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                      sharding=_named_sharding(leaf))
                 for leaf in arg_leaves]

    def rebuild(current_arg_leaves):
        merged = dict(zip(arg_idx, current_arg_leaves))
        merged.update(consts)
        ordered = [merged[i] for i in range(len(leaves))]
        return nnx.merge(graphdef, jax.tree.unflatten(treedef, ordered))

    return rebuild, arg_leaves, arg_specs


def _named_sharding(leaf):
    """The leaf's ``NamedSharding``, or None for single-device placements.

    Sharded-model exports must record the parameter layout: the StableHLO
    then carries logical HloShardings, so a program exported from one
    replica's submesh deserializes onto any same-shape submesh (the outer
    jit recompiles XLA for the actual devices; only the mesh *shape* is
    pinned, which the AOT key already fingerprints). Single-device leaves
    export unsharded, byte-identical to the pre-topology artifacts."""
    from jax.sharding import NamedSharding
    sharding = getattr(leaf, "sharding", None)
    return sharding if isinstance(sharding, NamedSharding) else None


def serialize_serve_forward(model, method: str, batch: int,
                            item_shape: tuple[int, ...],
                            in_dtype: Any,
                            x_sharding: Any = None) -> bytes:
    """Trace + export ``model.<method>`` at one padded-bucket shape and
    return the serialized artifact bytes. This is the expensive call the
    store exists to amortize — it runs once per (architecture, bucket) in
    ``aot warmup`` or on a write-through miss, never on the request path.

    Parameter shardings are read off the live model's leaves (a sharded
    replica model exports a sharded program); ``x_sharding`` optionally
    pins the batch input's ``NamedSharding`` to match the engine's single
    sharded ``device_put`` per micro-batch."""
    import jax
    from jax import export as jax_export

    rebuild, _arg_leaves, arg_specs = _partition_state(model)

    def fwd(param_leaves, x):
        return getattr(rebuild(param_leaves), method)(x)

    x_spec = jax.ShapeDtypeStruct((int(batch), *item_shape), in_dtype,
                                  sharding=x_sharding)
    exported = jax_export.export(jax.jit(fwd))(arg_specs, x_spec)
    return exported.serialize()


def load_serve_forward(payload: bytes, model,
                       method: str) -> Callable[[Any], Any]:
    """Deserialize an artifact against a live model; returns a callable
    over one padded batch. Raises on any incompatibility (arity/shape/dtype
    drift, calling-convention version skew) — the caller treats that as a
    fallback-to-fresh-compile signal, so a wrong program can never serve.

    The returned callable never re-traces the model's Python: the jit wraps
    ``Exported.call`` (a single StableHLO invocation), so the engine's
    compile-count gauge stays at zero on a fully warm store.
    """
    import jax
    from jax import export as jax_export

    exported = jax_export.deserialize(bytearray(payload))
    rebuild, arg_leaves, arg_specs = _partition_state(model)
    n_expected = len(arg_specs) + 1
    flat_avals = jax.tree.flatten(exported.in_avals)[0] \
        if hasattr(exported, "in_avals") else []
    if flat_avals and len(flat_avals) != len(arg_specs) + 1:
        raise ValueError(
            f"artifact expects {len(flat_avals)} input leaves, live model "
            f"provides {n_expected} — architecture drift")
    call = jax.jit(exported.call)
    # params go up front once; device-resident leaves are passed by
    # reference each call (no copy)
    params = list(arg_leaves)

    def forward(x):
        return call(params, x)

    return forward


#: where the cache lives when the environment does not say: a fixed path
#: inside the checkout (the path is part of the cache key, so a directory
#: that moves never hits)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_persistent_cache(cache_dir: str | os.PathLike | None = None) -> str:
    """Turn on jax's persistent compilation cache so repeat XLA compiles
    (train steps across restarts, deserialized serve modules) are disk hits,
    and return the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` wins: when it is set, jax has already read
    it and no directory is set here. Otherwise the cache goes to
    ``cache_dir``, or to :data:`DEFAULT_CACHE_DIR`. Thresholds drop to zero:
    on the cold-start path even a sub-second compile is worth persisting.
    This is the one place that sets ``jax_compilation_cache_dir``."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    chosen = str(cache_dir or DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", chosen)
    return chosen
