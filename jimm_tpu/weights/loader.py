"""Declarative checkpoint-mapping engine.

Replaces the reference's three ~200-line imperative mapping loops
(ref `models/vit.py:185-269`, `clip.py:267-414`, `siglip.py:224-383`) with a
table of :class:`M` entries applied by one engine that:

- stacks per-layer HF tensors into the scanned ``(layers, ...)`` params,
- applies transpose/reshape transforms (:class:`T`),
- places every tensor with ``jax.device_put`` onto the *existing* sharding of
  the target parameter (params stay born-sharded, ref `models/vit.py:254`),
- enforces the reference's strict verification: every model parameter
  assigned exactly once, every checkpoint tensor consumed, with
  ``position_ids`` buffers the only tolerated leftovers
  (ref `models/vit.py:259-268`, SURVEY Appendix A.13-14).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import numpy as np
from flax import nnx



class Transform:
    """An invertible tensor transform: ``fwd`` maps HF torch layout to
    jimm_tpu layout, ``inv`` maps back (used by the HF exporter)."""

    def __init__(self, fwd: Callable[[np.ndarray], np.ndarray],
                 inv: Callable[[np.ndarray], np.ndarray]):
        self.fwd = fwd
        self.inv = inv

    def __call__(self, w: np.ndarray) -> np.ndarray:
        return self.fwd(w)


class Chunk(Transform):
    """Take the idx-th of n equal chunks along axis 0 — used for torch's
    fused MAP-head ``in_proj_weight`` (ref `siglip.py:352-363`). The exporter
    re-fuses all n chunks of the same src key."""

    def __init__(self, n: int, idx: int, then: Transform | None = None):
        self.n = n
        self.idx = idx
        self.then = then
        super().__init__(self._fwd, self._inv)

    def _fwd(self, w: np.ndarray) -> np.ndarray:
        part = np.split(w, self.n, axis=0)[self.idx]
        return self.then(part) if self.then else part

    def _inv(self, w: np.ndarray) -> np.ndarray:
        """Inverse of the per-chunk path only; fusing happens in the
        exporter."""
        return self.then.inv(w) if self.then else w


def _patch_linear_to_hwio(w: np.ndarray) -> np.ndarray:
    """SigLIP2's NaFlex Linear patch embedding ``(out, p*p*C)`` -> flax conv
    kernel HWIO. The flattened input ordering is (patch_row, patch_col,
    channel) — transformers' ``convert_image_to_patches`` reshapes
    ``(gh, p, gw, p, C)`` then transposes ``(0, 2, 1, 3, 4)``."""
    out, flat = w.shape
    p = int(round((flat // 3) ** 0.5))
    if p * p * 3 != flat:
        raise ValueError(f"patch linear input dim {flat} is not p*p*3")
    return np.ascontiguousarray(w.reshape(out, p, p, 3).transpose(1, 2, 3, 0))


class T:
    """Standard transforms (HF torch layout <-> jimm_tpu layout)."""

    #: torch Linear (out, in) <-> flax kernel (in, out)
    linear = Transform(lambda w: np.ascontiguousarray(w.transpose()),
                       lambda w: np.ascontiguousarray(w.transpose()))
    #: torch Conv2d OIHW <-> flax HWIO (ref `models/vit.py:239-240`)
    conv = Transform(lambda w: np.ascontiguousarray(w.transpose(2, 3, 1, 0)),
                     lambda w: np.ascontiguousarray(w.transpose(3, 2, 0, 1)))
    #: patch embedding -> flax conv HWIO, accepting either the Conv2d OIHW
    #: layout (ViT/CLIP/SigLIP v1) or SigLIP2's NaFlex Linear (2-D). The
    #: exporter always writes the v1 Conv2d layout.
    patch = Transform(
        lambda w: (np.ascontiguousarray(w.transpose(2, 3, 1, 0))
                   if w.ndim == 4 else _patch_linear_to_hwio(w)),
        lambda w: np.ascontiguousarray(w.transpose(3, 2, 0, 1)))
    unsqueeze = Transform(lambda w: w[None], lambda w: w[0])
    #: reshape to a scalar; exporter restores a rank-1 (1,) tensor iff the
    #: checkpoint had one (SigLIP's logit_scale/bias are (1,), CLIP's is ())
    scalar = Transform(lambda w: np.asarray(w).reshape(()),
                       lambda w: np.asarray(w).reshape(()))
    scalar_1d = Transform(lambda w: np.asarray(w).reshape(()),
                          lambda w: np.asarray(w).reshape((1,)))
    reshape_1_1_d = Transform(lambda w: w.reshape(1, 1, -1),
                              lambda w: w.reshape(-1))
    chunk = Chunk


@dataclass(frozen=True)
class M:
    """One mapping entry: ``src`` may contain ``{i}`` to denote a per-layer
    tensor that is stacked over the ``layers`` axis of ``dst``."""

    dst: str
    src: str
    transform: Callable[[np.ndarray], np.ndarray] | None = None
    optional: bool = False  # skip silently if src/dst absent (CLIP-style
    #                         leniency, ref `clip.py:343-348`)


class MappingError(ValueError):
    pass


def order_for(dst: str, layer_order: dict[str, np.ndarray] | None
              ) -> np.ndarray | None:
    """Longest-matching-prefix lookup into a {dst-prefix: permutation} map
    ("" matches all). Shared by the loader and the HF exporter so load and
    export can never disagree on layer ordering."""
    best = None
    for prefix, order in (layer_order or {}).items():
        if dst.startswith(prefix) and (
                best is None or len(prefix) > len(best[0])):
            best = (prefix, order)
    return None if best is None else best[1]


def layer_orders(cfg) -> dict[str, np.ndarray] | None:
    """{dst-prefix: permutation} for model configs whose towers bake
    pipeline circular placement into storage (``pp_stages`` set with
    ``pp_virtual > 1`` — see `nn/transformer.py`). None when canonical."""
    def tower(t):
        if (t is not None and getattr(t, "pipeline", False)
                and t.pp_virtual > 1 and t.pp_stages):
            from jimm_tpu.parallel.pipeline import circular_layer_order
            return circular_layer_order(t.depth, t.pp_stages, t.pp_virtual)
        return None

    orders = {}
    v = tower(getattr(cfg, "vision", None))
    if v is not None:
        orders["vision."] = v
    t = tower(getattr(cfg, "text", None))
    if t is not None:
        orders["text."] = t
    return orders or None


def apply_mapping(model: nnx.Module, weights: dict[str, np.ndarray],
                  entries: list[M], *, num_layers: int,
                  num_layers_by_prefix: dict[str, int] | None = None,
                  allowed_unused: tuple[str, ...] = ("position_ids",),
                  param_dtype=None,
                  layer_order: dict[str, np.ndarray] | None = None) -> None:
    """``layer_order``: optional {dst-prefix: permutation} applied after
    stacking — stored row j receives canonical layer order[j] (models whose
    towers bake pipeline circular placement into storage,
    `nn/transformer.py`). Longest matching prefix wins; "" matches all."""
    def layer_count(dst: str) -> int:
        for prefix, n in (num_layers_by_prefix or {}).items():
            if dst.startswith(prefix):
                return n
        return num_layers
    params = dict(nnx.to_flat_state(nnx.state(model, nnx.Param)))
    consumed: set[str] = set()
    assigned: dict[tuple, jax.Array] = {}

    def take(key: str, optional: bool) -> np.ndarray | None:
        if key not in weights:
            if optional:
                return None
            raise MappingError(f"checkpoint missing tensor {key!r}")
        consumed.add(key)
        return weights[key]

    for e in entries:
        dst = tuple(e.dst.split("."))
        if dst not in params:
            if e.optional:
                continue
            raise MappingError(f"model has no parameter {e.dst!r}")
        if "{i}" in e.src:
            per_layer = []
            missing = False
            for i in range(layer_count(e.dst)):
                arr = take(e.src.format(i=i), e.optional)
                if arr is None:
                    missing = True
                    break
                per_layer.append(e.transform(arr) if e.transform else arr)
            if missing:
                continue
            arr = np.stack(per_layer)
            order = order_for(e.dst, layer_order)
            if order is not None:
                arr = arr[order]
        else:
            arr = take(e.src, e.optional)
            if arr is None:
                continue
            if e.transform:
                arr = e.transform(arr)
        var = params[dst]
        target = var.get_value()
        if tuple(arr.shape) != tuple(target.shape):
            raise MappingError(
                f"shape mismatch for {e.dst}: checkpoint {arr.shape} vs "
                f"model {target.shape} (src {e.src!r})")
        dtype = param_dtype if param_dtype is not None else target.dtype
        sharding = (target.sharding if isinstance(target, jax.Array)
                    else None)
        if dst in assigned:
            raise MappingError(f"parameter {e.dst} assigned twice")
        assigned[dst] = jax.device_put(arr.astype(dtype), sharding)

    not_assigned = set(params) - set(assigned)
    if not_assigned:
        pretty = sorted(".".join(map(str, p)) for p in not_assigned)
        raise MappingError(f"model parameters not loaded: {pretty}")
    leftovers = [k for k in weights if k not in consumed
                 and not any(k.endswith(suf) for suf in allowed_unused)]
    if leftovers:
        raise MappingError(f"unused checkpoint tensors: {sorted(leftovers)}")

    for path, value in assigned.items():
        params[path].set_value(value)
    nnx.update(model, nnx.from_flat_state(
        [(p, v) for p, v in params.items()]))
