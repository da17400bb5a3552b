"""`best_config` lookup + the offline tuning driver.

The contract the ops hot path relies on:

- **Never tune in the hot path.** `best_config` is called at trace time
  from `ops/flash_attention.py` / `ops/layer_norm.py`; it does a memo/store
  lookup and otherwise returns the kernel's safe default. Measurement only
  happens when the operator opted in — ``JIMM_TUNE=1`` in the environment,
  or an explicit offline ``jimm-tpu tune run`` / `tune_kernel` call.
- **Every outcome is counted**: ``jimm_tune_hit_total`` /
  ``jimm_tune_miss_total`` / ``jimm_tune_fallback_total`` (observability.md
  lists the series), so a fleet silently running on fallback defaults shows
  up on the first metrics dump.

The process-wide cache defaults to ``JIMM_TUNE_CACHE`` or
``~/.cache/jimm_tpu/tune``; ``serve --tune-cache`` repoints it via
`configure`.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Mapping, Sequence

from jimm_tpu import obs
from jimm_tpu.tune.cache import TuneCache, TuneKey, tune_key
from jimm_tpu.tune.measure import measure
from jimm_tpu.tune.space import (bias_flash_space, flash_space,
                                 fp8_matmul_space, int8_flash_space,
                                 int8_matmul_space, ivf_space, ln_space,
                                 masked_flash_space, retrieval_space,
                                 ring_space, sigmoid_space, tier_space)

__all__ = ["KERNELS", "KernelSpec", "best_config", "configure", "get_cache",
           "tune_kernel"]

Shapes = Sequence[Sequence[int]]
Dtypes = Sequence[Any]


# ---------------------------------------------------------------------------
# kernel registry
# ---------------------------------------------------------------------------

def _flash_default(shapes: Shapes, dtypes: Dtypes) -> dict:
    from jimm_tpu.ops.flash_attention import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q
    return {"block_q": DEFAULT_BLOCK_Q, "block_k": DEFAULT_BLOCK_K}


def _flash_bench(shapes: Shapes, dtypes: Dtypes,
                 config: Mapping[str, int]) -> Callable[[], Any]:
    """Timed closure: flash fwd+bwd at the candidate blocks (training is the
    sweep's consumer; a fwd-only winner that loses the backward would be a
    false economy). Explicit block kwargs bypass the tuner — no recursion."""
    import jax
    import jax.numpy as jnp

    from jimm_tpu.ops.flash_attention import flash_attention
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    dt = jnp.dtype(dtypes[0]) if dtypes else jnp.float32
    q = jax.random.normal(kq, tuple(shapes[0]), dt)
    k = jax.random.normal(kk, tuple(shapes[1]), dt)
    v = jax.random.normal(kv, tuple(shapes[2]), dt)
    bq, bk = int(config["block_q"]), int(config["block_k"])

    def loss(q, k, v):
        o = flash_attention(q, k, v, block_q=bq, block_k=bk)
        return jnp.sum(o.astype(jnp.float32))

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return lambda: step(q, k, v)


def _attn_qkv(shapes: Shapes, dtypes: Dtypes):
    import jax
    import jax.numpy as jnp
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    dt = jnp.dtype(dtypes[0]) if dtypes else jnp.float32
    return (jax.random.normal(kq, tuple(shapes[0]), dt),
            jax.random.normal(kk, tuple(shapes[1]), dt),
            jax.random.normal(kv, tuple(shapes[2]), dt))


def _masked_flash_bench(shapes: Shapes, dtypes: Dtypes,
                        config: Mapping[str, int]) -> Callable[[], Any]:
    """Timed closure: masked flash fwd+bwd with a NaFlex-shaped key-padding
    mask (~25% padded keys, every row keeps its first key). Explicit block
    kwargs bypass the tuner — no recursion."""
    import jax
    import jax.numpy as jnp

    from jimm_tpu.ops.flash_attention import flash_attention_masked
    q, k, v = _attn_qkv(shapes, dtypes)
    b, sk = q.shape[0], k.shape[1]
    mask = (jax.random.uniform(jax.random.PRNGKey(1), (b, sk)) > 0.25)
    mask = mask.at[:, 0].set(True)
    bq, bk = int(config["block_q"]), int(config["block_k"])

    def loss(q, k, v):
        o = flash_attention_masked(q, k, v, mask, block_q=bq, block_k=bk)
        return jnp.sum(o.astype(jnp.float32))

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return lambda: step(q, k, v)


def _ring_bench(shapes: Shapes, dtypes: Dtypes,
                config: Mapping[str, int]) -> Callable[[], Any]:
    """Timed closure for the sequence-parallel ring's per-hop kernel.
    ``shapes`` are the LOCAL chunk shapes ``(B, S/p, N, D)`` — the blocks
    only govern the per-hop flash call (`seqpar.ring_hop_fwd`/`_bwd`,
    which is the masked single-chip product over one chunk), so benching
    masked flash at chunk shape measures exactly what the config
    controls; the ppermute schedule is block-independent. Explicit block
    kwargs bypass the tuner — no recursion."""
    import jax
    import jax.numpy as jnp

    from jimm_tpu.ops.flash_attention import flash_attention_masked
    q, k, v = _attn_qkv(shapes, dtypes)
    b, sk = q.shape[0], k.shape[1]
    # the ring's traveling mask rows look like NaFlex padding per chunk
    mask = (jax.random.uniform(jax.random.PRNGKey(1), (b, sk)) > 0.25)
    mask = mask.at[:, 0].set(True)
    bq, bk = int(config["block_q"]), int(config["block_k"])

    def loss(q, k, v):
        o = flash_attention_masked(q, k, v, mask, block_q=bq, block_k=bk)
        return jnp.sum(o.astype(jnp.float32))

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return lambda: step(q, k, v)


def _bias_flash_bench(shapes: Shapes, dtypes: Dtypes,
                      config: Mapping[str, int]) -> Callable[[], Any]:
    """Timed closure: bias flash fwd+bwd including the dbias accumulation
    kernel (the variant's distinguishing cost). Explicit block kwargs
    bypass the tuner — no recursion."""
    import jax
    import jax.numpy as jnp

    from jimm_tpu.ops.flash_attention import flash_attention_bias
    q, k, v = _attn_qkv(shapes, dtypes)
    sq, sk, n = q.shape[1], k.shape[1], q.shape[2]
    bias = jax.random.normal(jax.random.PRNGKey(1), (n, sq, sk),
                             jnp.float32)
    bq, bk = int(config["block_q"]), int(config["block_k"])

    def loss(q, k, v, bias):
        o = flash_attention_bias(q, k, v, bias, block_q=bq, block_k=bk)
        return jnp.sum(o.astype(jnp.float32))

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
    return lambda: step(q, k, v, bias)


def _sigmoid_bench(shapes: Shapes, dtypes: Dtypes,
                   config: Mapping[str, int]) -> Callable[[], Any]:
    """Timed closure: sigmoid attention fwd+bwd (training is the consumer
    — the variant exists for SigLIP-style towers). Explicit block kwargs
    bypass the tuner — no recursion."""
    import jax
    import jax.numpy as jnp

    from jimm_tpu.ops.flash_attention import sigmoid_attention
    q, k, v = _attn_qkv(shapes, dtypes)
    bq, bk = int(config["block_q"]), int(config["block_k"])

    def loss(q, k, v):
        o = sigmoid_attention(q, k, v, block_q=bq, block_k=bk)
        return jnp.sum(o.astype(jnp.float32))

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return lambda: step(q, k, v)


def _ln_default(shapes: Shapes, dtypes: Dtypes) -> dict:
    from jimm_tpu.ops.layer_norm import DEFAULT_BLOCK_ROWS
    return {"block_rows": DEFAULT_BLOCK_ROWS}


def _layer_norm_bench(shapes: Shapes, dtypes: Dtypes,
                      config: Mapping[str, int]) -> Callable[[], Any]:
    """Timed closure: fused LN fwd+bwd (the backward is the kernel's whole
    reason to exist)."""
    import jax
    import jax.numpy as jnp

    from jimm_tpu.ops.layer_norm import layer_norm
    rows, feat = (int(d) for d in shapes[0][-2:])
    dt = jnp.dtype(dtypes[0]) if dtypes else jnp.float32
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, feat), dt)
    scale = jnp.ones((feat,), jnp.float32)
    bias = jnp.zeros((feat,), jnp.float32)
    br = int(config["block_rows"])

    def loss(x, scale, bias):
        o = layer_norm(x, scale, bias, 1e-6, br)
        return jnp.sum(o.astype(jnp.float32))

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return lambda: step(x, scale, bias)


def _retrieval_default(shapes: Shapes, dtypes: Dtypes) -> dict:
    from jimm_tpu.retrieval.topk import DEFAULT_BLOCK_N
    candidates = retrieval_space(shapes, dtypes)
    feasible = {c["block_n"] for c in candidates}
    return {"block_n": (DEFAULT_BLOCK_N if DEFAULT_BLOCK_N in feasible
                        else max(feasible))}


def _retrieval_bench(shapes: Shapes, dtypes: Dtypes,
                     config: Mapping[str, int]) -> Callable[[], Any]:
    """Timed closure: one streaming top-k pass at the candidate block over
    a synthetic normalized corpus shaped like the live one. Explicit
    block_n bypasses the tuner — no recursion."""
    import jax
    import numpy as np

    from jimm_tpu.retrieval.topk import corpus_layout, make_topk_fn
    batch, dim = int(shapes[0][-2]), int(shapes[0][-1])
    n_rows = int(shapes[-1][-2])
    dt = np.dtype(dtypes[-1]) if dtypes else np.dtype(np.float32)
    rng = np.random.default_rng(0)
    corpus = np.asarray(rng.standard_normal((n_rows, dim),
                                            dtype=np.float32), dt)
    queries = rng.standard_normal((batch, dim), dtype=np.float32)
    blocks, offsets, valid = corpus_layout(
        corpus, block_n=int(config["block_n"]))
    step = jax.jit(make_topk_fn(10))
    valid = np.int32(valid)
    return lambda: step(blocks, offsets, valid, queries)


def _ivf_default(shapes: Shapes, dtypes: Dtypes) -> dict:
    # the feasible set already accounts for the batch-multiplied gather;
    # prefer the largest feasible block up to the exact kernel's default
    # (fewer scan steps, less per-block top_k overhead)
    from jimm_tpu.retrieval.topk import DEFAULT_BLOCK_N
    feasible = {c["block_n"] for c in ivf_space(shapes, dtypes)}
    capped = {b for b in feasible if b <= DEFAULT_BLOCK_N}
    return {"block_n": max(capped) if capped else min(feasible)}


def _ivf_bench(shapes: Shapes, dtypes: Dtypes,
               config: Mapping[str, int]) -> Callable[[], Any]:
    """Timed closure: one fused IVF pass (coarse scan + probe + rescore)
    at the candidate block over a synthetic clustered corpus shaped like
    the live one. Explicit block_n bypasses the tuner — no recursion."""
    import jax
    import numpy as np

    from jimm_tpu.retrieval.ann.ivf import cluster_layout, make_ivf_fn
    from jimm_tpu.retrieval.ann.kmeans import (assign_clusters,
                                               clustered_rows)
    batch, dim = int(shapes[0][-2]), int(shapes[0][-1])
    n_rows = int(shapes[-1][-2])
    dt = np.dtype(dtypes[-1]) if dtypes else np.dtype(np.float32)
    clusters = max(1, min(64, n_rows // 64))
    rows, cents = clustered_rows(n_rows, dim, clusters, seed=0)
    corpus = np.asarray(rows, dt)
    assign = assign_clusters(rows, cents)
    blocks, rids, cl_start, cl_count = cluster_layout(
        corpus, assign, clusters, block_n=int(config["block_n"]))
    nprobe_max = max(1, min(8, clusters))
    max_bpc = max(1, int(cl_count.max(initial=0)))
    rng = np.random.default_rng(1)
    queries = rng.standard_normal((batch, dim), dtype=np.float32)
    step = jax.jit(make_ivf_fn(10, nprobe_max, max_bpc))
    live_c = np.int32(clusters)
    nprobe = np.int32(nprobe_max)
    return lambda: step(blocks, rids, np.asarray(cents, np.float32),
                        cl_start, cl_count, live_c, nprobe, queries)


def _tier_default(shapes: Shapes, dtypes: Dtypes) -> dict:
    # opposite preference to _ivf_default: block_n is also the hot
    # arena's allocation quantum, and a small corpus-per-cluster means a
    # large block mostly buys padding — pick the *smallest* feasible
    # block at or above the lane width so the budget packs more clusters
    feasible = {c["block_n"] for c in tier_space(shapes, dtypes)}
    return {"block_n": min(feasible)}


def _tier_bench(shapes: Shapes, dtypes: Dtypes,
                config: Mapping[str, int]) -> Callable[[], Any]:
    """Timed closure: one hot-arena tier pass (coarse scan + probe +
    rescore + probe-selection output) at the candidate block over a
    synthetic clustered corpus. Explicit block_n bypasses the tuner —
    no recursion."""
    import jax
    import numpy as np

    from jimm_tpu.retrieval.ann.ivf import cluster_layout
    from jimm_tpu.retrieval.ann.kmeans import (assign_clusters,
                                               clustered_rows)
    from jimm_tpu.retrieval.tier.engine import make_tier_fn
    batch, dim = int(shapes[0][-2]), int(shapes[0][-1])
    n_rows = int(shapes[-1][-2])
    dt = np.dtype(dtypes[-1]) if dtypes else np.dtype(np.float32)
    clusters = max(1, min(64, n_rows // 64))
    rows, cents = clustered_rows(n_rows, dim, clusters, seed=0)
    corpus = np.asarray(rows, dt)
    assign = assign_clusters(rows, cents)
    blocks, rids, cl_start, cl_count = cluster_layout(
        corpus, assign, clusters, block_n=int(config["block_n"]))
    nprobe_max = max(1, min(8, clusters))
    max_bpc = max(1, int(cl_count.max(initial=0)))
    rng = np.random.default_rng(1)
    queries = rng.standard_normal((batch, dim), dtype=np.float32)
    step = jax.jit(make_tier_fn(10, nprobe_max, max_bpc))
    live_c = np.int32(clusters)
    nprobe = np.int32(nprobe_max)
    return lambda: step(blocks, rids, np.asarray(cents, np.float32),
                        cl_start, cl_count, live_c, nprobe, queries)


def _int8_matmul_default(shapes: Shapes, dtypes: Dtypes) -> dict:
    from jimm_tpu.ops.int8_matmul import DEFAULT_BLOCK_M, DEFAULT_BLOCK_N
    return {"block_m": DEFAULT_BLOCK_M, "block_n": DEFAULT_BLOCK_N}


def _int8_matmul_bench(shapes: Shapes, dtypes: Dtypes,
                       config: Mapping[str, int]) -> Callable[[], Any]:
    """Timed closure: the fused dequantizing matmul (forward only — it is a
    serving kernel). Explicit block kwargs bypass the tuner — no
    recursion."""
    import jax
    import jax.numpy as jnp

    from jimm_tpu.ops.int8_matmul import int8_matmul
    m, k = (int(d) for d in shapes[0][-2:])
    n = int(shapes[1][-1])
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x_q = jax.random.randint(kx, (m, k), -127, 128, jnp.int8)
    w_q = jax.random.randint(kw, (k, n), -127, 128, jnp.int8)
    x_s = jnp.full((m,), 0.01, jnp.float32)
    w_s = jnp.full((n,), 0.01, jnp.float32)
    bias = jnp.zeros((n,), jnp.float32)
    bm, bn = int(config["block_m"]), int(config["block_n"])

    step = jax.jit(lambda xq, xs, wq, ws, b: int8_matmul(
        xq, xs, wq, ws, b, activation="gelu", block_m=bm, block_n=bn))
    return lambda: step(x_q, x_s, w_q, w_s, bias)


def _int8_flash_default(shapes: Shapes, dtypes: Dtypes) -> dict:
    from jimm_tpu.ops.flash_attention_int8 import (DEFAULT_BLOCK_K,
                                                   DEFAULT_BLOCK_Q)
    return {"block_q": DEFAULT_BLOCK_Q, "block_k": DEFAULT_BLOCK_K}


def _int8_flash_bench(shapes: Shapes, dtypes: Dtypes,
                      config: Mapping[str, int]) -> Callable[[], Any]:
    """Timed closure: int8 flash fwd+bwd at the candidate blocks (since
    the int8_qk training policy landed the backward, training is a
    consumer too — a fwd-only winner that loses the backward would be a
    false economy). Explicit block kwargs bypass the tuner — no
    recursion."""
    import jax
    import jax.numpy as jnp

    from jimm_tpu.ops.flash_attention_int8 import flash_attention_int8
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    dt = jnp.dtype(dtypes[0]) if dtypes else jnp.float32
    q = jax.random.normal(kq, tuple(shapes[0]), dt)
    k = jax.random.normal(kk, tuple(shapes[1]), dt)
    v = jax.random.normal(kv, tuple(shapes[2]), dt)
    bq, bk = int(config["block_q"]), int(config["block_k"])

    def loss(q, k, v):
        o = flash_attention_int8(q, k, v, block_q=bq, block_k=bk)
        return jnp.sum(o.astype(jnp.float32))

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return lambda: step(q, k, v)


def _fp8_matmul_default(shapes: Shapes, dtypes: Dtypes) -> dict:
    from jimm_tpu.ops.fp8_matmul import DEFAULT_BLOCK_M, DEFAULT_BLOCK_N
    return {"block_m": DEFAULT_BLOCK_M, "block_n": DEFAULT_BLOCK_N}


def _fp8_matmul_bench(shapes: Shapes, dtypes: Dtypes,
                      config: Mapping[str, int]) -> Callable[[], Any]:
    """Timed closure: fp8 matmul fwd+bwd (training is the kernel's whole
    consumer — the backward's two e5m2 contractions dominate). Explicit
    block kwargs bypass the tuner — no recursion."""
    import jax
    import jax.numpy as jnp

    from jimm_tpu.ops.fp8_matmul import fp8_matmul
    m, k = (int(d) for d in shapes[0][-2:])
    n = int(shapes[1][-1])
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    bias = jnp.zeros((n,), jnp.float32)
    bm, bn = int(config["block_m"]), int(config["block_n"])

    def loss(x, w, bias):
        y = fp8_matmul(x, w, bias, block_m=bm, block_n=bn)
        return jnp.sum(y)

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return lambda: step(x, w, bias)


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One tunable kernel: identity, search space, fallback, and bench."""

    version: int  # bump with the kernel implementation — stale configs miss
    space: Callable[[Shapes, Dtypes], list[dict]]
    default: Callable[[Shapes, Dtypes], dict]
    bench: Callable[[Shapes, Dtypes, Mapping[str, int]], Callable[[], Any]]


KERNELS: dict[str, KernelSpec] = {
    "flash_attention": KernelSpec(version=1, space=flash_space,
                                  default=_flash_default,
                                  bench=_flash_bench),
    "flash_attention_masked": KernelSpec(version=1,
                                         space=masked_flash_space,
                                         default=_flash_default,
                                         bench=_masked_flash_bench),
    "flash_attention_bias": KernelSpec(version=1, space=bias_flash_space,
                                       default=_flash_default,
                                       bench=_bias_flash_bench),
    "sigmoid_attention": KernelSpec(version=1, space=sigmoid_space,
                                    default=_flash_default,
                                    bench=_sigmoid_bench),
    "layer_norm": KernelSpec(version=1, space=ln_space,
                             default=_ln_default,
                             bench=_layer_norm_bench),
    "retrieval_topk": KernelSpec(version=1, space=retrieval_space,
                                 default=_retrieval_default,
                                 bench=_retrieval_bench),
    "retrieval_ivf": KernelSpec(version=1, space=ivf_space,
                                default=_ivf_default,
                                bench=_ivf_bench),
    "retrieval_tier": KernelSpec(version=1, space=tier_space,
                                 default=_tier_default,
                                 bench=_tier_bench),
    "int8_matmul": KernelSpec(version=1, space=int8_matmul_space,
                              default=_int8_matmul_default,
                              bench=_int8_matmul_bench),
    # version 2: the backward landed (lse output changed the fwd cell's
    # working set; blocks must now fit the dq/dkv cells too)
    "flash_attention_int8": KernelSpec(version=2, space=int8_flash_space,
                                       default=_int8_flash_default,
                                       bench=_int8_flash_bench),
    "fp8_matmul": KernelSpec(version=1, space=fp8_matmul_space,
                             default=_fp8_matmul_default,
                             bench=_fp8_matmul_bench),
    # keyed on the per-device LOCAL chunk shapes (B, S/p, N, D) — see
    # parallel/seqpar.py::_resolve_ring_blocks
    "ring_attention": KernelSpec(version=1, space=ring_space,
                                 default=_flash_default,
                                 bench=_ring_bench),
}


# ---------------------------------------------------------------------------
# process-wide cache
# ---------------------------------------------------------------------------

_cache: TuneCache | None = None


def get_cache() -> TuneCache:
    global _cache
    if _cache is None:
        _cache = TuneCache()
    return _cache


def configure(root: str | os.PathLike | None) -> TuneCache:
    """Point the process-wide tune cache at ``root`` (``serve --tune-cache``
    and the benchmark's harness call this before any kernel traces)."""
    global _cache
    _cache = TuneCache(root)
    return _cache


# ---------------------------------------------------------------------------
# lookup (hot path) and tuning (offline)
# ---------------------------------------------------------------------------

def _key_for(kernel: str, shapes: Shapes, dtypes: Dtypes) -> TuneKey:
    spec = KERNELS[kernel]
    return tune_key(kernel, shapes=shapes, dtypes=dtypes,
                    kernel_version=spec.version)


def best_config(kernel: str, shapes: Shapes, dtypes: Dtypes, *,
                default: Mapping[str, int] | None = None,
                cache: TuneCache | None = None) -> dict:
    """The tuned config for ``kernel`` at these shapes, else a safe default.

    Lookup-only unless ``JIMM_TUNE=1``: called host-side at trace time, so
    a cold cache costs one file probe per newly traced shape and a warm one
    costs a dict probe.
    """
    spec = KERNELS[kernel]
    key = _key_for(kernel, shapes, dtypes)
    cache = cache or get_cache()
    registry = obs.get_registry("jimm_tune")
    record = cache.get(key)
    if record is not None:
        registry.counter("hit_total").inc()
        return dict(record["config"])
    registry.counter("miss_total").inc()
    if os.environ.get("JIMM_TUNE") == "1":
        return dict(tune_kernel(kernel, shapes, dtypes,
                                cache=cache)["config"])
    registry.counter("fallback_total").inc()
    return dict(default) if default is not None else spec.default(shapes,
                                                                  dtypes)


def tune_kernel(kernel: str, shapes: Shapes, dtypes: Dtypes, *,
                cache: TuneCache | None = None, reps: int | None = None,
                candidates: Sequence[Mapping[str, int]] | None = None
                ) -> dict:
    """Measure every feasible candidate, persist the winner, return
    ``{"config", "time_s", "candidates", "fingerprint", "trials"}``."""
    spec = KERNELS[kernel]
    key = _key_for(kernel, shapes, dtypes)
    cache = cache or get_cache()
    cands = list(candidates) if candidates is not None \
        else spec.space(shapes, dtypes)
    trials = []
    for config in cands:
        fn = spec.bench(shapes, dtypes, config)
        trials.append({"config": dict(config),
                       "time_s": measure(fn, reps=reps, kernel=kernel)})
    best = min(trials, key=lambda t: t["time_s"])
    fingerprint = cache.put(key, best["config"],
                            metrics={"time_s": best["time_s"],
                                     "trials": trials})
    return {"config": dict(best["config"]), "time_s": best["time_s"],
            "candidates": len(trials), "fingerprint": fingerprint,
            "trials": trials}
