"""Exact on-TPU top-k retrieval: blocked scoring + streaming merge.

The scoring kernel follows FlashAttention's IO-aware blocking (PAPERS.md):
the ``(B, N)`` score matrix is never materialized. The corpus lives on
device as ``(shards, nblocks, block_n, D)``; a ``lax.scan`` streams one
``(block_n, D)`` block at a time through the MXU — ``(B, D) @ (D,
block_n)`` — and folds each block's ``jax.lax.top_k`` into a running
``(B, k)`` carry. Peak live intermediate is ``(B, block_n)`` scores plus
the ``(B, 2k)`` merge buffer, independent of corpus size N.

Exactness (recall@k == 1.0 vs a NumPy oracle) is by construction, not
approximation: every row is scored, and ``lax.top_k``'s stable
lowest-index-first tie order is preserved end to end — the running carry
(earlier, lower global indices) is concatenated *before* each block's
candidates, and the host-side merge of per-shard partials re-sorts the
bounded ``shards * k`` candidate set with an explicit (score desc, index
asc) key. Host code never full-sorts anything corpus-sized; lint rule
JL011 makes that an error in this package.

Sharding rides the PR 6 topology: the corpus splits on the ``model`` mesh
axis (shard axis of the 4-D layout), each replica scores its contiguous
row partition into a ``(shards, B, k)`` partial, and the final merge is
host-side over ``replicas * shards * k`` candidates. Block offsets and the
live-row count are *runtime* arguments, so every equally-padded partition
shares one compiled program — and one AOT fingerprint: the forward is
registered in the :mod:`jimm_tpu.aot` store exactly like a serve bucket
(``method="retrieval_topk"``), so a warm restart deserializes the scoring
program instead of re-tracing it. Block sizes resolve through
``tune.best_config("retrieval_topk", ...)``; an explicit ``block_n`` wins,
like the ops kernels.
"""

from __future__ import annotations

import math
import threading
from typing import Any, Callable, Sequence

import numpy as np

from jimm_tpu.retrieval.store import LoadedIndex, normalize_rows

__all__ = ["DEFAULT_BLOCK_N", "IndexSearcher", "Searcher", "corpus_layout",
           "make_topk_fn", "merge_partials", "streaming_topk"]

#: safe fallback block: lane-aligned, small enough that a (64, block_n)
#: f32 score tile + the (block_n, D) corpus block sit comfortably in VMEM
#: at ViT-scale D; tune.best_config refines it per (N, D, dtype)
DEFAULT_BLOCK_N = 1024

_LANES = 128


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# device program
# ---------------------------------------------------------------------------

def make_topk_fn(k: int) -> Callable:
    """The traceable scoring program for one ``k``.

    Signature: ``fn(corpus (S, nb, bn, D), offsets (S, nb) i32,
    valid () i32, queries (B, D) f32) -> (values (S, B, k) f32,
    indices (S, B, k) i32)`` where ``indices`` are *global* corpus rows
    (``offsets`` already carry any partition base) and rows at or beyond
    ``valid`` are masked to ``-inf`` / left as padding candidates.
    """
    import jax
    import jax.numpy as jnp

    k = int(k)

    def fn(corpus, offsets, valid, queries):
        qf = queries.astype(jnp.float32)
        batch = qf.shape[0]

        def per_shard(shard_blocks, shard_offsets):
            block_n = shard_blocks.shape[1]
            kk = min(k, block_n)

            def body(carry, xs):
                carry_vals, carry_idx = carry
                block, offset = xs
                # the MXU step: (B, D) @ (D, block_n); never (B, N)
                scores = qf @ block.astype(jnp.float32).T
                cols = offset + jax.lax.iota(jnp.int32, block_n)
                scores = jnp.where(cols[None, :] < valid, scores,
                                   -jnp.inf)
                block_vals, block_pos = jax.lax.top_k(scores, kk)
                block_idx = jnp.take(cols, block_pos)
                # carry first: on equal scores top_k keeps the earlier
                # position, i.e. the lower global index — matching a
                # stable NumPy argsort oracle
                merged_vals, merged_pos = jax.lax.top_k(
                    jnp.concatenate([carry_vals, block_vals], axis=1), k)
                merged_idx = jnp.take_along_axis(
                    jnp.concatenate([carry_idx, block_idx], axis=1),
                    merged_pos, axis=1)
                return (merged_vals, merged_idx), None

            init = (jnp.full((batch, k), -jnp.inf, jnp.float32),
                    jnp.full((batch, k), -1, jnp.int32))
            (vals, idx), _ = jax.lax.scan(body, init,
                                          (shard_blocks, shard_offsets))
            return vals, idx

        return jax.vmap(per_shard)(corpus, offsets)

    return fn


# ---------------------------------------------------------------------------
# host-side layout and merge
# ---------------------------------------------------------------------------

def corpus_layout(corpus: np.ndarray, *, shards: int = 1,
                  block_n: int = DEFAULT_BLOCK_N, base: int = 0,
                  pad_rows: int | None = None
                  ) -> tuple[np.ndarray, np.ndarray, int]:
    """Pack an ``(N, D)`` corpus into the device layout.

    Returns ``(blocks (S, nb, bn, D), offsets (S, nb) i32, valid)``.
    ``pad_rows`` pads the partition to a common row count so every replica
    partition of one index shares shapes (and therefore one compiled
    program and one AOT fingerprint); ``base`` shifts offsets so indices
    stay global across partitions.
    """
    corpus = np.asarray(corpus)
    if corpus.ndim != 2:
        raise ValueError(f"corpus must be (N, D); got {corpus.shape}")
    n, dim = corpus.shape
    shards = max(1, int(shards))
    block_n = max(1, int(block_n))
    target = max(int(pad_rows) if pad_rows is not None else n, 1)
    if target < n:
        raise ValueError(f"pad_rows={target} < corpus rows {n}")
    per_shard = _ceil_to(math.ceil(target / shards), block_n)
    nblocks = per_shard // block_n
    padded = np.zeros((shards * per_shard, dim), corpus.dtype)
    padded[:n] = corpus
    blocks = padded.reshape(shards, nblocks, block_n, dim)
    offsets = (base
               + np.arange(shards, dtype=np.int32)[:, None] * per_shard
               + np.arange(nblocks, dtype=np.int32)[None, :] * block_n)
    return blocks, np.ascontiguousarray(offsets), base + n


def merge_partials(values: np.ndarray, indices: np.ndarray,
                   k: int) -> tuple[np.ndarray, np.ndarray]:
    """Fold ``(P, B, k)`` per-shard/per-replica partials into the global
    ``(B, k)`` result. The candidate set is ``P * k`` per query — bounded
    by the merge fan-in, never by corpus size — so an explicit
    (score desc, global index asc) lexicographic sort here is O(Pk log Pk)
    and reproduces the stable-argsort oracle's tie order exactly. This is
    the sanctioned host-merge idiom JL011 points at.
    """
    values = np.asarray(values, np.float32)
    indices = np.asarray(indices, np.int64)
    partials, batch, kk = values.shape
    flat_v = values.transpose(1, 0, 2).reshape(batch, partials * kk)
    flat_i = indices.transpose(1, 0, 2).reshape(batch, partials * kk)
    if flat_v.shape[1] < k:
        # k exceeds the total candidate fan-in (k > live rows, or every
        # segment tombstoned): keep the (B, k) shape contract and let
        # (-inf, -1) padding mark the underfill explicitly
        pad = k - flat_v.shape[1]
        flat_v = np.pad(flat_v, ((0, 0), (0, pad)),
                        constant_values=-np.inf)
        flat_i = np.pad(flat_i, ((0, 0), (0, pad)), constant_values=-1)
    # padding candidates (idx -1, val -inf) must lose every comparison,
    # including against real -inf scores, so push their index to +inf-ish
    sort_i = np.where(flat_i < 0, np.iinfo(np.int64).max, flat_i)
    order = np.lexsort((sort_i, -flat_v), axis=-1)[:, :k]
    return (np.take_along_axis(flat_v, order, axis=1),
            np.take_along_axis(flat_i, order, axis=1))


def streaming_topk(queries: np.ndarray, corpus: np.ndarray, k: int, *,
                   block_n: int = DEFAULT_BLOCK_N
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Single-device convenience: exact top-k of ``queries`` against
    ``corpus`` via the streaming program. Returns host ``(values (B, k),
    indices (B, k))`` with ``-1``/``-inf`` rows past the corpus size when
    ``k > N``. The parity tests drive this directly."""
    import jax

    queries = np.asarray(queries, np.float32)
    blocks, offsets, valid = corpus_layout(corpus, shards=1,
                                           block_n=block_n)
    vals, idx = jax.jit(make_topk_fn(int(k)))(
        blocks, offsets, np.int32(valid), queries)
    return np.asarray(vals)[0], np.asarray(idx, np.int64)[0]


# ---------------------------------------------------------------------------
# warm searchers (AOT + tune integration)
# ---------------------------------------------------------------------------

def _resolve_block_n(n: int, dim: int, dtype, batch: int,
                     block_n: int | None) -> int:
    """Explicit block wins (tuner bench closures must not recurse);
    otherwise consult the persistent tune cache, falling back to the
    pruned-space default — same contract as the ops kernels."""
    if block_n is not None:
        return int(block_n)
    from jimm_tpu import tune
    config = tune.best_config(
        "retrieval_topk",
        shapes=[(int(batch), int(dim)), (int(n), int(dim))],
        dtypes=[np.dtype(dtype)])
    return int(config["block_n"])


class Searcher:
    """One partition's warm scoring forward: device-resident corpus blocks
    plus a store-first compiled program per query bucket.

    Mirrors :class:`~jimm_tpu.aot.warmup.AotForward`'s dispatch contract —
    ``prepare(bucket)`` consults the artifact store under an ``aot_load``
    span and returns ``"aot"``/``"miss"``/``"fallback"`` (counted in the
    ``jimm_aot`` registry), the fresh path is a counting jit whose getter
    feeds the zero-recompile checks, and a loaded executable that raises
    at call time quarantines itself and degrades to fresh.
    """

    def __init__(self, corpus: np.ndarray, *, k: int,
                 buckets: Sequence[int] = (1,), block_n: int | None = None,
                 mesh: Any = None, base: int = 0,
                 pad_rows: int | None = None, aot_store: Any = None,
                 label: str = "retrieval", write_through: bool = True):
        import jax

        corpus = np.ascontiguousarray(np.asarray(corpus))
        self.k = int(k)
        self.buckets = tuple(sorted(int(b) for b in buckets))
        self.dim = int(corpus.shape[1])
        self.n_rows = int(corpus.shape[0])
        self.mesh = mesh
        self.store = aot_store
        self.label = label
        self.write_through = write_through
        shards = int(dict(mesh.shape).get("model", 1)) if mesh is not None \
            else 1
        self.block_n = _resolve_block_n(self.n_rows, self.dim,
                                        corpus.dtype, self.buckets[-1],
                                        block_n)
        blocks, offsets, valid = corpus_layout(
            corpus, shards=shards, block_n=self.block_n, base=base,
            pad_rows=pad_rows)
        self.shards = shards
        self.nblocks = int(blocks.shape[1])
        self._corpus_dtype = str(blocks.dtype)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._corpus_sharding = NamedSharding(
                mesh, PartitionSpec("model", None, None, None))
            self._offsets_sharding = NamedSharding(
                mesh, PartitionSpec("model", None))
            self._blocks = jax.device_put(blocks, self._corpus_sharding)
            self._offsets = jax.device_put(offsets,
                                           self._offsets_sharding)
        else:
            self._corpus_sharding = self._offsets_sharding = None
            self._blocks = jax.device_put(blocks)
            self._offsets = jax.device_put(offsets)
        self._valid = np.int32(valid)
        self._traces = {"count": 0}
        fn = make_topk_fn(self.k)

        def counting(blocks_, offsets_, valid_, queries):
            self._traces["count"] += 1
            return fn(blocks_, offsets_, valid_, queries)

        self._fn = fn
        self._fresh = jax.jit(counting)
        self._loaded: dict[int, Callable] = {}
        #: bucket -> "aot" | "miss" | "fallback" | "compile"
        self.sources: dict[int, str] = {}

    def trace_count(self) -> int:
        return self._traces["count"]

    def resident_bytes(self) -> int:
        """Device-resident corpus bytes (padded blocks + offset table) —
        the same accounting the tiered searcher reports, so the number
        compares across index modes."""
        return int(self._blocks.nbytes) + int(self._offsets.nbytes)

    # -- AOT keys ---------------------------------------------------------

    def key_for(self, bucket: int):
        from jimm_tpu.aot.keys import serve_forward_key
        return serve_forward_key(
            {"kind": "retrieval_topk", "shards": self.shards,
             "nblocks": self.nblocks, "block_n": self.block_n,
             "dim": self.dim, "k": self.k,
             "corpus_dtype": self._corpus_dtype},
            method="retrieval_topk", bucket=int(bucket),
            item_shape=(self.dim,), in_dtype=np.float32,
            param_dtype=self._corpus_dtype, mesh=self.mesh)

    def _arg_specs(self, bucket: int):
        import jax
        return (
            jax.ShapeDtypeStruct(
                (self.shards, self.nblocks, self.block_n, self.dim),
                self._blocks.dtype, sharding=self._corpus_sharding),
            jax.ShapeDtypeStruct((self.shards, self.nblocks), np.int32,
                                 sharding=self._offsets_sharding),
            jax.ShapeDtypeStruct((), np.int32),
            jax.ShapeDtypeStruct((int(bucket), self.dim), np.float32),
        )

    # -- warm-start -------------------------------------------------------

    def prepare(self, bucket: int) -> str:
        """Store-first warm-start for one query bucket; never raises."""
        bucket = int(bucket)
        if bucket in self.sources:
            return self.sources[bucket]
        if self.store is None:
            self.sources[bucket] = "compile"
            return "compile"
        from jimm_tpu import obs
        from jimm_tpu.aot.warmup import _runtime_versions, aot_metrics
        hit, miss, fallback = aot_metrics()
        key = self.key_for(bucket)
        fp = key.fingerprint()
        existed = self.store.contains(fp)
        source = "miss"
        with obs.span("aot_load"):
            payload = self.store.get(fp,
                                     expect_versions=_runtime_versions())
            if payload is not None:
                try:
                    self._loaded[bucket] = self._bind(payload)
                    source = "aot"
                except Exception as e:  # noqa: BLE001 — degrade, never die
                    self.store.quarantine(fp,
                                          f"deserialize/bind failed: {e}")
                    source = "fallback"
            elif existed:
                source = "fallback"  # store.get already quarantined it
        if source == "aot":
            hit.inc()
        elif source == "fallback":
            fallback.inc()
        else:
            miss.inc()
            if self.write_through:
                self._export_and_put(bucket, key, fp)
        self.sources[bucket] = source
        return source

    def _bind(self, payload: bytes) -> Callable:
        import jax
        from jax import export as jax_export
        exported = jax_export.deserialize(bytearray(payload))
        flat_avals = jax.tree.flatten(exported.in_avals)[0] \
            if hasattr(exported, "in_avals") else []
        if flat_avals and len(flat_avals) != 4:
            raise ValueError(f"artifact expects {len(flat_avals)} input "
                             f"leaves, retrieval_topk provides 4")
        return jax.jit(exported.call)

    def _export_and_put(self, bucket: int, key, fp: str) -> None:
        """Write-through on a miss so the next process (and every sibling
        replica — same shapes, same fingerprint) starts warm. Failure to
        serialize must not break search."""
        try:
            import jax
            from jax import export as jax_export

            from jimm_tpu.aot.keys import AOT_FORMAT_VERSION
            exported = jax_export.export(jax.jit(self._fn))(
                *self._arg_specs(bucket))
            self.store.put(fp, exported.serialize(),
                           meta={"label": self.label, **key.describe(),
                                 "format_version": AOT_FORMAT_VERSION})
        except Exception:  # noqa: BLE001
            pass

    def warmup(self) -> dict[int, str]:
        """Prepare + prime every bucket; returns {bucket: source}."""
        zeros = None
        for bucket in self.buckets:
            self.prepare(bucket)
            zeros = np.zeros((bucket, self.dim), np.float32)
            self.search_partial(zeros)
        return dict(self.sources)

    # -- dispatch ---------------------------------------------------------

    def _bucket_for(self, batch: int) -> int:
        for bucket in self.buckets:
            if batch <= bucket:
                return bucket
        raise ValueError(f"query batch {batch} exceeds largest retrieval "
                         f"bucket {self.buckets[-1]}")

    def search_partial(self, queries: np.ndarray
                       ) -> tuple[np.ndarray, np.ndarray]:
        """Score a ``(B, D)`` f32 query batch; returns host partials
        ``(values (S, B, k), indices (S, B, k))`` with global indices.
        Batches past the largest bucket run as chunks of it — no new
        program shapes, so no recompiles."""
        queries = np.asarray(queries, np.float32)
        batch = queries.shape[0]
        top = self.buckets[-1]
        if batch > top:
            outs = [self.search_partial(queries[i:i + top])
                    for i in range(0, batch, top)]
            return (np.concatenate([o[0] for o in outs], axis=1),
                    np.concatenate([o[1] for o in outs], axis=1))
        bucket = self._bucket_for(batch)
        if batch < bucket:
            padded = np.zeros((bucket, self.dim), np.float32)
            padded[:batch] = queries
            queries = padded
        fn = self._loaded.get(bucket)
        if fn is not None:
            try:
                vals, idx = fn(self._blocks, self._offsets, self._valid,
                               queries)
            except Exception:  # noqa: BLE001 — a bad artifact must not
                # fail the query: quarantine, recompile fresh
                from jimm_tpu.aot.warmup import aot_metrics
                aot_metrics()[2].inc()
                del self._loaded[bucket]
                self.sources[bucket] = "fallback"
                if self.store is not None:
                    self.store.quarantine(
                        self.key_for(bucket).fingerprint(),
                        "loaded executable raised at call time")
                vals, idx = self._fresh(self._blocks, self._offsets,
                                        self._valid, queries)
        else:
            vals, idx = self._fresh(self._blocks, self._offsets,
                                    self._valid, queries)
        return (np.asarray(vals)[:, :batch],
                np.asarray(idx, np.int64)[:, :batch])


class IndexSearcher:
    """Search one :class:`LoadedIndex` across the serving topology.

    On a trivial (or absent) plan this is a single :class:`Searcher` on
    the default device. On an ``R x k`` plan the corpus splits into R
    contiguous, equally-padded row partitions — one per replica submesh,
    further sharded ``model``-axis-wise inside each — so all partitions
    share one compiled program and one AOT fingerprint (offsets and the
    live-row count are runtime arguments). ``search`` merges the
    ``R * shards`` partial top-k sets host-side and maps global row
    indices back to string ids.
    """

    def __init__(self, index: LoadedIndex, *, k: int = 10,
                 buckets: Sequence[int] = (1,),
                 block_n: int | None = None, plan: Any = None,
                 aot_store: Any = None, label: str | None = None):
        if len(index) == 0:
            raise ValueError(f"index {index.name!r} is empty")
        self.index = index
        self.k = int(k)
        self.buckets = tuple(sorted(int(b) for b in buckets))
        label = label or f"retrieval:{index.name}"
        corpus = index.vectors
        if plan is not None and not plan.is_trivial:
            replicas = plan.replicas
            chunk = math.ceil(len(index) / replicas)
            meshes = plan.meshes()
            self.searchers = [
                Searcher(corpus[r * chunk:(r + 1) * chunk], k=self.k,
                         buckets=self.buckets, block_n=block_n,
                         mesh=meshes[r], base=r * chunk, pad_rows=chunk,
                         aot_store=aot_store, label=label)
                for r in range(replicas)]
        else:
            self.searchers = [
                Searcher(corpus, k=self.k, buckets=self.buckets,
                         block_n=block_n, aot_store=aot_store,
                         label=label)]
        #: {bucket: "aot"|"miss"|"compile"|"fallback"|"mixed"} after warmup
        self.warmup_report: dict[int, str] = {}
        self._dispatch_lock = threading.Lock()

    @property
    def block_n(self) -> int:
        return self.searchers[0].block_n

    def trace_count(self) -> int:
        return sum(s.trace_count() for s in self.searchers)

    def resident_bytes(self) -> int:
        return sum(s.resident_bytes() for s in self.searchers)

    def prepare(self, bucket: int) -> str:
        sources = {s.prepare(bucket) for s in self.searchers}
        return sources.pop() if len(sources) == 1 else "mixed"

    def warmup(self) -> dict[int, str]:
        """Warm every (replica, bucket); returns the aggregated
        {bucket: source} map the serve ready line reports."""
        for searcher in self.searchers:
            searcher.warmup()
        report: dict[int, str] = {}
        for bucket in self.buckets:
            sources = {s.sources.get(bucket) for s in self.searchers}
            report[bucket] = (sources.pop() if len(sources) == 1
                              else "mixed")
        self.warmup_report = report
        return report

    def search(self, queries: np.ndarray
               ) -> tuple[np.ndarray, np.ndarray, list[list[str]]]:
        """Top-k over the whole index for a ``(B, D)`` (or ``(D,)``) query
        batch. Queries are unit-normalized host-side (cosine metric).
        Returns ``(values (B, k'), indices (B, k'), ids)`` with
        ``k' = min(k, N)``."""
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None, :]
        if queries.ndim != 2 or queries.shape[1] != self.index.dim:
            raise ValueError(
                f"queries must be (B, {self.index.dim}); got "
                f"{queries.shape}")
        queries = normalize_rows(queries)
        # one search on the device at a time: handler threads all land
        # here, and concurrently launched collective programs interleave
        # their rendezvous on the shared replica submeshes and deadlock
        with self._dispatch_lock:
            partials = [s.search_partial(queries) for s in self.searchers]
        values = np.concatenate([p[0] for p in partials], axis=0)
        indices = np.concatenate([p[1] for p in partials], axis=0)
        k_eff = min(self.k, len(self.index))
        vals, idx = merge_partials(values, indices, k_eff)
        ids = [[self.index.ids[j] for j in row] for row in idx]
        return vals, idx, ids
