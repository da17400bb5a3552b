"""Service facade gluing the vector store and the top-k searcher into the
serving stack, plus the ``jimm_retrieval`` observability namespace.

:class:`RetrievalService` is what ``serve --index`` constructs and
:class:`~jimm_tpu.serve.server.ServingServer` consults for ``/v1/search``:
it owns the loaded index, the warm searcher — exact
:class:`~jimm_tpu.retrieval.topk.IndexSearcher`, approximate
:class:`~jimm_tpu.retrieval.ann.ivf.IvfIndexSearcher`, or budgeted
:class:`~jimm_tpu.retrieval.tier.TieredSearcher` (which adds the
``jimm_tier_*`` residency gauges), per ``serve --index-mode`` — and the
metric series the obs docs list:

- ``jimm_retrieval_search_total`` / ``jimm_retrieval_embed_total``
  counters (embed counts rows, not requests: a bulk ``/v1/embed`` of 16
  images is 16),
- ``jimm_retrieval_index_size`` / ``jimm_retrieval_index_segments`` /
  ``jimm_retrieval_index_staleness_seconds`` gauges (staleness = seconds
  since the manifest last changed; a serving process holds the index
  snapshot it loaded, so a growing staleness under active writers says
  "restart or reload me"),
- in ivf mode, ``jimm_retrieval_ivf_nprobe`` /
  ``jimm_retrieval_ivf_candidate_frac`` /
  ``jimm_retrieval_ivf_recall_proxy`` gauges tracking the most recent
  search: probe width, fraction of the corpus rescored, and the fill
  ratio (results found / k — a cheap online recall proxy;
  ``scripts/ann_frontier.py`` measures recall@10 against the exact oracle),
- the ``retrieval_topk`` / ``retrieval_ivf`` span around every scoring
  call (device scan + host merge), in ``jimm_spans_*`` like every span.

Everything here is callable from HTTP handler threads (blocking is fine;
the engine's event loop is never entered) and from the CLI.
"""

from __future__ import annotations

import time
from typing import Any

import numpy as np

from jimm_tpu.retrieval.store import (LoadedIndex, RetrievalStoreError,
                                      VectorStore)
from jimm_tpu.retrieval.topk import IndexSearcher

__all__ = ["RetrievalService", "retrieval_metrics"]


def retrieval_metrics():
    """The ``jimm_retrieval`` registry's (search_total, embed_total)
    counters — shared by the service and the bulk-embed endpoint."""
    from jimm_tpu import obs
    reg = obs.get_registry("jimm_retrieval")
    return reg.counter("search_total"), reg.counter("embed_total")


class RetrievalService:
    """One named index, searchable: loaded snapshot + warm searcher +
    metrics. Built once at serve startup (``from_store``) or directly in
    tests/benches with a pre-built searcher. ``mode`` is ``"exact"``
    (streaming full-scan top-k) or ``"ivf"`` (two-stage approximate; the
    searcher must then be an ``IvfIndexSearcher`` and requests may carry
    a per-call ``nprobe``)."""

    def __init__(self, index: LoadedIndex, searcher: Any, *,
                 store: VectorStore | None = None, mode: str = "exact",
                 nprobe: int | None = None):
        from jimm_tpu import obs
        if mode not in ("exact", "ivf", "tiered"):
            raise ValueError(f"mode must be 'exact', 'ivf', or 'tiered'; "
                             f"got {mode!r}")
        self.index = index
        self.searcher = searcher
        self.store = store
        self.mode = mode
        self.search_counter, self.embed_counter = retrieval_metrics()
        reg = obs.get_registry("jimm_retrieval")
        reg.gauge("index_size", lambda: float(len(self.index)))
        reg.gauge("index_segments", fn=self._segments_now)
        reg.gauge("index_staleness_seconds", fn=self._staleness_now)
        if mode in ("ivf", "tiered"):
            from jimm_tpu.retrieval.ann.ivf import DEFAULT_NPROBE
            cap = searcher.nprobe_max
            self.default_nprobe = min(
                int(nprobe) if nprobe is not None else DEFAULT_NPROBE, cap)
            if self.default_nprobe < 1:
                raise ValueError(f"nprobe must be >= 1; got {nprobe}")
            stat = lambda key: lambda: float(  # noqa: E731
                self.searcher.last_stats.get(key, 0.0))
            reg.gauge("ivf_nprobe", fn=stat("nprobe"))
            reg.gauge("ivf_candidate_frac", fn=stat("candidate_frac"))
            # fill ratio (found / k) — online recall proxy: probing too
            # few clusters surfaces as under-filled result rows long
            # before an offline frontier run quantifies the recall loss
            reg.gauge("ivf_recall_proxy", fn=stat("fill_ratio"))
        else:
            self.default_nprobe = None

    @classmethod
    def from_store(cls, store: VectorStore, name: str, *, k: int = 10,
                   buckets=(1,), block_n: int | None = None,
                   plan: Any = None, aot_store: Any = None,
                   mode: str = "exact", nprobe: int | None = None,
                   nprobe_max: int = 32,
                   device_budget_bytes: int | None = None,
                   host_budget_bytes: int | None = None
                   ) -> "RetrievalService":
        index = store.load(name)
        if mode in ("ivf", "tiered"):
            loaded = store.codebook(name)
            if loaded is None:
                raise RetrievalStoreError(
                    f"index {name!r} has no trained codebook — run "
                    f"`jimm-tpu index train-centroids` (and `build-ivf`) "
                    f"before serving with --index-mode {mode}")
            centroids, _meta = loaded
            assign = store.load_assignments(name)
            if mode == "tiered":
                from jimm_tpu.retrieval.tier import TieredSearcher
                searcher: Any = TieredSearcher(
                    index, centroids, assign, k=k, nprobe_max=nprobe_max,
                    buckets=buckets, block_n=block_n,
                    device_budget_bytes=device_budget_bytes,
                    host_budget_bytes=host_budget_bytes,
                    aot_store=aot_store, artifacts=store.artifacts)
            else:
                from jimm_tpu.retrieval.ann.ivf import IvfIndexSearcher
                searcher = IvfIndexSearcher(
                    index, centroids, assign, k=k, nprobe_max=nprobe_max,
                    buckets=buckets, block_n=block_n, plan=plan,
                    aot_store=aot_store)
        else:
            searcher = IndexSearcher(index, k=k, buckets=buckets,
                                     block_n=block_n, plan=plan,
                                     aot_store=aot_store)
        return cls(index, searcher, store=store, mode=mode, nprobe=nprobe)

    # -- gauges -----------------------------------------------------------

    def _segments_now(self) -> float:
        if self.store is None:
            return 1.0
        try:
            return float(self.store.stats(self.index.name)["segments"])
        except Exception:  # noqa: BLE001 — a gauge must never raise
            return 0.0

    def _staleness_now(self) -> float:
        """Seconds since the *on-disk* manifest last changed — reads
        through to the store so concurrent writers move this gauge even
        though the serving snapshot is pinned."""
        updated = self.index.updated
        if self.store is not None:
            try:
                updated = float(
                    self.store.manifest(self.index.name)["updated"])
            except Exception:  # noqa: BLE001
                pass
        return max(0.0, round(time.time() - updated, 3))

    # -- lifecycle --------------------------------------------------------

    def warmup(self) -> dict[int, str]:
        """Warm every (replica, bucket); the serve ready line and healthz
        report the per-bucket sources."""
        return self.searcher.warmup()

    def trace_count(self) -> int:
        return self.searcher.trace_count()

    def describe(self) -> dict:
        out = {"index": self.index.name, "rows": len(self.index),
               "dim": self.index.dim, "dtype": self.index.dtype,
               "metric": self.index.metric, "k": self.searcher.k,
               "block_n": self.searcher.block_n,
               "buckets": list(self.searcher.buckets),
               "partitions": len(getattr(self.searcher, "searchers", [0])),
               "mode": self.mode,
               "staleness_s": self._staleness_now()}
        if self.mode in ("ivf", "tiered"):
            out["nprobe"] = self.default_nprobe
            out["nprobe_max"] = self.searcher.nprobe_max
            out["clusters"] = self.searcher.n_clusters
        if self.mode == "tiered":
            out["resident_bytes"] = self.searcher.resident_bytes()
            out["tiers"] = self.searcher.tier_plan().describe()
        return out

    # -- queries ----------------------------------------------------------

    def search_blocking(self, queries: np.ndarray, k: int | None = None,
                        nprobe: int | None = None
                        ) -> tuple[np.ndarray, list[list[str]]]:
        """Top-k ids + scores for a ``(D,)`` or ``(B, D)`` query batch.
        ``k`` may trim below the searcher's compiled k but never exceed it
        (the device program's carry width is fixed at build time). In ivf
        mode ``nprobe`` widens/narrows the probe per call — a runtime
        scalar up to the compiled ``nprobe_max``, never a recompile. Call
        from a handler thread or the CLI — this blocks on the device."""
        from jimm_tpu import obs
        from jimm_tpu.serve.admission import RequestError
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None, :]
        if queries.ndim != 2 or queries.shape[1] != self.index.dim:
            raise RequestError(
                f"query must have dim {self.index.dim} (index "
                f"{self.index.name!r}); got shape {tuple(queries.shape)}")
        if not np.all(np.isfinite(queries)):
            raise RequestError("query contains non-finite values")
        k_eff = self.searcher.k if k is None else int(k)
        if k_eff < 1 or k_eff > self.searcher.k:
            raise RequestError(
                f"k must be in [1, {self.searcher.k}] (the searcher's "
                f"compiled carry width); got {k_eff}")
        if self.mode in ("ivf", "tiered"):
            np_eff = self.default_nprobe if nprobe is None else int(nprobe)
            if np_eff < 1 or np_eff > self.searcher.nprobe_max:
                raise RequestError(
                    f"nprobe must be in [1, {self.searcher.nprobe_max}] "
                    f"(the searcher's compiled probe width); got {np_eff}")
            span_name = ("retrieval_tier" if self.mode == "tiered"
                         else "retrieval_ivf")
            with obs.span(span_name):
                values, _indices, ids = self.searcher.search(
                    queries, nprobe=np_eff)
        else:
            if nprobe is not None:
                raise RequestError(
                    "nprobe is only valid in ivf index mode (this server "
                    "runs --index-mode exact)")
            with obs.span("retrieval_topk"):
                values, _indices, ids = self.searcher.search(queries)
        self.search_counter.inc(queries.shape[0])
        return values[:, :k_eff], [row[:k_eff] for row in ids]
