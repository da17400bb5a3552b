"""Recall@k-vs-QPS frontier for IVF retrieval (docs/retrieval.md).

For each corpus size, trains a ~sqrt(N) codebook over a seeded clustered
corpus, then sweeps ``nprobe`` measuring, per point:

- **recall@10** against the exact-topk NumPy argsort oracle (the measured
  number that makes approximate retrieval a feature instead of a silent
  regression — see ISSUE/ROADMAP),
- **QPS** of the warm fused two-stage program (closed loop, single
  client: this is the kernel frontier, not the HTTP path); off the TPU the
  field is ``cpu_qps``, a host number that says nothing about the chip,
- **candidate_frac**, the fraction of the corpus the probe actually
  rescored (the work knob recall is being traded against).

An exact-mode row per corpus anchors the frontier at recall 1.0. Every
point is one JSON line with ``backend`` / ``index_mode`` / ``nprobe`` /
``recall_at_10`` fields.

Usage:
    JAX_PLATFORMS=cpu python -m scripts.ann_frontier
    python -m scripts.ann_frontier --corpus-sizes 200000 \
        --nprobes 1,2,4,8,16,32   # on a real TPU backend
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def frontier(args) -> None:
    import jax
    import numpy as np

    from jimm_tpu.retrieval.ann import (IvfIndexSearcher, clustered_rows,
                                        train_centroids)
    from jimm_tpu.retrieval.store import LoadedIndex
    from jimm_tpu.retrieval.topk import IndexSearcher

    on_tpu = jax.default_backend() == "tpu"
    backend = jax.default_backend()
    dim = args.dim or (512 if on_tpu else 64)
    nprobes = [int(x) for x in args.nprobes.split(",")]

    for n in (int(s) for s in args.corpus_sizes.split(",")):
        centers = max(8, n // 256)
        corpus, center_mat = clustered_rows(n, dim, centers, seed=3)
        queries, _ = clustered_rows(args.queries, dim, centers, seed=11,
                                    center_mat=center_mat)
        index = LoadedIndex(
            name=f"frontier{n}", ids=tuple(f"r{i}" for i in range(n)),
            vectors=corpus, dim=dim, dtype="float32", metric="cosine",
            state=f"frontier{n}", updated=time.time())
        k = min(10, n)
        # the oracle IS a host argsort — it is what "exact" means here
        oracle = np.argsort(-(queries @ corpus.T), axis=1,
                            kind="stable")[:, :k]
        oracle_sets = [set(row.tolist()) for row in oracle]

        clusters = max(1, min(int(np.sqrt(n)) or 1, n))
        codebook = train_centroids(corpus, clusters, iters=args.iters,
                                   seed=0)
        nprobe_max = max(min(max(nprobes), clusters), 1)
        bucket = min(args.queries, 64)
        searcher = IvfIndexSearcher(index, codebook, k=k,
                                    nprobe_max=nprobe_max,
                                    buckets=(bucket,),
                                    block_n=args.block_n)
        searcher.warmup()

        def timed(search_fn) -> tuple[float, list]:
            id_rows: list = []
            for _ in range(max(args.warmup_reps, 1)):
                search_fn(queries[:bucket])
            t0 = time.perf_counter()
            done = 0
            while done < args.queries:
                batch = queries[done:done + bucket]
                id_rows.extend(search_fn(batch)[2])
                done += len(batch)
            return (args.queries / (time.perf_counter() - t0)), id_rows

        qps_field = "qps" if on_tpu else "cpu_qps"
        base = {
            "metric": "ann_frontier",
            "workload": "ann_frontier", "backend": backend,
            "corpus_rows": n, "dim": dim, "clusters": clusters, "k": k,
            "block_n": searcher.block_n, "queries": args.queries,
        }
        for nprobe in nprobes:
            np_eff = min(nprobe, nprobe_max)
            qps, id_rows = timed(
                lambda q, np_=np_eff: searcher.search(q, nprobe=np_))
            recall = float(np.mean([
                len({int(r[1:]) for r in row} & oracle_sets[i]) / k
                for i, row in enumerate(id_rows)]))
            print(json.dumps({**base, "index_mode": "ivf", "nprobe": np_eff,
                              "recall_at_10": round(recall, 4),
                              qps_field: round(qps, 2),
                              "candidate_frac": searcher.last_stats.get(
                                  "candidate_frac")}), flush=True)
        exact = IndexSearcher(index, k=k, buckets=(bucket,),
                              block_n=args.block_n)
        exact.warmup()
        qps, id_rows = timed(lambda q: exact.search(q))
        recall = float(np.mean([
            len({int(r[1:]) for r in row} & oracle_sets[i]) / k
            for i, row in enumerate(id_rows)]))
        print(json.dumps({**base, "index_mode": "exact", "nprobe": None,
                          "recall_at_10": round(recall, 4),
                          qps_field: round(qps, 2), "candidate_frac": 1.0}),
              flush=True)


def main() -> int:
    import jimm_tpu.utils.env
    jimm_tpu.utils.env.configure_platform()

    p = argparse.ArgumentParser()
    p.add_argument("--corpus-sizes", default="50000",
                   help='comma-separated corpus sizes, e.g. "50000,200000"')
    p.add_argument("--nprobes", default="1,2,4,8,16",
                   help="comma-separated nprobe sweep")
    p.add_argument("--dim", type=int, default=None,
                   help="embedding dim (default: 512 on TPU, 64 off-TPU)")
    p.add_argument("--queries", type=int, default=256)
    p.add_argument("--iters", type=int, default=15,
                   help="k-means iterations")
    p.add_argument("--block-n", type=int, default=None,
                   help="rescore block size (default: tuner best_config)")
    p.add_argument("--warmup-reps", type=int, default=2)
    args = p.parse_args()

    frontier(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
