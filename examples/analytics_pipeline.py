"""The two pillars together: the Free Join engine running the *framework's*
relational work — corpus sample selection for LM training (DESIGN.md §5.1)
and distributed (HyperCube) counting of a graph statistic.

  PYTHONPATH=src python examples/analytics_pipeline.py
"""
import numpy as np

from repro.core.distributed import distributed_join_host, hypercube_shares
from repro.relational.relation import Relation
from repro.relational.schema import Atom, Query
from repro.train.data import DataConfig, select_corpus_samples, synthetic_batch


def main():
    rng = np.random.default_rng(0)
    n_docs = 200_000
    docs = Relation(
        "Docs",
        {
            "doc": np.arange(n_docs, dtype=np.int64),
            "shard": rng.integers(0, 64, n_docs),
            "lang": rng.integers(0, 30, n_docs),
        },
    )
    quality = Relation(
        "Quality",
        {"doc": np.arange(n_docs, dtype=np.int64), "score": rng.integers(0, 100, n_docs)},
    )
    canonical = np.arange(n_docs, dtype=np.int64)
    dup = rng.random(n_docs) < 0.2  # 20% duplicates point elsewhere
    canonical[dup] = rng.integers(0, n_docs, int(dup.sum()))
    dedup = Relation("Dedup", {"doc": np.arange(n_docs, dtype=np.int64), "canonical": canonical})

    keep = select_corpus_samples(docs, quality, dedup, min_quality=60)
    print(f"corpus selection: kept {len(keep):,} / {n_docs:,} docs "
          f"(quality>=60 and canonical) via Free Join")

    # feed the kept set into the deterministic batch stream
    dcfg = DataConfig(vocab=32000, seq_len=64, global_batch=8)
    batch = synthetic_batch(dcfg, step=0)
    print(f"first batch: inputs {batch['inputs'].shape}, labels {batch['labels'].shape}")

    # distributed analytics: triangle count over a follow graph, HyperCube
    n_edges, n_people = 60_000, 8_000
    knows = Relation(
        "knows",
        {"a": rng.integers(0, n_people, n_edges), "b": rng.integers(0, n_people, n_edges)},
    )
    q = Query(
        [
            Atom("knows", ("a", "b"), "K1"),
            Atom("knows", ("b", "c"), "K2"),
            Atom("knows", ("c", "a"), "K3"),
        ]
    )
    rels = {
        "K1": knows,
        "K2": knows.rename({"a": "b", "b": "c"}),
        "K3": knows.rename({"a": "c", "b": "a"}),
    }
    shares = hypercube_shares(q, {k: n_edges for k in rels}, 8)
    count = distributed_join_host(q, rels, num_shards=8, agg="count")
    print(f"triangle count over 8 HyperCube shards (shares={shares}): {count:,}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
