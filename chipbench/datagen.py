"""The benchmark's data generator: the Graph 500 Kronecker graph, as the
GAP Benchmark Suite builds it for its triangle-counting kernel.

Graph 500's generator draws `edgefactor * 2**scale` edges; each picks one
quadrant of the adjacency matrix per bit of the vertex id, with the
initiator probabilities A, B, C, D (0.57, 0.19, 0.19, 0.05), and the
vertex labels are then randomly permuted. GAP's builder turns the edge
list into an undirected graph without self-loops or repeated edges. Here
that graph is one relation `edges(a, b)` holding each undirected edge in
both directions, so the directed triangle query counts every triangle six
times.

This is the yardstick: the program may change, this may not, so the same
seed gives the same graph in every later check.
"""
from __future__ import annotations

import numpy as np

from repro.relational.relation import Relation
from repro.relational.schema import Atom, Query


def kronecker_edges(scale: int, edgefactor: int, initiator, seed: int):
    """Graph 500's edge list: `edgefactor * 2**scale` (src, dst) pairs over
    `2**scale` vertices, labels permuted; self-loops and repeats kept."""
    a, b, c, _d = initiator
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, edgefactor << scale
    ab = a + b
    c_norm, a_norm = c / (1.0 - ab), a / ab
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        row = rng.random(m) > ab
        col = rng.random(m) > np.where(row, c_norm, a_norm)
        src |= row.astype(np.int64) << bit
        dst |= col.astype(np.int64) << bit
    perm = rng.permutation(n).astype(np.int64)
    order = rng.permutation(m)
    return perm[src][order], perm[dst][order]


def undirected_simple(src, dst, n: int):
    """GAP's cleaning: self-loops and repeated edges dropped, each
    remaining undirected edge once as (lo, hi), sorted."""
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep])
    hi = np.maximum(src[keep], dst[keep])
    key = np.unique(lo * n + hi)
    return key // n, key % n


def kron_tables(
    scale: int, edgefactor: int = 16, initiator=(0.57, 0.19, 0.19, 0.05), seed: int = 1
) -> dict[str, Relation]:
    lo, hi = undirected_simple(*kronecker_edges(scale, edgefactor, initiator, seed), 1 << scale)
    edges = Relation("edges", {"a": np.concatenate([lo, hi]), "b": np.concatenate([hi, lo])})
    return {"edges": edges}


def kron_queries(tables: dict[str, Relation]):
    """(name, Query, relations): the triangle over the symmetric edges."""
    e = tables["edges"]
    q = Query(
        [
            Atom("edges", ("a", "b"), "K1"),
            Atom("edges", ("b", "c"), "K2"),
            Atom("edges", ("c", "a"), "K3"),
        ]
    )
    rels = {
        "K1": e,
        "K2": e.rename({"a": "b", "b": "c"}),
        "K3": e.rename({"a": "c", "b": "a"}),
    }
    return [("triangle", q, rels)]
