"""The plain reference of the barbell count: numpy alone.

It imports nothing of the program under test. The barbell B3,1 over one
symmetric edge multiset with adjacency A (two directed triangles
x -> a -> b -> x and y -> c -> d -> y joined by the bridge x -> y) counts

    sum over edges (x, y) of A[x, y] * t(x) * t(y),   t(v) = (A^3)[v, v],

each triangle in all six orientations and the bridge in both directions,
as the directed triangle counts its six. t is summed over the 2-paths
v -> m -> c, a block of them at a time, each closed by a lookup of the
edge c -> v, in int64. `BarbellCounter` recomputes the count from scratch
after every appended batch. An intermediate that could pass 2**62 raises.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference import _BLOCK, _LIMIT, _check, _edges, _lookup


def closed_walks(e, n: int) -> np.ndarray:
    """t(v) = (A^3)[v, v] for v < n, int64, over the distinct sorted edges
    `e` = (src, dst, multiplicity) of `reference._edges`."""
    es, ed, ew = e
    lo = np.searchsorted(es, ed, "left")
    k = np.searchsorted(es, ed, "right") - lo  # 2-paths through each edge
    ends = np.cumsum(k)
    t = np.zeros(n, np.int64)
    start = 0
    while start < len(es):
        stop = max(start + 1, int(np.searchsorted(ends, ends[start] - k[start] + _BLOCK, "right")))
        kb = k[start:stop]
        first = np.repeat(np.cumsum(kb) - kb, kb)
        rows_1 = np.repeat(np.arange(start, stop), kb)  # v -> m
        rows_2 = lo[rows_1] + (np.arange(len(rows_1)) - first)  # m -> c
        w = ew[rows_1] * ew[rows_2]
        _check(w)
        w = w * _lookup(e, ed[rows_2], es[rows_1])  # c -> v
        _check(w)
        # rows_1 is sorted, so each v's walks are one run of w
        v = es[rows_1]
        if len(v):
            cut = np.flatnonzero(np.diff(v)) + 1
            t[v[np.r_[0, cut]]] += np.add.reduceat(w, np.r_[0, cut])
        start = stop
    return t


def count(src, dst) -> int:
    """The barbell count of the edge multiset (src, dst)."""
    e = _edges(src, dst)
    if len(e[0]) == 0:
        return 0
    n = int(max(e[0].max(), e[1].max())) + 1
    t = closed_walks(e, n)
    es, ed, ew = e
    if float(t.max()) ** 2 * float(ew.max()) * len(es) >= _LIMIT:
        raise OverflowError("an intermediate count could pass 2**62")
    return int((ew * t[es] * t[ed]).sum())


class BarbellCounter:
    """The barbell count of an edge multiset, recomputed from scratch as
    batches of edges are appended."""

    def __init__(self, src, dst):
        self.src = np.asarray(src, np.int64)
        self.dst = np.asarray(dst, np.int64)
        self.count = count(self.src, self.dst)

    def append(self, src, dst) -> int:
        self.src = np.concatenate([self.src, np.asarray(src, np.int64)])
        self.dst = np.concatenate([self.dst, np.asarray(dst, np.int64)])
        self.count = count(self.src, self.dst)
        return self.count
