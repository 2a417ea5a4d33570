"""The plain reference: exact triangle counts in numpy alone.

It imports nothing of the program under test and takes nothing the program
made. A query is a list of atoms, each `(alias, vars)`, and the data is
`{alias: {var: numpy column}}`: the columns the benchmark generated.

* `count`: the directed triangle a -> b -> c -> a over one edge multiset
  with adjacency A is trace(A^3), summed over 2-paths a -> m -> c in
  blocks of rows, each closed by a lookup of the edge c -> a.
* `TriangleCounter`: the count kept exact under appends, by
  trace((E + D)^3) = trace(E^3) + 3 tr(DEE) + 3 tr(DDE) + tr(DDD).

Counts are exact int64; an intermediate that could pass 2**62 raises.
The control (`half_sample_count`) gives up exactness: the count over a
seeded half of the edges, doubled.
"""
from __future__ import annotations

import numpy as np

_LIMIT = 2.0**62
_BLOCK = 1 << 22  # 2-paths per block


def _check(w: np.ndarray) -> None:
    if len(w) and float(w.max()) * len(w) >= _LIMIT:
        raise OverflowError("an intermediate count could pass 2**62")


def _edges(src, dst):
    """Distinct directed edges, sorted by (src, dst), with multiplicities."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    pairs, w = np.unique(np.stack([src, dst], axis=1), axis=0, return_counts=True)
    return pairs[:, 0].copy(), pairs[:, 1].copy(), w.astype(np.int64)


def _lookup(e, a, b):
    """Multiplicity of edge (a, b) in e, elementwise (0 where absent)."""
    es, ed, ew = e
    if len(es) == 0:
        return np.zeros(len(a), ew.dtype)
    key = es * (2**31) + ed
    q = np.asarray(a, np.int64) * (2**31) + np.asarray(b, np.int64)
    i = np.minimum(np.searchsorted(key, q), len(key) - 1)
    return np.where(key[i] == q, ew[i], 0)


def _trace3(x, y, z) -> int:
    """tr(XYZ): sum over a -> m -> c -> a of X[a,m] Y[m,c] Z[c,a], over the
    2-paths of x then y, a block of x's rows at a time."""
    xs, xd, xw = x
    ys, yd, yw = y
    order = np.argsort(ys, kind="stable")
    ys, yd, yw = ys[order], yd[order], yw[order]
    lo = np.searchsorted(ys, xd, "left")
    n = np.searchsorted(ys, xd, "right") - lo
    ends = np.cumsum(n)
    total, start = 0, 0
    while start < len(xd):
        stop = max(start + 1, int(np.searchsorted(ends, ends[start] - n[start] + _BLOCK, "right")))
        nb = n[start:stop]
        rows_x = np.repeat(np.arange(start, stop), nb)
        first = np.repeat(np.cumsum(nb) - nb, nb)
        rows_y = lo[rows_x] + (np.arange(len(rows_x)) - first)
        w = xw[rows_x] * yw[rows_y]
        _check(w)
        total += int((w * _lookup(z, yd[rows_y], xs[rows_x])).sum())
        start = stop
    return total


def is_triangle(atoms, data) -> bool:
    """True when the atoms are one directed triangle x -> y -> z -> x of
    binary atoms that all read the same edge multiset."""
    if len(atoms) != 3 or any(len(v) != 2 for _a, v in atoms):
        return False
    nxt = {v[0]: v[1] for _a, v in atoms}
    start = atoms[0][1][0]
    walk = [start]
    for _ in atoms:
        walk.append(nxt.get(walk[-1]))
    if walk[-1] != start or len(set(walk[:-1])) != 3:
        return False
    a0, (s0, d0) = atoms[0]
    return all(
        np.array_equal(data[alias][s], data[a0][s0]) and np.array_equal(data[alias][d], data[a0][d0])
        for alias, (s, d) in atoms[1:]
    )


def count(atoms, data) -> int:
    """The count of a directed triangle query over one edge list."""
    if not is_triangle(atoms, data):
        raise ValueError("the reference counts directed triangles over one edge list only")
    alias, (s, d) = atoms[0]
    e = _edges(data[alias][s], data[alias][d])
    return _trace3(e, e, e)


class TriangleCounter:
    """The directed triangle count of an edge multiset, kept exact while
    batches of edges are appended."""

    def __init__(self, src, dst):
        self._e = _edges(src, dst)
        self.count = _trace3(self._e, self._e, self._e)

    def append(self, src, dst) -> int:
        d = _edges(src, dst)
        e = self._e
        self.count += 3 * _trace3(d, e, e) + 3 * _trace3(d, d, e) + _trace3(d, d, d)
        s = np.concatenate([e[0], d[0]])
        t = np.concatenate([e[1], d[1]])
        w = np.concatenate([e[2], d[2]])
        pairs, inv = np.unique(np.stack([s, t], axis=1), axis=0, return_inverse=True)
        merged = np.zeros(len(pairs), np.int64)
        np.add.at(merged, inv.ravel(), w)
        self._e = (pairs[:, 0].copy(), pairs[:, 1].copy(), merged)
        return self.count


def half_sample_count(atoms, data, seed: int) -> int:
    """The control of an exact count: the count over a seeded half of the
    edges (one edge list read under every alias), doubled. A count that
    gives up exactness for speed reads like this."""
    rng = np.random.default_rng([seed, 0x5A])
    alias = atoms[0][0]
    keep = rng.random(len(next(iter(data[alias].values())))) < 0.5
    half = {a: {v: c[keep] for v, c in cols.items()} for a, cols in data.items()}
    return 2 * count(atoms, half)
