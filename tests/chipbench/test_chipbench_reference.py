"""The plain reference against brute force and against the eager engine,
and the generator against the Graph 500 and GAP rules, at small sizes; the
reference's control gives up exactness."""
import numpy as np
import pytest
from chipbench_kit import harness

from chipbench import datagen, reference


def _dense(src, dst, n):
    a = np.zeros((n, n), np.int64)
    np.add.at(a, (src, dst), 1)
    return a


def _triangle(src, dst):
    q, rels = {}, {}
    for alias, (s, d) in zip(("K1", "K2", "K3"), (("a", "b"), ("b", "c"), ("c", "a"))):
        q[alias] = (s, d)
        rels[alias] = {s: src, d: dst}
    return list(q.items()), rels


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_triangle_count_is_the_trace_of_the_cube(seed, monkeypatch):
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, 30, 400), rng.integers(0, 30, 400)
    a = _dense(src, dst, 30)
    assert reference.count(*_triangle(src, dst)) == np.trace(a @ a @ a)
    monkeypatch.setattr(reference, "_BLOCK", 7)  # many blocks, one answer
    assert reference.count(*_triangle(src, dst)) == np.trace(a @ a @ a)


def test_triangle_counter_stays_exact_under_appends():
    rng = np.random.default_rng(7)
    src, dst = rng.integers(0, 40, 300), rng.integers(0, 40, 300)
    tc = reference.TriangleCounter(src, dst)
    for _ in range(4):
        a, b = rng.integers(0, 40, 60), rng.integers(0, 40, 60)
        got = tc.append(a, b)
        src, dst = np.concatenate([src, a]), np.concatenate([dst, b])
        m = _dense(src, dst, 40)
        assert got == np.trace(m @ m @ m)


def test_reference_takes_only_a_directed_triangle():
    atoms, data = _triangle(np.arange(5), np.arange(5))
    path = [("K1", ("a", "b")), ("K2", ("b", "c")), ("K3", ("c", "d"))]
    with pytest.raises(ValueError):
        reference.count(path, {"K1": data["K1"], "K2": data["K2"], "K3": {"c": [], "d": []}})
    assert reference.is_triangle(atoms, data)


@pytest.mark.parametrize("scale", [6, 8])
def test_counts_match_the_eager_engine(scale):
    from repro.core import free_join

    ((_name, q, rels),) = datagen.kron_queries(datagen.kron_tables(scale, seed=4))
    want = reference.count(*harness.plain(q, rels))
    assert want > 0 and want % 6 == 0  # six directed triangles per undirected one
    assert want == free_join(q, rels, agg="count")


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_kron_graph_follows_the_graph500_and_gap_rules(seed):
    n, m = 1 << 10, 16 << 10
    src, dst = datagen.kronecker_edges(10, 16, (0.57, 0.19, 0.19, 0.05), seed)
    assert len(src) == m and 0 <= src.min() and max(src.max(), dst.max()) < n
    again = datagen.kronecker_edges(10, 16, (0.57, 0.19, 0.19, 0.05), seed)
    assert np.array_equal(src, again[0]) and np.array_equal(dst, again[1])
    e = datagen.kron_tables(10, seed=seed)["edges"]
    a, b = np.asarray(e.columns["a"]), np.asarray(e.columns["b"])
    pairs = set(zip(a.tolist(), b.tolist()))
    assert len(pairs) == len(a) and all(u != v and (v, u) in pairs for u, v in pairs)
    assert pairs == {(u, v) for u, v in zip(src.tolist(), dst.tolist()) if u != v} | {
        (v, u) for u, v in zip(src.tolist(), dst.tolist()) if u != v
    }
    deg = np.bincount(a, minlength=n)
    assert deg.max() > 20 * deg[deg > 0].mean() / 4  # skewed: hubs far above the mean


def test_half_sample_control_is_not_exact():
    ((_name, q, rels),) = datagen.kron_queries(datagen.kron_tables(8))
    atoms, data = harness.plain(q, rels)
    exact = reference.count(atoms, data)
    assert exact > 1000
    assert reference.half_sample_count(atoms, data, seed=9) != exact
