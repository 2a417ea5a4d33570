"""The barbell B3,1 as a standing count: the optimizer's bushy plan (two
triangle stages that materialise on the device, weighted stage tries, a
root that weighs the bridge by them) kept exact under friend-of-a-friend
appends, against a dense numpy count."""
import numpy as np
import pytest

from chipbench import datagen
from chipbench.drivers import standing
from repro.core import obs, relcache
from repro.relational.schema import Atom, Query
from repro.serve import StandingQueryEngine

TRIANGLES = [("E1", ("x", "a")), ("E2", ("a", "b")), ("E3", ("b", "x")),
             ("E4", ("y", "c")), ("E5", ("c", "d")), ("E6", ("d", "y"))]


def dense_barbell(src, dst) -> tuple[int, int]:
    """(the count: sum over edges (x, y) of A[x, y] t(x) t(y), with
    t(v) = (A^3)[v, v]; the directed triangles, trace(A^3))."""
    n = int(max(src.max(), dst.max())) + 1
    a = np.zeros((n, n), np.int64)
    np.add.at(a, (src, dst), 1)
    t = np.diag(a @ a @ a)
    return int((a * np.outer(t, t)).sum()), int(t.sum())


def _rows() -> int:
    return obs.snapshot()["counters"].get("standing.stage_rows", 0)


@pytest.mark.parametrize("scale, seed", [(6, 11), (7, 2**31 + 77)])
def test_standing_barbell_tracks_a_dense_count(scale, seed):
    edges = datagen.kron_tables(scale)["edges"]
    atoms = TRIANGLES + [("B", ("x", "y"))]
    q = Query([Atom("edges", vs, alias) for alias, vs in atoms])
    rels = {alias: edges.rename({"a": s, "b": d}) for alias, (s, d) in atoms}
    src, dst = np.asarray(edges.columns["a"]), np.asarray(edges.columns["b"])
    eng = StandingQueryEngine()
    rows = _rows()
    sq = eng.register(q, rels, agg="count")
    count, triangles = dense_barbell(src, dst)
    assert sq.result == count
    # each triangle stage materialises one row per directed triangle
    assert _rows() - rows == 2 * triangles
    names = [n for n, *_ in eng._runners[sq.template.key]]
    assert len(names) == 3 and names[-1] == "__root"  # two stages and a root
    for bs, bd in standing.batches(seed, 3, src, dst, 64):
        recomputed, rows = eng.stages_recomputed, _rows()
        for alias, (s, d) in TRIANGLES:
            relcache.append(rels[alias], {s: bs, d: bd})
        eng.ingest(rels["B"], {"x": bs, "y": bd})
        src, dst = np.concatenate([src, bs]), np.concatenate([dst, bd])
        count, triangles = dense_barbell(src, dst)
        assert sq.result == count
        assert eng.stages_recomputed - recomputed == 3
        assert _rows() - rows == 2 * triangles
    assert eng.degraded_refreshes == 0 and sq.degraded_to is None
