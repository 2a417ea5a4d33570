"""A standing pattern count under appends: the loop of `standing.py` (the
same batches, timing, traced slice and `edges_per_s`) over a pattern that
the optimizer plans as a bushy, generalized-hypertree plan.

Mix keys: `query` (a pattern in PATTERNS, built here over the
configuration's symmetric edge relation, one alias per atom),
`ingest_alias` (the alias whose append goes through `ingest`, after the
other aliases' `relcache.append`), `batch_edges`, `setup_batches` and
`window_batches`, as in `standing.py`.

* `barbell`: EmptyHeaded's B3,1, two directed triangles x -> a -> b -> x
  and y -> c -> d -> y joined by the bridge B(x, y). The optimizer plans
  it as two triangle stages and a root over the bridge that weighs each
  bridge edge by the two stages' outputs.

Each batch's friend-of-a-friend edges close triangles, so every row raises
the count, and the count passes 2**31 at the configuration's scale:
`check` compares every count, exactly, with `reference_barbell.py`.
"""
from __future__ import annotations

import time

from chipbench import harness, reference_barbell
from chipbench.drivers import standing
from repro.relational.schema import Atom, Query


def barbell(edges):
    """The barbell B3,1 over the symmetric relation edges(a, b)."""
    atoms = [
        ("E1", ("x", "a")),
        ("E2", ("a", "b")),
        ("E3", ("b", "x")),
        ("E4", ("y", "c")),
        ("E5", ("c", "d")),
        ("E6", ("d", "y")),
        ("B", ("x", "y")),
    ]
    q = Query([Atom("edges", vs, alias) for alias, vs in atoms])
    rels = {alias: edges.rename({"a": s, "b": d}) for alias, (s, d) in atoms}
    return q, rels


PATTERNS = {"barbell": (barbell, reference_barbell.BarbellCounter)}


class Driver(standing.Driver):
    def setup(self) -> None:
        from repro.serve import StandingQueryEngine

        if self.mix["query"] not in PATTERNS:
            raise harness.BenchError(f"no pattern named {self.mix['query']!r}")
        build, self.counter = PATTERNS[self.mix["query"]]
        # the configuration's tables, rows shuffled by the seed as for every cell
        tables, _queries = harness.generate(self.run.config, self.run.seed)
        edges = tables["edges"]
        q, rels = build(edges)
        self.query, self.rels = q, rels
        self.atoms, _data = harness.plain(q, rels)
        self.base = (edges.columns["a"].copy(), edges.columns["b"].copy())
        n_setup = self.mix["setup_batches"]
        self.batches = standing.batches(
            self.run.seed,
            n_setup + self.mix["window_batches"],
            *self.base,
            self.mix["batch_edges"],
        )
        t0 = time.perf_counter()
        self._warm_appends(len(self.base[0]), len(self.batches))
        self.run.log({"phase": "setup", "warm_appends_s": time.perf_counter() - t0})
        self.engine = StandingQueryEngine()
        t0 = time.perf_counter()
        self.sq = self.engine.register(q, rels, agg="count")
        self.run.log({"phase": "setup", "register_s": time.perf_counter() - t0,
                      "count": self.sq.result, "stages": len(self.sq.states)})
        self.registered = (self.sq.result, self.sq.degraded_to)
        for i in range(n_setup):
            t0 = time.perf_counter()
            self.setup_results.append(self._apply(i))
            self.run.log({"phase": "setup", "batch": i, "seconds": time.perf_counter() - t0,
                          "count": self.setup_results[-1][0]})
        self.next = n_setup

    def expected(self) -> list:
        """The exact count after registering and after every batch, each
        recomputed from scratch by the plain reference."""
        ref = self.counter(*self.base)
        out = [ref.count]
        for i in range(self.next):
            out.append(ref.append(*self.batches[i]))
        return out
