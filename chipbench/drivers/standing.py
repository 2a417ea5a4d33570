"""Standing count under appends: a `StandingQueryEngine` holds one count
while batches of edges arrive, back to back.

Mix keys: `query` (a directed triangle over one symmetric edge relation,
read under three aliases), `ingest_alias` (the alias whose append goes
through `ingest`, after the others' `relcache.append`), `batch_edges`
(rows per batch: half as many new undirected edges, each in both
directions), `setup_batches` (applied in set-up, to bring the buffers
into their steady size) and `window_batches` (the most the window may
apply, so that the relation stays in one size bucket).

Every new edge closes a triangle with the graph as it was: a friend of a
friend, u - w - v with u drawn among the vertices that have edges, w among
u's neighbours and v among w's, and {u, v} not yet an edge. So every row
of a batch moves the count, and a refresh that leaves any part of a batch
out reads a count that is too low.

`edges_per_s` is the appended rows whose refreshed count was returned
over the time of those batches. The traced run traces the batches that
start in the middle third of the window.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import harness, reference


def batches(seed: int, n: int, src, dst, size: int):
    """`n` batches of `size` rows from the seed: `size // 2` new undirected
    edges {u, v}, each closing a triangle u - w - v of the symmetric graph
    (src, dst), first as u -> v, then as v -> u."""
    rng = np.random.default_rng([seed, 0x57A9D])
    order = np.argsort(src, kind="stable")
    nbr = dst[order]
    lo = np.searchsorted(src[order], np.arange(int(src.max()) + 2))
    deg = np.diff(lo)
    vertices = np.flatnonzero(deg)
    span = int(max(src.max(), dst.max())) + 1
    taken = set((src * span + dst).tolist())
    need = n * (size // 2)
    us, vs = [], []
    while len(us) < need:
        u = rng.choice(vertices, 4 * need)
        w = nbr[lo[u] + (rng.random(len(u)) * deg[u]).astype(np.int64)]
        v = nbr[lo[w] + (rng.random(len(w)) * deg[w]).astype(np.int64)]
        for a, b in zip(u.tolist(), v.tolist()):
            if a != b and a * span + b not in taken and len(us) < need:
                taken.update((a * span + b, b * span + a))
                us.append(a)
                vs.append(b)
    us, vs = np.asarray(us, np.int64), np.asarray(vs, np.int64)
    half = size // 2
    out = []
    for i in range(n):
        u, v = us[i * half : (i + 1) * half], vs[i * half : (i + 1) * half]
        out.append((np.concatenate([u, v]), np.concatenate([v, u])))
    return out


class Driver:
    def __init__(self, run: harness.Run):
        self.run = run
        self.mix = run.mix
        self.results: list = []  # per window batch: (count, degraded, seconds)
        self.setup_results: list = []
        self.compiles: list = []  # compiles (not cache loads) per window batch
        self.traced = {"batches": 0, "edges": 0}

    def setup(self) -> None:
        from repro.serve import StandingQueryEngine

        _tables, queries = harness.generate(self.run.config, self.run.seed)
        q, rels = queries[self.mix["query"]]
        self.query, self.rels = q, rels
        atoms, data = harness.plain(q, rels)
        if not reference.is_triangle(atoms, data):
            raise harness.BenchError(f"{self.mix['query']} is not a directed triangle")
        alias, (s, d) = atoms[0]
        self.base = (data[alias][s].copy(), data[alias][d].copy())
        self.atoms = atoms
        n_setup = self.mix["setup_batches"]
        self.batches = batches(
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
                      "count": self.sq.result})
        self.registered = (self.sq.result, self.sq.degraded_to)
        for i in range(n_setup):
            t0 = time.perf_counter()
            self.setup_results.append(self._apply(i))
            self.run.log({"phase": "setup", "batch": i, "seconds": time.perf_counter() - t0,
                          "count": self.setup_results[-1][0]})
        self.next = n_setup

    def _warm_appends(self, rows: int, n: int) -> None:
        """Every append shape the run will use, built in set-up: an append
        extends a relation's device columns by a concatenate whose shape
        grows by one batch each time. The same appends on a scratch
        relation of the same size compile (or load) those programs."""
        from repro.core import relcache
        from repro.core.compiled import device_columns
        from repro.relational.relation import Relation

        _alias, (a, b) = self.atoms[0]
        scratch = Relation("warm", {a: np.zeros(rows, np.int64), b: np.zeros(rows, np.int64)})
        device_columns(scratch)
        zeros = np.zeros(self.mix["batch_edges"], np.int64)
        for _ in range(n):
            relcache.append(scratch, {a: zeros, b: zeros})

    def _apply(self, i: int):
        from repro.core import relcache

        src, dst = self.batches[i]
        refreshes = self.engine.degraded_refreshes
        with self.run.spans("batch"):
            with self.run.spans("append"):
                for alias, (a, b) in self.atoms:
                    if alias != self.mix["ingest_alias"]:
                        relcache.append(self.rels[alias], {a: src, b: dst})
            a, b = dict(self.atoms)[self.mix["ingest_alias"]]
            with self.run.spans("ingest"):
                self.engine.ingest(self.rels[self.mix["ingest_alias"]], {a: src, b: dst})
            count = int(self.sq.result)
        degraded = self.sq.degraded_to or (
            "refresh" if self.engine.degraded_refreshes != refreshes else None
        )
        return count, degraded

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        deadline = t0 + seconds
        tracer = self.run.tracer
        while time.perf_counter() < deadline and self.next < len(self.batches):
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds / 3:
                tracer.start()
            if elapsed >= 2 * seconds / 3:
                tracer.stop()
            b0, c0 = time.perf_counter(), harness.COMPILES.compiled()
            count, degraded = self._apply(self.next)
            self.results.append((count, degraded, time.perf_counter() - b0))
            self.compiles.append(harness.COMPILES.compiled() - c0)
            if tracer.active:
                self.traced["batches"] += 1
                self.traced["edges"] += len(self.batches[self.next][0])
            self.next += 1
        tracer.stop()

    def window_report(self) -> dict:
        return {
            "batches": len(self.results),
            "batch_s": [r[2] for r in self.results][:40],
            "batch_compiles": self.compiles[:40],
        }

    def end_to_end(self) -> dict:
        if not self.results:
            return {}
        edges = self.mix["batch_edges"] * len(self.results)
        return {"edges_per_s": edges / sum(r[2] for r in self.results)}

    def slice_report(self) -> dict:
        return dict(self.traced)

    def release(self) -> None:
        self.engine = self.sq = self.rels = self.query = None

    def expected(self) -> list:
        """The exact count after registering and after every batch."""
        ref = reference.TriangleCounter(*self.base)
        out = [ref.count]
        for i in range(self.next):
            out.append(ref.append(*self.batches[i]))
        return out

    def check(self, control: bool = False) -> harness.Checks:
        want = self.expected()
        answers = [self.registered] + [r[:2] for r in self.setup_results]
        answers += [r[:2] for r in self.results]
        if control:
            # the control: a refresh that returns its state unchanged, so
            # each acknowledged batch reads the count from before it
            answers = [(want[0], None)] + [(w, None) for w in want[:-1]]
        checks = harness.Checks(attempted=len(want))
        for (got, degraded), w in zip(answers, want):
            if degraded is not None:
                checks.degraded += 1
            elif got != w:
                checks.wrong += 1
        checks.missing = len(want) - len(answers)
        return checks
