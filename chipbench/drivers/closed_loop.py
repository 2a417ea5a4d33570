"""Closed loop: one analyst re-runs a fixed list of count queries, in
whole passes, through `compiled_free_join`.

Mix keys: `queries` (names from the configuration's query generator, in
pass order).

Set-up runs each query twice (cold, then warm), so tries and runners are
cached and every shape the window uses is compiled. The window issues
passes back to back; at the deadline it issues nothing new and finishes
the query in flight. `queries_per_s` is the queries of completed passes
over the time of those passes. The traced run traces the window's first
pass.
"""
from __future__ import annotations

import time

from chipbench import harness, reference


class Driver:
    def __init__(self, run: harness.Run):
        self.run = run
        self.names = list(run.mix["queries"])
        self.answers: list = []  # (query name, count, degraded)
        self.passes: list = []  # (complete, seconds, n queries)
        self.traced = {"queries": 0}
        self.compiles: list = []  # compiles (not cache loads) per pass
        self.query_s: dict = {}  # query name -> seconds of each call

    def setup(self) -> None:
        from repro.core import compiled_free_join

        tables, queries = harness.generate(self.run.config, self.run.seed)
        missing = [n for n in self.names if n not in queries]
        if missing:
            raise harness.BenchError(f"queries not in the configuration: {missing}")
        self.queries = {n: queries[n] for n in self.names}
        for name in self.names:
            q, rels = self.queries[name]
            for when in ("cold", "warm"):
                t0 = time.perf_counter()
                info: dict = {}
                count = compiled_free_join(q, rels, agg="count", info=info)
                self.run.log(
                    {
                        "phase": "setup",
                        "query": name,
                        "call": when,
                        "seconds": time.perf_counter() - t0,
                        "count": int(count),
                        "retries": info["retries"],
                        "compiles": info["compiles"],
                    }
                )

    def _one(self, name: str):
        from repro.core import compiled_free_join

        q, rels = self.queries[name]
        info: dict = {}
        t0 = time.perf_counter()
        with self.run.spans(f"query:{name}"):
            count = compiled_free_join(q, rels, agg="count", info=info)
        self.query_s.setdefault(name, []).append(time.perf_counter() - t0)
        self.answers.append((name, int(count), info.get("degraded_to")))

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        deadline = t0 + seconds
        tracer = self.run.tracer
        while time.perf_counter() < deadline:
            traced = tracer.enabled and not tracer.done
            tracer.start()
            p0, c0 = time.perf_counter(), harness.COMPILES.compiled()
            done = 0
            for name in self.names:
                if time.perf_counter() >= deadline:
                    break
                self._one(name)
                done += 1
                if traced:
                    self.traced["queries"] += 1
            tracer.stop()
            self.passes.append((done == len(self.names), time.perf_counter() - p0, done))
            self.compiles.append(harness.COMPILES.compiled() - c0)

    def window_report(self) -> dict:
        return {
            "passes": len(self.passes),
            "complete_passes": sum(1 for c, _s, _n in self.passes if c),
            "pass_s": [s for _c, s, _n in self.passes][:20],
            "pass_compiles": self.compiles[:20],
            "query_s": {n: sorted(t)[len(t) // 2] for n, t in self.query_s.items()},
        }

    def end_to_end(self) -> dict:
        done = [(s, n) for c, s, n in self.passes if c]
        if not done:
            return {}
        return {"queries_per_s": sum(n for _s, n in done) / sum(s for s, _n in done)}

    def slice_report(self) -> dict:
        return dict(self.traced)

    def release(self) -> None:
        self.queries_plain = {n: harness.plain(*self.queries[n]) for n in self.names}
        self.queries = None

    def expected(self) -> dict:
        return {n: reference.count(*self.queries_plain[n]) for n in self.names}

    def control_answers(self) -> dict:
        """The control: each count approximated from a half sample of the
        query's largest relation, doubled (exactness given up)."""
        return {n: reference.half_sample_count(*self.queries_plain[n], seed=self.run.seed)
                for n in self.names}

    def check(self, control: bool = False) -> harness.Checks:
        want = self.expected()
        got_control = self.control_answers() if control else None
        checks = harness.Checks(attempted=len(self.answers))
        for name, got, degraded in self.answers:
            if got_control is not None:
                got, degraded = got_control[name], None
            if degraded is not None:
                checks.degraded += 1
            elif got != want[name]:
                checks.wrong += 1
        return checks
