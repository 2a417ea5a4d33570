"""The standing-triangle cell at a small size on the CPU: a run is correct
and reports its metrics; every appended row closes a triangle; a refresh
that keeps its state, either half of a batch or every other row left out,
an altered count, a degraded refresh and the control each read as not
correct."""
import time

import jax
import numpy as np
import pytest
from chipbench_kit import SEED, finish, harness, prepared, small_cell

from chipbench.drivers import standing


def test_standing_run_is_correct_and_reports_its_metrics():
    cell = small_cell("kron-standing")
    res = harness.execute(cell, SEED, 1.0, False, jax.devices()[:1], t_start=time.perf_counter())
    assert res["correct"], res
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"edges_per_s", "setup_s"}


def test_standing_counts_move_with_every_batch():
    driver = prepared("kron-standing")
    checks = finish(driver)
    assert checks.correct
    counts = [driver.registered[0]] + [r[0] for r in driver.setup_results + driver.results]
    assert all(b > a for a, b in zip(counts, counts[1:]))


@pytest.mark.parametrize("seed", [0, SEED])
def test_every_appended_row_closes_a_triangle_of_the_graph(seed):
    from chipbench import datagen

    e = datagen.kron_tables(8)["edges"]
    src, dst = np.asarray(e.columns["a"]), np.asarray(e.columns["b"])
    edges = set(zip(src.tolist(), dst.tolist()))
    nbrs: dict = {}
    for a, b in edges:
        nbrs.setdefault(a, set()).add(b)
    seen = set()
    for s, d in standing.batches(seed, 3, src, dst, 64):
        assert len(s) == 64
        assert np.array_equal(s[:32], d[32:]) and np.array_equal(d[:32], s[32:])
        for u, v in zip(s.tolist(), d.tolist()):
            assert u != v and (u, v) not in edges and (u, v) not in seen
            assert nbrs[u] & nbrs[v], "no common neighbour: the row closes no triangle"
            seen.add((u, v))


@pytest.mark.parametrize(
    "fault",
    [
        "state_unchanged",
        "half_batch",
        "half_batch_second",
        "every_other_row",
        "answer_altered",
        "degraded",
        "control",
    ],
)
def test_standing_fault_reads_not_correct(fault, monkeypatch):
    from repro.core import relcache
    from repro.serve import StandingQueryEngine

    real = StandingQueryEngine.ingest

    def state_unchanged(self, rel, delta_cols):
        relcache.append(rel, delta_cols)
        return []

    def half_batch(self, rel, delta_cols):
        return real(self, rel, {v: c[: len(c) // 2] for v, c in delta_cols.items()})

    def half_batch_second(self, rel, delta_cols):
        return real(self, rel, {v: c[len(c) // 2 :] for v, c in delta_cols.items()})

    def every_other_row(self, rel, delta_cols):
        return real(self, rel, {v: c[::2] for v, c in delta_cols.items()})

    def answer_altered(self, rel, delta_cols):
        out = real(self, rel, delta_cols)
        self.queries[0].result += 1
        return out

    def degraded(self, rel, delta_cols):
        out = real(self, rel, delta_cols)
        self.queries[0].degraded_to = "eager"
        return out

    driver = prepared("kron-standing")
    if fault != "control":
        monkeypatch.setattr(StandingQueryEngine, "ingest", locals()[fault])
    checks = finish(driver, control=fault == "control")
    assert checks.attempted > 0
    assert not checks.correct
    if fault.startswith("half") or fault == "every_other_row":
        # every batch of the window (set-up ran before the fault) reads too low
        assert len(driver.results) > 0 and checks.wrong == len(driver.results)


def test_a_control_run_checks_the_program_then_reads_not_correct():
    lines = []
    cell = small_cell("kron-standing")
    res = harness.execute(
        cell, SEED, 1.0, False, jax.devices()[:1], t_start=time.perf_counter(),
        log=lines.append, control=True,
    )
    program = [r for r in lines if "program_correct" in r]
    assert program and program[0]["program_correct"] is True
    assert not res["correct"] and res["checks"]["wrong_answers"]["value"] > 0
