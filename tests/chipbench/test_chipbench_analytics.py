"""The closed-loop mix kept for a later cell (the warm triangle count of
the kron graph, `mixes/kron-tc.json`) at a small size on the CPU: a run is
correct and reports its metrics; a broken answer, a degraded one and the
control each read as not correct."""
import time

import jax
import pytest
from chipbench_kit import SEED, finish, harness, prepared, small_cell


@pytest.mark.parametrize("name", ["kron-tc"])
def test_closed_loop_run_is_correct_and_reports_its_metrics(name):
    cell = small_cell(name, "gap-kron")
    res = harness.execute(cell, SEED, 1.0, False, jax.devices()[:1], t_start=time.perf_counter())
    assert res["correct"], res
    assert res["failed"] == 0 and res["attempted"] >= len(cell.mix["queries"])
    assert set(res["metrics"]) == {"queries_per_s", "setup_s"}
    assert res["metrics"]["queries_per_s"]["value"] > 0
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["answer_altered", "degraded", "control"])
def test_analytics_fault_reads_not_correct(fault, monkeypatch):
    import repro.core

    real = repro.core.compiled_free_join

    def answer_altered(*a, **kw):
        return real(*a, **kw) + 1

    def degraded(*a, info=None, **kw):
        out = real(*a, info=info, **kw)
        info["degraded_to"] = "eager"
        return out

    driver = prepared("kron-tc", config="gap-kron")
    if fault != "control":
        broken = {"answer_altered": answer_altered, "degraded": degraded}[fault]
        monkeypatch.setattr(repro.core, "compiled_free_join", broken)
    checks = finish(driver, control=fault == "control")
    assert checks.attempted > 0
    assert not checks.correct
    if fault == "degraded":
        assert checks.degraded == checks.attempted
