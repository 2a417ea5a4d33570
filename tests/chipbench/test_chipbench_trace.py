"""The trace reduction on a small trace recorded on the chip (the first
900 ms of a traced standing slice, on an earlier skewed graph): busy time, idle share, device time
per program and the labels of idle gaps, each checked against a plain
recomputation; and the per-layer readers on top of it."""
import json
from pathlib import Path

import numpy as np
import pytest
from chipbench_kit import harness

from chipbench import trace as tr

DATA = Path(__file__).with_name("data") / "standing_trace_slice.json"


@pytest.fixture(scope="module")
def recorded():
    trace = json.loads(DATA.read_text())
    bounds = tr.slice_bounds(trace)
    return trace, bounds, tr.reduce(trace, bounds)


def test_slice_is_the_recorded_window(recorded):
    _trace, bounds, red = recorded
    assert bounds == (0.0, 900e6)
    assert red.window_ns == 900e6 and red.devices == 1


def test_busy_time_is_the_union_of_op_intervals(recorded):
    trace, (lo, hi), red = recorded
    us = np.zeros(int((hi - lo) / 1e3) + 1, bool)  # a 1 us timeline
    for _name, s, d in trace["devices"]["0"]["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            us[int(a // 1e3): int(np.ceil(b / 1e3))] = True
    assert red.busy_ns == pytest.approx(us.sum() * 1e3, abs=2e6)
    assert 0 < red.busy_ns < red.window_ns


def test_device_time_per_program(recorded):
    trace, (lo, hi), red = recorded
    want: dict = {}
    for name, s, d in trace["devices"]["0"]["modules"]:
        prog = name.split("(")[0]
        want[prog] = want.get(prog, 0) + max(0.0, min(s + d, hi) - max(s, lo))
    for prog, ns in want.items():
        assert red.program_ns.get(prog, 0.0) == pytest.approx(ns)
    # the programs the build metric names are the ones this slice ran
    assert red.program_ns["jit__merge_append_jit"] > 5e8
    assert red.program_ns["jit__lsd_pass"] > 0 and red.program_ns["jit__assign_slots"] > 0
    assert red.program_ns["jit_run"] == pytest.approx(hi - 884_534_000, rel=1e-3)


def test_idle_gaps_are_labelled_by_the_innermost_span(recorded):
    trace, _bounds, red = recorded
    assert len(red.idle_gaps) == 10
    secs = [g[1] for g in red.idle_gaps]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= (red.window_ns - red.busy_ns) / 1e9 + 1e-9
    spans = [s for s in trace["spans"] if s[0] != tr.SLICE_SPAN]
    for label, _sec in red.idle_gaps:
        assert label in {s[0] for s in spans} | {"none"}
    assert red.idle_gaps[0][0] in ("append", "ingest")


def test_top_ops_are_by_self_time(recorded):
    _trace, _bounds, red = recorded
    assert len(red.top_ops) == 10
    assert all(":" in name for name, _s in red.top_ops)
    assert sum(s for _n, s in red.top_ops) <= red.busy_ns / 1e9 + 1e-6


def test_readers_on_the_recorded_slice(recorded):
    _trace, _bounds, red = recorded
    ctx = harness.MetricContext(reduction=red, slice={"batches": 1}, peaks={})
    idle = harness.load_metric("idle_share.standing").read(ctx)
    assert idle == pytest.approx(100 * (1 - red.busy_ns / red.window_ns))
    build = harness.load_metric("build_ms.standing")
    assert build.read(ctx) == pytest.approx(red.device_ns(build.PROGRAMS) / 1e6)
    assert "jit_run" not in build.PROGRAMS
    executor = harness.load_metric("executor_ms.standing").read(ctx)
    assert executor == pytest.approx(red.program_ns["jit_run"] / 1e6)
    empty = harness.MetricContext(reduction=red, slice={}, peaks={})
    assert harness.load_metric("executor_ms.standing").read(empty) is None
