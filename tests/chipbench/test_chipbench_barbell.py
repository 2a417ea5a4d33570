"""The standing barbell cell (kron-barbell-standing) at a small size on the
CPU: the plain reference against brute force, a run correct with its
metrics, `check` refusing the control, an int32-wrapped count and a batch
left out, and the readers that attribute device time by host span."""
import itertools
import time

import jax
import numpy as np
import pytest
from chipbench_kit import SEED, harness

from chipbench import reference_barbell, scopes, stage_spans
from chipbench.drivers import standing
from chipbench.trace import Tracer

CELL = "kron-barbell-standing"
READERS = (
    "stage_ms.barbell",
    "root_ms.barbell",
    "stage_trie_ms.barbell",
    "compact_ms.barbell",
    "stage_mrows.barbell",
    "host_ms.barbell",
)


def small_cell(scale: int = 6, **mix) -> harness.Cell:
    cell = harness.Cell.load(CELL)
    cell.config["params"].update(scale=scale)
    cell.mix.update({"batch_edges": 64, "window_batches": 3, **mix})
    return cell


def _run(cell, seconds=1.0, trace=False, tracer=None, seed=SEED):
    run = harness.Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
                      devices=jax.devices()[:1], log=lambda rec: None)
    run.tracer = tracer or Tracer(False, "")
    return run


@pytest.mark.parametrize("seed", [0, 1])
def test_reference_is_the_brute_force_count(seed):
    """Every (x, a, b, y, c, d) of a tiny multigraph, by enumeration."""
    rng = np.random.default_rng(seed)
    n = 6
    src, dst = rng.integers(0, n, 30), rng.integers(0, n, 30)
    a = np.zeros((n, n), np.int64)
    np.add.at(a, (src, dst), 1)
    want = 0
    for x, p, q, y, r, s in itertools.product(range(n), repeat=6):
        want += a[x, p] * a[p, q] * a[q, x] * a[y, r] * a[r, s] * a[s, y] * a[x, y]
    assert reference_barbell.count(src, dst) == want
    ctr = reference_barbell.BarbellCounter(src[:20], dst[:20])
    assert ctr.append(src[20:], dst[20:]) == want


def test_the_reference_refuses_a_count_past_its_width():
    src = np.zeros(2**12, np.int64)  # one self-loop 4096 times: t = 2**36
    with pytest.raises(OverflowError):
        reference_barbell.count(src, src)


def test_barbell_run_is_correct_and_reports_its_metrics():
    res = harness.execute(small_cell(), SEED, 1.0, False, jax.devices()[:1],
                          t_start=time.perf_counter())
    assert res["correct"], res
    assert res["attempted"] >= 4 and res["failed"] == 0
    assert set(res["metrics"]) == {"edges_per_s", "setup_s"}


JUDGED_SEED = 2**31 + 5


@pytest.fixture(scope="module")
def scale9():
    """The cell's configuration at scale 9, where every count passes 2**31:
    its edges, three batches of the seed, and the exact counts."""
    cell = harness.Cell.load(CELL)
    cell.config["params"].update(scale=9)
    tables, _q = harness.generate(cell.config, JUDGED_SEED)
    e = tables["edges"]
    base = (e.columns["a"], e.columns["b"])
    batches = standing.batches(JUDGED_SEED, 3, *base, 1024)
    ref = reference_barbell.BarbellCounter(*base)
    want = [ref.count] + [ref.append(*b) for b in batches]
    assert all(w > 2**31 for w in want)
    return cell, base, batches, want


def _judged(scale9, got=None, control=False):
    """`check` of the driver over the scale-9 counts, with `got` in the
    program's place."""
    cell, base, batches, want = scale9
    driver = harness.load_driver(cell.mix["driver"]).Driver(_run(cell, seed=JUDGED_SEED))
    driver.base, driver.batches, driver.next = base, batches, 3
    driver.counter = reference_barbell.BarbellCounter
    got = want if got is None else got
    driver.registered = (got[0], None)
    driver.setup_results = [(g, None) for g in got[1:2]]
    driver.results = [(g, None, 1.0) for g in got[2:]]
    return driver.check(control=control)


def test_check_passes_the_exact_counts(scale9):
    assert _judged(scale9).correct


@pytest.mark.parametrize("fault", ["control", "int32_wrapped", "batch_left_out"])
def test_check_refuses(fault, scale9):
    _cell, base, batches, want = scale9
    if fault == "control":
        checks = _judged(scale9, control=True)
    elif fault == "int32_wrapped":
        # the exact counts in int32, as the executor's sum read them
        checks = _judged(scale9, [int(np.int64(w).astype(np.int32)) for w in want])
        assert checks.wrong == 4
    else:
        # the second batch never reaches the count
        ref = reference_barbell.BarbellCounter(*base)
        got = [ref.count, ref.append(*batches[0]), ref.count, ref.append(*batches[2])]
        checks = _judged(scale9, got)
        assert checks.wrong == 2
    assert checks.attempted == 4 and not checks.correct


def _read_all(monkeypatch, trace, batches_traced):
    monkeypatch.setattr(scopes, "traced", lambda: trace)
    ctx = harness.MetricContext(reduction=None, slice={"batches": batches_traced}, peaks={})
    return {name: harness.load_metric(name).read(ctx) for name in READERS}


def _synthetic():
    """One batch: stage 0 and stage 1 (an executor run each, inside their
    spans), the root's span with two stage-trie spans whose programs run
    after they close, a base merge, then the root's executor."""
    ms = 1e6

    def span(name, start, dur, **stats):
        return [name, start * ms, dur * ms, 1, stats]

    spans = [
        span("slice", 0, 100),
        span("fj.standing.stage", 1, 20, stage=0, root=0),
        span("fj.standing.stage", 22, 20, stage=1, root=0),
        span("fj.standing.stage", 43, 50, stage=2, root=1),
        span("fj.standing.stage_trie", 44, 1),
        span("fj.standing.stage_trie", 46, 1),
    ]
    modules = [
        ["jit_run(7)", 5 * ms, 10 * ms],  # stage 0
        ["jit_run(7)", 25 * ms, 12 * ms],  # stage 1: the same program
        ["jit__lsd_pass(3)", 45 * ms, 4 * ms],  # the first trie's sort
        ["jit__build_trie_jit(4)", 49 * ms, 2 * ms],
        ["jit__assign_slots(5)", 51 * ms, 3 * ms],
        ["jit__merge_append_jit(6)", 54 * ms, 1 * ms],  # the bridge's merge
        ["jit_run(8)", 60 * ms, 30 * ms],  # the root
        ["jit__lsd_pass(3)", 95 * ms, 2 * ms],  # a later sort, no stage trie's
    ]
    return {"spans": spans, "devices": {"0": {"modules": modules, "ops": []}}}


def test_device_time_follows_the_host_spans(monkeypatch):
    trace = _synthetic()
    lo, hi = scopes.bounds(trace)
    assert stage_spans.executor_ns(trace, lo, hi, root=False) == 22e6
    assert stage_spans.executor_ns(trace, lo, hi, root=True) == 30e6
    assert stage_spans.stage_trie_ns(trace, lo, hi) == 9e6
    got = _read_all(monkeypatch, trace, 1)
    assert got["stage_ms.barbell"] == pytest.approx(22.0)
    assert got["root_ms.barbell"] == pytest.approx(30.0)
    assert got["stage_trie_ms.barbell"] == pytest.approx(9.0)
    # a program without the spans and counters reads nothing
    bare = {"spans": [s for s in trace["spans"] if s[0] == "slice"],
            "devices": trace["devices"]}
    assert _read_all(monkeypatch, bare, 1) == dict.fromkeys(READERS)
    assert _read_all(monkeypatch, None, 1) == dict.fromkeys(READERS)


def test_a_traced_window_counts_the_stage_rows(tmp_path, monkeypatch):
    """On the CPU (no TPU plane) the device readers find nothing, and the
    stage rows are the two stages' directed triangles of each batch's
    graph."""
    cell = small_cell()
    run = _run(cell, seconds=1.0, trace=True, tracer=Tracer(True, str(tmp_path / CELL)))
    driver = harness.load_driver(cell.mix["driver"]).Driver(run)
    driver.setup()
    # one batch traced as the window traces its middle third
    run.tracer.start()
    driver.results.append(driver._apply(driver.next))
    driver.next += 1
    run.tracer.stop()
    n = 1
    trace = scopes.load(run.tracer.path())
    got = _read_all(monkeypatch, trace, n)
    assert got["host_ms.barbell"] > 0
    assert [got[k] for k in READERS[:4]] == [None] * 4
    lo, hi = scopes.bounds(trace)
    stages = [s[4] for s in trace["spans"] if s[0] == "fj.standing.stage" and lo <= s[1] < hi]
    assert sorted((s["stage"], s["root"]) for s in stages) == sorted([(0, 0), (1, 0), (2, 1)] * n)
    # directed triangles of the graph after each traced batch, twice
    src, dst = driver.base
    want = []
    for bs, bd in driver.batches[: driver.next]:
        src, dst = np.concatenate([src, bs]), np.concatenate([dst, bd])
        m = int(max(src.max(), dst.max())) + 1
        a = np.zeros((m, m), np.int64)
        np.add.at(a, (src, dst), 1)
        want.append(2 * int(np.trace(a @ a @ a)))
    rows = got["stage_mrows.barbell"] * 1e6 * n
    assert any(rows == pytest.approx(sum(want[i : i + n])) for i in range(len(want)))
