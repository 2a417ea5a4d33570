"""The program's spans, counters and named scopes (core/obs.py).

Spans nest per thread and keep count, host seconds and self seconds; the
outermost span carries the counters moved inside it as stats, and a
profiler trace on the CPU holds the `fj.` spans with those stats. A steady
standing refresh of the triangle dispatches once, reads twice and counts
its frontier lanes, with no device-to-host read outside the counted
helper. The executor's lowered program carries the stage scopes in its op
names.
"""
import glob
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compiled_free_join, obs, relcache
from repro.core.compiled import TRIE_CACHE, device_columns
from repro.relational.relation import Relation
from repro.relational.schema import Atom, Query
from repro.serve import StandingQueryEngine


def _moved(before, after):
    """The counters that moved between two snapshots, by how much."""
    moved = {k: n - before.get(k, 0) for k, n in after.items()}
    return {k: n for k, n in moved.items() if n}


def _spans(before, after):
    """The span totals added between two snapshots."""
    out = {}
    for name, tot in after["spans"].items():
        b = before["spans"].get(name, {"count": 0, "seconds": 0.0, "self_seconds": 0.0})
        out[name] = {k: tot[k] - b[k] for k in tot}
    return {k: v for k, v in out.items() if v["count"]}


def test_spans_nest_and_keep_self_time():
    s0 = obs.snapshot()
    with obs.span("fj.test.outer"):
        time.sleep(0.02)
        with obs.span("fj.test.inner"):
            time.sleep(0.03)
        with obs.span("fj.test.inner"):
            time.sleep(0.03)
    got = _spans(s0, obs.snapshot())
    outer, inner = got["fj.test.outer"], got["fj.test.inner"]
    assert outer["count"] == 1 and inner["count"] == 2
    assert inner["seconds"] >= 0.06 and inner["self_seconds"] == pytest.approx(inner["seconds"])
    assert outer["seconds"] >= inner["seconds"] + 0.02
    assert outer["self_seconds"] == pytest.approx(outer["seconds"] - inner["seconds"], abs=1e-3)


def test_a_span_that_raises_still_closes():
    s0 = obs.snapshot()
    with pytest.raises(ValueError), obs.span("fj.test.raises"):
        raise ValueError("boom")
    with obs.span("fj.test.after"):
        pass
    got = _spans(s0, obs.snapshot())
    assert got["fj.test.raises"]["count"] == 1
    # the stack is empty again: the next span is outermost, so its self
    # time is its whole time
    assert got["fj.test.after"]["self_seconds"] == pytest.approx(got["fj.test.after"]["seconds"])


def test_counters_and_reads():
    s0 = obs.snapshot()["counters"]
    x = jnp.arange(4)
    with obs.span("fj.test.outer"):
        obs.count("test.things", 3)
        got = obs.read(x, "fj.test.read")
    assert np.array_equal(got, np.arange(4))
    assert _moved(s0, obs.snapshot()["counters"]) == {"test.things": 3, "sync_reads": 1}


def test_threads_count_without_losing_updates():
    """More threads than cores, a short switch interval: every count and
    every span lands in the totals, and each thread's outermost span
    carries its own counts alone."""
    import sys

    threads, rounds = 16, 2000
    s0 = obs.snapshot()
    carried = []

    def work():
        for _ in range(rounds):
            with obs.span("fj.test.thread"):
                obs.count("test.threads")
                moved = dict(obs._thread().moved)
        carried.append((moved, obs._thread().moved))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
    s1 = obs.snapshot()
    assert _moved(s0["counters"], s1["counters"]) == {"test.threads": threads * rounds}
    assert _spans(s0, s1)["fj.test.thread"]["count"] == threads * rounds
    # each closing span took its thread's counts with it
    assert carried == [({"test.threads": 1}, {})] * threads


def test_profiler_trace_holds_the_spans_and_their_stats(tmp_path):
    from jax.profiler import ProfileData

    with obs.span("fj.test.flush"):
        pass  # takes whatever this thread counted before
    jax.profiler.start_trace(str(tmp_path))
    obs.count("test.before", 2)  # outside every span: rides with the next
    with obs.span("fj.test.outer", seq=41):
        with obs.span("fj.test.inner", outcome="merge") as sp:
            obs.count("test.lanes", 123)
            sp.stats(rows=7)
        obs.read(jnp.ones(3), "fj.test.read")
        # another thread's counts belong to its own spans, not to this one
        other = threading.Thread(target=obs.count, args=("test.other", 5))
        other.start()
        other.join()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("fj.test."):
                    events[e.name] = {k: v for k, v in e.stats}
    assert events["fj.test.inner"] == {"outcome": "merge", "rows": 7}
    # only the outermost span carries the counters moved while it was open
    assert events["fj.test.outer"] == {
        "seq": 41, "test.before": 2, "test.lanes": 123, "sync_reads": 1
    }
    assert events["fj.test.read"] == {}


def _trace_events(directory, prefix="fj."):
    """The `fj.` host events of the one trace under `directory`, in time
    order: [(name, stats)]."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(directory / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    events.append((e.start_ns, e.name, dict(e.stats)))
    return [(name, stats) for _t, name, stats in sorted(events)]


def test_a_one_shot_query_carries_its_counters_into_the_trace(rng, tmp_path):
    """`compiled_free_join` opens no span of its own around the executor:
    the executor's call span carries the dispatches, the need-vector read
    and the lanes, and the count's read carries its own read."""
    q, rels = _triangle(rng)
    compiled_free_join(q, rels, agg="count")  # plans, compiles, settles
    s0 = obs.snapshot()["counters"]
    jax.profiler.start_trace(str(tmp_path))
    got = compiled_free_join(q, rels, agg="count")
    jax.profiler.stop_trace()
    moved = _moved(s0, obs.snapshot()["counters"])
    assert got == _directed_triangles(rels["K1"])
    events = _trace_events(tmp_path)
    calls = [stats for name, stats in events if name == "fj.executor.call"]
    assert len(calls) == 1 and calls[0]["executor.lanes"] == moved["executor.lanes"] > 0
    carried = {}
    for _name, stats in events:
        for k in moved:
            carried[k] = carried.get(k, 0) + stats.get(k, 0)
    assert carried == moved
    assert moved["sync_reads"] == moved["executor.dispatches"] + 1


def _triangle(rng, n=48, m=400):
    """The directed triangle over one symmetric random edge relation, read
    under three aliases, as the standing benchmark's configuration is."""
    a, b = rng.integers(0, n, m), rng.integers(0, n, m)
    keep = a != b
    key = np.unique(np.minimum(a, b)[keep] * n + np.maximum(a, b)[keep])
    lo, hi = key // n, key % n
    e = Relation("edges", {"a": np.concatenate([lo, hi]), "b": np.concatenate([hi, lo])})
    q = Query([Atom("edges", ("a", "b"), "K1"), Atom("edges", ("b", "c"), "K2"),
               Atom("edges", ("c", "a"), "K3")])
    rels = {"K1": e, "K2": e.rename({"a": "b", "b": "c"}), "K3": e.rename({"a": "c", "b": "a"})}
    return q, rels


def _append_edges(eng, rels, u, v):
    """A batch as the benchmark applies it: K2 and K3 through the relcache,
    then an ingest into K1, which refreshes the count."""
    src, dst = np.concatenate([u, v]), np.concatenate([v, u])
    for alias in ("K2", "K3"):
        x, y = rels[alias].schema
        relcache.append(rels[alias], {x: src, y: dst})
    x, y = rels["K1"].schema
    eng.ingest(rels["K1"], {x: src, y: dst})


def _directed_triangles(rel) -> int:
    """trace(A^3) of the edge rows' count matrix: repeated rows count with
    their multiplicity, as the join's bag semantics does."""
    n = int(max(rel.columns["a"].max(), rel.columns["b"].max())) + 1
    a = np.zeros((n, n), np.int64)
    np.add.at(a, (rel.columns["a"], rel.columns["b"]), 1)
    return int(np.trace(a @ a @ a))


@pytest.fixture
def reads_only_through_obs(monkeypatch):
    """Every host read of a device array outside `obs.read` fails. The
    transfer guard does this on an accelerator; on the CPU backend, whose
    arrays live in host memory, it never fires, so the array's own host
    conversions (`_value`, `__array__`) are checked here too. (Numpy's
    buffer-protocol reads of CPU arrays bypass both.)"""
    from jax._src.array import ArrayImpl

    value, array = ArrayImpl._value, ArrayImpl.__array__

    def check():
        stack = obs._thread().stack
        if not stack or stack[-1].name not in ("fj.executor.sync", "fj.result.read"):
            raise AssertionError("a device-to-host read outside obs.read")

    def guarded_value(self):
        check()
        return value.fget(self)

    def guarded_array(self, *args, **kwargs):
        check()
        return array(self, *args, **kwargs)

    monkeypatch.setattr(ArrayImpl, "_value", property(guarded_value))
    monkeypatch.setattr(ArrayImpl, "__array__", guarded_array)
    with jax.transfer_guard_device_to_host("disallow_explicit"):
        yield


def test_the_read_guard_catches_a_read_outside_obs(reads_only_through_obs):
    x = jnp.arange(4) + 1
    with pytest.raises(AssertionError, match="outside obs.read"):
        int(x[0])
    with pytest.raises(AssertionError, match="outside obs.read"):
        jax.device_get(x)
    assert int(obs.read(x, "fj.result.read")[1]) == 2


def _steady_batches(rng, eng, rels, runner):
    """Two batches after the first, each with the counters, spans and
    executor totals it moved, the lanes of the executor's last needs, and
    the lanes the graph gives: node 0 walks K1's padded bucket, node 1
    every 2-path, so the bucket plus sum(deg^2) of the graph as it then
    stands."""
    # the first batch moves K1 into its padded bucket: a re-run or two
    _append_edges(eng, rels, rng.integers(0, 48, 4), rng.integers(0, 48, 4))
    out = []
    for _ in range(2):
        s0 = obs.snapshot()
        c0 = (runner.calls, runner.retries, runner.reshapes)
        u, v = rng.integers(0, 48, 4), rng.integers(0, 48, 4)
        _append_edges(eng, rels, u, v)
        s1 = obs.snapshot()
        c1 = (runner.calls, runner.retries, runner.reshapes)
        k1 = rels["K1"].columns["a"]
        bucket = max(1024, 1 << (len(k1) - 1).bit_length())
        out.append(
            dict(
                counters=_moved(s0["counters"], s1["counters"]),
                spans=_spans(s0, s1),
                executor=[x - y for x, y in zip(c1, c0)],
                last_needs=sum(int(n.sum()) for n in runner._last_needs),
                graph=bucket + int((np.bincount(k1) ** 2).sum()),
            )
        )
    return out


def test_a_steady_standing_refresh_dispatches_once_and_reads_twice(rng):
    q, rels = _triangle(rng)
    eng = StandingQueryEngine()
    sq = eng.register(q, rels, agg="count")
    ((_name, _plan, runner, _fv),) = eng._runners[sq.template.key]
    for batch in _steady_batches(rng, eng, rels, runner):
        moved, spans = batch["counters"], batch["spans"]
        calls, retries, reshapes = batch["executor"]
        assert moved["executor.dispatches"] == calls + retries + reshapes == 1
        assert moved["sync_reads"] == 2  # the need vectors, then the count
        assert moved["executor.lanes"] == batch["last_needs"] == batch["graph"]
        assert spans["fj.standing.refresh"]["count"] == spans["fj.executor.call"]["count"] == 1
        assert spans["fj.relcache.append"]["count"] == 3
        assert spans["fj.trie.get"]["count"] == 3
        assert spans["fj.executor.sync"]["count"] == spans["fj.result.read"]["count"] == 1
        assert "fj.executor.grow" not in spans
    assert sq.result == _directed_triangles(rels["K1"])


def test_a_steady_refresh_reads_the_device_only_through_obs(rng, reads_only_through_obs):
    q, rels = _triangle(rng)
    eng = StandingQueryEngine()
    sq = eng.register(q, rels, agg="count")
    ((_name, _plan, runner, _fv),) = eng._runners[sq.template.key]
    batches = _steady_batches(rng, eng, rels, runner)
    assert [b["counters"]["sync_reads"] for b in batches] == [2, 2]
    assert sq.result == _directed_triangles(rels["K1"])


def test_the_executor_carries_its_stage_scopes(rng):
    q, rels = _triangle(rng)
    eng = StandingQueryEngine()
    sq = eng.register(q, rels, agg="count")
    ((_name, _plan, runner, _fv),) = eng._runners[sq.template.key]
    t_rels = sq.template.relations
    data = {
        a: TRIE_CACHE.get(t_rels[a], device_columns(t_rels[a]), runner._alias_lops[a])
        for a in t_rels
    }
    text = runner._fn(runner._as_chain(runner.cap_plan)).lower(data).as_text(debug_info=True)
    names = re.findall(r'loc\("(jit\(run\)/[^"]+)"', text)
    for stage in ("expand", "probe", "count"):
        assert any(f"/{stage}/" in n for n in names), stage
    assert any(n.startswith("jit(run)/node0/expand/") for n in names)
    assert any(n.startswith("jit(run)/node1/probe/") for n in names)

