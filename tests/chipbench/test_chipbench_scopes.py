"""The per-layer readers of the program's own scopes, spans and counters
(chipbench/scopes.py).

The parser is checked against `jax.profiler.ProfileData` on a trace the
CPU records of the standing cell at its small size, and that trace's
program counters against what the run did. The stage split, host time and
counters are then checked on a small trace recorded on the chip
(`data/standing_scopes_slice.json`): one traced kron-standing batch as
`scopes.parse` returns it, the ops of that slice only. A program without
the scopes, spans and counters (the parent of this benchmark's readers)
reads None in every reader. The `compact` stage, which that slice does not
run, is checked on the compiled executor of a plan that compacts.
"""
import json
import re
from pathlib import Path

import jax
import numpy as np
import pytest
from chipbench_kit import SEED, harness, small_cell

from chipbench import scopes
from chipbench import trace as tr
from chipbench.drivers.standing import batches
from chipbench.trace import Tracer
from repro.core.capacity import plan_capacities
from repro.core.compiled import make_executor, relations_to_cols
from repro.core.plan import binary2fj, factor
from repro.relational.relation import Relation
from repro.relational.schema import Atom, Query
from tests.conftest import rand_rel

DATA = Path(__file__).with_name("data") / "standing_scopes_slice.json"
READERS = (
    "expand_ms.standing",
    "probe_ms.standing",
    "compact_ms.standing",
    "count_ms.standing",
    "host_ms.standing",
    "dispatches.standing",
    "sync_reads.standing",
    "frontier_mlanes.standing",
)


def _read_all(monkeypatch, trace, batches_traced):
    monkeypatch.setattr(scopes, "traced", lambda: trace)
    ctx = harness.MetricContext(reduction=None, slice={"batches": batches_traced}, peaks={})
    return {name: harness.load_metric(name).read(ctx) for name in READERS}


def _graph_lanes(config: dict, seed: int, mix: dict) -> list[int]:
    """The lanes the standing triangle's executor expands after each batch
    of the seed: K1's padded bucket (node 0) plus sum(deg^2) of the graph
    as it then stands (node 1, every 2-path)."""
    _tables, queries = harness.generate(config, seed)
    q, rels = queries[mix["query"]]
    atoms, data = harness.plain(q, rels)
    alias, (s, d) = atoms[0]
    src = data[alias][s]
    n = mix["setup_batches"] + mix["window_batches"]
    out = []
    for b in batches(seed, n, src, data[alias][d], mix["batch_edges"]):
        src = np.concatenate([src, b[0]])
        bucket = max(1024, 1 << (len(src) - 1).bit_length())
        out.append(bucket + int((np.bincount(src).astype(np.int64) ** 2).sum()))
    return out


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A traced window of the standing cell at its small size, on the CPU."""
    run = harness.Run(
        cell=small_cell("kron-standing"),
        seed=SEED,
        seconds=0.6,
        trace=True,
        devices=jax.devices()[:1],
        log=lambda rec: None,
    )
    run.tracer = Tracer(True, str(tmp_path_factory.mktemp("trace") / "kron-standing"))
    driver = harness.load_driver(run.mix["driver"]).Driver(run)
    driver.setup()
    driver.window(run.seconds)
    return run, driver, run.tracer.path()


def test_the_parser_reads_what_profile_data_reads(cpu_trace):
    _run, _driver, path = cpu_trace
    from jax.profiler import ProfileData

    want = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("fj.") or e.name == tr.SLICE_SPAN:
                    want.append((e.name, e.start_ns, e.duration_ns, dict(list(e.stats))))
    got = scopes.parse(path)["spans"]
    assert len(got) == len(want) > 0
    for (name, start, dur, _thread, stats), w in zip(sorted(got), sorted(want)):
        assert (name, stats) == (w[0], w[3])
        assert start == pytest.approx(w[1], abs=1) and dur == pytest.approx(w[2], abs=1)
    assert scopes.load(path) is scopes.load(path)  # parsed once


def test_the_counters_of_a_traced_window(cpu_trace, monkeypatch):
    run, driver, path = cpu_trace
    n = driver.traced["batches"]
    assert n >= 1
    trace = scopes.load(path)
    got = _read_all(monkeypatch, trace, n)
    # the CPU trace has no TPU plane: the device readers find nothing
    assert [got[k] for k in READERS[:4]] == [None] * 4
    assert got["dispatches.standing"] == 1 and got["sync_reads.standing"] == 2
    assert got["host_ms.standing"] > 0
    # each traced refresh counted the lanes its batch's graph gives, and
    # the batches are consecutive ones of the seed
    lo, hi = scopes.bounds(trace)
    counted = [
        s[4]["executor.lanes"]
        for s in sorted(trace["spans"], key=lambda s: s[1])
        if s[0] == "fj.standing.refresh" and lo <= s[1] < hi
    ]
    graph = _graph_lanes(run.config, run.seed, run.mix)
    assert len(counted) == n and counted[0] in graph
    first = graph.index(counted[0])
    assert counted == graph[first : first + n]
    assert got["frontier_mlanes.standing"] == pytest.approx(sum(counted) / n / 1e6)
    # each batch merges its delta into the three aliases' cached tries
    gets = [s[4] for s in trace["spans"] if s[0] == "fj.trie.get" and lo <= s[1] < hi]
    assert len(gets) == 3 * n and {g["outcome"] for g in gets} == {"merge"}


def test_scope_of_takes_the_innermost_scopes():
    assert scopes.scope_of("jit(run)/node1/probe/jit(_probe_jnp)/while/body/gather:") == (
        "probe",
        "node1",
    )
    assert scopes.scope_of("jit(run)/node0/expand/jit(searchsorted)/while/body/gather:gather") == (
        "expand",
        "node0",
    )
    assert scopes.scope_of("jit(run)/count/reduce_sum:") == ("count", None)
    # the op's own name is not a scope
    assert scopes.scope_of("jit(run)/count:count") == (None, None)
    assert scopes.scope_of("") == (None, None)


def test_a_compacting_plan_reads_under_compact(rng):
    """A plan that squeezes its frontier after a selective probe: the
    compiled executor names the squeeze's ops under `compact`, and ops so
    named read as compact time."""
    q = Query([Atom("R", ("x", "y")), Atom("S", ("y", "a")), Atom("T", ("y", "b"))])
    y_live = rng.choice(40, 3, replace=False)  # S kills most lanes
    rels = {
        "R": rand_rel(rng, "R", ("x", "y"), 400, 40),
        "S": Relation("S", {"y": y_live[rng.integers(0, 3, 6)], "a": rng.integers(0, 40, 6)}),
        "T": rand_rel(rng, "T", ("y", "b"), 100, 40),
    }
    fj = factor(binary2fj(q.atoms, q))
    cp = plan_capacities(fj, rels, block=128)
    assert cp.compact_to[0] is not None
    run = jax.jit(make_executor(fj, cp.capacities, compact_to=cp.compact_to,
                                compact_probe=cp.compact_probe))
    hlo = run.lower(relations_to_cols(fj, rels)).compile().as_text()
    names = re.findall(r'op_name="(jit\(run\)/[^"]+)"', hlo)
    compact = [n for n in names if scopes.scope_of(n + ":")[0] == "compact"]
    assert compact and all(n.startswith("jit(run)/node0/compact/") for n in compact)
    # each named op as a device op of 1 us, inside one run of the executor
    ops = [[f"op{i}", 1e3 * i, 1e3, n + ":"] for i, n in enumerate(names)]
    trace = {"spans": [], "devices": {"0": {"modules": [["jit_run(1)", 0.0, 1e3 * len(ops)]],
                                             "ops": ops}}}
    by_stage, _by_node = scopes.stage_ns(trace, 0.0, 1e3 * len(ops))
    assert by_stage["compact"] == pytest.approx(1e3 * len(compact))


@pytest.fixture(scope="module")
def recorded():
    trace = json.loads(DATA.read_text())
    return trace, scopes.bounds(trace)


def test_the_stages_cover_the_executor(recorded):
    trace, (lo, hi) = recorded
    by_stage, by_node = scopes.stage_ns(trace, lo, hi)
    red = tr.reduce(
        {"devices": {k: {"modules": d["modules"], "ops": [o[:3] for o in d["ops"]]}
                     for k, d in trace["devices"].items()},
         "spans": []},
        (lo, hi),
    )
    executor = red.program_ns[scopes.EXECUTOR]
    staged = sum(by_stage.get(s, 0.0) for s in scopes.STAGES)
    assert staged >= 0.95 * executor
    assert sum(by_stage.values()) <= executor * 1.001
    assert sum(by_node.values()) == pytest.approx(sum(by_stage.values()))
    # the closing probe's loop, then the 2-path expansion; no compaction
    assert by_stage["probe"] > by_stage["expand"] > by_stage["count"] > 0
    assert "compact" not in by_stage
    assert set(by_node) >= {"node0", "node1"}


def test_the_readers_on_the_recorded_slice(recorded, monkeypatch):
    trace, (lo, hi) = recorded
    got = _read_all(monkeypatch, trace, trace["batches"])
    by_stage, _ = scopes.stage_ns(trace, lo, hi)
    for stage in scopes.STAGES:
        want = by_stage.get(stage, 0.0) / 1e6 / trace["batches"]
        assert got[f"{stage}_ms.standing"] == pytest.approx(want)
    assert got["dispatches.standing"] == 1 and got["sync_reads.standing"] == 2
    self_ns = scopes.span_self_ns(trace, lo, hi)
    host = sum(v for k, v in self_ns.items() if k not in scopes.BLOCKING) / 1e6
    assert got["host_ms.standing"] == pytest.approx(host / trace["batches"])
    assert 0 < got["host_ms.standing"] < 1000
    # the lanes the chip counted are the lanes the graph gives
    config = harness.load_config("gap-kron")
    mix = harness.load_mix("kron-standing")
    want = _graph_lanes(config, trace["seed"], mix)[trace["batch"]]
    assert got["frontier_mlanes.standing"] == pytest.approx(want / 1e6)


def test_idle_gaps_are_labelled_by_the_program_spans(recorded):
    trace, (lo, hi) = recorded
    gaps = scopes.idle_gaps(trace, lo, hi)
    assert gaps and all(label.startswith("fj.") or label == "none" for label, _s in gaps)


def test_a_program_without_scopes_reads_nothing(recorded, monkeypatch):
    trace, _bounds = recorded
    bare = {
        "spans": [s for s in trace["spans"] if s[0] == tr.SLICE_SPAN],
        "devices": {
            k: {"modules": d["modules"], "ops": [o[:3] + [""] for o in d["ops"]]}
            for k, d in trace["devices"].items()
        },
    }
    assert _read_all(monkeypatch, bare, trace["batches"]) == dict.fromkeys(READERS)
    assert _read_all(monkeypatch, None, trace["batches"]) == dict.fromkeys(READERS)
